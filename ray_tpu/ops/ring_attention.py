"""Ring attention: blockwise causal attention over a sequence-parallel mesh axis.

The reference has no sequence/context parallelism of its own (verified absent —
see SURVEY.md §5.7; it delegates to engines like vLLM). Here it is first-class:
sequences are sharded over the ``sp`` mesh axis; each device holds a Q/K/V
shard, K/V shards rotate around the ICI ring via ``lax.ppermute`` while an
online-softmax accumulator folds in one block per step (Ring Attention,
blockwise-parallel pattern from the public literature).

Call **inside** shard_map with q, k, v already sharded on the sp axis:
shapes [batch_local, heads_local, seq_local, head_dim].

Differentiable: the scan + ppermute composition is transparent to jax.grad
(ppermute's transpose is the inverse rotation), so the backward pass is itself
a ring schedule.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import DEFAULT_MASK_VALUE


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "sp",
                   causal: bool = True,
                   sm_scale: Optional[float] = None) -> jax.Array:
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    q_pos = my_idx * s_local + jnp.arange(s_local)[:, None]  # global q positions
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def accumulate(block, i):
        k_cur, v_cur, acc, m, l = block
        kv_idx = (my_idx - i) % axis_size  # which global shard we hold at step i
        k_pos = kv_idx * s_local + jnp.arange(s_local)[None, :]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        return acc_new, m_new, l_new

    def step(carry, i):
        # Rotate K/V one hop around the ring (rides ICI neighbours), then fold
        # in the received block. The local (step-0) block is folded in before
        # the scan, so exactly axis_size-1 hops are issued.
        k_cur, v_cur, acc, m, l = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        acc, m, l = accumulate((k_cur, v_cur, acc, m, l), i)
        return (k_cur, v_cur, acc, m, l), None

    # Accumulators derived from q (times zero) so they inherit q's full
    # varying-manual-axes type — works no matter which enclosing shard_map
    # axes (sp, pp, ...) are manual here.
    qf = q.astype(jnp.float32)
    acc0 = qf * 0
    m0 = qf[..., :1] * 0 - jnp.inf
    l0 = qf[..., :1] * 0
    acc0, m0, l0 = accumulate((k, v, acc0, m0, l0), 0)
    (_, _, acc, m, l), _ = jax.lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(1, axis_size))
    l = jnp.maximum(l, 1e-30)
    return (acc / l).astype(q.dtype)

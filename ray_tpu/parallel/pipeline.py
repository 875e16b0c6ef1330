"""Pipeline parallelism over the ``pp`` mesh axis.

The reference's substrate for pipeline-style execution is the compiled actor
DAG with NCCL P2P channels (reference: python/ray/dag/compiled_dag_node.py and
python/ray/experimental/channel/torch_tensor_accelerator_channel.py:49). The
TPU-native equivalent is compiled *into* the XLA program: a GPipe microbatch
schedule expressed as a ``lax.scan`` whose per-step stage-to-stage activation
transfer is a ``lax.ppermute`` hop on the ``pp`` axis. Autodiff through the
scan + ppermute yields the reverse pipeline schedule for the backward pass.

Runs inside a shard_map whose manual axes include "pp"; all other mesh axes
(dp/fsdp/tp/sp/ep) stay automatic, so GSPMD still inserts the tensor-parallel
and FSDP collectives inside each stage.

Round-1 schedule is plain GPipe (bubble = (pp-1)/(M+pp-1)); interleaved /
circular schedules are a planned optimization.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp


def gpipe_spmd(stage_fn: Callable[[Any, jax.Array], "tuple[jax.Array, jax.Array] | jax.Array"],
               stage_params: Any,
               microbatches: jax.Array,
               *,
               axis_name: str = "pp",
               with_aux: bool = False):
    """GPipe forward over the pp axis. Call inside shard_map (manual on pp).

    stage_fn(params_local, x) -> y (or (y, aux_scalar) with with_aux=True)
      with x, y of one microbatch's shape.
    stage_params: pytree whose leaves have a leading stacked-stage axis of
      local size 1 (sharded P("pp") on that axis by the caller's in_specs).
    microbatches: [M, mb, ...] — replicated across pp.
    Returns [M, mb, ...] outputs of the final stage broadcast to all
    stages; with_aux=True also returns the per-stage aux summed over the
    pp axis and averaged over microbatches (warmup/drain steps, whose
    inputs are bubble garbage, are excluded).
    """
    pp = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    params_local = jax.tree.map(lambda p: p[0], stage_params)
    num_mb = microbatches.shape[0]
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    # mb_in: cast to pp-varying; init buffers derive from it (times zero) so
    # they inherit every other manual axis the caller's shard_map has (e.g. sp).
    mb_in = jax.lax.pcast(microbatches, (axis_name,), to="varying")
    out0 = mb_in * 0
    state0 = out0[0]
    # Scalar zero derived from out0 so it inherits the manual-axis varying
    # type (same idiom as the model's aux accumulator).
    aux0 = (out0[(0,) * out0.ndim] * 0).astype(jnp.float32)

    def step(carry, t):
        state, outputs, aux_acc = carry
        mb_idx = jnp.clip(t, 0, num_mb - 1)
        x_in = jnp.where(stage == 0,
                         jax.lax.dynamic_index_in_dim(mb_in, mb_idx, 0,
                                                      keepdims=False),
                         state)
        res = stage_fn(params_local, x_in)
        y, aux = res if with_aux else (res, jnp.zeros((), jnp.float32))
        # This stage computes REAL microbatches only for t in
        # [stage, stage + num_mb); outside that window it chews bubble
        # garbage whose aux must not count.
        active = (t >= stage) & (t - stage < num_mb)
        aux_acc = aux_acc + jnp.where(active, aux, 0.0)
        out_idx = t - (pp - 1)
        valid = (stage == pp - 1) & (out_idx >= 0)
        safe_idx = jnp.clip(out_idx, 0, num_mb - 1)
        prev = jax.lax.dynamic_index_in_dim(outputs, safe_idx, 0, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, y, prev), safe_idx, 0)
        state = jax.lax.ppermute(y, axis_name, perm)
        return (state, outputs, aux_acc), None

    (_, outputs, aux_acc), _ = jax.lax.scan(
        step, (state0, out0, aux0), jnp.arange(num_mb + pp - 1))
    # Broadcast final-stage outputs to every stage (indicator + psum).
    mask = (stage == pp - 1).astype(outputs.dtype)
    out = jax.lax.psum(outputs * mask, axis_name)
    if not with_aux:
        return out
    # Sum stage-local aux across stages; average over microbatches so the
    # scale matches the non-pp full-batch aux.
    aux = jax.lax.psum(aux_acc, axis_name) / num_mb
    return out, aux

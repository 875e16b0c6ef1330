"""The plain reference of `arch: olmoe`: the OLMoE decoder block in
straightforward jax.numpy, float32, matmuls at precision "highest", every
expert computed densely on every token and combined by the routing weights;
no kernel, no cache, no sort, no grouped matmul, no batching (one `lax.scan`,
over the experts, in place of a Python loop the compiler takes minutes over). Written from
the published implementation (transformers `modeling_olmoe.py`, which the
configuration files cite), not from the program's code; it shares with the
program only the layout of the parameter tree it is handed (stacked leaves
`layers/<name>[L, ...]`, `embed`, `final_norm`, `lm_head`).

  h  = rmsnorm(x, w_in)                                   every rmsnorm float32
  q  = rmsnorm(h Wq, w_qn)   k = rmsnorm(h Wk, w_kn)      over ALL features of the
  v  = h Wv                                               projection, BEFORE the heads
  q, k -> heads, rotate-half RoPE;  x = x + causal_softmax(q k^T / sqrt(hd)) v Wo
  h  = rmsnorm(x, w_post)
  p  = softmax(h Wr) over all experts;  (w, e) = top_k(p, k)
  w  is renormalised to sum to one only if `norm_topk_prob` (published: false)
  x  = x + sum_j w_j (silu(h Wg[e_j]) * (h Wu[e_j])) Wd[e_j]     no token dropped

then a final rmsnorm and the untied head. Departures from the published
training recipe: the loss is next-token cross-entropy alone, without the
router's load-balancing and z losses (the benchmark's train check builds the
program with `moe_aux_weight` 0 to match). `clip_qkv`, biases, a shared
expert and tied embeddings are refused by the adapter, not computed.

Memory: one layer's float32 copy is alive at a time (1.7 GB at the published
widths); attention is computed for a block of queries against the whole
context; the final layer and the head run on the last `last` positions.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 256


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [S, heads, hd]; rotate pairs (i, i + hd/2) by position * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, q_pos, k_pos):
    """q [Sq, H, hd], k/v [Sk, H, hd] (multi-head: as many K/V heads as
    queries, or fewer and shared by groups); causal by position; in blocks of
    queries."""
    sq, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    outs = []
    for start in range(0, sq, Q_BLOCK):
        qb = q[start:start + Q_BLOCK].reshape(-1, kvh, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(F32(hd))
        mask = k_pos[None, :] <= q_pos[start:start + Q_BLOCK, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, h * hd))
    return jnp.concatenate(outs, 0)


def _experts(h, lp, m):
    """h [T, D] -> [T, D]: the published mixture, every expert on every row."""
    n, k = m["num_experts"], m["num_experts_per_tok"]
    p = jax.nn.softmax(h @ lp["router"], axis=-1)              # [T, E]
    w, e = jax.lax.top_k(p, k)
    if m.get("norm_topk_prob", False):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    combine = jnp.sum(w[:, :, None] * jax.nn.one_hot(e, n, dtype=F32), axis=1)

    def add_expert(out, expert):
        w_gate, w_up, w_down, share = expert            # share [T]: 0 if not chosen
        y = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return out + share[:, None] * y, None

    # A loop over the experts, one at a time (a Python loop over 64 experts
    # takes the compiler minutes at the published widths).
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out


def _layer(x, lp, m, q_from):
    """One block on x [S, D]; returns the rows from `q_from` on (every row of
    K and V is still computed, from every row of x)."""
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    s = x.shape[0]
    pos = jnp.arange(s)
    hn = _rms_norm(x, lp["attn_norm"], eps)
    k = _rms_norm(hn @ lp["wk"], lp["k_norm"], eps)
    q = _rms_norm(hn[q_from:] @ lp["wq"], lp["q_norm"], eps)
    v = (hn @ lp["wv"]).reshape(s, kvh, hd)
    k = _rope(k.reshape(s, kvh, hd), pos, theta)
    q = _rope(q.reshape(s - q_from, h, hd), pos[q_from:], theta)
    x = x[q_from:] + _attention(q, k, v, pos[q_from:], pos) @ lp["wo"]
    return x + _experts(_rms_norm(x, lp["mlp_norm"], eps), lp, m)


def _layer_f32(params, i):
    return {k: v[i].astype(F32) for k, v in params["layers"].items()}


# What of a configuration the block's equations read.
_WIDTHS = ("num_attention_heads", "num_key_value_heads", "hidden_size",
           "rms_norm_eps", "rope_theta", "num_experts", "num_experts_per_tok",
           "norm_topk_prob")


@functools.lru_cache(maxsize=None)
def _programs(widths):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = dict(widths)
    layer = jax.jit(functools.partial(_layer, m=m), static_argnames="q_from")

    @jax.jit
    def head(x, norm, w):
        return _rms_norm(x, norm.astype(F32), m["rms_norm_eps"]) @ w.astype(F32)

    return layer, head


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int):
    """Float32 logits [last, V] at the last `last` positions of one sequence.
    Every layer but the final one runs on every position (its K and V feed the
    next layer); the final layer and the head run on the last `last` queries
    against the whole context."""
    n_layers = params["layers"]["wq"].shape[0]
    toks = jnp.asarray(tokens, jnp.int32)
    layer, head = _programs(tuple((k, m.get(k, False)) for k in _WIDTHS))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32)
        for i in range(n_layers):
            q_from = len(tokens) - last if i == n_layers - 1 else 0
            x = layer(x, _layer_f32(params, i), q_from=q_from)
        return head(x[-last:], params["final_norm"], params["lm_head"])


def served_token_gaps(params, m, prompt: List[int], served: List[int]):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    cache; the reference sees neither, only prompt + served as one sequence."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n)            # [n, V]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


# The stacked leaves whose gradients the train check compares, with
# `final_norm` (the adapter's CHECK_LEAVES): the norms, the q/k norms, the router.
CHECKED = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "router")


def loss_and_check_grads(params, m, tokens, checked=CHECKED):
    """Mean next-token cross-entropy over all positions but each row's last of
    tokens [B, S], and its gradients with respect to `final_norm` and the
    stacked leaves named in `checked` (any leaf of `layers`: a test adds the
    experts' `w_gate`)."""
    n_layers = params["layers"]["wq"].shape[0]

    def loss_of(leaves):
        total, count = 0.0, 0
        for row in tokens:
            x = params["embed"][row].astype(F32)
            for i in range(n_layers):
                lp = _layer_f32(params, i)
                lp.update({k: leaves[k][i] for k in checked})
                x = _layer(x, lp, m, 0)
            x = _rms_norm(x, leaves["final_norm"], m["rms_norm_eps"])
            logp = jax.nn.log_softmax(
                x[:-1] @ params["lm_head"].astype(F32), axis=-1)
            total = total - jnp.sum(jnp.take_along_axis(
                logp, row[1:, None], axis=-1))
            count += row.shape[0] - 1
        return total / count

    leaves = {k: params["layers"][k].astype(F32) for k in checked}
    leaves["final_norm"] = params["final_norm"].astype(F32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_of)(leaves)

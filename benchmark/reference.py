"""The plain reference: the decoder block of `arch: llama` in straightforward
jax.numpy, float32, matmuls at precision "highest", no kernel, no cache, no
scan, no remat. Written from the published description (pre-norm residual
block, RMSNorm, rotary embedding in the split-half convention of the public
implementation, grouped-query causal attention, SwiGLU, untied linear head),
not from the program's code; it shares with the program only the layout of
the parameter tree it is handed (stacked leaves `layers/<name>[L, ...]`,
`embed`, `final_norm`, `lm_head`), which is the system's interface.

Memory: one layer's float32 copy is alive at a time; attention is computed
for a block of queries against the whole context.
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
    """q [Sq, H, hd], k/v [Sk, KVH, hd]; causal by position; query blocks."""
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
        o = jnp.einsum("kgqs,skd->qkgd", p, v)
        outs.append(o.reshape(-1, h * hd))
    return jnp.concatenate(outs, 0)


def _layer(x, lp, m, q_from):
    """One block on x [S, D]; returns the rows from `q_from` on (every row of
    K and V is still computed, from every row of x)."""
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    s = x.shape[0]
    pos = jnp.arange(s)
    hn = _rms_norm(x, lp["attn_norm"], eps)
    k = _rope((hn @ lp["wk"]).reshape(s, kvh, hd), pos, theta)
    v = (hn @ lp["wv"]).reshape(s, kvh, hd)
    q = _rope((hn[q_from:] @ lp["wq"]).reshape(s - q_from, h, hd),
              pos[q_from:], theta)
    x = x[q_from:] + _attention(q, k, v, pos[q_from:], pos) @ lp["wo"]
    hn = _rms_norm(x, lp["mlp_norm"], eps)
    return x + (jax.nn.silu(hn @ lp["w_gate"]) * (hn @ lp["w_up"])) @ lp["w_down"]


def _layer_f32(params, i):
    return {k: v[i].astype(F32) for k, v in params["layers"].items()}


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int):
    """Float32 logits [last, V] at the last `last` positions of one sequence.
    Every layer but the final one runs on every position (its K and V feed the
    next layer); the final layer and the head run on the last `last` queries
    against the whole context."""
    n_layers = params["layers"]["wq"].shape[0]
    toks = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(functools.partial(_layer, m=m), static_argnames="q_from")
        x = params["embed"][toks].astype(F32)
        for i in range(n_layers):
            q_from = len(tokens) - last if i == n_layers - 1 else 0
            x = layer(x, _layer_f32(params, i), q_from=q_from)

        @jax.jit
        def head(x, norm, w):
            return _rms_norm(x, norm.astype(F32), m["rms_norm_eps"]) @ w.astype(F32)

        return head(x[-last:], params["final_norm"], params["lm_head"])


def served_token_gaps(params, m, prompt: List[int], served: List[int]):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    cache; the reference sees neither, only prompt + served as one sequence."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n)            # [n, V]
    top = jnp.max(logits, axis=-1)
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (top - got)]


def loss_and_norm_grads(params, m, tokens):
    """Next-token cross-entropy (mean over all positions but the last, as the
    published training objective) of tokens [B, S], and its gradients with
    respect to the norm weights: {"final_norm", "attn_norm", "mlp_norm"}."""
    n_layers = params["layers"]["wq"].shape[0]

    def loss_of(norms):
        total, count = 0.0, 0
        for row in tokens:
            x = params["embed"][row].astype(F32)
            for i in range(n_layers):
                lp = _layer_f32(params, i)
                lp["attn_norm"] = norms["attn_norm"][i]
                lp["mlp_norm"] = norms["mlp_norm"][i]
                x = _layer(x, lp, m, 0)
            x = _rms_norm(x, norms["final_norm"], m["rms_norm_eps"])
            logits = x @ params["lm_head"].astype(F32)
            logp = jax.nn.log_softmax(logits[:-1], axis=-1)
            total = total - jnp.sum(jnp.take_along_axis(
                logp, row[1:, None], axis=-1))
            count += row.shape[0] - 1
        return total / count

    norms = {"final_norm": params["final_norm"].astype(F32),
             "attn_norm": params["layers"]["attn_norm"].astype(F32),
             "mlp_norm": params["layers"]["mlp_norm"].astype(F32)}
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_of)(norms)


loss_and_check_grads = loss_and_norm_grads   # the adapter contract's name

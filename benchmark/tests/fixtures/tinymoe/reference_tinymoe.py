"""The plain reference of the fixture architecture `tinymoe`: float32,
precision "highest", every expert computed densely on every token, no
dispatch buffers. Attention is the dense block's (benchmark/reference.py);
the feed-forward follows the equations of ops/moe.py:

  logits = h W_r                              [T, E], T = all tokens of the batch
  (v, idx) = top_k(logits, k); w = softmax(v) over the k selected
  capacity = max(1, ceil(T k 1.25 / E)); assignments are ranked token-major
  (t, j); one whose expert already holds `capacity` earlier ones is dropped
  out[t] = sum_j keep[t, j] w[t, j] FFN_idx[t, j](h[t])
  aux = E sum_e mean_t(#assignments of t to e) mean_t(softmax(logits)[t, e])
  loss = next-token cross-entropy + 0.01 sum_layers aux
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import F32, _attention, _rms_norm, _rope

CAPACITY_FACTOR = 1.25
AUX_WEIGHT = 0.01


def _moe(h, lp, k):
    """h [T, D] -> (out [T, D], aux)."""
    t, e = h.shape[0], lp["router"].shape[-1]
    logits = h @ lp["router"]
    vals, idx = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(vals, axis=-1)
    chosen = jax.nn.one_hot(idx.reshape(-1), e, dtype=jnp.int32)  # [T k, E]
    earlier = jnp.cumsum(chosen, axis=0) - chosen
    rank = jnp.sum(earlier * chosen, axis=-1)
    keep = rank < max(1, math.ceil(t * k * CAPACITY_FACTOR / e))
    combine = ((w.reshape(-1) * keep)[:, None] * chosen).reshape(t, k, e).sum(1)
    out = jnp.zeros_like(h)
    for i in range(e):
        y = (jax.nn.silu(h @ lp["w_gate"][i]) * (h @ lp["w_up"][i])) \
            @ lp["w_down"][i]
        out = out + combine[:, i, None] * y
    share = jnp.mean(chosen.reshape(t, k, e).sum(1).astype(F32), axis=0)
    aux = e * jnp.sum(share * jnp.mean(jax.nn.softmax(logits, -1), axis=0))
    return out, aux


def _block(x, lp, m):
    """x [B, S, D] -> (x, aux): attention a row at a time, experts over all
    the batch's tokens at once (the capacity is theirs together)."""
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    b, s, d = x.shape
    pos = jnp.arange(s)
    rows = []
    for row in x:
        hn = _rms_norm(row, lp["attn_norm"], eps)
        q = _rope((hn @ lp["wq"]).reshape(s, h, hd), pos, theta)
        kk = _rope((hn @ lp["wk"]).reshape(s, kvh, hd), pos, theta)
        v = (hn @ lp["wv"]).reshape(s, kvh, hd)
        rows.append(row + _attention(q, kk, v, pos, pos) @ lp["wo"])
    x = jnp.stack(rows)
    hn = _rms_norm(x, lp["mlp_norm"], eps).reshape(b * s, d)
    out, aux = _moe(hn, lp, m["num_experts_per_tok"])
    return x + out.reshape(b, s, d), aux


def _logits(params, m, tokens, leaves):
    """tokens [B, S] -> (float32 logits [B, S, V], summed aux). `leaves`
    replaces the parameters the gradient is taken of."""
    x = params["embed"][tokens].astype(F32)
    aux = 0.0
    for i in range(params["layers"]["wq"].shape[0]):
        lp = {k: v[i].astype(F32) for k, v in params["layers"].items()}
        lp.update({k: v[i] for k, v in leaves.items() if k != "final_norm"})
        x, a = _block(x, lp, m)
        aux = aux + a
    x = _rms_norm(x, leaves["final_norm"], m["rms_norm_eps"])
    return x @ params["lm_head"].astype(F32), aux


def _check_leaves(params):
    lay = params["layers"]
    return {"final_norm": params["final_norm"].astype(F32),
            "attn_norm": lay["attn_norm"].astype(F32),
            "mlp_norm": lay["mlp_norm"].astype(F32),
            "router": lay["router"].astype(F32)}


def logits_all(params, m, tokens):
    with jax.default_matmul_precision("highest"):
        return _logits(params, m, jnp.asarray(tokens, jnp.int32),
                       _check_leaves(params))[0]


def served_token_gaps(params, m, prompt, served):
    """As benchmark/reference.py's: the reference's largest logit minus its
    logit of each served token, over prompt + served as one sequence."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_all(params, m, [seq])[0, -n:]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


def loss_and_check_grads(params, m, tokens):
    """Mean next-token cross-entropy over all positions but each row's last,
    plus the weighted balancing term, and its gradients with respect to the
    adapter's CHECK_LEAVES: the three norms and the router."""

    def loss_of(leaves):
        logits, aux = _logits(params, m, tokens, leaves)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll) + AUX_WEIGHT * aux

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_of)(_check_leaves(params))

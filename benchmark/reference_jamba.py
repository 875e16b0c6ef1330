"""The plain reference of `arch: jamba` (AI21-Jamba2-3B): a hybrid stack of
Mamba-1 layers and attention layers, in straightforward jax.numpy, float32,
matmuls at precision "highest": no kernel, no cache, no carried state, the
recurrence one token at a time in a `lax.scan`. Written from the published
`config.json` keys and transformers' `modeling_jamba.py` (JambaMambaMixer's
slow path, JambaAttention, JambaMLP), not from the program's code; it shares
with the program only the layout of the parameter tree it is handed.

Layer i is attention where i % attn_layer_period == attn_layer_offset, else
Mamba. With D = hidden_size, Di = mamba_expand * D, N = mamba_d_state, R =
mamba_dt_rank, K = mamba_d_conv, every norm an RMS norm in float32:

  every layer   h = h + mixer(rmsnorm(h; input norm))
                h = h + (silu(g Wg) * (g Wu)) Wd,   g = rmsnorm(h; pre-ff norm)
  Mamba mixer   x, z   = split(g W_in)                          [S, Di] each
                x_t    = silu(b_c + sum_{k<K} w_c[k] * x_{t-K+1+k})   zeros
                         before the sequence's start
                r, B, C = split(x W_x)   (R, N, N), each RMS-normalised
                dt     = softplus(r W_dt + b_dt)                     [S, Di]
                A      = -exp(A_log)                                 [N, Di]
                s_t    = exp(dt_t (x) A) * s_{t-1} + (dt_t * x_t) (x) B_t
                y_t    = s_t . C_t + D * x_t
                out    = (y * silu(z)) W_out
  attention     q, k, v, o without bias, num_attention_heads query heads on
                num_key_value_heads kv heads of hidden_size / heads, scale
                head_dim^-1/2, causal, NO rotary or other position signal
  after the last layer rmsnorm(h; final norm); logits = h embed^T (tied)

The tree: `layers` holds the attention layers in order (the leaves of
benchmark/reference.py's block), `mamba` the others (`norm`, `in_proj`,
`conv_w` [K, Di], `conv_b`, `x_proj`, `dt_norm`, `b_norm`, `c_norm`,
`dt_proj`, `dt_bias`, `A_log` [N, Di], `D`, `out_proj`, and its own
`mlp_norm`, `w_gate`, `w_up`, `w_down`); `embed`, `final_norm`; no `lm_head`.

Memory: one layer's float32 copy is alive at a time; attention runs for a
block of queries against the whole context.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _feed_forward(h, lp, eps):
    g = _rms_norm(h, lp["mlp_norm"], eps)
    return h + (jax.nn.silu(g @ lp["w_gate"]) * (g @ lp["w_up"])) @ lp["w_down"]


def _mamba_mixer(g, lp, m, state_dtype=F32):
    """g [S, D], already normed -> the mixer's output [S, D]. `state_dtype`
    is float32; the tests pass a narrower one to show that their tolerance
    tells the two apart."""
    eps = m["rms_norm_eps"]
    n, r, k = m["mamba_d_state"], m["mamba_dt_rank"], m["mamba_d_conv"]
    s_len = g.shape[0]
    x, z = jnp.split(g @ lp["in_proj"], 2, axis=-1)
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    x = jax.nn.silu(lp["conv_b"] + sum(
        lp["conv_w"][j] * padded[j:j + s_len] for j in range(k)))
    p = x @ lp["x_proj"]
    dt = _rms_norm(p[:, :r], lp["dt_norm"], eps)
    b = _rms_norm(p[:, r:r + n], lp["b_norm"], eps)
    c = _rms_norm(p[:, r + n:], lp["c_norm"], eps)
    dt = jax.nn.softplus(dt @ lp["dt_proj"] + lp["dt_bias"])        # [S, Di]
    a = -jnp.exp(lp["A_log"])                                       # [N, Di]

    def token(s, row):
        dt_t, x_t, b_t, c_t = row
        s = jnp.exp(dt_t[None, :] * a) * s.astype(F32) \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        s = s.astype(state_dtype)
        return s, jnp.sum(s.astype(F32) * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros(a.shape, state_dtype), (dt, x, b, c),
                        unroll=8)
    y = y + lp["D"] * x
    return (y * jax.nn.silu(z)) @ lp["out_proj"]


def _attention(g, lp, m, rope_theta=None):
    """g [S, D], already normed -> [S, D]. `rope_theta` is None: the model
    takes no position signal; the tests pass one to show that a model with
    RoPE is another model."""
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    s_len = g.shape[0]
    q = (g @ lp["wq"]).reshape(s_len, h, hd)
    k = (g @ lp["wk"]).reshape(s_len, kvh, hd)
    v = (g @ lp["wv"]).reshape(s_len, kvh, hd)
    if rope_theta is not None:
        inv = 1.0 / (rope_theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
        ang = jnp.arange(s_len, dtype=F32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

        def turn(t):
            t1, t2 = t[..., : hd // 2], t[..., hd // 2:]
            return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                                   -1)
        q, k = turn(q), turn(k)
    pos = jnp.arange(s_len)
    outs = []
    for start in range(0, s_len, Q_BLOCK):
        rows = slice(start, start + Q_BLOCK)
        qb = q[rows].reshape(-1, kvh, h // kvh, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(F32(hd))
        causal = pos[None, :] <= pos[rows, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, h * hd))
    return jnp.concatenate(outs, 0) @ lp["wo"]


def _mamba_layer(h, lp, m, state_dtype=F32):
    eps = m["rms_norm_eps"]
    h = h + _mamba_mixer(_rms_norm(h, lp["norm"], eps), lp, m, state_dtype)
    return _feed_forward(h, lp, eps)


def _attention_layer(h, lp, m, rope_theta=None):
    eps = m["rms_norm_eps"]
    h = h + _attention(_rms_norm(h, lp["attn_norm"], eps), lp, m, rope_theta)
    return _feed_forward(h, lp, eps)


def _is_attention(m, i: int) -> bool:
    return i % m["attn_layer_period"] == m["attn_layer_offset"]


def _layer_f32(stack, i):
    return {k: v[i].astype(F32) for k, v in stack.items()}


# What of a configuration the layers' equations read.
_WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "rms_norm_eps", "mamba_d_state", "mamba_dt_rank", "mamba_d_conv")


@functools.lru_cache(maxsize=None)
def _programs(widths, rope_theta, state_dtype):
    """(mamba layer, attention layer, head) compiled once for a set of
    widths: the control calls `logits_last` once a token, and a `jax.jit`
    made anew is traced anew."""
    m = dict(widths)
    mamba = jax.jit(functools.partial(_mamba_layer, m=m,
                                      state_dtype=state_dtype))
    attention = jax.jit(functools.partial(_attention_layer, m=m,
                                          rope_theta=rope_theta))

    @jax.jit
    def head(x, norm, embed):
        return _rms_norm(x, norm.astype(F32), m["rms_norm_eps"]) \
            @ embed.astype(F32).T

    return mamba, attention, head


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int, *, rope_theta=None,
                state_dtype=F32):
    """Float32 logits [last, V] at the last `last` positions of one sequence.
    Every layer runs on every position: a state-space layer's last rows
    depend on all before them."""
    toks = jnp.asarray(tokens, jnp.int32)
    mamba, attention, head = _programs(
        tuple((k, m[k]) for k in _WIDTHS), rope_theta, state_dtype)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32)
        a = s = 0
        for i in range(m["num_hidden_layers"]):
            if _is_attention(m, i):
                x = attention(x, _layer_f32(params["layers"], a))
                a += 1
            else:
                x = mamba(x, _layer_f32(params["mamba"], s))
                s += 1
        return head(x[-last:], params["final_norm"], params["embed"])


def served_token_gaps(params, m, prompt: List[int], served: List[int]):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    cache and the recurrent state; the reference sees neither, only prompt +
    served as one sequence."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n)            # [n, V]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


# (stack, leaf) of the stacked leaves whose gradients a train check would
# compare, with `final_norm` (the adapter's CHECK_LEAVES).
CHECKED = (("layers", "attn_norm"), ("mamba", "norm"), ("mamba", "dt_norm"))


def loss_and_check_grads(params, m, tokens, checked=CHECKED):
    """Mean next-token cross-entropy over all positions but each row's last of
    tokens [B, S], and its gradients with respect to `final_norm` and the
    stacked leaves named in `checked` (keyed by the leaf's name)."""
    eps = m["rms_norm_eps"]

    def loss_of(leaves):
        total, count = 0.0, 0
        for row in tokens:
            x = params["embed"][row].astype(F32)
            a = s = 0
            for i in range(m["num_hidden_layers"]):
                stack, at = ("layers", a) if _is_attention(m, i) \
                    else ("mamba", s)
                lp = _layer_f32(params[stack], at)
                lp.update({k: leaves[k][at] for st, k in checked
                           if st == stack})
                if stack == "layers":
                    x, a = _attention_layer(x, lp, m), a + 1
                else:
                    x, s = _mamba_layer(x, lp, m), s + 1
            x = _rms_norm(x, leaves["final_norm"], eps)
            logp = jax.nn.log_softmax(
                x[:-1] @ params["embed"].astype(F32).T, axis=-1)
            total = total - jnp.sum(jnp.take_along_axis(
                logp, row[1:, None], axis=-1))
            count += row.shape[0] - 1
        return total / count

    leaves = {k: params[st][k].astype(F32) for st, k in checked}
    leaves["final_norm"] = params["final_norm"].astype(F32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_of)(leaves)

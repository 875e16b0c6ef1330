"""The plain reference of `arch: granitemoehybrid` (Granite-4.0-H-Small): a
hybrid stack of Mamba-2 layers and attention layers over sparse experts
beside a shared expert, in straightforward jax.numpy, float32, matmuls at
precision "highest": no kernel, no chunks, no cache, no carried state, the
recurrence ROW BY ROW in a `lax.scan` over t. Written from the published
`config.json` keys and transformers' `modeling_granitemoehybrid.py`
(GraniteMoeHybridMambaLayer, whose mixer is Bamba's, its slow path;
GraniteMoeHybridAttention; GraniteMoeHybridMoE and its shared MLP), not from
the program's code; it shares with the program only the layout of the
parameter tree it is handed, and imports nothing of it.

With D = hidden_size, H = mamba_n_heads heads of P = mamba_d_head channels, Di
= H P (= mamba_expand D), N = mamba_d_state, ONE group of B and C
(mamba_n_groups 1), K = mamba_d_conv taps, every norm an RMS norm with a
weight, eps rms_norm_eps, in float32:

  h_0    = embedding_multiplier * embed[ids]
  layer    h = x + residual_multiplier * Mixer(norm_1(x))
           y = h + residual_multiplier * (Routed(norm_2(h)) + Shared(norm_2(h)))
  logits = norm_f(h_L) embed^T / logits_scaling                        (tied)

  Mamba-2 mixer, u = norm_1(x) (layer_types[i] == "mamba"):
    z, xBC, dt = split(u W_in, [Di, Di + 2N, H])                    (no bias)
    xBC  = silu(b + sum_k w[k] * xBC_{t-K+1+k})     zeros before the start
    x, B, C = split(xBC, [Di, N, N])
    dt_t^h = softplus(dt_t^h + dt_bias^h);  a_t^h = exp(dt_t^h A^h),
    A^h = -exp(A_log^h)                  (time_step_limit (0, inf): no clamp)
    S_t^h = a_t^h S_{t-1}^h + dt_t^h x_t^h (x) B_t        (S^h is P x N)
    y_t^h = S_t^h C_t + D^h x_t^h
    out  = rmsnorm(y * silu(z); w_norm) W_out    (the gate BEFORE the norm,
                                                  which is over all Di)
  attention ("attention"): q, k, v, o without bias, num_attention_heads query
    heads on num_key_value_heads kv heads of hidden_size / heads, causal, NO
    position signal, softmax(q . k * attention_multiplier)
  Routed: l = u W_r (float32); the num_experts_per_tok largest of the
    router's outputs (`lax.top_k`); g = softmax over those alone;
    sum_j g_j W_down^e (silu(u W_gate^e) * (u W_up^e))
  Shared: the same form at shared_intermediate_size, every token, weight 1

A SHARE. The tree may hold the experts `num_local_experts` counts of the
`expert_parallel.routed_experts_total` its router scores (rank r: experts r n
.. r n + n - 1): the routed part is then the sum over the chosen experts that
are held, the others add nothing, and the shared expert is counted here.
Without `expert_parallel` every expert is held.

Departures from transformers' text, none of which moves a number: its
convolution cache keeps K = 4 columns where a causal convolution of 4 taps
reads the 3 before the current one; its fast path computes a prompt by chunks
(`mamba_chunk_size`), which is an implementation's size and no equation; the
state is float32 here whatever the checkpoint's dtype; the experts' input
matrix is published fused (`input_linear`, gate rows then up rows) and is two
leaves here.

The tree: `layers` holds the attention layers in order (`attn_norm`, `wq`,
`wk`, `wv`, `wo`), `mamba` the others (`norm`, `in_proj` [D, 2 Di + 2N + H],
`conv_w` [K, Di + 2N], `conv_b`, `dt_bias` [H], `A_log` [H], `D` [H],
`w_norm` [Di], `out_proj`); both have `mlp_norm`, `router` [D, total],
`w_gate`, `w_up` [held, D, F], `w_down` [held, F, D], `ws_gate`, `ws_up`,
`ws_down`; `embed`, `final_norm`; no `lm_head`.

Memory: one layer's float32 copy is alive at a time and the experts' stacks
stay as they are stored, ONE expert read out of its stack and made float32 at
a time; attention runs for a block of queries against the whole context.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512
_EXPERTS = ("w_gate", "w_up", "w_down")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def inner(m: Dict[str, Any]) -> int:
    return m["mamba_n_heads"] * m["mamba_d_head"]


def held_experts(m: Dict[str, Any]) -> Tuple[Tuple[int, int], int]:
    """((offset, count), the router's width) of a configuration."""
    n = m["num_local_experts"]
    ep = m.get("expert_parallel")
    if not ep:
        return (0, n), n
    return (ep["rank"] * n, n), ep["routed_experts_total"]


def mamba2_mixer(u, lp, m, state_dtype=F32):
    """u [S, D], already normed -> the mixer's output [S, D]. `state_dtype`
    is float32; the tests pass a narrower one to show that their tolerance
    tells the two apart."""
    h, p, n, k = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                  m["mamba_d_conv"])
    di, s_len = h * p, u.shape[0]
    z, xbc, dt = jnp.split(u @ lp["in_proj"], [di, 2 * di + 2 * n], axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(lp["conv_b"] + sum(
        lp["conv_w"][j] * padded[j:j + s_len] for j in range(k)))
    x, b, c = jnp.split(xbc, [di, di + n], axis=-1)
    x = x.reshape(s_len, h, p)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                        # [S, H]
    a = -jnp.exp(lp["A_log"])                                       # [H]

    def token(s, row):
        dt_t, x_t, b_t, c_t = row           # [H], [H, P], [N], [N]
        s = jnp.exp(dt_t * a)[:, None, None] * s.astype(F32) \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        s = s.astype(state_dtype)
        return s, jnp.sum(s.astype(F32) * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((h, p, n), state_dtype),
                        (dt, x, b, c), unroll=8)
    y = (y + lp["D"][:, None] * x).reshape(s_len, di)
    return _rms_norm(y * jax.nn.silu(z), lp["w_norm"], m["rms_norm_eps"]) \
        @ lp["out_proj"]


def attention(u, lp, m, rope_theta=None):
    """u [S, D], already normed -> [S, D]. `rope_theta` is None: the model
    takes no position signal; the tests pass one to show that a model with
    RoPE is another model."""
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    s_len = u.shape[0]
    q = (u @ lp["wq"]).reshape(s_len, h, hd)
    k = (u @ lp["wk"]).reshape(s_len, kvh, hd)
    v = (u @ lp["wv"]).reshape(s_len, kvh, hd)
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
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * m["attention_multiplier"]
        causal = pos[None, :] <= pos[rows, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, h * hd))
    return jnp.concatenate(outs, 0) @ lp["wo"]


def route(u, router, m):
    """u [T, D] -> the router's combine matrix [T, total]: a token's weight
    for each expert the router scores, 0 where it is not among its chosen."""
    logits = u @ router                                          # [T, total]
    vals, chosen = jax.lax.top_k(logits, m["num_experts_per_tok"])
    gates = jax.nn.softmax(vals, axis=-1)
    return jnp.sum(gates[:, :, None] * jax.nn.one_hot(
        chosen, logits.shape[-1], dtype=F32), axis=1)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def routed_part(u, lp, m, layer=None):
    """u [T, D] -> [T, D]: the part of the routed mixture that the experts
    held give, each of them on every row, weighted by the router's weight for
    it (0 where the token did not choose it). lp["w_gate"/"w_up"/"w_down"]
    hold those experts, or with `layer` all the layers' (`[L, held, ...]`:
    the stacks as they are stored, an expert read out of them where it is
    used)."""
    (offset, count), _ = held_experts(m)
    share = route(u, lp["router"], m)[:, offset:offset + count]

    def add_expert(out, expert):
        e, weight = expert
        w_gate, w_up, w_down = (
            lp[k][e] if layer is None else lp[k][layer, e] for k in _EXPERTS)
        return out + weight[:, None] * _swiglu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                          (jnp.arange(count), share.T))
    return out


def shared_part(u, lp):
    return _swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def feed_forward_half(h, lp, m, layer=None):
    u = _rms_norm(h, lp["mlp_norm"], m["rms_norm_eps"])
    return h + m["residual_multiplier"] * (routed_part(u, lp, m, layer)
                                           + shared_part(u, lp))


def mixer_half(x, lp, m, mamba: bool, rope_theta=None, state_dtype=F32):
    eps = m["rms_norm_eps"]
    if mamba:
        out = mamba2_mixer(_rms_norm(x, lp["norm"], eps), lp, m, state_dtype)
    else:
        out = attention(_rms_norm(x, lp["attn_norm"], eps), lp, m, rope_theta)
    return x + m["residual_multiplier"] * out


def _layer(x, lp, m, mamba, rope_theta=None, state_dtype=F32, layer=None):
    return feed_forward_half(
        mixer_half(x, lp, m, mamba, rope_theta, state_dtype), lp, m, layer)


def stack_order(m: Dict[str, Any]) -> List[Tuple[str, int, bool]]:
    """(stack, ordinal in it, whether a Mamba-2 layer) of each layer in the
    order they run."""
    out, at = [], {"layers": 0, "mamba": 0}
    for kind in m["layer_types"][:m["num_hidden_layers"]]:
        name = "mamba" if kind == "mamba" else "layers"
        out.append((name, at[name], kind == "mamba"))
        at[name] += 1
    return out


# What of a configuration the layers' equations read.
_WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
           "mamba_d_conv", "num_experts_per_tok", "num_local_experts",
           "attention_multiplier", "residual_multiplier")


@functools.lru_cache(maxsize=None)
def _programs(widths, share, rope_theta, state_dtype):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = dict(widths)
    if share:
        m["expert_parallel"] = dict(share)
    layer = jax.jit(functools.partial(
        _layer, m=m, rope_theta=rope_theta, state_dtype=state_dtype),
        static_argnames="mamba")

    @jax.jit
    def head(x, norm, embed):
        return _rms_norm(x, norm.astype(F32), m["rms_norm_eps"]) \
            @ embed.astype(F32).T

    return layer, head


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int, *, rope_theta=None,
                state_dtype=F32):
    """Float32 logits [last, V] at the last `last` positions of one sequence.
    Every layer runs on every position: a state-space layer's last rows
    depend on all before them."""
    toks = jnp.asarray(tokens, jnp.int32)
    ep = m.get("expert_parallel")
    layer, head = _programs(
        tuple((k, m[k]) for k in _WIDTHS),
        tuple((k, ep[k]) for k in ("rank", "routed_experts_total"))
        if ep else None, rope_theta, state_dtype)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32) * m["embedding_multiplier"]
        for name, i, mamba in stack_order(m):
            # the experts' stacks whole, with the layer's index
            lp = {k: v if k in _EXPERTS else v[i].astype(F32)
                  for k, v in params[name].items()}
            x = layer(x, lp, mamba=mamba, layer=i)
        return head(x[-last:], params["final_norm"], params["embed"]) \
            / m["logits_scaling"]


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


def loss_and_check_grads(params, m, tokens, checked=None):
    raise NotImplementedError(
        "arch 'granitemoehybrid' serves only: the program's training forward "
        "refuses state-space layers, and a share of the experts takes no "
        "gradient for the experts that are absent")

"""The plain reference of `arch: nemotron_h` (NVIDIA-Nemotron-3-Nano-30B-A3B):
a stack whose layers are ONE part each, a Mamba-2 mixer with groups of B and
C, OR sparse relu^2 experts beside a shared one, OR attention without a
position signal, in straightforward jax.numpy, float32, matmuls at precision
"highest": no kernel, no chunks, no cache, no carried state, no batching, the
recurrence ROW BY ROW in a `lax.scan` over t. Written from the published
`config.json` keys and the family's published block (`NemotronHBlock`,
`NemotronHMamba2Mixer`, `NemotronHMOE`, `NemotronHAttention`), as ISSUE 55
writes its equations, not from the program's code; it shares with the program
only the layout of the parameter tree it is handed, and imports nothing of
it.

With D = hidden_size (2,688), eps = layer_norm_epsilon (1e-5), every norm an
RMS norm with a weight, no bias anywhere but the convolution's, in float32:

  h_0    = embed[ids]
  layer i  h <- h + part_i(rmsnorm(h; w_i)),  part_i by the i-th letter of
           hybrid_override_pattern: M, E or *
  logits = rmsnorm(h_L; w_f) W_head                     (the head untied)

  M, a Mamba-2 mixer: H = mamba_num_heads heads of P = mamba_head_dim
  channels, Di = H P (4,096: NOT expand x D), N = ssm_state_size, G =
  n_groups groups of B and C, head h reads group g(h) = h // (H / G), K =
  conv_kernel taps:
    z, xBC, dt = split(u W_in, [Di, Di + 2 G N, H])
    xBC  = silu(b + sum_k w[k] * xBC_{t-K+1+k})     zeros before the start
    x, B, C = split(xBC, [Di, G N, G N]);  B, C a row: [G, N]
    dt_t^h = softplus(dt_t^h + dt_bias^h)    (time_step_limit (0, inf): no
             clamp; time_step_min, _max, _floor shape dt_bias's start alone)
    A^h = -exp(A_log^h)
    S_t^h = exp(dt_t^h A^h) S_{t-1}^h + dt_t^h x_t^h (x) B_t^{g(h)}   (P x N)
    y_t^h = S_t^h C_t^{g(h)} + D^h x_t^h
    y    <- y * silu(z), then an RMS norm over each GROUP's Di / G channels
            apart, times w_norm [Di]       (the gate BEFORE the norm)
    out  = y W_out
  E, the experts: s = sigmoid(u W_r) over all n routed experts; the
    num_experts_per_tok largest of s + e_score_correction_bias (n_group 1,
    topk_group 1: no group limit); w = s at the chosen, / (sum w + 1e-20)
    (norm_topk_prob), x routed_scaling_factor; expert e: relu(u W_up^e)^2
    W_down^e, no gate; the shared expert the same at
    moe_shared_expert_intermediate_size with weight 1;
    out = sum_e w_e expert_e(u) + shared(u)
  *, attention: q [D, heads x head_dim], k and v [D, kv heads x head_dim], o
    [heads x head_dim, D]; causal softmax(q k^T / sqrt(head_dim)) v,
    heads / kv heads query heads a kv head; NO rotary embedding (the
    family's attention applies none: ASSUMED from its published code, the one
    statement here that no key of `config.json` makes; `rope_theta` and
    `partial_rotary_factor` are read by nothing in the block)

A SHARE. The tree may hold the `n_routed_experts` experts of the
`expert_parallel.routed_experts_total` its router scores (rank r: experts r n
.. r n + n - 1): the routed part is then the sum over the chosen experts that
are held, the others add nothing, and the shared expert is counted here.
Without `expert_parallel` every expert is held.

Departures from the published text, none of which moves a number: a
convolution cache of K columns where a causal convolution of K taps reads the
K - 1 before the current one; a prompt by chunks (`chunk_size`), which is an
implementation's size and no equation; the state is float32 here whatever
the checkpoint's dtype; the residual stream is float32 here (the model's
`residual_in_fp32` false keeps it in the model's dtype, which this reference
has none of).

The tree: `layers` holds the attention layers in order (`attn_norm`, `wq`,
`wk`, `wv`, `wo`), `mamba` the mixers (`norm`, `in_proj` [D, 2 Di + 2 G N +
H], `conv_w` [K, Di + 2 G N], `conv_b`, `dt_bias` [H], `A_log` [H], `D` [H],
`w_norm` [Di], `out_proj`), `experts` the expert layers (`mlp_norm`,
`router` [D, total], `router_bias` [total], `w_up` [held, F, D] (the up
matrix as its `nn.Linear` weight is published, [out, in]), `w_down` [held, F,
D], `ws_up` [Fs, D], `ws_down` [Fs, D]); `embed`, `final_norm`, `lm_head`.

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
_EXPERTS = ("w_up", "w_down")
_STACKS = {"M": "mamba", "E": "experts", "*": "layers"}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def inner(m: Dict[str, Any]) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def pattern(m: Dict[str, Any]) -> str:
    return m["hybrid_override_pattern"][:m["num_hidden_layers"]]


def held_experts(m: Dict[str, Any]) -> Tuple[Tuple[int, int], int]:
    """((offset, count), the router's width) of a configuration."""
    n = m["n_routed_experts"]
    ep = m.get("expert_parallel")
    if not ep:
        return (0, n), n
    return (ep["rank"] * n, n), ep["routed_experts_total"]


def mamba2_mixer(u, lp, m, state_dtype=F32):
    """u [S, D], already normed -> the mixer's output [S, D]. `state_dtype`
    is float32; the tests pass a narrower one to show that their tolerance
    tells the two apart."""
    h, p, n, k, g = (m["mamba_num_heads"], m["mamba_head_dim"],
                     m["ssm_state_size"], m["conv_kernel"], m["n_groups"])
    di, s_len = h * p, u.shape[0]
    z, xbc, dt = jnp.split(u @ lp["in_proj"], [di, 2 * di + 2 * g * n],
                           axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(lp["conv_b"] + sum(
        lp["conv_w"][j] * padded[j:j + s_len] for j in range(k)))
    x, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
    x = x.reshape(s_len, h, p)
    # head h reads group h // (H / G): a group's row at each of its heads
    b, c = (jnp.repeat(t.reshape(s_len, g, n), h // g, axis=1)
            for t in (b, c))                                     # [S, H, N]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                        # [S, H]
    a = -jnp.exp(lp["A_log"])                                       # [H]

    def token(s, row):
        dt_t, x_t, b_t, c_t = row           # [H], [H, P], [H, N], [H, N]
        s = jnp.exp(dt_t * a)[:, None, None] * s.astype(F32) \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        s = s.astype(state_dtype)
        return s, jnp.sum(s.astype(F32) * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((h, p, n), state_dtype),
                        (dt, x, b, c), unroll=8)
    y = (y + lp["D"][:, None] * x).reshape(s_len, di)
    gated = (y * jax.nn.silu(z)).reshape(s_len, g, di // g)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), -1, keepdims=True)
        + m["layer_norm_epsilon"])
    return (normed.reshape(s_len, di) * lp["w_norm"]) @ lp["out_proj"]


def attention(u, lp, m, rope_theta=None):
    """u [S, D], already normed -> [S, D]. `rope_theta` is None: the model
    takes no position signal; the tests pass one to show that a model with
    RoPE is another model."""
    h, kvh, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
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
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * hd ** -0.5
        causal = pos[None, :] <= pos[rows, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, h * hd))
    return jnp.concatenate(outs, 0) @ lp["wo"]


def route(u, router, bias, m):
    """u [T, D] -> the router's combine matrix [T, total]: a token's weight
    for each expert the router scores, 0 where it is not among its chosen.
    The bias CHOOSES and does not weigh."""
    s = jax.nn.sigmoid(u @ router)                               # [T, total]
    _, chosen = jax.lax.top_k(s + bias, m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]
    return jnp.sum(w[:, :, None] * jax.nn.one_hot(
        chosen, s.shape[-1], dtype=F32), axis=1)


def _expert(u, w_up, w_down):
    """Both matrices [F, D]: the up matrix as an `nn.Linear` weight is
    published, [out, in]."""
    return _relu2(u @ w_up.astype(F32).T) @ w_down.astype(F32)


def routed_part(u, lp, m, layer=None, held=None):
    """u [T, D] -> [T, D]: the part of the routed mixture that the experts
    held give (`held` = ((offset, count), total); None: the configuration's,
    `held_experts`), each of them on every row, weighted by the router's
    weight for it (0 where the token did not choose it). lp["w_up"/"w_down"]
    hold those experts, or with `layer` all the layers' (`[L, held, ...]`:
    the stacks as they are stored, an expert read out of them where it is
    used)."""
    (offset, count), _ = held or held_experts(m)
    share = route(u, lp["router"], lp["router_bias"], m)[
        :, offset:offset + count]

    def add_expert(out, expert):
        e, weight = expert
        w_up, w_down = (lp[k][e] if layer is None else lp[k][layer, e]
                        for k in _EXPERTS)
        return out + weight[:, None] * _expert(u, w_up, w_down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                          (jnp.arange(count), share.T))
    return out


def shared_part(u, lp):
    return _expert(u, lp["ws_up"], lp["ws_down"])


def experts_part(u, lp, m, layer=None):
    return routed_part(u, lp, m, layer) + shared_part(u, lp)


def _layer(x, lp, m, part, rope_theta=None, state_dtype=F32, layer=None):
    """h + part(rmsnorm(h)): `part` the layer's letter."""
    eps = m["layer_norm_epsilon"]
    if part == "M":
        return x + mamba2_mixer(_rms_norm(x, lp["norm"], eps), lp, m,
                                state_dtype)
    if part == "E":
        return x + experts_part(_rms_norm(x, lp["mlp_norm"], eps), lp, m,
                                layer)
    return x + attention(_rms_norm(x, lp["attn_norm"], eps), lp, m,
                         rope_theta)


def stack_order(m: Dict[str, Any]) -> List[Tuple[str, int, str]]:
    """(stack, ordinal in it, the layer's letter) of each layer in the order
    they run."""
    out, at = [], dict.fromkeys(_STACKS.values(), 0)
    for part in pattern(m):
        name = _STACKS[part]
        out.append((name, at[name], part))
        at[name] += 1
    return out


# What of a configuration the layers' equations read.
_WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "head_dim", "layer_norm_epsilon", "mamba_num_heads",
           "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
           "num_experts_per_tok", "n_routed_experts", "routed_scaling_factor",
           "norm_topk_prob")


@functools.lru_cache(maxsize=None)
def _programs(widths, share, rope_theta, state_dtype):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = dict(widths)
    if share:
        m["expert_parallel"] = dict(share)
    layer = jax.jit(functools.partial(
        _layer, m=m, rope_theta=rope_theta, state_dtype=state_dtype),
        static_argnames="part")

    @jax.jit
    def head(x, norm, w_head):
        return _rms_norm(x, norm.astype(F32), m["layer_norm_epsilon"]) \
            @ w_head.astype(F32)

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
        tuple((k, m.get(k, True) if k == "norm_topk_prob" else m[k])
              for k in _WIDTHS),
        tuple((k, ep[k]) for k in ("rank", "routed_experts_total"))
        if ep else None, rope_theta, state_dtype)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32)
        for name, i, part in stack_order(m):
            # the experts' stacks whole, with the layer's index
            lp = {k: v if k in _EXPERTS else v[i].astype(F32)
                  for k, v in params[name].items()}
            x = layer(x, lp, part=part, layer=i)
        return head(x[-last:], params["final_norm"], params["lm_head"])


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
        "arch 'nemotron_h' serves only: the program's training forward "
        "refuses state-space layers, and a share of the experts takes no "
        "gradient for the experts that are absent")

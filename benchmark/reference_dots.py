"""The plain reference of `arch: dots`: the language model of dots.vlm1.inst,
a DeepSeek-V3-shaped decoder (latent attention, leading dense layers, a
sigmoid group-limited router over routed experts beside a shared one), in
straightforward jax.numpy, float32, matmuls at precision "highest": no
kernel, no cache, no absorbed products, every held expert computed densely
on every token. Written from the published description (transformers
`modeling_deepseek_v3.py`), not from the program's code; it shares with the
program only the layout of the parameter tree it is handed.

  g  = rmsnorm(x, w_in)                                  every norm in float32
  cq = rmsnorm(g W_DQ, w_q);  q = cq W_UQ -> 128 heads of [q_n (128); q_r (64)]
  [c ; kr] = g W_DKV (512 + 64);  c = rmsnorm(c, w_kv)
  q_r, kr rotated (YaRN's frequencies, below); ONE kr a token for all heads
  [k_n ; v] = c W_UKV -> 128 heads of (128 + 128);  k = [k_n ; kr]
  x  = x + (causal softmax of scale * q k^T) v W_O        NAIVE, for a prompt
                                                          and a decoded token
  scale = (128 + 64)^-1/2 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
  g  = rmsnorm(x, w_post)
  the first `first_k_dense_replace` layers:  x = x + SwiGLU_18432(g)
  the rest:  s = sigmoid(g W_r)  [256];  s' = s + b
      8 groups of 32; a group's score the sum of its two largest s'; the 4
      best groups stay; the 8 largest s' among THEIR experts are chosen
      (ties to the smaller index); weights s (not s') at those 8, divided by
      their sum, times 2.5
      x = x + sum_e w_e SwiGLU_e(g) + SwiGLU_shared(g)

  YaRN over the 32 pairs: f_i = theta^(-2i/64); lo, hi = floor, ceil of
  64 ln(orig / (b 2 pi)) / (2 ln theta) at b = beta_fast, beta_slow, clipped
  to [0, 63]; ramp_i = clip((i - lo) / (hi - lo), 0, 1);
  freq_i = f_i / factor * ramp_i + f_i (1 - ramp_i).

then a final rmsnorm and the untied head over the vocabulary's slice.

THE SHARE. The configuration holds `n_routed_experts` of the
`expert_parallel.routed_experts_total` experts its router scores (rank r:
experts r n .. r n + n - 1). The sum over e above runs over the HELD experts
among a token's 8 alone, as the program's does: what the absent experts
would have added is left out here too, and that partial result goes on to
the next layer. `routed_part(...)` with another `held` gives another
share's part: tests/test_dots.py adds the parts of all shares and the
shared expert once and finds the whole layer.

Departures from the published code, each the configuration's too
(`assumed`): RoPE pairs (i, i + 32), not (2i, 2i + 1) (a permutation of
weight columns that seeded weights do not distinguish); experts outside the
kept groups are out of the choice (the source fills their scores with 0.0,
which differs only for a negative biased score); the selection bias is a
parameter leaf (the source: a buffer that training moves until the load is
even; the adapter moves it so, benchmark/models/dots.py::init_params); the
multi-token-prediction module and the vision tower are not built. No loss: the adapter serves only.

Memory: weights come as they are stored and are made float32 where they are
used, a block at a time: the experts one at a time, the dense feed-forward
in four blocks of columns, attention 16 heads at a time (a dense layer whole
in float32 is 2.3 GB and all heads' q, k and v at 3,500 positions 1.4,
beside serving's 10); a block of heads runs a block of queries against the
whole context; the final layer and the head run on the last `last`
positions.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 128


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def yarn_inv_freq(m: Dict[str, Any]):
    """The 32 (qk_rope_head_dim / 2) rotary frequencies, by the closed form
    at the top."""
    dr, theta, r = m["qk_rope_head_dim"], m["rope_theta"], m["rope_scaling"]
    orig = r["original_max_position_embeddings"]

    def pair(turns):
        return dr * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair(r["beta_fast"])), 0)
    hi = min(math.ceil(pair(r["beta_slow"])), dr - 1)
    out = []
    for i in range(dr // 2):
        f = theta ** (-2.0 * i / dr)
        ramp = min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
        out.append(f / r["factor"] * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, F32)


def softmax_scale(m: Dict[str, Any]) -> float:
    r = m["rope_scaling"]
    mscale = 0.1 * r["mscale_all_dim"] * math.log(r["factor"]) + 1.0
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * mscale ** 2


def _rope(x, positions, inv_freq):
    """x [S, heads, dr]; pairs (i, i + dr/2) turned by position * freq_i."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, q_pos, scale):
    """q [Sq, H, dq] at positions q_pos against k [Sk, H, dq], v [Sk, H, dv]:
    causal, in blocks of queries."""
    k_pos = jnp.arange(k.shape[0])
    outs = []
    for start in range(0, q.shape[0], Q_BLOCK):
        rows = slice(start, start + Q_BLOCK)
        s = jnp.einsum("qhd,shd->hqs", q[rows], k) * scale
        s = jnp.where(k_pos[None, None, :] <= q_pos[rows][None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqs,shd->qhd", p, v).reshape(
            -1, v.shape[1] * v.shape[2]))
    return jnp.concatenate(outs, 0)


def route(g, router, bias, m, total: int):
    """g [T, D] -> the router's combine matrix [T, total]: a token's weight
    for each of the `total` experts, 0 where it is not among its 8."""
    k, n_group, topk_group = (m["num_experts_per_tok"], m["n_group"],
                              m["topk_group"])
    s = jax.nn.sigmoid(g @ router.astype(F32))                   # [T, total]
    return combine_from_scores(s, bias.astype(F32), k, n_group, topk_group,
                               bool(m["norm_topk_prob"]),
                               m["routed_scaling_factor"])


def combine_from_scores(s, bias, k, n_group, topk_group, norm, factor):
    """The choice and the weights from the sigmoid scores s [T, E]."""
    t, e = s.shape
    sp = s + bias
    groups = sp.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)  # [T, G]
    _, kept = jax.lax.top_k(group_score, topk_group)
    keep = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    among = jnp.where(jnp.repeat(keep, e // n_group, axis=1), sp, -jnp.inf)
    _, chosen = jax.lax.top_k(among, k)                          # [T, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * factor
    return jnp.sum(w[:, :, None] * jax.nn.one_hot(chosen, e, dtype=F32),
                   axis=1)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate.astype(F32)) * (g @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def routed_part(g, lp, m, held: Tuple[int, int], total: int, layer=None):
    """g [T, D] -> [T, D]: the part of the routed mixture that the experts
    `held` = (offset, count) give, each of them on every row, weighted by the
    router's weight for it (0 where the token did not choose it).
    lp["w_gate"/"w_up"/"w_down"] hold those `count` experts, or with `layer`
    all the layers' (`[L, count, ...]`: the stacks as they are stored, an
    expert read out of them where it is used; a layer's experts sliced out
    first are a copy of 1.4 GB at the published widths)."""
    offset, count = held
    combine = route(g, lp["router"], lp["router_bias"], m, total)
    share = combine[:, offset:offset + count]                    # [T, count]

    def add_expert(out, expert):
        e, weight = expert
        w_gate, w_up, w_down = (
            lp[k][e] if layer is None else lp[k][layer, e]
            for k in ("w_gate", "w_up", "w_down"))
        return out + weight[:, None] * _swiglu(g, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(g),
                          (jnp.arange(count), share.T))
    return out


def shared_part(g, lp):
    return _swiglu(g, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def held_experts(m: Dict[str, Any]) -> Tuple[Tuple[int, int], int]:
    """((offset, count), the router's width) of a configuration."""
    ep, n = m["expert_parallel"], m["n_routed_experts"]
    return (ep["rank"] * n, n), ep["routed_experts_total"]


HEAD_BLOCK = 16      # heads whose q, k, v and scores are alive at once
DENSE_BLOCKS = 4     # column blocks of the dense feed-forward


def _dense_ffn(g, lp):
    """SwiGLU over `intermediate_size` columns in DENSE_BLOCKS blocks: a
    block's three matrices are float32 at once, not the layer's (1.6 GB)."""
    f = lp["w_gate"].shape[-1]
    n = DENSE_BLOCKS if f % DENSE_BLOCKS == 0 else 1

    def add_block(out, j):
        cols = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=j * (f // n), slice_size=f // n)
        return out + _swiglu(g, cols(lp["w_gate"], axis=1),
                             cols(lp["w_up"], axis=1),
                             cols(lp["w_down"], axis=0)), None

    return jax.lax.scan(add_block, jnp.zeros_like(g), jnp.arange(n))[0]


def attention_half(x, lp, m, q_from=0):
    """x [S, D] -> x + attention(rmsnorm(x)), the rows from `q_from` on
    (every row's latent and key are still computed, from every row of x).
    Attention runs HEAD_BLOCK heads at a time, each block's share of W_O
    added to the sum: the mathematics is per head, and all 128 heads' q, k
    and v in float32 are 1.4 GB at 3,500 positions."""
    h = m["num_attention_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    rkv, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    freq = yarn_inv_freq(m)
    scale = softmax_scale(m)
    g = _rms_norm(x, lp["attn_norm"], eps)
    cq = _rms_norm(g[q_from:] @ lp["w_dq"].astype(F32), lp["q_norm"], eps)
    ckr = g @ lp["w_dkv"].astype(F32)
    c = _rms_norm(ckr[:, :rkv], lp["kv_norm"], eps)
    kr = _rope(ckr[:, None, rkv:], pos, freq)                   # [S, 1, dr]
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h

    def add_heads(out, block):
        w_uq, w_ukv, wo = block             # this block of heads' columns
        q = (cq @ w_uq.astype(F32)).reshape(-1, hb, dn + dr)
        kv = (c @ w_ukv.astype(F32)).reshape(s, hb, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(kr, (s, hb, dr))], -1)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], pos[q_from:], freq)], -1)
        attn = _attention(q, k, kv[..., dn:], pos[q_from:], scale)
        return out + attn @ wo.astype(F32), None

    blocks = (lp["w_uq"].reshape(-1, h // hb, hb * (dn + dr)).transpose(1, 0, 2),
              lp["w_ukv"].reshape(-1, h // hb, hb * (dn + dv)).transpose(1, 0, 2),
              lp["wo"].reshape(h // hb, hb * dv, -1))
    return x[q_from:] + jax.lax.scan(
        add_heads, jnp.zeros((s - q_from, x.shape[1]), F32), blocks)[0]


def feed_forward_half(x, lp, m, layer=None):
    """x [S, D] -> x + ffn(rmsnorm(x)). A layer with a router is sparse, one
    without is dense; `layer` as `routed_part`'s."""
    g = _rms_norm(x, lp["mlp_norm"], m["rms_norm_eps"])
    if "router" not in lp:
        return x + _dense_ffn(g, lp)
    held, total = held_experts(m)
    return x + routed_part(g, lp, m, held, total, layer) + shared_part(g, lp)


def _layer(x, lp, m, q_from, layer=None):
    """One block on x [S, D]; returns the rows from `q_from` on."""
    return feed_forward_half(attention_half(x, lp, m, q_from), lp, m, layer)


def _freeze(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    return tuple(x) if isinstance(x, list) else x


def _thaw(x):
    if isinstance(x, tuple) and x and all(
            isinstance(i, tuple) and len(i) == 2 and isinstance(i[0], str)
            for i in x):
        return {k: _thaw(v) for k, v in x}
    return list(x) if isinstance(x, tuple) else x


_EXPERTS = ("w_gate", "w_up", "w_down")

# What of a configuration the block's equations read.
_WIDTHS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
           "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
           "rope_scaling", "num_experts_per_tok", "n_group", "topk_group",
           "norm_topk_prob", "routed_scaling_factor", "n_routed_experts",
           "expert_parallel")


@functools.lru_cache(maxsize=None)
def _programs(widths):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = _thaw(widths)
    m["expert_parallel"] = {k: v for k, v in m["expert_parallel"].items()
                            if k != "what"}
    layer = jax.jit(functools.partial(_layer, m=m), static_argnames="q_from")

    @jax.jit
    def head(x, norm, w):
        return _rms_norm(x, norm, m["rms_norm_eps"]) @ w.astype(F32)

    return layer, head


def _stack(params) -> List[Tuple[str, int]]:
    """The layers in the order they run: the leading dense ones (`dense`),
    then `layers`."""
    out = [("dense", i) for i in range(
        params["dense"]["wo"].shape[0])] if "dense" in params else []
    return out + [("layers", i)
                  for i in range(params["layers"]["wo"].shape[0])]


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int):
    """Float32 logits [last, V] at the last `last` positions of one sequence.
    Every layer but the final one runs on every position; the final layer
    and the head run on the last `last` queries against the whole context."""
    toks = jnp.asarray(tokens, jnp.int32)
    layer, head = _programs(_freeze({k: m[k] for k in _WIDTHS}))
    stack = _stack(params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32)
        for n, (name, i) in enumerate(stack):
            q_from = len(tokens) - last if n == len(stack) - 1 else 0
            # the experts' stacks whole, with the layer's index
            lp = {k: v if k in _EXPERTS and "router" in params[name]
                  else v[i] for k, v in params[name].items()}
            x = layer(x, lp, q_from=q_from,
                      layer=i if "router" in lp else None)
        return head(x[-last:], params["final_norm"], params["lm_head"])


def served_token_gaps(params, m, prompt: List[int], served: List[int]):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    latent cache in the absorbed form; the reference sees neither, only
    prompt + served as one sequence through naive attention."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n)            # [n, V]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


def loss_and_check_grads(params, m, tokens, checked: Optional[tuple] = None):
    raise NotImplementedError(
        "arch 'dots' serves only: the program's training forward refuses "
        "latent attention, and a share of the experts takes no gradient for "
        "the experts that are absent")

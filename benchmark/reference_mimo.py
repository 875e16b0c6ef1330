"""The plain reference of `arch: mimo`: MiMo-V2-Flash's decoder, window
attention with a learned sink in five layers of six beside full attention on
other kv-head counts, keys 192 wide and values 128, a sigmoid router over
routed experts of which a SHARE is held, in straightforward jax.numpy,
float32, matmuls at precision "highest": no kernel, no cache, no ring, no
batching, every held expert computed densely on every token, a full `[T, T]`
score matrix under the causal and the window masks. Written from the
published keys (`config.json`, the catalog's row) and the equations of
ISSUE 42, not from the program's code; it shares with the program only the
layout of the parameter tree it is handed (stacks by kind: `dense`, `window`,
`layers`).

  layer l is FULL where hybrid_layer_pattern[l] is 0 (num_key_value_heads kv
  heads, rope_theta, no sink) and WINDOW where it is 1 (swa_num_key_value_heads,
  swa_rope_theta, sliding_window, a sink); both: num_attention_heads query
  heads, q and k heads of head_dim (192), v heads of v_head_dim (128)

  g = rmsnorm(x, w_in)                                  every norm in float32
  q = g Wq [T, 64, 192];  k = g Wk [T, KVH, 192];  v = g Wv [T, KVH, 128]
  RoPE turns the first r = int(192 x partial_rotary_factor) = 64 numbers of
  each q and k head, pairs (i, i + r/2) by position x theta^(-2i/r), at the
  KIND's theta; the other 128 pass
  s_ij = q_i . k_j / sqrt(192)   for j <= i and, in a window layer,
                                 i - j < sliding_window
  full:    p_i = softmax_j(s_ij)
  window:  head h has a learned scalar b_h; the softmax runs over the live
           s_ij and ONE further column of logit b_h, whose probability is
           then dropped: p_ij = exp(s_ij - m) / (sum_j exp(s_ij - m)
                                                 + exp(b_h - m))
  o_i = attention_value_scale x sum_j p_ij v_j;   x = x + [o_i heads joined] Wo
  g = rmsnorm(x, w_post)
  moe_layer_freq[l] 0:  x = x + SwiGLU_16384(g)
  otherwise:  s = sigmoid(g W_r) [256];  the 8 largest of s + b are chosen
      (ties to the smaller index; n_group 1: no group limit); weights s (not
      s + b) at those 8, divided by their sum (norm_topk_prob), times
      routed_scaling_factor (null: 1.0);  x = x + sum_e w_e SwiGLU_e(g)

then a final rmsnorm and the untied head over the vocabulary's slice.

THE SHARE. The configuration holds `n_routed_experts` of the
`expert_parallel.routed_experts_total` experts its router scores (rank r:
experts r n .. r n + n - 1). The sum over e runs over the HELD experts among
a token's 8 alone, as the program's does; what the absent experts would have
added is left out here too, and that partial result goes on.
`routed_part(...)` with another `held` gives another share's part:
tests/test_mimo.py adds the parts of all shares and finds the whole layer.

Departures and readings of the published keys, each the configuration's too
(`assumed`): no q/k norm (none among the keys); WHICH 64 numbers turn and
their pairing; a query sees itself and the 127 positions before it; the
selection bias is a parameter leaf the adapter balances; the three
multi-token-prediction layers are not built. No loss: the adapter serves
only.

Memory: weights come as they are stored and are made float32 where they are
used; attention runs Q_BLOCK queries at a time against the whole context (64
heads x 128 x 8,031 scores are 263 MB), the experts one at a time, the dense
feed-forward in four blocks of columns; the final layer and the head run on
the last `last` positions.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 128
DENSE_BLOCKS = 4     # column blocks of the dense feed-forward
_EXPERTS = ("w_gate", "w_up", "w_down")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def rotary_dim(m: Dict[str, Any]) -> int:
    return int(m["head_dim"] * m["partial_rotary_factor"])


def attention_kind(m: Dict[str, Any], window: bool
                   ) -> Tuple[int, float, int, bool]:
    """(kv heads, rope theta, window or 0, sink) of a kind of layer."""
    if window:
        return (m["swa_num_key_value_heads"], m["swa_rope_theta"],
                m["sliding_window"], bool(m["add_swa_attention_sink_bias"]))
    return (m["num_key_value_heads"], m["rope_theta"], 0,
            bool(m.get("add_full_attention_sink_bias")))


def _rope(x, positions, theta: float, r: int):
    """x [S, heads, d]: the first r numbers of a head turned, pairs (i, i +
    r/2) by position * theta^(-2i/r); the rest pass."""
    half = r // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / r)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., r:]], -1)


def _attention(q, k, v, q_pos, scale, window: int, sink):
    """q [Sq, H, dk] at positions q_pos against k [Sk, KVH, dk], v [Sk, KVH,
    dv] at positions 0..Sk-1: every score, the causal and the window masks,
    the sink's column; query head h reads kv head h // (H // KVH). In blocks
    of Q_BLOCK queries. -> [Sq, H * dv]."""
    sq, h, _ = q.shape
    kvh = k.shape[1]
    k_pos = jnp.arange(k.shape[0])
    pad = -sq % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, kvh, h // kvh, q.shape[-1])
    pb = jnp.pad(q_pos, (0, pad)).reshape(-1, Q_BLOCK)

    def block(args):
        qs, pos = args                              # [B, KVH, g, dk], [B]
        s = jnp.einsum("qkgd,skd->kgqs", qs, k) * scale
        back = pos[:, None] - k_pos[None, :]        # [B, Sk]
        live = back >= 0
        if window:
            live &= back < window
        s = jnp.where(live[None, None], s, -jnp.inf)
        if sink is not None:
            col = jnp.broadcast_to(
                sink.astype(F32).reshape(kvh, h // kvh, 1, 1),
                s.shape[:3] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(Q_BLOCK, -1)

    return jax.lax.map(block, (qb, pb)).reshape(sq + pad, -1)[:sq]


def attention_half(x, lp, m, window: bool, q_from=0):
    """x [S, D] -> x + attention(rmsnorm(x)), the rows from `q_from` on
    (every row's key and value are still computed, from every row of x)."""
    h, dk, dv = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    kvh, theta, width, sink = attention_kind(m, window)
    s = x.shape[0]
    pos = jnp.arange(s)
    r = rotary_dim(m)
    g = _rms_norm(x, lp["attn_norm"], m["layernorm_epsilon"])
    q = (g[q_from:] @ lp["wq"].astype(F32)).reshape(-1, h, dk)
    k = (g @ lp["wk"].astype(F32)).reshape(s, kvh, dk)
    v = (g @ lp["wv"].astype(F32)).reshape(s, kvh, dv)
    q, k = _rope(q, pos[q_from:], theta, r), _rope(k, pos, theta, r)
    o = _attention(q, k, v, pos[q_from:], dk ** -0.5, width,
                   lp["sink"] if sink else None)
    return x[q_from:] + (m["attention_value_scale"] * o) \
        @ lp["wo"].astype(F32)


def route(g, router, bias, m, total: int):
    """g [T, D] -> the router's combine matrix [T, total]: a token's weight
    for each of the `total` experts, 0 where it is not among its 8."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(g @ router.astype(F32))                   # [T, total]
    _, chosen = jax.lax.top_k(s + bias.astype(F32), k)           # [T, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * (m.get("routed_scaling_factor") or 1.0)
    return jnp.sum(w[:, :, None] * jax.nn.one_hot(chosen, total, dtype=F32),
                   axis=1)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate.astype(F32)) * (g @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def routed_part(g, lp, m, held: Tuple[int, int], total: int, layer=None):
    """g [T, D] -> [T, D]: the part of the routed mixture that the experts
    `held` = (offset, count) give, each of them on every row, weighted by the
    router's weight for it (0 where the token did not choose it).
    lp["w_gate"/"w_up"/"w_down"] hold those `count` experts, or with `layer`
    all the stack's layers' (`[L, count, ...]`, an expert read out of the
    stack where it is used)."""
    offset, count = held
    combine = route(g, lp["router"], lp["router_bias"], m, total)
    share = combine[:, offset:offset + count]                    # [T, count]

    def add_expert(out, expert):
        e, weight = expert
        w_gate, w_up, w_down = (
            lp[k][e] if layer is None else lp[k][layer, e] for k in _EXPERTS)
        return out + weight[:, None] * _swiglu(g, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(g),
                          (jnp.arange(count), share.T))
    return out


def held_experts(m: Dict[str, Any]) -> Tuple[Tuple[int, int], int]:
    """((offset, count), the router's width) of a configuration."""
    ep, n = m["expert_parallel"], m["n_routed_experts"]
    return (ep["rank"] * n, n), ep["routed_experts_total"]


def _dense_ffn(g, lp):
    """SwiGLU over `intermediate_size` columns in DENSE_BLOCKS blocks: a
    block's three matrices are float32 at once, not the layer's (0.8 GB)."""
    f = lp["w_gate"].shape[-1]
    n = DENSE_BLOCKS if f % DENSE_BLOCKS == 0 else 1

    def add_block(out, j):
        cols = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=j * (f // n), slice_size=f // n)
        return out + _swiglu(g, cols(lp["w_gate"], axis=1),
                             cols(lp["w_up"], axis=1),
                             cols(lp["w_down"], axis=0)), None

    return jax.lax.scan(add_block, jnp.zeros_like(g), jnp.arange(n))[0]


def feed_forward_half(x, lp, m, layer=None):
    """x [S, D] -> x + ffn(rmsnorm(x)). A layer with a router is sparse, one
    without is dense; `layer` as `routed_part`'s."""
    g = _rms_norm(x, lp["mlp_norm"], m["layernorm_epsilon"])
    if "router" not in lp:
        return x + _dense_ffn(g, lp)
    held, total = held_experts(m)
    return x + routed_part(g, lp, m, held, total, layer)


def _layer(x, lp, m, window, q_from, layer=None):
    """One block on x [S, D]; returns the rows from `q_from` on."""
    return feed_forward_half(attention_half(x, lp, m, window, q_from), lp, m,
                             layer)


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


# What of a configuration the block's equations read.
_WIDTHS = ("num_attention_heads", "num_key_value_heads", "head_dim",
           "v_head_dim", "partial_rotary_factor", "rope_theta",
           "swa_num_key_value_heads", "swa_rope_theta", "sliding_window",
           "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
           "attention_value_scale", "layernorm_epsilon",
           "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
           "n_routed_experts", "expert_parallel")


@functools.lru_cache(maxsize=None)
def _programs(widths):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = _thaw(widths)
    m["expert_parallel"] = {k: v for k, v in m["expert_parallel"].items()
                            if k in ("rank", "routed_experts_total")}
    layer = jax.jit(functools.partial(_layer, m=m),
                    static_argnames=("window", "q_from"))

    @jax.jit
    def head(x, norm, w):
        return _rms_norm(x, norm, m["layernorm_epsilon"]) @ w.astype(F32)

    return layer, head


def stack_order(m: Dict[str, Any]) -> List[Tuple[str, int, bool]]:
    """The layers in the order they run, each (the stack that holds it, its
    ordinal there, whether it is a window layer): `dense` holds the layers
    whose moe_layer_freq is 0, `window` the sparse window layers, `layers`
    the sparse full ones."""
    out, at = [], {"dense": 0, "window": 0, "layers": 0}
    for window, sparse in zip(m["hybrid_layer_pattern"], m["moe_layer_freq"]):
        name = "dense" if not sparse else "window" if window else "layers"
        out.append((name, at[name], bool(window)))
        at[name] += 1
    return out


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int):
    """Float32 logits [last, V] at the last `last` positions of one sequence.
    Every layer but the final one runs on every position; the final layer
    and the head run on the last `last` queries against the whole context."""
    toks = jnp.asarray(tokens, jnp.int32)
    layer, head = _programs(_freeze({k: m.get(k) for k in _WIDTHS}))
    order = stack_order(m)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32)
        for n, (name, i, window) in enumerate(order):
            q_from = len(tokens) - last if n == len(order) - 1 else 0
            sparse = "router" in params[name]
            # the experts' stacks whole, with the layer's index
            lp = {k: v if sparse and k in _EXPERTS else v[i]
                  for k, v in params[name].items()}
            x = layer(x, lp, window=window, q_from=q_from,
                      layer=i if sparse else None)
        return head(x[-last:], params["final_norm"], params["lm_head"])


def served_token_gaps(params, m, prompt: List[int], served: List[int]):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    pages and the rings; the reference sees neither, only prompt + served as
    one sequence through naive attention."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n)            # [n, V]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


def loss_and_check_grads(params, m, tokens, checked: Optional[tuple] = None):
    raise NotImplementedError(
        "arch 'mimo' serves only: the program's training forward refuses "
        "mixed attention, and a share of the experts takes no gradient for "
        "the experts that are absent")

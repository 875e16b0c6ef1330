"""The plain reference of `arch: laguna`: Laguna-S-2.1's decoder, window
layers of 72 query heads beside full layers of 48 on 8 kv heads each, the
full layers' rotation under YaRN with a magnitude on cos and sin, a sigmoid
gate a query head on attention's output, a softmax router over routed experts
of which a SHARE is held and a shared expert beside them, in straightforward
jax.numpy, float32, matmuls at precision "highest": no kernel, no cache, no
ring, no batching, every held expert computed densely on every token, a full
`[T, T]` score matrix under the causal and the window masks. Written from the
published keys (`config.json`, the catalog's row) and the equations of ISSUE
62, not from the program's code; it shares with the program only the layout
of the parameter tree it is handed (stacks by kind: `dense`, `window`,
`layers`).

  layer l is of kind layer_types[l], with H = num_attention_heads_per_layer[l]
  query heads (48 where `full_attention`, 72 where `sliding_attention`) on
  num_key_value_heads (8) kv heads, heads of head_dim (128)

  h = rmsnorm(x, w_in)                                  every norm in float32
  q = h Wq [T, H, 128];  k = h Wk [T, 8, 128];  v = h Wv [T, 8, 128]
  g = sigmoid(h Wg) [T, H]                        (gating_types[l] per_head)
  RoPE by rope_parameters[kind]: the first r = 128 x partial_rotary_factor
  numbers of each q and k head turn, pairs (i, i + r/2), the rest pass.
    sliding_attention: r = 128, angle position x theta^(-2i/r), theta 1e4
    full_attention:    r = 64, YaRN: f_i = theta^(-2i/r) (theta 5e5) where
      pair i turns fast, f_i / factor where it turns slowly, a linear ramp
      between the pairs that make beta_fast and beta_slow turns over
      original_max_position_embeddings; cos and sin times attention_factor
  s_ij = q_i . k_j / sqrt(128)   for j <= i and, in a window layer,
                                 i - j < sliding_window
  p_i = softmax_j(s_ij);  o_ih = sum_j p_ij v_j of kv head h // (H / 8)
  x = x + [g_ih o_ih, heads joined] Wo
  h2 = rmsnorm(x, w_post)
  mlp_layer_types[l] dense:   x = x + SwiGLU_12288(h2)
  sparse:  p = softmax(h2 W_r) [256];  T = the 10 largest (ties to the
      smaller index);  w_e = moe_routed_scaling_factor x p_e / sum_T p
      (norm_topk_prob);  x = x + sum_{e in T} w_e SwiGLU_e(h2) + S(h2), S the
      shared expert, SwiGLU of shared_expert_intermediate_size, no gate

then a final rmsnorm and the untied head over the vocabulary's slice.

THE SHARE. The configuration holds `num_experts` of the
`expert_parallel.routed_experts_total` experts its router scores (rank r:
experts r n .. r n + n - 1). The sum over e runs over the HELD experts among
a token's 10 alone, as the program's does; what the absent experts would have
added is left out here too, and that partial result goes on. The shared
expert is added once, here. `routed_part(...)` with another `held` gives
another share's part: tests/test_laguna.py adds the parts of all shares and
the shared expert once and finds the whole layer.

Readings of the published keys where they name a mechanism without its form
are the configuration's `assumed`. No loss: the adapter serves only.

Memory: weights come as they are stored and are made float32 where they are
used; attention runs Q_BLOCK queries at a time against the whole context (72
heads x 128 x 8,031 scores are 296 MB), the experts one at a time, the dense
feed-forward in four blocks of columns; the final layer and the head run on
the last `last` positions.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 128
DENSE_BLOCKS = 4     # column blocks of the dense feed-forward
_EXPERTS = ("w_gate", "w_up", "w_down")
KINDS = {"full_attention": False, "sliding_attention": True}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope_of(m: Dict[str, Any], window: bool) -> Dict[str, Any]:
    return m["rope_parameters"][
        "sliding_attention" if window else "full_attention"]


def rotary_dim(m: Dict[str, Any], window: bool) -> int:
    return int(m["head_dim"] * rope_of(m, window)["partial_rotary_factor"])


def inv_frequencies(m: Dict[str, Any], window: bool):
    """(the r/2 angles a position of a kind of layer, the factor on cos and
    sin): `default`, theta^(-2i/r) and 1; `yarn`, by the published six
    numbers."""
    rp, r = rope_of(m, window), rotary_dim(m, window)
    theta = float(rp["rope_theta"])
    f = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    if rp["rope_type"] == "default":
        return f, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    orig = rp["original_max_position_embeddings"]

    def pair(turns):    # the pair that makes `turns` turns over `orig`
        return r * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair(rp["beta_fast"])), 0)
    hi = min(math.ceil(pair(rp["beta_slow"])), r - 1)
    ramp = jnp.clip((jnp.arange(r // 2, dtype=F32) - lo)
                    / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    return f / rp["factor"] * ramp + f * (1.0 - ramp), \
        float(rp["attention_factor"])


def _rope(x, positions, inv, magnitude: float):
    """x [S, heads, d]: the first r = 2 len(inv) numbers of a head turned,
    pairs (i, i + r/2) by position * inv[i], cos and sin times `magnitude`;
    the rest pass."""
    half = inv.shape[0]
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * magnitude)[:, None, :]
    sin = (jnp.sin(ang) * magnitude)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., 2 * half:]], -1)


def _attention(q, k, v, q_pos, scale, window: int):
    """q [Sq, H, dk] at positions q_pos against k [Sk, KVH, dk], v [Sk, KVH,
    dv] at positions 0..Sk-1: every score, the causal and the window masks;
    query head h reads kv head h // (H // KVH). In blocks of Q_BLOCK queries.
    -> [Sq, H, dv]."""
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
        p = jax.nn.softmax(jnp.where(live[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(Q_BLOCK, h, -1)

    return jax.lax.map(block, (qb, pb)).reshape(sq + pad, h, -1)[:sq]


def attention_half(x, lp, m, window: bool, q_from=0, gate=True,
                   magnitude=None):
    """x [S, D] -> x + attention(rmsnorm(x)), the rows from `q_from` on
    (every row's key and value are still computed, from every row of x). The
    layer's query heads are read off its `wq`. `gate` False, or another
    `magnitude` than the published one, is a WRONG model, for the tests that
    show the comparison sees it."""
    dk, kvh = m["head_dim"], m["num_key_value_heads"]
    s = x.shape[0]
    pos = jnp.arange(s)
    inv, factor = inv_frequencies(m, window)
    factor = factor if magnitude is None else magnitude
    h = _rms_norm(x, lp["attn_norm"], m["rms_norm_eps"])
    q = (h[q_from:] @ lp["wq"].astype(F32)).reshape(s - q_from, -1, dk)
    k = (h @ lp["wk"].astype(F32)).reshape(s, kvh, dk)
    v = (h @ lp["wv"].astype(F32)).reshape(s, kvh, dk)
    q, k = _rope(q, pos[q_from:], inv, factor), _rope(k, pos, inv, factor)
    o = _attention(q, k, v, pos[q_from:], dk ** -0.5,
                   m["sliding_window"] if window else 0)
    if gate:
        o = o * jax.nn.sigmoid(h[q_from:] @ lp["wg"].astype(F32))[..., None]
    return x[q_from:] + o.reshape(s - q_from, -1) @ lp["wo"].astype(F32)


def route(h, router, m, total: int, norm: Optional[bool] = None,
          scale: Optional[float] = None):
    """h [T, D] -> the router's combine matrix [T, total]: a token's weight
    for each of the `total` experts, 0 where it is not among its 10. `norm`
    and `scale` other than the published ones: a wrong model, for the tests."""
    k = m["num_experts_per_tok"]
    p = jax.nn.softmax(h @ router.astype(F32), axis=-1)          # [T, total]
    w, chosen = jax.lax.top_k(p, k)                              # [T, k]
    if m["norm_topk_prob"] if norm is None else norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * (m["moe_routed_scaling_factor"] if scale is None else scale)
    return jnp.sum(w[:, :, None] * jax.nn.one_hot(chosen, total, dtype=F32),
                   axis=1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def routed_part(h, lp, m, held: Tuple[int, int], total: int, layer=None,
                **wrong):
    """h [T, D] -> [T, D]: the part of the routed mixture that the experts
    `held` = (offset, count) give, each of them on every row, weighted by the
    router's weight for it (0 where the token did not choose it).
    lp["w_gate"/"w_up"/"w_down"] hold those `count` experts, or with `layer`
    all the stack's layers' (`[L, count, ...]`, an expert read out of the
    stack where it is used)."""
    offset, count = held
    combine = route(h, lp["router"], m, total, **wrong)
    share = combine[:, offset:offset + count]                    # [T, count]

    def add_expert(out, expert):
        e, weight = expert
        w_gate, w_up, w_down = (
            lp[k][e] if layer is None else lp[k][layer, e] for k in _EXPERTS)
        return out + weight[:, None] * _swiglu(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (jnp.arange(count), share.T))
    return out


def shared_part(h, lp):
    """The shared expert on every row: SwiGLU, no gate of its own."""
    return _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def held_experts(m: Dict[str, Any]) -> Tuple[Tuple[int, int], int]:
    """((offset, count), the router's width) of a configuration."""
    ep, n = m["expert_parallel"], m["num_experts"]
    return (ep["rank"] * n, n), ep["routed_experts_total"]


def _dense_ffn(h, lp):
    """SwiGLU over `intermediate_size` columns in DENSE_BLOCKS blocks: a
    block's three matrices are float32 at once, not the layer's."""
    f = lp["w_gate"].shape[-1]
    n = DENSE_BLOCKS if f % DENSE_BLOCKS == 0 else 1

    def add_block(out, j):
        cols = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=j * (f // n), slice_size=f // n)
        return out + _swiglu(h, cols(lp["w_gate"], axis=1),
                             cols(lp["w_up"], axis=1),
                             cols(lp["w_down"], axis=0)), None

    return jax.lax.scan(add_block, jnp.zeros_like(h), jnp.arange(n))[0]


def feed_forward_half(x, lp, m, layer=None, shared=True, **wrong):
    """x [S, D] -> x + ffn(rmsnorm(x)). A layer with a router is sparse, one
    without is dense; `layer` as `routed_part`'s. `shared` False, or `norm` /
    `scale` (`route`): a wrong model, for the tests."""
    h = _rms_norm(x, lp["mlp_norm"], m["rms_norm_eps"])
    if "router" not in lp:
        return x + _dense_ffn(h, lp)
    held, total = held_experts(m)
    out = x + routed_part(h, lp, m, held, total, layer, **wrong)
    return out + shared_part(h, lp) if shared else out


def _layer(x, lp, m, window, q_from, layer=None, wrong=()):
    """One block on x [S, D]; returns the rows from `q_from` on. `wrong`:
    (name, value) pairs of what is computed wrongly on purpose."""
    wrong = dict(wrong)
    attn = {k: wrong.pop(k) for k in ("gate", "magnitude") if k in wrong}
    return feed_forward_half(attention_half(x, lp, m, window, q_from, **attn),
                             lp, m, layer, **wrong)


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
_WIDTHS = ("num_key_value_heads", "head_dim", "rope_parameters",
           "sliding_window", "rms_norm_eps", "num_experts_per_tok",
           "norm_topk_prob", "moe_routed_scaling_factor", "num_experts",
           "expert_parallel")


@functools.lru_cache(maxsize=None)
def _programs(widths):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = _thaw(widths)
    m["expert_parallel"] = {k: v for k, v in m["expert_parallel"].items()
                            if k in ("rank", "routed_experts_total")}
    layer = jax.jit(functools.partial(_layer, m=m),
                    static_argnames=("window", "q_from", "wrong"))

    @jax.jit
    def head(x, norm, w):
        return _rms_norm(x, norm, m["rms_norm_eps"]) @ w.astype(F32)

    return layer, head


def stack_order(m: Dict[str, Any]) -> List[Tuple[str, int, bool]]:
    """The layers in the order they run, each (the stack that holds it, its
    ordinal there, whether it is a window layer): `dense` holds the layers
    whose mlp_layer_types is `dense`, `window` the sparse window layers,
    `layers` the sparse full ones."""
    out, at = [], {"dense": 0, "window": 0, "layers": 0}
    held = m["num_hidden_layers"]   # the lists may go on to the model's depth
    for kind, mlp in zip(m["layer_types"][:held],
                         m["mlp_layer_types"][:held]):
        window = KINDS[kind]
        name = "dense" if mlp == "dense" else "window" if window else "layers"
        out.append((name, at[name], window))
        at[name] += 1
    return out


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int, wrong=()):
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
                      layer=i if sparse else None, wrong=tuple(wrong))
        return head(x[-last:], params["final_norm"], params["lm_head"])


def served_token_gaps(params, m, prompt: List[int], served: List[int],
                      wrong=()):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    pages and the rings; the reference sees neither, only prompt + served as
    one sequence through naive attention."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n, wrong)     # [n, V]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


def loss_and_check_grads(params, m, tokens, checked: Optional[tuple] = None):
    raise NotImplementedError(
        "arch 'laguna' serves only: the program's training forward refuses "
        "mixed attention, and a share of the experts takes no gradient for "
        "the experts that are absent")

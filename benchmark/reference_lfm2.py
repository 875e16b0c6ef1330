"""The plain reference of `arch: lfm2` (LFM2-24B-A2B, `model_type: lfm2_moe`):
gated short-convolution layers beside grouped-query attention on heads of 64,
sparse experts behind a sigmoid router after leading dense layers, a head tied
to the embedding; in straightforward jax.numpy, float32, matmuls at precision
"highest": no kernel, no cache, no carried window, no batching, the
convolution as `conv_L_cache` shifted products, every expert computed densely
on every token, a `[T, T]` score matrix under the causal mask. Written from
the published keys (`config.json`, the catalog's row) and the equations of
ISSUE 46, not from the program's code; it shares with the program only the
layout of the parameter tree it is handed (stacks by kind: `dense`, `conv`,
`layers`).

  every layer   h = x + Op(rmsnorm(x; operator norm))
                y = h + FFN(rmsnorm(h; ffn norm))          eps norm_eps
  layer_types[l] == "conv":
      B, C, X = split(u W_in)   [T, D] each, in that order;   z = B * X
      c_t = sum_{j<K} w[j] * z_{t-K+1+j}     K = conv_L_cache taps a channel,
            depthwise, causal, no bias (conv_bias false), z before position 0
            is zero
      Op  = (C * c) W_out                        no position signal
  layer_types[l] == "full_attention":
      q = u Wq [T, H, hd], k = u Wk [T, KVH, hd], v = u Wv [T, KVH, hd],
      hd = hidden_size / num_attention_heads (64), no bias; an RMS norm over
      each head's hd numbers of q and of k (q_layernorm, k_layernorm) BEFORE
      RoPE; RoPE turns the whole head, pairs (i, i + hd/2), by position x
      theta^(-2i/hd), theta = rope_parameters.rope_theta; causal softmax at
      scale hd^-1/2; Op = [heads joined] Wo
  FFN of the first num_dense_layers layers: SwiGLU of intermediate_size
  FFN of the rest:  s = sigmoid(g W_r) in float32 [num_experts]; the
      num_experts_per_tok largest of s + expert_bias are CHOSEN
      (use_expert_bias; ties to the smaller index); the weights are s (not
      s + bias) at the chosen, over (their sum + 1e-6) (norm_topk_prob),
      times routed_scaling_factor;  FFN = sum_e w_e SwiGLU_e(g), each expert
      of moe_intermediate_size, no shared expert

then rmsnorm(y; embedding_norm) and logits = y embed^T (the tied head).

The tree: `dense` holds the leading dense layers (conv operators over the
dense feed-forward: `norm`, `in_proj` [D, 3 D], `conv_w` [K, D], `out_proj`,
`mlp_norm`, `w_gate`, `w_up`, `w_down`), `conv` the sparse conv layers (the
same operator; `router`, `router_bias`, the experts' `w_gate`, `w_up`,
`w_down` [E, ...]), `layers` the attention layers (`attn_norm`, `wq`, `wk`,
`wv`, `wo`, `q_norm`, `k_norm` [hd], and the sparse feed-forward's leaves);
`embed`, `final_norm`; no `lm_head`.

Departures from transformers' `modeling_lfm2_moe.py` and readings of the
published keys, each the configuration's too (`assumed`):
  * a conv layer's cache. transformers keeps `conv_L_cache` = 3 columns of z
    a layer a sequence and convolves them with the step's; the PROGRAM keeps
    the 2 columns a causal convolution of 3 taps reads before the current
    one (`ops/slot_state.py`). This reference keeps none: it convolves the
    whole sequence. The results are the same numbers.
  * the head is the embedding transposed (`Lfm2Config.tie_embedding`, the
    family's convention; the catalog's row has no key for it).
  * RoPE pairs (i, i + hd/2) (rotate-half); another pairing is a fixed
    permutation of the columns of Wq and Wk, which seeded weights do not
    distinguish.
  * the input projection's three parts are B, C, X in that order.
  * the leading dense layers take `intermediate_size` as it stands.
  * `expert_bias` is a parameter leaf (`router_bias`) that the adapter
    balances as training does; transformers holds it as a buffer.
No loss: the adapter serves only.

Memory: weights come as they are stored and are made float32 where they are
used: attention runs Q_BLOCK queries at a time against the whole context, the
experts are read out of the whole stack EXPERT_BLOCK at a time (8 experts of
9.4 M parameters are 0.3 GB in float32, a layer's 64 would be 2.4), the dense
feed-forward in four blocks of columns; the head runs on the last `last`
positions.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512
DENSE_BLOCKS = 4     # column blocks of the dense feed-forward
EXPERT_BLOCK = 8     # experts made float32 at once
ROUTER_EPS = 1e-6    # what the renormalisation adds to the chosen scores' sum
_EXPERTS = ("w_gate", "w_up", "w_down")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def head_dim(m: Dict[str, Any]) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def _rope(x, positions, theta: float):
    """x [T, heads, hd] turned whole, pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def conv_operator(u, lp, m):
    """u [T, D], already normed -> the operator's output [T, D]: the three
    taps as three shifted products."""
    taps = m["conv_L_cache"]
    t = u.shape[0]
    b, c, x = jnp.split(u @ lp["in_proj"].astype(F32), 3, axis=-1)
    z = jnp.pad(b * x, ((taps - 1, 0), (0, 0)))
    w = lp["conv_w"].astype(F32)
    conv = sum(w[j] * z[j:j + t] for j in range(taps))
    return (c * conv) @ lp["out_proj"].astype(F32)


def attention_operator(u, lp, m):
    """u [T, D], already normed -> [T, D]."""
    h, kvh, hd, eps = (m["num_attention_heads"], m["num_key_value_heads"],
                       head_dim(m), m["norm_eps"])
    t = u.shape[0]
    pos = jnp.arange(t)
    theta = float(m["rope_parameters"]["rope_theta"])
    q = (u @ lp["wq"].astype(F32)).reshape(t, h, hd)
    k = (u @ lp["wk"].astype(F32)).reshape(t, kvh, hd)
    v = (u @ lp["wv"].astype(F32)).reshape(t, kvh, hd)
    q = _rope(_rms_norm(q, lp["q_norm"], eps), pos, theta)
    k = _rope(_rms_norm(k, lp["k_norm"], eps), pos, theta)
    outs = []
    for start in range(0, t, Q_BLOCK):
        rows = slice(start, start + Q_BLOCK)
        qb = q[rows].reshape(-1, kvh, h // kvh, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(F32(hd))
        causal = pos[None, :] <= pos[rows, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, h * hd))
    return jnp.concatenate(outs, 0) @ lp["wo"].astype(F32)


def operator_half(x, lp, m, conv: bool):
    """x [T, D] -> x + Op(rmsnorm(x)): the layer's first half."""
    if conv:
        return x + conv_operator(_rms_norm(x, lp["norm"], m["norm_eps"]), lp,
                                 m)
    return x + attention_operator(
        _rms_norm(x, lp["attn_norm"], m["norm_eps"]), lp, m)


def combine_from_scores(s, bias, k: int, norm: bool, factor: float):
    """The choice and the weights from the sigmoid scores s [T, E] -> the
    combine matrix [T, E]: a token's weight for each expert, 0 where it is
    not among its k."""
    _, chosen = jax.lax.top_k(s + bias, k)                       # [T, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_EPS)
    w = w * factor
    return jnp.sum(w[:, :, None] * jax.nn.one_hot(chosen, s.shape[-1],
                                                  dtype=F32), axis=1)


def route(g, lp, m):
    """g [T, D] -> the router's combine matrix [T, num_experts]."""
    s = jax.nn.sigmoid(g @ lp["router"].astype(F32))
    bias = lp["router_bias"].astype(F32) if m.get("use_expert_bias", True) \
        else jnp.zeros(s.shape[-1], F32)
    return combine_from_scores(s, bias, m["num_experts_per_tok"],
                               bool(m["norm_topk_prob"]),
                               float(m.get("routed_scaling_factor") or 1.0))


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def routed_ffn(g, lp, m, layer=None):
    """g [T, D] -> (sum_e w_e SwiGLU_e(g) [T, D], the combine matrix), every
    expert on every row, EXPERT_BLOCK experts float32 at a time.
    lp["w_gate"/"w_up"/"w_down"] hold the layer's experts `[E, ...]`, or with
    `layer` the stack's `[L, E, ...]`, a block read out of it where it is
    used."""
    combine = route(g, lp, m)
    total = combine.shape[-1]
    n = EXPERT_BLOCK if total % EXPERT_BLOCK == 0 else 1

    def add_block(out, j):
        def block(w):
            if layer is not None:
                w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
            return jax.lax.dynamic_slice_in_dim(w, j * n, n, 0).astype(F32)
        w_gate, w_up, w_down = (block(lp[k]) for k in _EXPERTS)
        weight = jax.lax.dynamic_slice_in_dim(combine, j * n, n, 1)  # [T, n]
        h = jax.nn.silu(jnp.einsum("td,edf->etf", g, w_gate)) \
            * jnp.einsum("td,edf->etf", g, w_up)
        y = jnp.einsum("etf,efd->etd", h, w_down)
        return out + jnp.einsum("etd,te->td", y, weight), None

    out, _ = jax.lax.scan(add_block, jnp.zeros_like(g),
                          jnp.arange(total // n))
    return out, combine


def _dense_ffn(g, lp):
    """SwiGLU over `intermediate_size` columns in DENSE_BLOCKS blocks."""
    f = lp["w_gate"].shape[-1]
    n = DENSE_BLOCKS if f % DENSE_BLOCKS == 0 else 1

    def add_block(out, j):
        cols = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=j * (f // n), slice_size=f // n)
        return out + _swiglu(g, cols(lp["w_gate"], axis=1).astype(F32),
                             cols(lp["w_up"], axis=1).astype(F32),
                             cols(lp["w_down"], axis=0).astype(F32)), None

    return jax.lax.scan(add_block, jnp.zeros_like(g), jnp.arange(n))[0]


def feed_forward_half(x, lp, m, layer=None):
    """x [T, D] -> (x + FFN(rmsnorm(x)), the combine matrix or None). A layer
    with a router is sparse, one without is dense; `layer` as
    `routed_ffn`'s."""
    g = _rms_norm(x, lp["mlp_norm"], m["norm_eps"])
    if "router" not in lp:
        return x + _dense_ffn(g, lp), None
    out, combine = routed_ffn(g, lp, m, layer)
    return x + out, combine


def _layer(x, lp, m, conv, layer=None):
    return feed_forward_half(operator_half(x, lp, m, conv), lp, m, layer)


# What of a configuration the layers' equations read.
_WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "norm_eps", "conv_L_cache", "num_experts_per_tok",
           "norm_topk_prob", "routed_scaling_factor", "use_expert_bias")


@functools.lru_cache(maxsize=None)
def _programs(widths, theta):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = dict(widths, rope_parameters={"rope_theta": theta})
    layer = jax.jit(functools.partial(_layer, m=m), static_argnames=("conv",))

    @jax.jit
    def head(x, norm, embed):
        return _rms_norm(x, norm, m["norm_eps"]) @ embed.astype(F32).T

    return layer, head


def stack_order(m: Dict[str, Any]) -> List[Tuple[str, int, bool]]:
    """The layers in the order they run, each (the stack that holds it, its
    ordinal there, whether it is a conv layer): `dense` holds the first
    num_dense_layers layers, `conv` the sparse conv layers, `layers` the
    sparse attention layers."""
    out, at = [], {"dense": 0, "conv": 0, "layers": 0}
    for i, kind in enumerate(m["layer_types"]):
        conv = kind == "conv"
        name = "dense" if i < m["num_dense_layers"] \
            else "conv" if conv else "layers"
        out.append((name, at[name], conv))
        at[name] += 1
    return out


def _forward(params, m, tokens: Sequence[int], last: int):
    """(float32 logits [last, V] at the last `last` positions of one
    sequence, the sparse layers' combine matrices [T, E] in the order they
    run). Every layer runs on every position: a conv layer's row reads the
    rows before it."""
    toks = jnp.asarray(tokens, jnp.int32)
    layer, head = _programs(
        tuple((k, m.get(k)) for k in _WIDTHS),
        float(m["rope_parameters"]["rope_theta"]))
    combines = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32)
        for name, i, conv in stack_order(m):
            sparse = "router" in params[name]
            # the experts' stacks whole, with the layer's index
            lp = {k: v if sparse and k in _EXPERTS else v[i]
                  for k, v in params[name].items()}
            x, combine = layer(x, lp, conv=conv, layer=i if sparse else None)
            if sparse:
                combines.append(combine)
        return head(x[-last:], params["final_norm"], params["embed"]), \
            combines


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int):
    """Float32 logits [last, V] at the last `last` positions of one
    sequence."""
    return _forward(params, m, tokens, last)[0]


def expert_counts(params, m, tokens: Sequence[int], rows: slice):
    """Tokens per expert [num_experts] over the positions `rows` of one
    sequence, summed over the sparse layers: what the program's routing
    counts of those rows add up to."""
    _, combines = _forward(params, m, tokens, 1)
    return sum(jnp.sum(c[rows] > 0, axis=0) for c in combines)


def served_token_gaps(params, m, prompt: List[int], served: List[int]):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    pages and the slots' windows; the reference sees neither, only prompt +
    served as one sequence."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n)            # [n, V]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


def loss_and_check_grads(params, m, tokens, checked: Optional[tuple] = None):
    raise NotImplementedError(
        "arch 'lfm2' serves only: the program's training forward refuses "
        "short-convolution layers (no stack of segments by kind, no flash "
        "backward at a head of 64)")

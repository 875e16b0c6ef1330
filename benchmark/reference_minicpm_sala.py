"""The plain reference of `arch: minicpm_sala`: MiniCPM-SALA's decoder, decayed
linear attention (`lightning-attn`) in three layers of four beside a
grouped-query attention layer that selects BLOCKS of keys (`minicpm4`), in
straightforward jax.numpy, float32, matmuls at precision "highest": no
kernel, no cache, no state, no chunk, no page, no batching; the linear layer
in its ATTENTION form (every pair under its decay), the selection by a sort.
Written from the published keys (`config.json`, the catalog's row), the
configuration's `assumed` and the equations of ISSUE 65, not from the
program's code; it shares with the program only the layout of the parameter
tree it is handed (stacks by kind: `sparse`, `linear`).

  x0 = scale_emb * E[id];  every sublayer adds r * f(rmsnorm(x)), r =
  scale_depth / sqrt(L), L the PUBLISHED depth (the length of `mixer_types`,
  whatever `num_hidden_layers` is run); feed-forward SwiGLU; logits = W_head
  (rmsnorm(x) / (hidden_size / dim_model_base)).

  layer l of kind mixer_types[l]:

  lightning-attn: H = lightning_nh heads of d = lightning_head_dim, as many kv
    heads. q, k, v = h W; rmsnorm over each head of q and of k (qk_norm);
    RoPE at rope_theta over the whole head, pairs (i, i + d/2)
    (lightning_use_rope); with rate_h = 2^(-8 (h + 1) / H) * (1 - l / (L - 1)
    + 1e-5), h = 0..H-1:
      o_t = d^-1/2 * sum_{s<=t} exp(-rate_h (t - s)) (q_t . k_s) v_s
    y = W_o ( rmsnorm(o over the joined heads; o_norm) * sigmoid(h W_g) )
    (use_output_norm, use_output_gate)

  minicpm4: num_attention_heads query heads on num_key_value_heads kv heads of
    head_dim, NO rotation, no norm. For the query at t (context n = t + 1),
    with sparse_config's kernel_size, kernel_stride, block_size, topk,
    init_blocks, window_size, dense_len:
    n < dense_len: causal softmax attention over every key. Else
      kbar_i = mean(k[stride i .. stride i + kernel - 1]), seen when its last
        row is <= t;
      p_h[i] = softmax_i(q_h . kbar_i / sqrt(head_dim)) over the seen ones;
        P_g[i] = sum of p_h over the query heads of kv head g;
      block b scores max P_g[i] over the kbar that overlap it; the first
        init_blocks and the window_size / block_size blocks that end at t's
        own score +inf;
      the topk blocks of largest score, ties to the smaller b (all where
        there are no more), are the keys t reads, causally in its own block;
        softmax attention of every head of g over them.
    y = W_o ( o * sigmoid(h W_g) )   (attn_use_output_gate)

Memory: weights come as they are stored and are made float32 where they are
used; both kinds of layer run Q_BLOCK queries at a time against the whole
context (32 heads x 128 x 12,191 scores are 200 MB), the feed-forward in four
blocks of columns; the final layer and the head run on the last `last`
positions.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 128
DENSE_BLOCKS = 4     # column blocks of the feed-forward
KINDS = {"minicpm4": "sparse", "lightning-attn": "linear"}

# `sparse_config` as MiniCPM4 publishes it; a configuration states its own.
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "topk": 64, "init_blocks": 1, "window_size": 2048,
          "dense_len": 8192}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def published_depth(m: Dict[str, Any]) -> int:
    """The depth the model is published with: `mixer_types` whole."""
    return len(m["mixer_types"])


def branch_scale(m: Dict[str, Any]) -> float:
    return m["scale_depth"] / published_depth(m) ** 0.5


def sparse_sizes(m: Dict[str, Any]) -> Dict[str, int]:
    return dict(SPARSE, **(m.get("sparse_config") or {}))


def decay_rates(m: Dict[str, Any], layer: int, factor: bool = True):
    """rate_h [H] of the linear layer at place `layer` of the published
    stack; `factor` False drops the layer's factor (a wrong model)."""
    H = m["lightning_nh"]
    place = 1.0 - layer / (published_depth(m) - 1) + 1e-5 if factor else 1.0
    return 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=F32) / H) * place


def _rope(x, positions, theta: float):
    """x [S, heads, d]: the whole head turned, pairs (i, i + d/2) by position
    * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(q, q_pos):
    """q [Sq, ...] and its positions in blocks of Q_BLOCK rows (padded)."""
    pad = -q.shape[0] % Q_BLOCK
    qb = jnp.pad(q, ((0, pad),) + ((0, 0),) * (q.ndim - 1))
    return (qb.reshape(-1, Q_BLOCK, *q.shape[1:]),
            jnp.pad(q_pos, (0, pad)).reshape(-1, Q_BLOCK))


def _linear_attention(q, k, v, q_pos, rates, scale):
    """q [Sq, H, d] at positions q_pos against k, v [Sk, H, d] at 0..Sk-1:
    every pair under its decay -> [Sq, H, d]."""
    sq = q.shape[0]
    k_pos = jnp.arange(k.shape[0])

    def block(args):
        qs, pos = args                                  # [B, H, d], [B]
        back = pos[:, None] - k_pos[None, :]            # [B, Sk]
        decay = jnp.where(back >= 0, jnp.exp(
            -rates[:, None, None] * jnp.maximum(back, 0).astype(F32)), 0.0)
        s = jnp.einsum("qhd,shd->hqs", qs, k) * decay * scale
        return jnp.einsum("hqs,shd->qhd", s, v)

    return jax.lax.map(block, _blocks(q, q_pos)).reshape(-1, *q.shape[1:])[:sq]


def linear_half(x, lp, m, layer: int, q_from=0, decay=True, factor=True,
                gate=True, norm=True):
    """x [S, D] -> x + r * linear attention(rmsnorm(x)), the rows from
    `q_from` on, for the linear layer at place `layer` of the published
    stack. `decay`, `factor`, `gate` or `norm` False is a WRONG model, for
    the tests that show the comparison sees it."""
    H, d, eps = m["lightning_nh"], m["lightning_head_dim"], m["rms_norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h[q_from:] @ lp["wq"].astype(F32)).reshape(s - q_from, H, d)
    k = (h @ lp["wk"].astype(F32)).reshape(s, H, d)
    v = (h @ lp["wv"].astype(F32)).reshape(s, H, d)
    if m.get("qk_norm"):
        q, k = _rms_norm(q, lp["q_norm"], eps), _rms_norm(k, lp["k_norm"], eps)
    if m.get("lightning_use_rope"):
        q = _rope(q, pos[q_from:], m["rope_theta"])
        k = _rope(k, pos, m["rope_theta"])
    rates = decay_rates(m, layer, factor) if decay else jnp.zeros((H,), F32)
    o = _linear_attention(q, k, v, pos[q_from:], rates, d ** -0.5)
    o = o.reshape(s - q_from, H * d)
    if norm and m.get("use_output_norm"):
        o = _rms_norm(o, lp["o_norm"], eps)
    if gate and m.get("use_output_gate"):
        o = o * jax.nn.sigmoid(h[q_from:] @ lp["wg"].astype(F32))
    return x[q_from:] + branch_scale(m) * (o @ lp["wo"].astype(F32))


def selected_blocks(q, pooled, pos, sizes, nb, per_head=False, window=True,
                    init=True):
    """q [B, KVH, G, dk] at positions pos [B], pooled [NK, KVH, dk] -> bool
    [B, KVH (, G), nb]: of the context's `nb` blocks, those each query reads
    once its context reaches dense_len, by a sort. `per_head`: a selection a
    query head, not a kv head's group (a wrong model); `window`, `init`
    False: those blocks not forced (wrong models)."""
    stride, kernel = sizes["kernel_stride"], sizes["kernel_size"]
    block, topk = sizes["block_size"], sizes["topk"]
    dk = q.shape[-1]
    nk = pooled.shape[0]
    seen = (jnp.arange(nk) * stride + kernel - 1)[None, :] <= pos[:, None]
    s = jnp.einsum("qkgd,ikd->qkgi", q, pooled) * dk ** -0.5
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf), axis=-1)
    p = jnp.where(seen[:, None, None], p, 0.0)          # (no kbar seen: 0)
    if not per_head:
        p = jnp.sum(p, axis=2)                          # [B, KVH, NK]
    # block b meets the kbar whose rows overlap its own
    first = jnp.arange(nk) * stride
    meets = (first[None, :] + kernel - 1 >= (jnp.arange(nb) * block)[:, None]) \
        & (first[None, :] <= (jnp.arange(nb) * block + block - 1)[:, None])
    score = jnp.max(jnp.where(meets, p[..., None, :], 0.0), axis=-1)
    own = pos // block                                   # [B]
    b = jnp.arange(nb)
    lead = (slice(None),) + (None,) * (score.ndim - 2)
    forced = jnp.zeros((pos.shape[0], nb), bool)
    if init:
        forced |= b[None, :] < sizes["init_blocks"]
    if window:
        forced |= b[None, :] > (own - sizes["window_size"] // block)[:, None]
    valid = (b[None, :] <= own[:, None])[lead]
    score = jnp.where(valid, jnp.where(forced[lead], jnp.inf, score), -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)[..., :topk]
    picked = jnp.any(order[..., None] == b, axis=-2)
    return picked & valid


def _sparse_attention(q, k, v, q_pos, sizes, wrong):
    """q [Sq, H, dk] at positions q_pos against k, v [Sk, KVH, dk] at
    0..Sk-1 -> [Sq, H, dk]."""
    sq, H, dk = q.shape
    sk, kvh = k.shape[:2]
    stride, kernel = sizes["kernel_stride"], sizes["kernel_size"]
    block = sizes["block_size"]
    k_pos = jnp.arange(sk)
    nk = max((sk - kernel) // stride + 1, 0)
    pooled = k[jnp.arange(nk)[:, None] * stride
               + jnp.arange(kernel)[None, :]].mean(axis=1)   # [NK, KVH, dk]
    qb, pb = _blocks(q.reshape(sq, kvh, H // kvh, dk), q_pos)

    def block_of(args):
        qs, pos = args                              # [B, KVH, G, dk], [B]
        s = jnp.einsum("qkgd,skd->qkgs", qs, k) * dk ** -0.5
        live = (k_pos[None, :] <= pos[:, None])[:, None, None]  # [B,1,1,Sk]
        if nk:
            picked = selected_blocks(qs, pooled, pos, sizes,
                                     -(-sk // block), **wrong)
            if picked.ndim == 3:
                picked = picked[:, :, None]          # [B, KVH, 1, NB]
            read = jnp.repeat(picked, block, axis=-1)[..., :sk]
            dense = (pos + 1 < sizes["dense_len"])[:, None, None, None]
            live = live & (dense | read)
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.einsum("qkgs,skd->qkgd", p, v).reshape(Q_BLOCK, H, dk)

    return jax.lax.map(block_of, (qb, pb)).reshape(-1, H, dk)[:sq]


def sparse_half(x, lp, m, q_from=0, gate=True, rope=False, **wrong):
    """x [S, D] -> x + r * block-sparse attention(rmsnorm(x)), the rows from
    `q_from` on. `gate` False, `rope` True, or `selected_blocks`' `wrong`
    arguments: wrong models, for the tests."""
    H, kvh, dk = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms_norm(x, lp["attn_norm"], m["rms_norm_eps"])
    q = (h[q_from:] @ lp["wq"].astype(F32)).reshape(s - q_from, H, dk)
    k = (h @ lp["wk"].astype(F32)).reshape(s, kvh, dk)
    v = (h @ lp["wv"].astype(F32)).reshape(s, kvh, dk)
    if rope or m.get("attn_use_rope"):
        q = _rope(q, pos[q_from:], m["rope_theta"])
        k = _rope(k, pos, m["rope_theta"])
    o = _sparse_attention(q, k, v, pos[q_from:], sparse_sizes(m), wrong)
    o = o.reshape(s - q_from, H * dk)
    if gate and m.get("attn_use_output_gate"):
        o = o * jax.nn.sigmoid(h[q_from:] @ lp["wg"].astype(F32))
    return x[q_from:] + branch_scale(m) * (o @ lp["wo"].astype(F32))


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def feed_forward_half(x, lp, m):
    """x [S, D] -> x + r * SwiGLU(rmsnorm(x)), `intermediate_size` columns in
    DENSE_BLOCKS blocks: a block's three matrices are float32 at once, not
    the layer's."""
    h = _rms_norm(x, lp["mlp_norm"], m["rms_norm_eps"])
    f = lp["w_gate"].shape[-1]
    n = DENSE_BLOCKS if f % DENSE_BLOCKS == 0 else 1

    def add_block(out, j):
        cols = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=j * (f // n), slice_size=f // n)
        return out + _swiglu(h, cols(lp["w_gate"], axis=1),
                             cols(lp["w_up"], axis=1),
                             cols(lp["w_down"], axis=0)), None

    out = jax.lax.scan(add_block, jnp.zeros_like(h), jnp.arange(n))[0]
    return x + branch_scale(m) * out


def _layer(x, lp, m, kind, layer, q_from, wrong=()):
    """One block on x [S, D]; returns the rows from `q_from` on. `wrong`:
    (name, value) pairs of what is computed wrongly on purpose."""
    wrong = dict(wrong)
    if kind == "linear":
        x = linear_half(x, lp, m, layer, q_from, **{
            k[len("linear_"):]: v for k, v in wrong.items()
            if k.startswith("linear_")})
    else:
        x = sparse_half(x, lp, m, q_from, **{
            k[len("sparse_"):]: v for k, v in wrong.items()
            if k.startswith("sparse_")})
    return feed_forward_half(x, lp, m)


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
           "lightning_nh", "lightning_head_dim", "lightning_use_rope",
           "qk_norm", "attn_use_rope", "rope_theta", "rms_norm_eps",
           "scale_depth", "mixer_types", "use_output_gate", "use_output_norm",
           "attn_use_output_gate", "sparse_config")


@functools.lru_cache(maxsize=None)
def _programs(widths):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = _thaw(widths)
    layer = jax.jit(functools.partial(_layer, m=m),
                    static_argnames=("kind", "layer", "q_from", "wrong"))

    @functools.partial(jax.jit, static_argnames=("divisor",))
    def head(x, norm, w, divisor):
        return (_rms_norm(x, norm, m["rms_norm_eps"]) / divisor) \
            @ w.astype(F32)

    return layer, head


def stack_order(m: Dict[str, Any]) -> List[Tuple[str, int, int]]:
    """The layers in the order they run, each (the stack that holds it, its
    ordinal there, its place in the published stack)."""
    out, at = [], {"sparse": 0, "linear": 0}
    for place, kind in enumerate(
            m["mixer_types"][:m["num_hidden_layers"]]):
        name = KINDS[kind]
        out.append((name, at[name], place))
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
        x = params["embed"][toks].astype(F32) * m["scale_emb"]
        for n, (name, i, place) in enumerate(order):
            q_from = len(tokens) - last if n == len(order) - 1 else 0
            lp = {k: v[i] for k, v in params[name].items()}
            x = layer(x, lp, kind=name, layer=place, q_from=q_from,
                      wrong=tuple(wrong))
        return head(x[-last:], params["final_norm"], params["lm_head"],
                    divisor=m["hidden_size"] / m["dim_model_base"])


def served_token_gaps(params, m, prompt: List[int], served: List[int],
                      wrong=()):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    pages, the pooled keys and the state; the reference sees none of them,
    only prompt + served as one sequence through every pair."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n, wrong)     # [n, V]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


def loss_and_check_grads(params, m, tokens, checked: Optional[tuple] = None):
    raise NotImplementedError(
        "arch 'minicpm_sala' serves only: the program's training forward "
        "refuses linear and block-sparse layers")

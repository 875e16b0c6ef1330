"""The plain reference of `arch: keye`: the language model of
Keye-VL-2.0-30B-A3B, a Qwen3-MoE-shaped decoder whose attention reads only
the keys a learned indexer selects (the DeepSeek-Sparse-Attention indexer,
`sa_config`), in straightforward jax.numpy, float32, matmuls at precision
"highest": no kernel, no cache, no mask arithmetic on bit patterns,
`lax.top_k` on each query's full score row, every expert computed densely on
every token. Written from the published descriptions (transformers
`modeling_qwen3_moe.py` for the block; DeepSeek-V3.2-Exp's `Indexer` for the
selection; Qwen2-VL's multimodal RoPE), not from the program's code; it
shares with the program only the layout of the parameter tree it is handed.

  h  = rmsnorm(x, w_in)                                  every norm in float32
  q  = h Wq, k = h Wk, v = h Wv -> heads of 128; q and k RMS-normalised over
       each head's own 128 (w_qn, w_kn), then rotated: mRoPE, the rotary
       frequencies in sections [16, 24, 24] of three position streams,
       which text sets equal
  qI[t, j] = rope(h[t] WIq)[j]            j = 1..16 heads of 64
  kI[s]    = rope(layernorm(h[s] WIk))    one head of 64
  w[t]     = (h[t] WIw) * 16^-1/2 * 64^-1/2
  I[t, s]  = sum_j w[t, j] relu(qI[t, j] . kI[s])
  S[t]     = the 2048 s <= t with the largest I[t, s] (all while t < 2048)
  x  = x + (softmax over s in S[t] of q[t] . k[s] / sqrt(128)) v Wo     GQA 32/4
  h  = rmsnorm(x, w_post)
  p  = softmax(h Wr) over 128 experts; (g, e) = top_8(p); g renormalised to 1
  x  = x + sum_j g_j (silu(h Wg[e_j]) * (h Wu[e_j])) Wd[e_j]      no token dropped

then a final rmsnorm and the untied head. Departures from the source, each
the configuration's too (`assumed`): DeepSeek's fp8 quantisation of the
indexer's q and k and the Hadamard rotation before it are left out (the
rotation is orthogonal and changes no dot product; the quantisation is an
approximation of these equations); the indexer's rotary embedding turns all
of its 64 dims (DeepSeek's turns 64 of 128) at the text stream's positions;
the vision tower is not built, so the three position streams are always
equal here. The loss is next-token cross-entropy alone (no router loss; the
indexer, which DeepSeek trains by a loss of its own, takes no gradient).

Memory: one layer's float32 copy is alive at a time, its experts one at a
time; attention and the selection run for a block of queries against the
whole context; the final layer and the head run on the last `last`
positions.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 256


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w + b


def _rope(x, streams, theta, sections=None):
    """x [S, heads, hd]; streams [3, S] positions. Frequency i is
    theta^(-2i/hd); with `sections` it turns by the stream of the section it
    lies in, else by the first. Pairs are (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    which = jnp.zeros(hd // 2, jnp.int32) if sections is None else jnp.repeat(
        jnp.arange(len(sections)), jnp.asarray(sections),
        total_repeat_length=hd // 2)
    pos = streams.astype(F32)[which, :].T                       # [S, hd/2]
    ang = pos * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _selected_attention(q, k, v, qi, ki, w, q_pos, topk):
    """q [Sq, H, hd] at positions q_pos against k/v [Sk, KVH, hd], under the
    selection of the indexer (qi [Sq, IH, Id], ki [Sk, Id], w [Sq, IH]); in
    blocks of queries."""
    sq, h, hd = q.shape
    sk, kvh = k.shape[0], k.shape[1]
    g = h // kvh
    k_pos = jnp.arange(sk)
    outs = []
    for start in range(0, sq, Q_BLOCK):
        rows = slice(start, start + Q_BLOCK)
        causal = k_pos[None, :] <= q_pos[rows, None]
        index = jnp.einsum("tj,tjs->ts", w[rows], jax.nn.relu(
            jnp.einsum("tjd,sd->tjs", qi[rows], ki)))
        index = jnp.where(causal, jax.lax.stop_gradient(index), -jnp.inf)
        n = index.shape[0]
        _, chosen = jax.lax.top_k(index, min(topk, sk))
        keep = jnp.zeros((n, sk), bool).at[
            jnp.arange(n)[:, None], chosen].set(True) & causal
        qb = q[rows].reshape(-1, kvh, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(F32(hd))
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, h * hd))
    return jnp.concatenate(outs, 0)


def _experts(h, lp, m):
    """h [T, D] -> [T, D]: the published mixture, every expert on every row."""
    n, k = m["num_experts"], m["num_experts_per_tok"]
    p = jax.nn.softmax(h @ lp["router"], axis=-1)              # [T, E]
    w, e = jax.lax.top_k(p, k)
    if m.get("norm_topk_prob", False):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    combine = jnp.sum(w[:, :, None] * jax.nn.one_hot(e, n, dtype=F32), axis=1)

    def add_expert(out, expert):
        # share [T]: 0 if not chosen. The experts' stacks come as they are
        # stored and one expert at a time is made float32 here (all 128 of a
        # layer at once are 2.4 GB at the published widths).
        w_gate, w_up, w_down, share = expert
        y = (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
            @ w_down.astype(F32)
        return out + share[:, None] * y, None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out


def _layer(x, lp, m, q_from):
    """One block on x [S, D]; returns the rows from `q_from` on (every row of
    K, V and the indexer's keys is still computed, from every row of x)."""
    h, kvh, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    sa = m["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    sections = m["rope_scaling"]["mrope_section"]
    s = x.shape[0]
    streams = jnp.broadcast_to(jnp.arange(s), (3, s))          # text
    hn = _rms_norm(x, lp["attn_norm"], eps)
    hq = hn[q_from:]
    q = _rms_norm((hq @ lp["wq"]).reshape(-1, h, hd), lp["q_norm"], eps)
    k = _rms_norm((hn @ lp["wk"]).reshape(s, kvh, hd), lp["k_norm"], eps)
    v = (hn @ lp["wv"]).reshape(s, kvh, hd)
    q = _rope(q, streams[:, q_from:], theta, sections)
    k = _rope(k, streams, theta, sections)
    qi = _rope((hq @ lp["wiq"]).reshape(-1, ih, idim), streams[:, q_from:],
               theta)
    ki = _rope(_layer_norm(hn @ lp["wik"], lp["ik_norm"], lp["ik_bias"],
                           eps)[:, None, :], streams, theta)[:, 0]
    w = (hq @ lp["wiw"]) * (ih ** -0.5 * idim ** -0.5)
    x = x[q_from:] + _selected_attention(
        q, k, v, qi, ki, w, streams[0, q_from:], sa["topk"]) @ lp["wo"]
    return x + _experts(_rms_norm(x, lp["mlp_norm"], eps), lp, m)


_EXPERTS = ("w_gate", "w_up", "w_down")


def _layer_f32(params, i):
    """Layer i's weights in float32, but the experts' stacks (`_experts`)."""
    return {k: v[i] if k in _EXPERTS else v[i].astype(F32)
            for k, v in params["layers"].items()}


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
           "rms_norm_eps", "rope_theta", "rope_scaling", "sa_config",
           "num_experts", "num_experts_per_tok", "norm_topk_prob")


@functools.lru_cache(maxsize=None)
def _programs(widths):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = _thaw(widths)
    layer = jax.jit(functools.partial(_layer, m=m), static_argnames="q_from")

    @jax.jit
    def head(x, norm, w):
        return _rms_norm(x, norm.astype(F32), m["rms_norm_eps"]) @ w.astype(F32)

    return layer, head


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int):
    """Float32 logits [last, V] at the last `last` positions of one sequence.
    Every layer but the final one runs on every position (its K, V and
    indexer keys feed the next layer); the final layer and the head run on
    the last `last` queries against the whole context."""
    n_layers = params["layers"]["wq"].shape[0]
    toks = jnp.asarray(tokens, jnp.int32)
    layer, head = _programs(_freeze({k: m.get(k, False) for k in _WIDTHS}))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32)
        for i in range(n_layers):
            q_from = len(tokens) - last if i == n_layers - 1 else 0
            x = layer(x, _layer_f32(params, i), q_from=q_from)
        return head(x[-last:], params["final_norm"], params["lm_head"])


def served_token_gaps(params, m, prompt: List[int], served: List[int]):
    """For greedy tokens `served` after `prompt`: at each step, the reference's
    largest logit minus its logit of the served token (0 where they agree).
    Prefill produced served[0]; served[i>0] came from decoding through the
    caches; the reference sees neither, only prompt + served as one sequence."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n)            # [n, V]
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (jnp.max(logits, axis=-1) - got)]


# The stacked leaves whose gradients the train check compares, with
# `final_norm` (the adapter's CHECK_LEAVES). The indexer's leaves take no
# gradient from this loss.
CHECKED = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "router")


def loss_and_check_grads(params, m, tokens, checked=CHECKED):
    """Mean next-token cross-entropy over all positions but each row's last of
    tokens [B, S], and its gradients with respect to `final_norm` and the
    stacked leaves named in `checked`."""
    n_layers = params["layers"]["wq"].shape[0]

    def loss_of(leaves):
        total, count = 0.0, 0
        for row in tokens:
            x = params["embed"][row].astype(F32)
            for i in range(n_layers):
                lp = _layer_f32(params, i)
                lp.update({k: leaves[k][i] for k in checked})
                x = _layer(x, lp, m, 0)
            x = _rms_norm(x, leaves["final_norm"], m["rms_norm_eps"])
            logp = jax.nn.log_softmax(
                x[:-1] @ params["lm_head"].astype(F32), axis=-1)
            total = total - jnp.sum(jnp.take_along_axis(
                logp, row[1:, None], axis=-1))
            count += row.shape[0] - 1
        return total / count

    leaves = {k: params["layers"][k].astype(F32) for k in checked}
    leaves["final_norm"] = params["final_norm"].astype(F32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_of)(leaves)

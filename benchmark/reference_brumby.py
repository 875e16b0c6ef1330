"""The plain reference of `arch: brumby` (Brumby-14B-Base, `model_type:
brumby`): a Qwen3-14B dense decoder whose softmax attention is replaced by
POWER RETENTION of degree 2 (arXiv:2507.04239, "Scaling Context Requires
Rethinking Attention"; the public modelling file beside the published
config.json; kernels: github.com/m-a-n-i-f-e-s-t/retention), in
straightforward jax.numpy, float32, matmuls at precision "highest": the
ATTENTION FORM, with no kernel, no cache, no recurrent state and no expansion
`phi` anywhere. Written from the equations below, not from the program's
code; it shares with the program only the layout of the parameter tree it is
handed (stacked leaves `layers/<name>[L, ...]`, `embed`, `final_norm`,
`lm_head`).

THE EQUATIONS (D = 5,120, H = 40 query heads, K = 8 kv heads, G = H / K = 5,
d = 128, degree p = 2, eps_norm = 1e-6, no bias on any projection):

  stack    h_0 = embed[ids]; each layer h <- h + retention(rmsnorm(h; w_1)),
           h <- h + (silu(u W_gate) * (u W_up)) W_down, u = rmsnorm(h; w_2)
           (widths [5120, 17408], [17408, 5120]); logits = rmsnorm(h; w_f)
           W_head, the head untied.
  inputs   x the normed input at position t: q_t = rope_t(rmsnorm_d(x W_q;
           w_qn)) [H, d]; k_t = rope_t(rmsnorm_d(x W_k; w_kn)) [K, d]; v_t = x
           W_v [K, d]; the log-gate g_t = logsigmoid(x W_g + b_g) [K], float32,
           one a KV head (W_g [D, K]). The q/k norm is Qwen3's (over each
           head's d, a weight of d), the rotary Qwen3's (rotate-half over the
           whole head, theta 1e6, no scaling).
  attention form (what is computed here): query head h of kv head k = h // G,
           j <= i:  a_ij = exp(sum_{l=j+1..i} g_l[k]) * (q_i[h] . k_j[k] /
           sqrt(d))^2;  y_i[h] = sum_j a_ij v_j[k] / (sum_j a_ij + eps), eps =
           1e-6;  out_i = concat_h(y_i[h]) W_o [H d, D]. The gates of positions
           j+1..i decay what j wrote; a token's own term is undecayed. Every
           a_ij >= 0 (an even power): the sum normalises without a softmax.
  recurrent form (what the PROGRAM keeps, not computed here): phi: R^d ->
           R^{d(d+1)/2}, entries u_a^2 and sqrt(2) u_a u_b (a < b), so that
           phi(u) . phi(w) = (u . w)^2; a kv head: S_t = e^{g_t} S_{t-1} +
           phi(k_t) v_t^T / d, z_t = e^{g_t} z_{t-1} + phi(k_t) / d, y_t[h] =
           phi(q_t[h])^T S_t / (phi(q_t[h]) . z_t + eps). The two agree to
           rounding at every position.

WHAT IS ASSUMED, beyond config.json (the configuration file's `assumed` says
why): the degree 2; the gate's form and its one output a kv head; `b_g`, a
constant a kv head a layer inside the logsigmoid (a constant of the
initialisation, no published weight); normalisation by the sum with eps
1e-6; the scale 1/sqrt(d) inside the square; q/k norm and RoPE kept from the
Qwen3 block. `max_window_layers`, `use_sliding_window` and `sliding_window`
are read by nothing.

Memory: one layer's float32 copy is alive at a time; the retention is
computed for a block of queries against the whole context, the feed-forward
for a block of rows.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 256
V_BLOCK = 16384
EPS = 1e-6


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [S, heads, d]; rotate pairs (i, i + d/2) by position * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _retention(q, k, v, g, q_from):
    """The attention form: q [Sq, H, d] the queries at positions `q_from`..,
    k, v [S, K, d] and the log-gates g [S, K] of every position -> [Sq, H d],
    a block of queries at a time against the whole context."""
    sq, h, d = q.shape
    s, kvh = k.shape[0], k.shape[1]
    grp = h // kvh
    total = jnp.cumsum(g, axis=0)               # [S, K]: sum of g_0..g_t
    pos = jnp.arange(s)
    outs = []
    for start in range(0, sq, Q_BLOCK):
        qb = q[start:start + Q_BLOCK].reshape(-1, kvh, grp, d)
        at = q_from + start + jnp.arange(qb.shape[0])
        score = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(F32(d))
        seen = pos[None, :] <= at[:, None]                      # [q, s]
        # sum_{l=j+1..i} g_l = total_i - total_j, <= 0 where j <= i
        decay = jnp.exp(jnp.where(seen[None], total[at].T[:, :, None]
                                  - total.T[:, None, :], -jnp.inf))
        a = decay[:, None] * jnp.square(score)                  # [k, g, q, s]
        num = jnp.einsum("kgqs,skd->qkgd", a, v)
        den = jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]
        outs.append((num / (den + EPS)).reshape(-1, h * d))
    return jnp.concatenate(outs, 0)


@functools.partial(jax.jit, static_argnames=("q_from", "h", "kvh", "d", "eps",
                                              "theta"))
def _layer(x, lp, q_from, h, kvh, d, eps, theta):
    """One block on x [S, D]; returns the rows from `q_from` on (k, v and the
    gates of every row are still computed, from every row of x). One jitted
    function for the process: a control that asks a thousand forwards of one
    length compiles one."""
    s = x.shape[0]
    pos = jnp.arange(s)
    hn = _rms_norm(x, lp["attn_norm"], eps)
    k = _rope(_rms_norm((hn @ lp["wk"]).reshape(s, kvh, d), lp["k_norm"], eps),
              pos, theta)
    v = (hn @ lp["wv"]).reshape(s, kvh, d)
    g = jax.nn.log_sigmoid(hn @ lp["wg"] + lp["bg"])            # [S, K]
    q = _rope(_rms_norm((hn[q_from:] @ lp["wq"]).reshape(s - q_from, h, d),
                        lp["q_norm"], eps), pos[q_from:], theta)
    x = x[q_from:] + _retention(q, k, v, g, q_from) @ lp["wo"]
    outs = []
    for start in range(0, x.shape[0], 4 * Q_BLOCK):
        rows = x[start:start + 4 * Q_BLOCK]
        u = _rms_norm(rows, lp["mlp_norm"], eps)
        outs.append(rows + (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"]))
                    @ lp["w_down"])
    return jnp.concatenate(outs, 0)


@functools.partial(jax.jit, static_argnames="eps")
def _head(x, norm, w, eps):
    return _rms_norm(x, norm.astype(F32), eps) @ w.astype(F32)


def _layer_f32(params, i):
    return {k: v[i].astype(F32) for k, v in params["layers"].items()}


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int):
    """Float32 logits [last, V] at the last `last` positions of one sequence:
    the prompt and the generated tokens in ONE full forward. Every layer but
    the final one runs on every position; the final layer and the head on the
    last `last` queries against the whole context."""
    n_layers = params["layers"]["wq"].shape[0]
    toks = jnp.asarray(tokens, jnp.int32)
    sizes = dict(h=m["num_attention_heads"], kvh=m["num_key_value_heads"],
                 d=m["head_dim"], eps=float(m["rms_norm_eps"]),
                 theta=float(m["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(F32)
        for i in range(n_layers):
            q_from = len(tokens) - last if i == n_layers - 1 else 0
            # (waited for: the next layer's float32 copy, 1.3 GB at these
            # widths, is not made while this one's is still read)
            x = jax.block_until_ready(
                _layer(x, _layer_f32(params, i), q_from=q_from, **sizes))
        # The head a block of columns at a time: its float32 copy whole is
        # 3.1 GB at 151,936 columns, beside a serving engine's memory.
        w = params["lm_head"]
        return jnp.concatenate(
            [_head(x[-last:], params["final_norm"], w[:, c:c + V_BLOCK],
                   sizes["eps"]) for c in range(0, w.shape[1], V_BLOCK)],
            axis=1)


def served_token_gaps(params, m, prompt: List[int], served: List[int]):
    """For greedy tokens `served` after `prompt`: at each step, the
    reference's largest logit minus its logit of the served token (0 where
    they agree). Prefill produced served[0]; served[i>0] came from decoding
    through the slot's recurrent state; the reference sees neither, only
    prompt + served as one sequence."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    logits = logits_last(params, m, seq, n)            # [n, V]
    top = jnp.max(logits, axis=-1)
    got = logits[jnp.arange(n), jnp.asarray(served, jnp.int32)]
    return [float(g) for g in (top - got)]


def loss_and_check_grads(params, m, tokens):
    raise NotImplementedError(
        "arch 'brumby' is served, not trained: the program's training "
        "forward refuses power retention by name")

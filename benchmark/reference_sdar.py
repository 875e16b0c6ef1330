"""The plain reference of `arch: sdar`: SDAR-30B-A3B-Chat (JetLM; the method
is arXiv:2510.06303, block diffusion), a Qwen3-MoE decoder that generates a
BLOCK of positions at a time, in straightforward jax.numpy, float32, matmuls
at precision "highest": no kernel, no cache, every expert computed densely on
every token, the whole sequence recomputed at every forward. Written from the
published descriptions (transformers `modeling_qwen3_moe.py` for the block;
the release's `generate.py` for the procedure), not from the program's code;
it shares with the program only the layout of the parameter tree it is handed.

  h  = rmsnorm(x, w_in)                                  every norm in float32
  q  = h Wq, k = h Wk, v = h Wv -> heads of 128; q and k RMS-normalised over
       each head's own 128 (w_qn, w_kn), then rotated (rotate-half, theta 1e6)
  x  = x + (softmax over the s that t SEES of q[t] . k[s] / sqrt(128)) v Wo
       t sees s  iff  floor(s / B) <= floor(t / B): all of its own block of B
       positions, both ways, and every block before it           GQA 32/4
  h  = rmsnorm(x, w_post)
  p  = softmax(h Wr) over 128 experts; (g, e) = top_8(p); g renormalised to 1
  x  = x + sum_j g_j (silu(h Wg[e_j]) * (h Wu[e_j])) Wd[e_j]      no token dropped

then a final rmsnorm and the untied head; the logits of position t are the
prediction of ITS OWN token (no shift by one).

Generation (`low_confidence_static` of the release's `generate.py`; its
default, `low_confidence_dynamic` at 0.9, falls back to exactly this where
fewer than n_s rows clear the threshold), B = block_length, T =
denoise_steps, m = mask_id:

  a prompt of L ids, then block after block at positions p = B floor(L / B),
  p + B, ...; z = the block: the prompt's tail (L - p ids, the first block
  only), then masks
  for s = 1..T:  logits = f(ids before p ; z) at the block's rows
                 for each masked i: x_i = argmax logits_i, c_i =
                     softmax(logits_i)[x_i] (float32)
                 the n_s masked i of largest c_i (ties to the smaller i; all
                     if fewer are left) take x_i;  n_s = B // T + (s <= B mod T)

A masked row is embedded as the mask id; WHICH rows are masked is carried
beside the ids, not read off `z == m`, so a prompt id or a committed token
that happens to equal the mask id stays what it is (the published loop tests
`z == m`: a departure, the configuration's `assumed` has it).

All blocks at once. Under the mask a block's rows depend on the FINAL ids of
the blocks before it and on its own state, nothing else. So the state "every
block after the prompt at step s" is one forward of two streams side by side,
as the method trains: the final stream (every position's final id) and the
noised stream (every block as step s finds it), a noised row seeing the final
stream's earlier blocks and the noised stream's own block. `_states` runs it,
and `served_token_gaps` and `logits_last` are built on it.

Memory: one layer's float32 copy is alive at a time, its experts one at a
time; attention runs for a block of queries against the whole context.
"""

from __future__ import annotations

import functools
import itertools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, heads, hd] at positions pos [S]. Frequency i is theta^(-2i/hd);
    pairs are (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, sees):
    """q [Sq, H, hd] against k/v [Sk, KVH, hd] under `sees` [Sq, Sk] (every
    query sees itself at least); in blocks of queries."""
    sq, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    outs = []
    for start in range(0, sq, Q_BLOCK):
        rows = slice(start, start + Q_BLOCK)
        qb = q[rows].reshape(-1, kvh, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(F32(hd))
        p = jax.nn.softmax(jnp.where(sees[rows][None, None], s, -jnp.inf),
                           axis=-1)
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


def _layer(x, lp, pos, sees, m):
    """One block on rows x [R, D], row r at position pos[r], seeing the rows
    `sees[r]` marks."""
    h, kvh, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    r = x.shape[0]
    hn = _rms_norm(x, lp["attn_norm"], eps)
    q = _rms_norm((hn @ lp["wq"]).reshape(r, h, hd), lp["q_norm"], eps)
    k = _rms_norm((hn @ lp["wk"]).reshape(r, kvh, hd), lp["k_norm"], eps)
    v = (hn @ lp["wv"]).reshape(r, kvh, hd)
    x = x + _attention(_rope(q, pos, theta), _rope(k, pos, theta), v,
                       sees) @ lp["wo"]
    return x + _experts(_rms_norm(x, lp["mlp_norm"], eps), lp, m)


_EXPERTS = ("w_gate", "w_up", "w_down")


def _layer_f32(params, i):
    """Layer i's weights in float32, but the experts' stacks (`_experts`)."""
    return {k: v[i] if k in _EXPERTS else v[i].astype(F32)
            for k, v in params["layers"].items()}


# What of a configuration the block's equations read.
_WIDTHS = ("num_attention_heads", "num_key_value_heads", "head_dim",
           "rms_norm_eps", "rope_theta", "num_experts", "num_experts_per_tok",
           "norm_topk_prob")


@functools.lru_cache(maxsize=None)
def _programs(widths):
    """(layer, head) compiled once for a set of widths: the control calls
    `logits_last` once a token, and a `jax.jit` made anew is traced anew."""
    m = dict(widths)
    layer = jax.jit(functools.partial(_layer, m=m))

    @jax.jit
    def head(x, norm, w):
        return _rms_norm(x, norm.astype(F32), m["rms_norm_eps"]) @ w.astype(F32)

    return layer, head


def _run(params, m, ids, pos, sees, rows):
    """Float32 logits [len(rows), V] of the rows `rows` of ONE forward over
    the rows `ids` [R] (already ids: a masked row holds the mask id), row r
    at position pos[r] under `sees` [R, R]."""
    layer, head = _programs(tuple((k, m.get(k, False)) for k in _WIDTHS))
    n_layers = params["layers"]["wq"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(F32)
        pos, sees = jnp.asarray(pos, jnp.int32), jnp.asarray(sees)
        for i in range(n_layers):
            x = layer(x, _layer_f32(params, i), pos, sees)
        return head(x[jnp.asarray(rows, jnp.int32)], params["final_norm"],
                    params["lm_head"])


def _sizes(m) -> Tuple[int, int, int]:
    """(block_length, denoise_steps, mask_id), the configuration's
    `assumed`."""
    return (int(m["block_length"]), int(m["denoise_steps"]),
            int(m["mask_id"]))


def forward(params: Dict[str, Any], m: Dict[str, Any],
            tokens: Sequence[int], rows=None):
    """Float32 logits [S, V] of one sequence under the block mask (of the
    positions `rows` alone, where given): position t attends to s iff floor(s
    / B) <= floor(t / B), and its logits predict its own token."""
    B = _sizes(m)[0]
    pos = np.arange(len(tokens))
    sees = pos[None, :] // B <= pos[:, None] // B
    return _run(params, m, tokens, pos, sees, pos if rows is None else rows)


def _quota(B: int, T: int, s: int) -> int:
    """Rows step s of 1..T commits."""
    return B // T + (1 if s <= B % T else 0)


def _candidates(logits: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(the argmax of each row of logits [R, V], the float32 softmax's
    probability of it)."""
    x = logits.argmax(-1)
    lg = logits - logits.max(-1, keepdims=True)
    conf = np.exp(lg) / np.exp(lg).sum(-1, keepdims=True)
    return x, conf[np.arange(len(x)), x].astype(np.float32)


def _commit(conf: np.ndarray, masked: np.ndarray, n: int) -> np.ndarray:
    """Of the masked rows of ONE block, the n of largest confidence, ties to
    the smaller index (all of them where fewer are left): a bool mask."""
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
    take = np.zeros(len(masked), bool)
    take[order[:n]] = True
    return take


def generate(params, m, prompt: Sequence[int], n: int,
             with_logits: bool = False):
    """n greedy tokens after `prompt` by the equations at the top, the whole
    sequence recomputed at every forward (padded with masks to the stream's
    final length: no row sees a later block), no cache. With `with_logits`: also
    the float32 logits each token was committed from [n, V], and the step
    (1..T) it was committed in."""
    B, T, mask_id = _sizes(m)
    seq = list(prompt)
    L = len(seq)
    # One length for every forward of a stream: what lies after a block is
    # masks that no row of it sees, so one program serves them all.
    N = -(-(L + n) // B) * B
    out_logits, out_steps = {}, {}
    while len(seq) < L + n:
        p = len(seq) // B * B
        z = np.asarray(seq[p:] + [mask_id] * (p + B - len(seq)), np.int64)
        masked = np.arange(p, p + B) >= len(seq)
        for s in range(1, T + 1):
            logits = np.asarray(forward(
                params, m, seq[:p] + list(z) + [mask_id] * (N - p - B),
                rows=np.arange(p, p + B)))
            x, conf = _candidates(logits)
            for i in np.flatnonzero(_commit(conf, masked, _quota(B, T, s))):
                z[i], masked[i] = x[i], False
                out_logits[p + i], out_steps[p + i] = logits[i], s
        seq = seq[:p] + [int(t) for t in z]
    tokens = seq[L:L + n]
    if not with_logits:
        return tokens
    at = range(L, L + n)
    return (tokens, np.stack([out_logits[i] for i in at]),
            [out_steps[i] for i in at])


def _states(params, m, final: Sequence[int], L: int,
            noised: np.ndarray, masked: np.ndarray):
    """ONE forward of every block after the prompt at once. `final`: the
    final ids of positions 0..N-1 (N a multiple of B); `noised` [N - P] the
    ids of positions P.. (P = B floor(L / B)) as this step finds them and
    `masked` which of them are masks. -> float32 logits [N - P, V] of the
    noised rows: a noised row at position t sees the final stream's blocks
    before its own and the noised stream's own block."""
    B, _, mask_id = _sizes(m)
    N = len(final)
    P = L // B * B
    ids = np.concatenate([np.asarray(final, np.int64),
                          np.where(masked, mask_id, noised)])
    pos = np.concatenate([np.arange(N), np.arange(P, N)])
    blk = pos // B
    is_noised = np.arange(len(ids)) >= N
    # final rows: the block mask among themselves, no noised row; noised rows:
    # final rows of EARLIER blocks, noised rows of their own.
    sees = np.where(
        is_noised[:, None],
        np.where(is_noised[None, :], blk[None, :] == blk[:, None],
                 blk[None, :] < blk[:, None]),
        ~is_noised[None, :] & (blk[None, :] <= blk[:, None]))
    return np.asarray(_run(params, m, ids, pos, sees, np.arange(N, len(ids))))


def _padded(prompt: Sequence[int], served: Sequence[int], B: int):
    """(final ids padded with zeros to whole blocks, L, P, rows: the index
    into the noised stream of each served token)."""
    L = len(prompt)
    P = L // B * B
    final = list(prompt) + list(served)
    final += [0] * (-len(final) % B)
    return final, L, P, np.arange(L - P, L - P + len(served))


def served_token_gaps(params, m, prompt: List[int], served: List[int],
                      with_steps: bool = False):
    """For greedy tokens `served` after `prompt`: for each, the reference's
    largest logit at its position, in the state it was committed from, less
    its logit of the served token (0 where they agree). The served stream
    does not say in which step a token was committed, so for T = 2 each
    block's step 1 is run (all of its masks, the earlier blocks final) and
    its step 2 under each of the C(masked, n_1) subsets that step 1 may have
    committed, and a block is held to the subset under which its served
    tokens read the smallest summed gap. For T = 1 there is one state, for T
    > 2 the subsets multiply and this is not written. The reference sees only
    prompt + served as one sequence (and, of a last block the request ends
    inside, its own greedy candidates at the rows that were not served). With
    `with_steps`: also the step each token is held to."""
    B, T, _ = _sizes(m)
    if T > 2:
        raise NotImplementedError(
            "served_token_gaps walks the subsets of one intermediate step: "
            "denoise_steps 1 or 2")
    final, L, P, rows = _padded(prompt, served, B)
    N = len(final)
    noised = np.asarray(final[P:], np.int64)
    start = np.arange(P, N) >= L                 # step 1: every mask
    served_rows = np.zeros(N - P, bool)
    served_rows[rows] = True

    def gaps_of(logits):
        return logits.max(-1) - logits[np.arange(N - P), noised]

    logits = _states(params, m, final, L, noised, start)
    first = gaps_of(logits)
    # A request that ends inside a block: the block's other rows were
    # computed and not served. Where step 1 may have committed one, the
    # state of step 2 holds the reference's own candidate there.
    noised = np.where(served_rows | ~start, noised, logits.argmax(-1))
    n1 = _quota(B, T, 1)
    blocks = start.reshape(-1, B)
    # every block's k-th subset of its masked rows, k = 0..: one forward a k.
    # The reference's own choice first (the n_1 most confident of ITS step 1),
    # so that where two subsets read alike a block is held to that one.
    conf = _candidates(logits)[1].reshape(-1, B)
    subsets = []
    for b, c in zip(blocks, conf):
        own = tuple(np.flatnonzero(_commit(c, b, n1)))
        subsets.append([own] + [s for s in itertools.combinations(
            np.flatnonzero(b), len(own)) if s != own])
    best = np.full(len(blocks), np.inf)
    gaps = np.zeros(N - P)
    steps = np.ones(N - P, int)
    for k in range(max(len(s) for s in subsets) if T == 2 else 0):
        early = np.zeros_like(blocks)
        for b, subs in enumerate(subsets):
            early[b, list(subs[k % len(subs)])] = True
        early = early.reshape(-1)
        second = gaps_of(_states(params, m, final, L, noised,
                                 start & ~early))
        mixed = np.where(early, first, second)
        # (what lies past the served tokens, in the last block, is padding)
        total = np.where(served_rows & start, mixed, 0.0).reshape(-1, B).sum(-1)
        for b in np.flatnonzero(total < best):
            best[b] = total[b]
            cell = slice(b * B, (b + 1) * B)
            gaps[cell], steps[cell] = mixed[cell], np.where(early[cell], 1, 2)
    if T == 1:
        gaps = first
    out = [float(g) for g in gaps[rows]]
    return (out, [int(s) for s in steps[rows]]) if with_steps else out


def logits_last(params: Dict[str, Any], m: Dict[str, Any],
                tokens: Sequence[int], last: int):
    """Float32 logits [last, V] from which the tokens at the `last` positions
    after `tokens[:len(tokens) - last + 1]` (the prompt) are committed, given
    the ids `tokens` holds at the blocks before each (benchmark/control.py
    fills them in as it reads its greedy tokens off these rows, in order, so
    by the time it reads row i every earlier block is final). Every block
    after the prompt at once, the T steps one after the other, greedy."""
    B, T, _ = _sizes(m)
    L = len(tokens) - last + 1
    final, _, P, rows = _padded(tokens[:L], list(tokens[L:]) + [0], B)
    N = len(final)
    z = np.asarray(final[P:], np.int64)
    masked = np.arange(P, N) >= L
    out = np.zeros((N - P, 0), np.float32)
    for s in range(1, T + 1):
        logits = _states(params, m, final, L, z, masked)
        if not out.shape[1]:
            out = np.zeros_like(logits)
        x, conf = _candidates(logits)
        for b in range((N - P) // B):
            cell = slice(b * B, (b + 1) * B)
            take = _commit(conf[cell], masked[cell], _quota(B, T, s))
            idx = np.flatnonzero(take) + b * B
            z[idx], masked[idx], out[idx] = x[idx], False, logits[idx]
    return jnp.asarray(out[rows])


def loss_and_check_grads(params, m, tokens, checked=()):
    raise NotImplementedError(
        "arch 'sdar' serves only: the masked-block objective of "
        "arXiv:2510.06303 is not built, and no cell trains this model")

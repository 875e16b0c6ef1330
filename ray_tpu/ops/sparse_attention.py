"""Learned sparse attention (the DeepSeek-Sparse-Attention indexer): a small
scorer picks, for every query, the `topk` earlier positions its attention
may read, and softmax attention runs over those alone.

For a query t with indexer queries qI[t, j] (j = 1..IH heads of width Id),
one indexer key kI[s] a position and head weights w[t, j] (already scaled):

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        float32
    S[t]    = the `topk` positions s <= t with the largest I[t, s]
              (all of them while t < topk; ties to the smaller s)
    o[t]    = softmax attention of q[t] over {k[s], v[s] : s in S[t]}

The selection is EXACT: `jax.lax.top_k` on the XLA paths, and on the TPU
kernel a bisection on the scores' bit patterns that returns the same set,
ties included (`lax.approx_max_k` would be another model). Scores are
accumulated in float32 from the inputs' dtype and never exist as
`[IH, T, T]`: both paths work a block of queries at a time.

  * ``sparse_attention`` is the whole-sequence form (training, the engine's
    prefill): on a TPU two Pallas kernels, `index_select` (scores and
    selection of a block of query rows in fast memory, out comes the mask)
    and `masked_flash` (flash attention under it, a kv head's whole group of
    query heads a grid step); elsewhere XLA, a block of queries at a time
    (`attention.attention_path_counts()`: `sparse_pallas`, `sparse_reference`).
  * ``sparse_decode_attention`` is one query token a slot against the paged
    caches (`ops/paged_kv.py`; K and V BY TOKEN): it scores the slot's live
    indexer keys, selects, and on a TPU STREAMS the slot's live pages under
    the selection's mask (`sparse_paged_decode`: a page is one contiguous
    run) while `_streams` says so; else it gathers the selected rows alone.

Scope names `indexer`, `select` and `sparse_attn` lie inside the caller's
`attn`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention, paged_kv
from ray_tpu.ops.attention import DEFAULT_MASK_VALUE

_INT_MIN = -2 ** 31


def index_scores(qi: jax.Array, ki: jax.Array, w: jax.Array) -> jax.Array:
    """qi [..., T, IH, Id], ki [..., S, Id], w [..., T, IH] -> I [..., T, S]
    float32. An exact zero is +0.0 (a negative weight on a dead relu gives
    -0.0, which a comparison of bit patterns would rank below it)."""
    s = jnp.einsum("...tjd,...sd->...tjs", qi, ki,
                   preferred_element_type=jnp.float32)
    scores = jnp.einsum("...tjs,...tj->...ts", jax.nn.relu(s),
                        w.astype(jnp.float32))
    return jnp.where(scores == 0, 0.0, scores)


def select_mask(scores: jax.Array, valid: jax.Array, topk: int) -> jax.Array:
    """scores [..., T, S] float32, valid [..., T, S] bool (the positions a
    query may see at all) -> bool [..., T, S]: the `topk` valid positions of
    each row with the largest score, ties to the smaller index
    (`lax.top_k`'s order); every valid position of a row that has no more
    than `topk`."""
    S = scores.shape[-1]
    if topk >= S:
        return valid
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), topk)
    idx = idx.reshape(-1, topk)
    picked = jnp.zeros((idx.shape[0], S), bool).at[
        jnp.arange(idx.shape[0])[:, None], idx].set(True)
    return picked.reshape(scores.shape) & valid


# ---------------------------------------------------------------------------
# XLA path: a block of queries at a time
# ---------------------------------------------------------------------------

_REF_BLOCK = 512


def _masked_attention_rows(q, k, v, mask, sm_scale):
    """q [B, KVH, G, T, hd], k/v [B, KVH, S, hd], mask [B, T, S] bool."""
    s = jnp.einsum("bkgtd,bksd->bkgts", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask[:, None, None], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgts,bksd->bkgtd", p.astype(v.dtype), v)


def _sparse_reference(q, k, v, qi, ki, w, topk, sm_scale):
    B, H, S, hd = q.shape
    KVH = k.shape[1]
    qg = q.reshape(B, KVH, H // KVH, S, hd)
    block = _REF_BLOCK if S % _REF_BLOCK == 0 else S
    cols = jnp.arange(S)

    def rows(start):
        at = lambda x, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            x, start, block, axis)
        with jax.named_scope("indexer"):
            scores = jax.lax.stop_gradient(
                index_scores(at(qi, 1), ki, at(w, 1)))       # [B, block, S]
        with jax.named_scope("select"):
            causal = cols[None, :] <= (start + jnp.arange(block))[:, None]
            mask = select_mask(scores, causal[None], topk)
        with jax.named_scope("sparse_attn"):
            return _masked_attention_rows(at(qg, 3), k, v, mask, sm_scale)

    if block == S:
        out = rows(0)
    else:
        out = jax.lax.map(rows, jnp.arange(0, S, block))   # [n, B, KVH, G, block, hd]
        out = jnp.moveaxis(out, 0, 3).reshape(B, KVH, H // KVH, S, hd)
    return out.reshape(B, H, S, hd)


# ---------------------------------------------------------------------------
# TPU kernels
# ---------------------------------------------------------------------------

def _index_select_kernel(qi_ref, w_ref, ki_ref, mask_ref, key_ref, *,
                         topk: int, block_q: int, chunk: int):
    """One grid step = `block_q` query rows against every key: their scores
    as order-preserving int32 keys in `key_ref` [block_q, S], the topk-th
    largest key of each row by bisection on its 32 bits (each probe one
    compare-and-count pass over the rows' live columns), the ties at it by a
    second bisection on the column index, and out goes the mask. Only the
    column chunks at or under the block's last row are ever touched."""
    heads = qi_ref.shape[0]
    S = ki_ref.shape[0]
    row0 = pl.program_id(0) * block_q
    n_chunks = pl.cdiv(row0 + block_q, chunk)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, chunk), 1)

    def at(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def score_chunk(c, carry):
        kib = ki_ref[at(c), :]                                # [chunk, Id]
        acc = jnp.zeros((block_q, chunk), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(
                qi_ref[j], kib, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [block_q, chunk]
            acc = acc + jnp.maximum(s, 0.0) * w_ref[j]
        acc = jnp.where(acc == 0.0, 0.0, acc)
        bits = jax.lax.bitcast_convert_type(acc, jnp.int32)
        key = bits ^ ((bits >> 31) & 0x7FFFFFFF)     # int order = float order
        key_ref[:, at(c)] = jnp.where(c * chunk + lane <= rows, key, _INT_MIN)
        return carry

    jax.lax.fori_loop(0, n_chunks, score_chunk, 0)

    def count(pred):
        """Per row, over the live chunks, how many columns `pred(key, col)`
        holds for."""
        def body(c, acc):
            hit = pred(key_ref[:, at(c)], c * chunk + lane)
            return acc + jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)
        return jax.lax.fori_loop(0, n_chunks, body,
                                 jnp.zeros((block_q, 1), jnp.int32))

    def write(pick):
        def body(c, carry):
            key = key_ref[:, at(c)]
            keep = pick(key, c * chunk + lane) & (key != _INT_MIN)
            mask_ref[:, at(c)] = keep.astype(mask_ref.dtype)
            return carry
        jax.lax.fori_loop(0, n_chunks, body, 0)

        def rest(c, carry):
            mask_ref[:, at(c)] = jnp.zeros((block_q, chunk), mask_ref.dtype)
            return carry
        jax.lax.fori_loop(n_chunks, S // chunk, rest, 0)

    @pl.when(row0 + block_q <= topk)
    def _all():           # no row of the block has more than topk candidates
        write(lambda key, col: col >= 0)

    @pl.when(row0 + block_q > topk)
    def _select():
        nonneg = count(lambda key, col: key >= 0)
        t0 = jnp.where(nonneg >= topk, 0, _INT_MIN).astype(jnp.int32)

        def bit(i, t):
            cand = t + jax.lax.shift_left(jnp.int32(1), 30 - i)
            n = count(lambda key, col: key >= cand)
            return jnp.where(n >= topk, cand, t)

        t = jax.lax.fori_loop(0, 31, bit, t0)    # the topk-th largest key
        need = topk - count(lambda key, col: key > t)
        # The ties at t: the `need` of the smallest columns, i.e. those
        # under the largest p with count(key == t, col < p) <= need.

        def tie_bit(i, p):
            cand = p + jax.lax.shift_left(jnp.int32(1), S.bit_length() - 1 - i)
            n = count(lambda key, col: (key == t) & (col < cand))
            return jnp.where(n <= need, cand, p)

        p = jax.lax.fori_loop(0, S.bit_length(), tie_bit,
                              jnp.zeros((block_q, 1), jnp.int32))
        write(lambda key, col: (key > t) | ((key == t) & (col < p)))


def _index_select_pallas(qi, ki, w, topk, *, block_q=256, chunk=1024,
                         interpret=False):
    """qi [S, IH, Id], ki [S, Id], w [S, IH] float32 -> int8 [S, S]: 1 where
    the query (row) selects the key (column)."""
    S, heads, dim = qi.shape
    block_q = attention._pick_block(S, block_q)
    chunk = attention._pick_block(S, chunk)
    kernel = functools.partial(_index_select_kernel, topk=topk,
                               block_q=block_q, chunk=chunk)
    return pl.pallas_call(
        kernel,
        name="index_select",
        grid=(S // block_q,),
        in_specs=[
            pl.BlockSpec((heads, block_q, dim), lambda i: (0, i, 0)),
            pl.BlockSpec((heads, block_q, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((S, dim), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, S), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, S), jnp.int8),
        scratch_shapes=[pltpu.VMEM((block_q, S), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(qi.transpose(1, 0, 2), w.astype(jnp.float32).T[:, :, None], ki)


def _masked_flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, sm_scale: float,
                         block_q: int, block_k: int):
    """Flash attention of one kv head's GROUP of query heads (G x block_q
    rows) against a block of its keys, under the causal selection mask."""
    q_idx, kv_idx = pl.program_id(1), pl.program_id(2)
    G, _, hd = q_ref.shape[1:]

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, DEFAULT_MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)

    # The mask is causal: a block wholly above the diagonal selects nothing.
    @pl.when(kv_idx * block_k <= q_idx * block_q + (block_q - 1))
    def _body():
        q = q_ref[0].reshape(G * block_q, hd)
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        keep = mask_ref[...].astype(jnp.int32) != 0           # [block_q, block_k]
        s = jnp.where(keep[None], s.reshape(G, block_q, block_k),
                      DEFAULT_MASK_VALUE).reshape(G * block_q, block_k)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # A row whose every column so far is masked has m == the mask value
        # and p == 1 there: its l and acc are wiped by `corr` == 0 when its
        # first real score arrives, and every row selects some column.
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = out.reshape(G, block_q, hd).astype(o_ref.dtype)


def _masked_flash_pallas(q, k, v, mask, *, sm_scale, block_q=256,
                         block_k=1024, interpret=False):
    """q [KVH, G, S, hd], k/v [KVH, S, hd], mask int8 [S, S] (causal)
    -> [KVH, G, S, hd]."""
    KVH, G, S, hd = q.shape
    block_q = attention._pick_block(S, block_q)
    block_k = attention._pick_block(S, block_k)

    def last_block(qi):          # the last kv block a query block can see
        return (qi * block_q + block_q - 1) // block_k

    kernel = functools.partial(_masked_flash_kernel, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k)
    # Blocks above the diagonal are skipped; their index is clamped to the
    # last visible one, so nothing is fetched for them.
    kv_spec = pl.BlockSpec(
        (1, block_k, hd),
        lambda h, qi, ki: (h, jnp.minimum(ki, last_block(qi)), 0))
    q_spec = pl.BlockSpec((1, G, block_q, hd), lambda h, qi, ki: (h, 0, qi, 0))
    return pl.pallas_call(
        kernel,
        name="masked_flash",
        grid=(KVH, S // block_q, S // block_k),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((block_q, block_k),
                         lambda h, qi, ki: (qi, jnp.minimum(ki, last_block(qi)))),
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * block_q, hd), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(q, k, v, mask)


def _sparse_pallas_fwd(q, k, v, qi, ki, w, topk, sm_scale, interpret=False):
    B, H, S, hd = q.shape
    KVH = k.shape[1]

    def one(args):
        q, k, v, qi, ki, w = args
        with jax.named_scope("select"):       # scores and selection, fused
            mask = _index_select_pallas(qi, ki, w, topk, interpret=interpret)
        with jax.named_scope("sparse_attn"):
            return _masked_flash_pallas(
                q.reshape(KVH, H // KVH, S, hd), k, v, mask,
                sm_scale=sm_scale, interpret=interpret).reshape(H, S, hd)

    if B == 1:
        return one((q[0], k[0], v[0], qi[0], ki[0], w[0]))[None]
    return jax.lax.map(one, (q, k, v, qi, ki, w))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _sparse_pallas(q, k, v, qi, ki, w, topk, sm_scale):
    return _sparse_pallas_fwd(q, k, v, qi, ki, w, topk, sm_scale)


def _sparse_pallas_vjp_fwd(q, k, v, qi, ki, w, topk, sm_scale):
    return (_sparse_pallas_fwd(q, k, v, qi, ki, w, topk, sm_scale),
            (q, k, v, qi, ki, w))


def _sparse_pallas_vjp_bwd(topk, sm_scale, res, dout):
    """The XLA path's gradient (the selection is recomputed there; the
    indexer takes none: its scores only choose)."""
    q, k, v, qi, ki, w = res
    _, vjp = jax.vjp(lambda q, k, v: _sparse_reference(
        q, k, v, qi, ki, w, topk, sm_scale), q, k, v)
    return vjp(dout) + tuple(jnp.zeros_like(x) for x in (qi, ki, w))


_sparse_pallas.defvjp(_sparse_pallas_vjp_fwd, _sparse_pallas_vjp_bwd)


def sparse_attention(q, k, v, qi, ki, w, topk: int, *,
                     sm_scale: Optional[float] = None,
                     interpret: bool = False) -> jax.Array:
    """Causal attention of a whole sequence under the indexer's selection.

    q [B, H, S, hd]; k, v [B, KVH, S, hd] (query head h reads kv head
    h // (H // KVH)); qi [B, S, IH, Id] and ki [B, S, Id], rotated; w
    [B, S, IH], scaled. -> [B, H, S, hd]. With `topk >= S` it is dense
    causal attention. The indexer takes no gradient through this."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    S, hd = q.shape[2], q.shape[3]
    use = interpret or (attention._on_tpu() and S % 128 == 0
                        and hd % 128 == 0)
    attention._path_counts["sparse_pallas" if use else "sparse_reference"] += 1
    if interpret:
        return _sparse_pallas_fwd(q, k, v, qi, ki, w, topk, scale, True)
    if use:
        return _sparse_pallas(q, k, v, qi, ki, w, topk, scale)
    return _sparse_reference(q, k, v, qi, ki, w, topk, scale)


# ---------------------------------------------------------------------------
# Decode: one query token a slot against the paged caches
# ---------------------------------------------------------------------------

# Which way a decode step reads the selected K and V (`_streams`). A gather
# of the selected rows costs by `topk` (1.2 ms a layer for 16 slots x 2,048
# rows of 1 KiB on a v5e, 7% of the memory's rate: a DMA a row); a stream of
# the slot's live pages under the selection's mask costs by the live context.
# The table's static width bounds that context, so the rule is the width
# against `topk`; the constant is the probe's (PERF.md, PR 44).
_STREAM_UP_TO = 8

# The stream's unit of DMA and of matmul, K and V of a block in two buffers
# each (4 MiB at 4 kv heads of 128 in bfloat16). Where `paged_decode`'s layout
# gains nothing past 512 tokens (`paged_kv._DECODE_BLOCK_TOKENS`), a block by
# token is 16 DMAs of 64 KiB and reads 8-13% faster at 1,024 than at 512 from
# 7,000 live positions a slot on, the same under them (a v5e; PERF.md, PR 44).
_STREAM_BLOCK_TOKENS = 1024


def _streams(ctx: int, topk: int) -> bool:
    """Whether a decode step over a block table `ctx` positions wide streams
    the live pages (True) or gathers the `topk` selected rows."""
    return ctx <= _STREAM_UP_TO * topk


def decode_select_mask(scores: jax.Array, topk: int) -> jax.Array:
    """scores [ns, ctx] float32, -inf at the positions a slot may not read ->
    int8 [ns, ctx]: 1 at the positions `jax.lax.top_k(scores, topk)` returns
    with a finite score, exactly: every score above the topk-th largest, and
    of the ties AT it the earliest (`top_k`'s order), as many as are left."""
    live = scores > -jnp.inf
    if topk >= scores.shape[-1]:
        return live.astype(jnp.int8)
    vals, _ = jax.lax.top_k(scores, topk)
    t = vals[:, -1:]
    above, tied = scores > t, scores == t
    need = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    nth = jnp.cumsum(tied, axis=-1, dtype=jnp.int32)   # a tie's rank, from 1
    return ((above | (tied & (nth <= need))) & live).astype(jnp.int8)


def _sparse_paged_decode_kernel(layer_ref, len_ref, bt_ref, q_ref, mask_ref,
                                k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_ref,
                                l_ref, acc_ref, *, sm_scale: float,
                                groups: int, split: bool,
                                pages_per_block: int):
    """One grid step = one slot, as `paged_kv._paged_decode_kernel`: its live
    pages come in by DMA, a block of `pages_per_block` at a time,
    double-buffered, under a DYNAMIC trip count. The arena lies by token, so
    a page `[page, KVH * hd]` is one contiguous run and kv head h's keys are
    the lanes `h * hd ..` of the block. A score counts where the slot's row
    of the selection's mask is set and the position is under its length.
    Online softmax a kv head over its query heads, float32 statistics and
    accumulator."""
    _, T, _ = kbuf.shape
    n_kv, rows, hd = q_ref.shape[1:]
    page = T // pages_per_block
    max_pages = bt_ref.shape[1]
    slot = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[slot]
    live_pages = pl.cdiv(length, page)
    n_blocks = pl.cdiv(live_pages, pages_per_block)

    @pl.when(slot == 0)
    def _clear():
        # A block's tail past the live pages is never fetched, only masked:
        # what lies there must be finite (0 x NaN is NaN).
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def each_copy(block, buf, fn):
        for i in range(pages_per_block):
            idx = block * pages_per_block + i
            page_id = bt_ref[slot, jnp.minimum(idx, max_pages - 1)]
            at = pl.ds(i * page, page)

            @pl.when(idx < live_pages)
            def _():
                fn(pltpu.make_async_copy(k_hbm.at[layer, page_id],
                                         kbuf.at[buf, at, :], sem.at[0, buf]))
                fn(pltpu.make_async_copy(v_hbm.at[layer, page_id],
                                         vbuf.at[buf, at, :], sem.at[1, buf]))

    m_ref[...] = jnp.full_like(m_ref, DEFAULT_MASK_VALUE)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _first():
        each_copy(0, 0, lambda c: c.start())

    def block_body(b, carry):
        buf = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():
            each_copy(b + 1, 1 - buf, lambda c: c.start())

        each_copy(b, buf, lambda c: c.wait())
        picked = mask_ref[0, :, pl.ds(pl.multiple_of(b * T, T), T)]  # [1, T]
        keep = (picked.astype(jnp.int32) != 0) & (
            b * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) < length)
        keep = jnp.broadcast_to(keep, (rows, T))
        upper = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 0) < groups
        for h in range(n_kv):
            lanes = pl.ds(h * hd, hd)
            k = kbuf[buf, :, lanes]                              # [T, hd]
            v = vbuf[buf, :, lanes]
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale   # [rows, T]
            s = jnp.where(keep, s, DEFAULT_MASK_VALUE)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # While a head has met no selected position m is the mask value
            # and p is 1 at every masked column: `corr` == 0 wipes that sum
            # when its first real score arrives, and a live slot selects some.
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            if split:
                # q's rows come twice: the upper copy carries p rounded to
                # the cache's dtype, the lower what the rounding dropped
                # (`paged_kv._paged_decode_kernel`).
                p = jnp.where(upper, p,
                              p - p.astype(v.dtype).astype(jnp.float32))
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block_body, 0)
    # An idle slot (length 0) walked nothing: l is 0 and so is its output.
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _sparse_paged_decode(q, mask, kc, vc, layer, block_table, lengths, *,
                         sm_scale, interpret=False):
    """q [ns, H, hd], mask int8 [ns, ctx], kc/vc `[L, n_pages, page, KVH *
    hd]`: softmax attention of each slot's query heads over the positions
    its mask names, under `lengths`. -> [ns, H, hd]."""
    ns, H, hd = q.shape
    _, _, page, row = kc.shape
    n_kv = row // hd
    groups = H // n_kv
    # float32 softmax weights against a narrower cache: see `split` above.
    split = jnp.dtype(kc.dtype).itemsize < 4
    copies = 2 if split else 1
    tile = paged_kv._sublanes(kc.dtype)
    rows = -(-copies * groups // tile) * tile
    qg = q.reshape(ns, n_kv, groups, hd).astype(kc.dtype)
    qg = jnp.concatenate(
        [qg] * copies + [jnp.zeros((ns, n_kv, rows - copies * groups, hd),
                                   kc.dtype)], axis=2)
    pages_per_block = min(max(1, _STREAM_BLOCK_TOKENS // page),
                          block_table.shape[1])
    T = pages_per_block * page
    ctx = mask.shape[1]
    mask = jnp.pad(mask, ((0, 0), (0, -ctx % T)))[:, None]     # whole blocks
    kernel = functools.partial(
        _sparse_paged_decode_kernel, sm_scale=sm_scale, groups=groups,
        split=split, pages_per_block=pages_per_block)
    slot_block = pl.BlockSpec((1, n_kv, rows, hd), lambda s, *_: (s, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        name="sparse_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,       # layer, lengths, block table
            grid=(ns,),
            in_specs=[slot_block,
                      pl.BlockSpec((1, 1, mask.shape[2]),
                                   lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot_block,
            scratch_shapes=[
                pltpu.VMEM((2, T, row), kc.dtype),
                pltpu.VMEM((2, T, row), vc.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_kv, rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, rows, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((ns, n_kv, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      block_table.astype(jnp.int32), qg, mask, kc, vc)
    out = sum(out[:, :, i * groups:(i + 1) * groups] for i in range(copies))
    return out.reshape(ns, H, hd).astype(q.dtype)


def sparse_decode_attention(q, qi, w, kc, vc, ic, layer, block_table,
                            lengths, topk: int, *,
                            sm_scale: Optional[float] = None,
                            interpret: bool = False) -> jax.Array:
    """q [ns, H, hd]; qi [ns, IH, Id] and w [ns, IH] this token's indexer
    query and head weights; kc, vc the K/V arena laid out BY TOKEN, `[L,
    n_pages, page, KVH * hd]`, and ic the indexer keys' `[L, n_pages, page,
    Id]` (`ops/paged_kv.py`), `layer` the index into all three; block_table
    [ns, max_pages]; lengths [ns], the positions 0..lengths-1 a slot may
    read (0: an idle slot, whose output is 0). -> [ns, H, hd].

    Scores every live indexer key of the slot (its pages of `ic`: 128 B a
    position) and selects the `topk` largest exactly. Then, on a TPU (or
    with `interpret`) and while the table is no wider than `_streams` says,
    the Pallas kernel `sparse_paged_decode` streams the slot's live pages of
    K and V where they lie and counts the selected positions alone;
    otherwise XLA gathers the selected rows out of the arena. Which one a
    program took is in `attention.attention_path_counts()` as
    `sparse_decode_stream_pallas` / `sparse_decode_gather`."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    ns, H, hd = q.shape
    _, n_pages, page, row = kc.shape
    KVH = row // hd
    ctx = block_table.shape[1] * page
    kk = min(topk, ctx)
    stream = interpret or (
        attention._on_tpu() and _streams(ctx, topk) and hd % 128 == 0
        and page % paged_kv._sublanes(kc.dtype) == 0)
    attention._path_counts["sparse_decode_stream_pallas" if stream
                           else "sparse_decode_gather"] += 1
    with jax.named_scope("indexer"):
        keys = ic[layer, block_table].reshape(ns, ctx, ic.shape[-1])
        scores = index_scores(qi[:, None], keys, w[:, None])[:, 0]   # [ns, ctx]
        live = jnp.arange(ctx)[None, :] < lengths[:, None]
        scores = jnp.where(live, scores, -jnp.inf)
    if stream:
        with jax.named_scope("select"):
            mask = decode_select_mask(scores, kk)
        with jax.named_scope("sparse_attn"):
            return _sparse_paged_decode(
                q, mask, kc, vc, layer, block_table, lengths, sm_scale=scale,
                interpret=interpret)
    with jax.named_scope("select"):
        vals, idx = jax.lax.top_k(scores, kk)                 # [ns, kk]
        picked = vals > -jnp.inf
        idx = jnp.where(picked, idx, 0)
    with jax.named_scope("sparse_attn"):
        # The arena as rows of one position each: a gather of rows leaves
        # it where and how it lies (indexed `kc[layer, pages, rows]`, XLA
        # re-lays the whole arena and copies it to and from every page
        # write; AOT for v5e).
        pages = jnp.take_along_axis(block_table, idx // page, axis=1)
        at = (layer * n_pages + pages) * page + idx % page        # [ns, kk]
        ks = kc.reshape(-1, row)[at].reshape(ns, kk, KVH, hd)
        vs = vc.reshape(-1, row)[at].reshape(ns, kk, KVH, hd)
        qg = q.reshape(ns, KVH, H // KVH, hd)
        s = jnp.einsum("nkgd,nskd->nkgs", qg, ks,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(picked[:, None, None], s, DEFAULT_MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1)
        # What an unpicked row holds is masked out of v too (0 x NaN), and an
        # idle slot, whose every weight is 1 / kk, gives 0.
        vs = jnp.where(picked[:, :, None, None], vs, 0)
        out = jnp.einsum("nkgs,nskd->nkgd", p.astype(vs.dtype), vs)
    return out.reshape(ns, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Selection by BLOCKS (InfLLM-v2, the MiniCPM4 / MiniCPM-SALA family's sparse
# layers): no learned scorer. Keys are mean-pooled over `kernel` positions
# every `stride`, a query's softmax over the pooled keys it may see is summed
# over the query heads of its kv head, a block of `block` positions scores the
# max over the pooled keys that overlap it, and the query reads the `topk`
# best blocks (the first `init_blocks` and the `window` positions that end at
# its own always among them), ONE selection a kv head. A query whose context
# is under `dense_len` reads every earlier key. No rotation anywhere.
# ---------------------------------------------------------------------------

class BlockSparse(NamedTuple):
    """The sizes of a block-selecting layer (`LlamaConfig.block_sparse`)."""
    kernel: int         # positions a pooled key is the mean of
    stride: int         # positions between two pooled keys' first ones
    block: int          # positions a selected block holds: the cache's page
    topk: int           # blocks a query reads
    init_blocks: int    # the leading blocks every query reads
    window: int         # positions before a query's own that it always reads
    dense_len: int      # contexts under it are read whole

    @property
    def window_blocks(self) -> int:
        return self.window // self.block


def compress(k: jax.Array, sizes: BlockSparse, length=None):
    """k `[KVH, T, hd]` -> (pooled keys `[KVH, T / stride, hd]` float32, entry
    i the mean of rows `stride * i .. stride * i + kernel - 1`, the last
    `kernel / stride - 1` entries and whatever reaches a row at or past
    `length` not meaningful; sums `[KVH, 2, hd]` float32: of the whole
    `stride` rows before row `length`'s own group, and of that group's rows
    under `length`: what a decode step adds its key to
    (`compress_step`)). `kernel` is two strides."""
    KVH, T, hd = k.shape
    stride = sizes.stride
    rows = k.astype(jnp.float32)
    if length is not None:
        rows = jnp.where((jnp.arange(T) < length)[None, :, None], rows, 0.0)
    else:
        length = T
    groups = rows.reshape(KVH, T // stride, stride, hd).sum(axis=2)
    padded = jnp.pad(groups, ((0, 0), (1, 1), (0, 0)))
    pooled = (padded[:, 1:-1] + padded[:, 2:]) / sizes.kernel
    at = length // stride
    sums = jnp.stack([jax.lax.dynamic_index_in_dim(padded, at + i, 1, False)
                      for i in (0, 1)], axis=1)
    return pooled, sums


def compress_step(pooled, sums, k, w, active, sizes: BlockSparse):
    """A decode step's key into a slot's pooled keys: pooled `[ns, NK, KVH *
    hd]`, sums `[ns, 2, KVH * hd]` float32 (`compress`'s), k `[ns, KVH * hd]`
    the key at position `w` `[ns]`. The key joins its group's sum; at a
    group's last row (`w % stride == stride - 1`) the pooled key that ends
    there, entry `(w + 1 - kernel) / stride`, is the two sums over `kernel`,
    and the group's sum becomes the one before. An idle slot's rows stay."""
    ns, NK, _ = pooled.shape
    stride = sizes.stride
    before, group = sums[:, 0], sums[:, 1] + k.astype(jnp.float32)
    ends = active & (w % stride == stride - 1)
    entry = (w + 1 - sizes.kernel) // stride
    done = ((before + group) / sizes.kernel).astype(pooled.dtype)
    here = (jnp.arange(NK)[None, :] == entry[:, None]) \
        & (ends & (entry >= 0))[:, None]
    pooled = jnp.where(here[..., None], done[:, None], pooled)
    keep = active[:, None]
    new = jnp.stack([jnp.where(ends[:, None], group, before),
                     jnp.where(ends[:, None], 0.0, group)], axis=1)
    return pooled, jnp.where(keep[..., None], new, sums)


def block_scores(q, pooled, pos, sizes: BlockSparse, sm_scale: float):
    """q `[.., KVH, G, R, hd]`, pooled `[.., KVH, NK, hd]`, pos `[.., R]` the
    rows' positions -> `[.., KVH, R, NB]` float32, NB = NK * stride / block:
    each block's score for each row, the max over the pooled keys that
    overlap the block of the group's summed softmax over the pooled keys the
    row may see (entry i when `stride * i + kernel - 1 <= pos`); 0 where it
    sees none of them."""
    NK = pooled.shape[-2]
    per, lead = sizes.block // sizes.stride, sizes.kernel // sizes.stride - 1
    s = jnp.einsum("...kgrd,...kid->...kgri", q, pooled.astype(q.dtype),
                   preferred_element_type=jnp.float32) * sm_scale
    seen = (jnp.arange(NK) * sizes.stride + sizes.kernel - 1
            <= pos[..., None])[..., None, None, :, :]
    p = jax.nn.softmax(jnp.where(seen, s, DEFAULT_MASK_VALUE), axis=-1)
    p = jnp.sum(jnp.where(seen, p, 0.0), axis=-3)            # [.., KVH, R, NK]
    # block b meets entries per * b - lead .. per * b + per - 1
    pad = [(0, 0)] * (p.ndim - 1) + [(lead, 0)]
    p = jnp.pad(p, pad)
    NB = NK // per
    best = p[..., :per * NB].reshape(*p.shape[:-1], NB, per).max(axis=-1)
    for j in range(lead):
        best = jnp.maximum(best, p[..., per + j::per][..., :NB])
    return best


def block_select(scores, pos, sizes: BlockSparse):
    """scores `[.., KVH, R, NB]` float32 (`block_scores`), pos `[.., R]` ->
    bool `[.., KVH, R, NB]`: the blocks each row reads. A row whose context
    `pos + 1` is under `dense_len`: every block up to its own. Else the
    `topk` of largest score among them, the first `init_blocks` and the
    `window / block` that end at its own scoring +inf, ties to the smaller
    block, exactly: a block is read when fewer than `topk` go before it."""
    NB = scores.shape[-1]
    b = jnp.arange(NB)
    own = (pos // sizes.block)[..., None, :, None]
    valid = b <= own
    forced = (b < sizes.init_blocks) | (b > own - sizes.window_blocks)
    s = jnp.where(valid, jnp.where(forced, jnp.inf, scores), -jnp.inf)
    ahead = (s[..., None, :] > s[..., :, None]) | (
        (s[..., None, :] == s[..., :, None]) & (b[None, :] < b[:, None]))
    picked = (jnp.sum(ahead, axis=-1, dtype=jnp.int32) < sizes.topk) & valid
    dense = (pos + 1 < sizes.dense_len)[..., None, :, None]
    return jnp.where(dense, valid, picked)


_SELECT_ROWS = 256      # rows of a prompt scored and selected at once


def block_mask(q, pooled, sizes: BlockSparse, sm_scale: float):
    """q `[KVH, G, T, hd]`, pooled `[KVH, T / stride, hd]` -> bool `[KVH, T,
    T / block]`: `block_select` of every row of a prompt at positions 0..T-1,
    `_SELECT_ROWS` rows at a time from the first row whose context reaches
    `dense_len` (the rows before it read every block up to their own, and
    score nothing)."""
    KVH, G, T, hd = q.shape
    NB = T // sizes.block
    rows = attention._pick_block(T, _SELECT_ROWS) if T % 128 == 0 else T
    first = max(sizes.dense_len - 1, 0) // rows * rows
    causal = jnp.broadcast_to(
        jnp.arange(NB)[None, :] <= (jnp.arange(first) // sizes.block)[:, None],
        (KVH, first, NB))
    if first >= T:
        return causal[:, :T]

    def select(start):
        pos = start + jnp.arange(rows)
        qs = jax.lax.dynamic_slice_in_dim(q, start, rows, 2)
        return block_select(block_scores(qs, pooled, pos, sizes, sm_scale),
                            pos, sizes)

    picked = jax.lax.map(select, jnp.arange(first, T, rows))
    picked = picked.transpose(1, 0, 2, 3).reshape(KVH, T - first, NB)
    return jnp.concatenate([causal, picked], axis=1)


def _block_sparse_reference(q, k, v, mask, sizes, sm_scale):
    """q `[KVH, G, T, hd]`, k/v `[KVH, T, hd]`, mask `[KVH, T, NB]` bool."""
    T = q.shape[2]
    keep = jnp.repeat(mask, sizes.block, axis=-1) \
        & (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    s = jnp.einsum("kgtd,ksd->kgts", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    p = jax.nn.softmax(jnp.where(keep[:, None], s, DEFAULT_MASK_VALUE), -1)
    return jnp.einsum("kgts,ksd->kgtd", p.astype(v.dtype), v)


def _block_flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref, m_ref,
                        l_ref, *, sm_scale: float, block_q: int, block_k: int,
                        shift: int):
    """`_masked_flash_kernel` under a mask BY BLOCKS: `mask_ref` `[block_q,
    block_k >> shift]` names, for each query row, the blocks of `1 << shift`
    keys it reads of this block of keys; spread to the keys by a product
    with a constant of ones (lanes are not repeated otherwise), then cut to
    the causal half inside a row's own block."""
    q_idx, kv_idx = pl.program_id(1), pl.program_id(2)
    G, _, hd = q_ref.shape[1:]
    nb = block_k >> shift

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, DEFAULT_MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(kv_idx * block_k <= q_idx * block_q + (block_q - 1))
    def _body():
        q = q_ref[0].reshape(G * block_q, hd)
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        spread = (jax.lax.broadcasted_iota(jnp.int32, (nb, block_k), 1) >> shift
                  == jax.lax.broadcasted_iota(jnp.int32, (nb, block_k), 0)
                  ).astype(mask_ref.dtype)
        picked = jax.lax.dot_general(
            mask_ref[0, 0], spread, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) > 0.5        # [block_q, block_k]
        rows = q_idx * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kv_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        keep = picked & (cols <= rows)
        s = jnp.where(keep[None], s.reshape(G, block_q, block_k),
                      DEFAULT_MASK_VALUE).reshape(G * block_q, block_k)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # (a row that has met no selected key yet: `_masked_flash_kernel`;
        # every row reads its own block)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = out.reshape(G, block_q, hd).astype(o_ref.dtype)


# Query rows of a kv head's whole group a grid step of `block_flash` holds
# (their scores against a block of keys are float32 in fast memory).
_BLOCK_FLASH_ROWS = 2048


@functools.partial(jax.jit,
                   static_argnames=("block", "sm_scale", "interpret"))
def _block_flash_pallas(q, k, v, mask, *, block, sm_scale, interpret=False):
    """q `[KVH, G, T, hd]`, k/v `[KVH, T, hd]`, mask bool `[KVH, T, T /
    block]` -> `[KVH, G, T, hd]`. Under a `jit` of its own."""
    KVH, G, T, hd = q.shape
    block_q = attention._pick_block(T, max(128, _BLOCK_FLASH_ROWS // G))
    block_k = attention._pick_block(T, 1024)
    nb = block_k // block
    # [KVH, key blocks, T, blocks of a key block]: a grid step's part is a
    # block whose last dimension is the array's own.
    laid = mask.astype(q.dtype).reshape(KVH, T, T // block_k, nb).transpose(
        0, 2, 1, 3)

    def last_block(qi):          # the last kv block a query block can see
        return (qi * block_q + block_q - 1) // block_k

    kernel = functools.partial(
        _block_flash_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, shift=block.bit_length() - 1)
    kv_spec = pl.BlockSpec(
        (1, block_k, hd),
        lambda h, qi, ki: (h, jnp.minimum(ki, last_block(qi)), 0))
    q_spec = pl.BlockSpec((1, G, block_q, hd), lambda h, qi, ki: (h, 0, qi, 0))
    return pl.pallas_call(
        kernel,
        name="block_flash",
        grid=(KVH, T // block_q, T // block_k),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((1, 1, block_q, nb), lambda h, qi, ki: (
                h, jnp.minimum(ki, last_block(qi)), qi, 0)),
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * block_q, hd), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(q, k, v, laid)


def block_flash_tiles(T: int, hd: int, block: int) -> bool:
    """Whether `block_flash` takes a prompt of T rows: whole tiles, and a
    block that is a power of two and divides a block of keys."""
    return T % 128 == 0 and hd % 128 == 0 and block & (block - 1) == 0 \
        and attention._pick_block(T, 1024) % block == 0


def block_sparse_attention(q, k, v, pooled, sizes: BlockSparse, *,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """Causal attention of a whole prompt under the selection by blocks: q
    `[H, T, hd]`, k, v `[KVH, T, hd]` (query head h reads kv head h // (H //
    KVH)), pooled `[KVH, T / stride, hd]` (`compress`) -> `[H, T, hd]`. On a
    TPU the kernel `block_flash` under the mask by blocks `[KVH, T, T /
    block]`; elsewhere XLA over every pair. Counted as `block_sparse_pallas`
    / `block_sparse_reference`."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    H, T, hd = q.shape
    KVH = k.shape[0]
    qg = q.reshape(KVH, H // KVH, T, hd)
    use = interpret or (attention._on_tpu()
                        and block_flash_tiles(T, hd, sizes.block))
    attention._path_counts["block_sparse_pallas" if use
                           else "block_sparse_reference"] += 1
    with jax.named_scope("block_select"):
        mask = block_mask(qg, pooled, sizes, scale)
    with jax.named_scope("block_sparse_attn"):
        out = _block_flash_pallas(
            qg, k, v, mask, block=sizes.block, sm_scale=float(scale),
            interpret=interpret) if use \
            else _block_sparse_reference(qg, k, v, mask, sizes, scale)
    return out.reshape(H, T, hd)


def block_sparse_decode(q, pooled, kc, vc, layer, block_table, w, active,
                        sizes: BlockSparse, paged_decode, *,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """One query token a slot: q `[ns, H, hd]` at positions `w` `[ns]`;
    pooled `[ns, NK, KVH * hd]` the slots' pooled keys (`compress_step`'s,
    this step's key in); kc, vc the arena with ONE kv head a layer, `[L * KVH,
    n_pages, 1, page, hd]`, kv head g of this layer its layer `layer * KVH +
    g` (a selection is a kv head's own, so each reads by a table of its
    own); `page` is `sizes.block`. -> `[ns, H, hd]`, zeros for an idle slot.

    A slot whose context `w + 1` is under `dense_len` reads its live pages,
    every one. Any other scores the pooled keys it may see, selects (`block_
    select`), and reads the selected pages ALONE: their entries of its row
    of the block table moved to the row's front, in order, the row's own
    (partial) page last, so `paged_decode` (`ops.paged_kv.
    paged_decode_attention` as the caller holds it) walks `topk` pages and
    masks the last one's tail, as it does a dense slot's."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    ns, H, hd = q.shape
    KVH = pooled.shape[-1] // hd
    NB = block_table.shape[1]
    with jax.named_scope("block_select"):
        qg = q.reshape(ns, KVH, H // KVH, 1, hd)
        keys = pooled.reshape(ns, -1, KVH, hd).transpose(0, 2, 1, 3)
        scores = block_scores(qg, keys, w[:, None], sizes, scale)
        # (a table wider than the pooled keys' blocks has none to read there)
        scores = jnp.pad(scores, [(0, 0)] * 3 + [(0, max(
            0, NB - scores.shape[-1]))])[..., :NB]
        picked = block_select(scores, w[:, None], sizes)[:, :, 0]  # [ns,KVH,NB]
        count = jnp.sum(picked, axis=-1, dtype=jnp.int32)
        # the selected blocks first, in order (a stable sort of 0s and 1s)
        order = jnp.argsort(~picked, axis=-1, stable=True)
        tables = jnp.take_along_axis(
            jnp.broadcast_to(block_table[:, None], picked.shape), order,
            axis=-1)
        lengths = jnp.where(
            active[:, None], (count - 1) * sizes.block + w[:, None]
            % sizes.block + 1, 0)
    with jax.named_scope("block_sparse_attn"):
        G = H // KVH
        out = [paged_decode(q[:, g * G:(g + 1) * G], kc, vc, layer * KVH + g,
                            tables[:, g], lengths[:, g], sm_scale=scale)
               for g in range(KVH)]
    return jnp.concatenate(out, axis=1)

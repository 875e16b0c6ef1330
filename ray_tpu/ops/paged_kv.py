"""The paged KV cache on the device: its layout, and the only ops on it.

The arena is two arrays (K and V) of `[n_layers, n_pages, kv_heads, page,
head_dim]`: a page of all heads is one contiguous run, a (page, head) block
a whole tile. A slot's positions live in the physical pages its row of the
BLOCK TABLE `[n_slots, max_pages]` names, position t at page
`table[slot, t // page]`, row `t % page`. Page 0 is the NULL page: unused
table entries point at it, padding and idle slots write to it, and no
attention ever reads it, so every write is a fixed-shape scatter with no
data-dependent branch. Which pages a slot holds is the host's business
(`serve.page_pool.PagePool`); nothing outside this module indexes the arena.

  * ``empty`` makes the arena, ``write_prompt`` puts a prefill's K/V into a
    slot's pages, ``write_token`` one decode step's row a slot.
  * A model with a sparse-attention indexer keeps a SECOND kind of row under
    the same block table: one indexer key a position a layer, in an array of
    its own, `[n_layers, n_pages, page, index_dim]` (``empty_index``,
    ``write_prompt_rows``, ``write_token_rows``). Same pages, same null
    page, same host policy: a slot's page p holds its K, V and indexer keys.
    `ops.sparse_attention.sparse_decode_attention` reads all three. Its K
    and V lie BY TOKEN (``empty(..., by_token=True)``): `[n_layers, n_pages,
    page, kv_heads * head_dim]`. A page is then ONE contiguous run, which its
    decode kernel streams as `paged_decode` does a page here (PERF.md, PR 44),
    and a position's K of every kv head one row of 1 KiB, which the gather it
    keeps for wide tables moves three times as fast as 256 B a head (PR 32).
    ``write_prompt`` and ``write_token`` tell the two layouts by their rank,
    and write a by-token arena as they write the indexer's: a row a position.
  * A latent-attention model (MLA) keeps a THIRD kind of row and no other:
    one row a position a layer of `kv_lora_rank + qk_rope_dim` numbers (the
    normed latent, then the rotated shared key; 512 + 64 at DeepSeek-V3's
    widths), `[n_layers, n_pages, page, width]` (``empty_latent``), written
    a row a position as the indexer's are. There is ONE such row for all
    query heads and no V arena at all: ``write_prompt`` and ``write_token``
    are not used, the model's programs call ``write_prompt_rows`` and
    ``write_token_rows`` with rows from ``latent_rows``. One array, not
    latent and key apart, and its rows as wide as the tiles they lie in: a
    TPU tile is 128 lanes, so a 576-wide row lies in 640 (five tiles, the
    last half empty) whatever shape is declared, a DMA can only cut whole
    tiles out of it (Mosaic refuses a slice of 576 lanes), and rows of 512
    and of 64 apart would lie in 512 + 128, the same 640. So the arena is
    DECLARED 640 wide, lanes 576..639 zero, 1,280 B a token a layer in
    bfloat16 for 1,152 of use; one array is one DMA a page and one scatter a
    write.
    ``paged_latent_decode`` reads it in place: all heads of a slot against
    each block of its live rows, counted as `latent_decode_pallas` /
    `latent_decode_reference`.
  * A model of window and full attention layers (`LlamaConfig.attn_pattern`)
    keeps pages for its FULL layers alone, the arena's layers being their
    ordinals, with keys wider than values (``empty(..., v_head_dim=...)``): a
    key of 192 numbers lies in 256 lanes (two tiles, the second half empty,
    as the latent row's 576 lie in 640), `[k_n ; k_r ; zeros]` with the part
    RoPE passed on the tile's boundary, a value of 128 in one: 768 B a kv
    head a token a layer in bfloat16 for 640 of use (3,072 B a token a layer
    at MiMo-V2's 4 kv heads, 2,560 of use). Its window layers' rows are no
    pages at all: a ring a slot (`ops/slot_state.py`).
  * A head of HALF a tile (64 numbers, the LFM2 family's) lies two kv heads
    to a row: `[n_layers, n_pages, kv_heads / 2, page, 128]`, kv heads 2r and
    2r + 1 side by side in row r's lanes 0..63 and 64..127 (``empty`` packs
    where `head_dim` is 64 and the kv heads are even). Declared 64 wide a
    head would lie in 128 lanes all the same, half of the arena and of
    every read padding; packed, a token is 2 x kv_heads x 64 numbers a layer
    and nothing else (2,048 B at 8 kv heads in bfloat16). ``write_prompt``
    and ``write_token`` tell the layout by the arena's shape and write a row
    of two heads; ``paged_decode_attention`` hands its kernel each query head
    in ITS kv head's half of the lanes, zeros in the other (a score is then
    q . k of its own head, the other's lanes times zero), and keeps that half
    of the output.
  * ``paged_decode_attention`` is one query token a slot against the arena:
    a Pallas TPU kernel that reads a slot's live pages where they lie (the
    XLA gather over the whole block table elsewhere), counted at trace time
    in `attention.attention_path_counts()` as `decode_pallas` /
    `decode_reference`.
  * Generation by blocks (`LlamaConfig.block_length` B > 1): a step is B
    rows a slot, at positions p..p + B - 1, p a multiple of B. B divides the
    page, so a block never crosses one: ``write_token`` takes k/v `[ns, B,
    KVH, hd]` and replaces ONE cell of B rows of the slot's page; every row
    of a block attends to the same keys, 0..p + B - 1, so
    ``paged_decode_attention`` takes q `[ns, B, H, hd]` as B x the query
    heads of each kv head and needs no mask of its own (counted as
    `block_decode_pallas` / `block_decode_reference`). A denoising forward
    overwrites the block's rows, and the rows the cache KEEPS of a block are
    written by the next block's first forward, which carries the finished
    block's B rows (its final ids) before its own: TWO blocks a slot, q `[ns,
    2B, H, hd]` with `lag` = B. Both blocks' K and V are written first
    (``write_token`` twice: with p at a page's first row the finished block
    lies in the page BEFORE the open one's), then one call reads the slot's K
    and V ONCE for the 2B x the query heads of each kv head, the first
    block's rows masked B positions short (they see 0..p - 1, the open
    block's 0..p + B - 1). Which rows lag is fixed by the layout, so the
    mask is a static per-row length, one more compare a block of keys, and a
    call without `lag` (one block: a block's later forwards; a token a slot:
    every other stack) lowers to the kernel it was.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The module, not its names: the path counters are one per process, and
# tests steer `_on_tpu` by patching it there.
from ray_tpu.ops import attention
from ray_tpu.ops.attention import DEFAULT_MASK_VALUE


# ---------------------------------------------------------------------------
# The layout
# ---------------------------------------------------------------------------

def empty(n_layers: int, n_pages: int, kv_heads: int, page: int,
          head_dim: int, dtype, by_token: bool = False,
          v_head_dim: Optional[int] = None):
    """-> (kc, vc), the zeroed arena; `by_token`: for a reader that gathers
    positions (see the top); `v_head_dim`: values of a width of their own,
    and each width in rows of the next multiple of 128 lanes. Heads of half
    a tile lie two to a row (`_packed`)."""
    if v_head_dim is not None:
        return tuple(jnp.zeros((n_layers, n_pages, kv_heads, page,
                                _lanes(d)), dtype)
                     for d in (head_dim, v_head_dim))
    if not by_token and _packed(kv_heads, head_dim):
        kv_heads, head_dim = kv_heads // 2, 2 * head_dim
    shape = (n_layers, n_pages, page, kv_heads * head_dim) if by_token \
        else (n_layers, n_pages, kv_heads, page, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def empty_index(n_layers: int, n_pages: int, page: int, index_dim: int,
                dtype):
    """-> ic, the zeroed arena of indexer keys."""
    return jnp.zeros((n_layers, n_pages, page, index_dim), dtype)


_LANES = 128


def _lanes(width: int) -> int:
    """`width` rounded up to whole tiles of 128 lanes."""
    return -(-width // _LANES) * _LANES


def _packed(kv_heads: int, head_dim: int) -> bool:
    """Whether an arena of these heads holds two to a row: a head of half a
    tile, an even number of them."""
    return 2 * head_dim == _LANES and kv_heads % 2 == 0


def empty_latent(n_layers: int, n_pages: int, page: int, width: int, dtype):
    """-> the zeroed arena of a latent-attention model's rows (see the top),
    all it caches: `width` numbers a position (latent + rotary key), in rows
    of the next multiple of 128 lanes."""
    return jnp.zeros((n_layers, n_pages, page, _lanes(width)), dtype)


def _to_width(rows, arena):
    """`rows` [..., n] with zeros up to the arena's width, in its dtype."""
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, arena.shape[-1] - rows.shape[-1])]
    return jnp.pad(rows, pad).astype(arena.dtype)


def latent_rows(c, kr, arena):
    """A latent arena's rows from the normed latent `c` [..., rank] and the
    rotated shared key `kr` [..., dr]: side by side, then zeros up to the
    arena's width."""
    return _to_width(jnp.concatenate([c, kr], axis=-1), arena)


def write_prompt_rows(arena, pages, rows):
    """Scatter a prefill's rows [L, W, R], one a position, into the physical
    pages of an arena `[L, n_pages, page, R]` (K or V by token; an indexer's
    keys). As `write_prompt`: W static, `pages[:wp]` entries of 0 route
    padding into the null page."""
    L, W, R = rows.shape
    page = arena.shape[2]
    wp = -(-W // page)
    with jax.named_scope("kv_write"):
        rows = jnp.pad(rows, ((0, 0), (0, wp * page - W), (0, 0)))
        return arena.at[:, pages[:wp]].set(rows.reshape(L, wp, page, R))


def write_prompt(kc, vc, pages, ks, vs):
    """Scatter prefilled [L, W, KVH, hd] k/v into physical pages.
    W is static (one program per bucket width); `pages[:wp]` entries
    of 0 route padding into the null page."""
    if vc is None:          # a latent arena: `ks` [L, W, rank + dr]
        return write_prompt_rows(kc, pages, _to_width(ks, kc)), None
    L, W, KVH, hd = ks.shape
    if kc.ndim == 5 and kc.shape[2] != KVH:
        # heads of half a tile, two to a row: adjacent heads' numbers are
        # adjacent in a position's row already
        KVH, hd = kc.shape[2], kc.shape[-1]
        ks, vs = ks.reshape(L, W, KVH, hd), vs.reshape(L, W, KVH, hd)
    if kc.ndim == 5 and (hd, vs.shape[-1]) != (kc.shape[-1], vc.shape[-1]):
        # a mixed stack's: each narrower than the lanes it lies in
        ks, vs = _to_width(ks, kc), _to_width(vs, vc)
        hd = kc.shape[-1]
    if kc.ndim == 4:        # by token
        return (write_prompt_rows(kc, pages, ks.reshape(L, W, KVH * hd)),
                write_prompt_rows(vc, pages, vs.reshape(L, W, KVH * hd)))
    page = kc.shape[3]
    wp = -(-W // page)
    pad = wp * page - W
    with jax.named_scope("kv_write"):
        ksp = jnp.pad(ks, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vsp = jnp.pad(vs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ksp = ksp.reshape(L, wp, page, KVH, hd).transpose(0, 1, 3, 2, 4)
        vsp = vsp.reshape(L, wp, page, KVH, -1).transpose(0, 1, 3, 2, 4)
        kc = kc.at[:, pages[:wp]].set(ksp)
        vc = vc.at[:, pages[:wp]].set(vsp)
    return kc, vc


def write_token_rows(arena, layer, block_table, w, active, row):
    """One decode step's row [ns, R] a slot into an arena `[L, n_pages,
    page, R]`, as `write_token` (which see): the slot's page read, its row
    replaced, the page put back; inactive slots to the null page."""
    ns, page = row.shape[0], arena.shape[2]
    with jax.named_scope("kv_write"):
        pp = jnp.where(active, block_table[jnp.arange(ns), w // page], 0)
        off = jnp.where(active, w % page, 0)
        here = (jnp.arange(page) == off[:, None])[:, :, None]
        return arena.at[layer, pp].set(
            jnp.where(here, row[:, None], arena[layer, pp]))


def write_token(kc, vc, layer, block_table, w, active, k, v):
    """Put one decode step's k/v [ns, KVH, hd] at each slot's (layer, page,
    offset) for its position w [ns], straight into the arena, no layer slab
    cut out or put back: the slot's page is read, its row replaced, and the
    page scattered back. A scatter of the `ns` rows alone would be less to
    move, but its window (every kv head's row `off`) is strided in this
    layout, and XLA then lays the WHOLE arena out the other way round and
    copies it to and from the attention kernel every layer (AOT for v5e,
    PR 28); whole pages are the layout's own unit.
    Inactive slots (and positions past a slot's reservation) route to the
    NULL page 0, which attention never reads: the write stays a fixed-shape
    scatter with no data-dependent branches."""
    if k.ndim == 4:         # a block of rows a slot
        return _write_block(kc, vc, layer, block_table, w, active, k, v)
    if kc.ndim == 4:        # by token
        ns = k.shape[0]
        return (write_token_rows(kc, layer, block_table, w, active,
                                 k.reshape(ns, -1)),
                write_token_rows(vc, layer, block_table, w, active,
                                 v.reshape(ns, -1)))
    ns, page = k.shape[0], kc.shape[3]
    if kc.shape[2] != k.shape[1]:       # heads of half a tile, two to a row
        k, v = (t.reshape(ns, kc.shape[2], -1) for t in (k, v))
    if (k.shape[-1], v.shape[-1]) != (kc.shape[-1], vc.shape[-1]):
        k, v = _to_width(k, kc), _to_width(v, vc)   # narrower than their lanes
    with jax.named_scope("kv_write"):
        idx = jnp.arange(ns)
        pp = jnp.where(active, block_table[idx, w // page], 0)
        off = jnp.where(active, w % page, 0)
        here = (jnp.arange(page) == off[:, None])[:, None, :, None]
        kc = kc.at[layer, pp].set(
            jnp.where(here, k[:, :, None], kc[layer, pp]))
        vc = vc.at[layer, pp].set(
            jnp.where(here, v[:, :, None], vc[layer, pp]))
    return kc, vc


def _write_block(kc, vc, layer, block_table, w, active, k, v):
    """`write_token` for a step of B rows a slot (generation by blocks): k/v
    [ns, B, KVH, hd] at each slot's positions w..w + B - 1, w [ns] a multiple
    of B. B divides the page, so a block lies in ONE page, at rows that are
    a whole cell of the page cut in cells of B rows: the slot's page is
    read, that cell replaced, and the page scattered back, as a token's row
    is."""
    ns, B, KVH, hd = k.shape
    page = kc.shape[3]
    if kc.ndim != 5 or kc.shape[2:] != (KVH, page, hd) \
            or vc.shape != kc.shape or page % B:
        raise NotImplementedError(
            "a block of rows a slot is written to an arena of whole heads by "
            "(page, head), K and V alike, whose page the block divides")
    with jax.named_scope("kv_write"):
        pp = jnp.where(active, block_table[jnp.arange(ns), w // page], 0)
        cell = jnp.where(active, (w % page) // B, 0)
        here = (jnp.arange(page // B) == cell[:, None])[:, None, :, None, None]

        def put(arena, rows):
            old = arena[layer, pp].reshape(ns, KVH, page // B, B, hd)
            new = rows.astype(arena.dtype).transpose(0, 2, 1, 3)[:, :, None]
            return arena.at[layer, pp].set(
                jnp.where(here, new, old).reshape(ns, KVH, page, hd))

        return put(kc, k), put(vc, v)


# ---------------------------------------------------------------------------
# Decode attention over the arena
# ---------------------------------------------------------------------------

# A block of pages, the kernel's unit of DMA and of matmul: 512 tokens where
# VMEM allows (on a v5e, at both Mistral-7B's and OLMoE's head layouts, 256
# tokens a block read 46-52% of the HBM roofline and 512 read 69-80%; 1,024
# no more, and 16 kv heads of them do not fit), and never more than 2 MiB a
# buffer: there are four, K and V of the block computed and of the next.
_DECODE_BLOCK_TOKENS = 512
_DECODE_BLOCK_BYTES = 2 << 20


def _sublanes(dtype) -> int:
    """Rows of one packed (sublane, 128) tile of `dtype`."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _paged_decode_kernel(layer_ref, len_ref, bt_ref, q_ref, k_hbm, v_hbm,
                         o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *,
                         sm_scale: float, groups: int, split: bool,
                         pages_per_block: int, lag: int = 0,
                         lag_rows: int = 0):
    """One grid step = one slot. Its live pages come in by DMA, a block of
    `pages_per_block` at a time, double-buffered; the loop over blocks has a
    DYNAMIC trip count, so a short or idle slot costs what it holds and the
    grid does not grow with the block table. Online softmax a kv head, f32
    statistics and accumulator. `lag` > 0: the first `lag_rows` of a kv
    head's `groups` rows (of each copy, see `split`) see `lag` positions
    fewer than the slot's length; static, so without it the kernel is the
    one it was."""
    _, n_kv, T, _ = kbuf.shape
    page = T // pages_per_block
    max_pages = bt_ref.shape[1]
    slot = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[slot]
    live_pages = pl.cdiv(length, page)
    n_blocks = pl.cdiv(live_pages, pages_per_block)

    @pl.when(slot == 0)
    def _clear():
        # A block's tail past the live pages is never fetched: what lies
        # there is masked, and must be finite (0 x NaN is NaN). After this
        # the buffers only ever hold zeros or real K/V.
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def each_copy(block, buf, fn):
        for i in range(pages_per_block):
            idx = block * pages_per_block + i
            page_id = bt_ref[slot, jnp.minimum(idx, max_pages - 1)]
            rows = pl.ds(i * page, page)

            @pl.when(idx < live_pages)
            def _():
                fn(pltpu.make_async_copy(k_hbm.at[layer, page_id],
                                         kbuf.at[buf, :, rows, :],
                                         sem.at[0, buf]))
                fn(pltpu.make_async_copy(v_hbm.at[layer, page_id],
                                         vbuf.at[buf, :, rows, :],
                                         sem.at[1, buf]))

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
        rows = q_ref.shape[2]
        reach = length
        if lag:
            # each row's own length: the lagging rows of each copy see less
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 0)
            lagging = row < lag_rows
            if split:
                lagging |= (row >= groups) & (row < groups + lag_rows)
            reach = length - jnp.where(lagging, lag, 0)
        live = (b * T + jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
                < reach)
        upper = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 0) < groups
        for h in range(n_kv):
            k = kbuf[buf, h]                                   # [T, hd]
            v = vbuf[buf, h]
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [rows, T]
            s = jnp.where(live, s, DEFAULT_MASK_VALUE)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            if split:
                # q's rows come twice (see the wrapper): the upper copy
                # carries p rounded to the cache's dtype, the lower what the
                # rounding dropped, so ONE pass of V through the MXU gives
                # p.v with p's float32 mantissa to 16 bits.
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


def _paged_decode_pallas(q, kc, vc, layer, block_table, lengths, *,
                         sm_scale, pages_per_block, interpret, lag=0,
                         lag_rows=0):
    """`lag`, `lag_rows`: `_paged_decode_kernel`'s, the rows counted in a
    kv head's `groups` of q's heads."""
    ns, H, hd = q.shape
    _, _, n_kv, page, _ = kc.shape
    hv = vc.shape[-1]       # values of a width of their own (a mixed stack)
    groups = H // n_kv
    # float32 softmax weights against a narrower cache: see `split` above.
    split = jnp.dtype(kc.dtype).itemsize < 4
    copies = 2 if split else 1
    tile = _sublanes(kc.dtype)
    rows = -(-copies * groups // tile) * tile
    qg = q.reshape(ns, n_kv, groups, hd).astype(kc.dtype)
    qg = jnp.concatenate(
        [qg] * copies + [jnp.zeros((ns, n_kv, rows - copies * groups, hd),
                                   kc.dtype)], axis=2)
    if pages_per_block is None:
        page_bytes = n_kv * page * hd * jnp.dtype(kc.dtype).itemsize
        pages_per_block = max(1, min(_DECODE_BLOCK_TOKENS // page,
                                     _DECODE_BLOCK_BYTES // page_bytes))
    pages_per_block = min(pages_per_block, block_table.shape[1])
    T = pages_per_block * page
    kernel = functools.partial(
        _paged_decode_kernel, sm_scale=sm_scale, groups=groups, split=split,
        pages_per_block=pages_per_block, lag=lag, lag_rows=lag_rows)
    def slot_block(d):
        return pl.BlockSpec((1, n_kv, rows, d), lambda s, *_: (s, 0, 0, 0))

    out = pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,       # layer, lengths, block table
            grid=(ns,),
            in_specs=[slot_block(hd),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot_block(hv),
            scratch_shapes=[
                pltpu.VMEM((2, n_kv, T, hd), kc.dtype),
                pltpu.VMEM((2, n_kv, T, hv), vc.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_kv, rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, rows, hv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((ns, n_kv, rows, hv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      block_table.astype(jnp.int32), qg, kc, vc)
    out = sum(out[:, :, i * groups:(i + 1) * groups] for i in range(copies))
    return out.reshape(ns, H, hv).astype(q.dtype)


def _paged_decode_reference(q, kc, vc, layer, block_table, lengths, *,
                            sm_scale, packed=False, lag=0, lag_rows=0):
    """The XLA path: gather every page of every slot's table out of the
    layer, float32 softmax over the whole context under a length mask.
    `packed`: a row holds two kv heads, taken apart after the gather. `lag`,
    `lag_rows`: as the kernel's, the first `lag_rows` query heads of each kv
    head masked `lag` positions short (their weights on the positions past
    their own length are 0, so v's mask by the slot's length serves both)."""
    ns, H, hd = q.shape
    _, _, n_kv, page, _ = kc.shape
    n_kv *= 2 if packed else 1
    groups, ctx = H // n_kv, block_table.shape[1] * page
    qg = q.reshape(ns, n_kv, groups, hd).astype(jnp.float32)
    # [ns, max_pages, n_kv, page, hd]
    kh = kc[layer, block_table].astype(jnp.float32)
    vh = vc[layer, block_table].astype(jnp.float32)
    if packed:
        kh, vh = (t.reshape(*t.shape[:-1], 2, hd).transpose(
            0, 1, 2, 4, 3, 5).reshape(ns, -1, n_kv, page, hd)
            for t in (kh, vh))
    scores = jnp.einsum("nkgd,npktd->nkgpt", qg, kh).reshape(
        ns, n_kv, groups, ctx) * sm_scale
    live = jnp.arange(ctx)[None, :] < lengths[:, None]          # [ns, ctx]
    seen = live[:, None, None, :]
    if lag:
        seen = jnp.arange(ctx) < lengths[:, None, None, None] - jnp.where(
            jnp.arange(groups) < lag_rows, lag, 0)[:, None]
    scores = jnp.where(seen, scores, DEFAULT_MASK_VALUE)
    wts = jax.nn.softmax(scores, axis=-1).reshape(
        ns, n_kv, groups, ctx // page, page)
    # What a dead position holds is masked out of v too: 0 x NaN is NaN.
    vh = jnp.where(live.reshape(ns, ctx // page, 1, page, 1), vh, 0.0)
    out = jnp.einsum("nkgpt,npktd->nkgd", wts, vh)
    return out.reshape(ns, H, vh.shape[-1]).astype(q.dtype)


def paged_decode_attention(q, kc, vc, layer, block_table, lengths, *,
                           sm_scale: Optional[float] = None,
                           pages_per_block: Optional[int] = None,
                           interpret: bool = False, lag: int = 0):
    """Attention of ONE query token a slot against a paged KV cache (or of a
    block of R rows, q `[ns, R, H, hd]` -> `[ns, R, H, hd]`, the first `lag`
    of them `lag` positions short: see below).

    q [ns, H, hd]; kc, vc the WHOLE arena [L, n_pages, KVH, page, hd] (vc's
    rows may be of a width of their own, which is then the result's) and
    `layer` the index into it (a traced scalar: a kernel handed `kc[layer]`
    is first given a copy of that slab); block_table [ns, max_pages] of
    physical page ids; lengths [ns], the positions each slot attends to
    (0: an idle slot, whose output is 0). Slot s reads positions
    0..lengths[s]-1, position t at page block_table[s, t // page], row
    t % page. Query head h reads kv head h // (H // KVH). -> [ns, H, hd].
    `lag` (static; rows of a slot only, q `[ns, R, H, hd]`): the first `lag`
    rows read positions 0..lengths[s]-lag-1, the others as above.

    On a TPU (or with `interpret`, for tests on the CPU) a Pallas kernel
    that walks only the live pages, in place; elsewhere XLA's gather of the
    whole table. Table entries past a slot's live pages are never read by
    the kernel; what lies past `lengths` inside the last live page is read
    and masked, so it must be finite.
    """
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    page, hd = kc.shape[3], kc.shape[4]
    use = interpret or (attention._on_tpu() and hd % 128 == 0
                        and page % _sublanes(kc.dtype) == 0)
    if q.ndim == 4:
        # A block of B rows a slot (generation by blocks), [ns, B, H, hd]:
        # every row of a block sees the same keys, positions 0..lengths-1
        # (the block's own rows among them), so there is no mask to add:
        # the B rows of a kv head's query heads are B x as many query heads
        # of that kv head, [ns, KVH, B x groups, hd] to the kernel.
        # TWO blocks a slot (`lag` = B, q [ns, 2B, H, hd]: the block before
        # the open one, then the open one) read the slot's K and V once: the
        # first block's rows are the first `lag x groups` of a kv head's, and
        # see positions 0..lengths - lag - 1.
        attention._path_counts["block_decode_pallas" if use
                               else "block_decode_reference"] += 1
        ns, B, H, d = q.shape
        n_kv = kc.shape[2]
        wide = q.reshape(ns, B, n_kv, H // n_kv, d).transpose(
            0, 2, 1, 3, 4).reshape(ns, B * H, d)
        run = functools.partial(
            _paged_decode_pallas, pages_per_block=pages_per_block,
            interpret=interpret) if use else _paged_decode_reference
        out = run(wide, kc, vc, layer, block_table, lengths, sm_scale=scale,
                  lag=lag, lag_rows=lag * (H // n_kv))
        return out.reshape(ns, n_kv, B, H // n_kv, -1).transpose(
            0, 2, 1, 3, 4).reshape(ns, B, H, -1)
    if lag:
        raise NotImplementedError("a token a slot has no row to lag")
    attention._path_counts["decode_pallas" if use else "decode_reference"] += 1
    # Heads of half a tile, two to a row (see the top): q's own width says so.
    packed = hd == 2 * q.shape[-1] and vc.shape[4] == hd
    if not use:
        return _paged_decode_reference(q, kc, vc, layer, block_table, lengths,
                                       sm_scale=scale, packed=packed)
    if packed:
        ns, H, d = q.shape
        # [row, kv head of the row, query head of the kv head]: each query
        # head into its kv head's half of the lanes, and that half kept
        halves = jnp.eye(2, dtype=q.dtype)
        q = jnp.einsum("nrpgd,pq->nrpgqd", q.reshape(ns, kc.shape[2], 2, -1, d),
                       halves).reshape(ns, H, hd)
    out = _paged_decode_pallas(
        q, kc, vc, layer, block_table, lengths, sm_scale=scale,
        pages_per_block=pages_per_block, interpret=interpret)
    if packed:
        out = jnp.einsum("nrpgqd,pq->nrpgd",
                         out.reshape(ns, kc.shape[2], 2, -1, 2, d),
                         halves).reshape(ns, H, d)
    return out


# ---------------------------------------------------------------------------
# Decode attention over a latent arena (MLA, the absorbed form)
# ---------------------------------------------------------------------------

def _latent_decode_kernel(layer_ref, len_ref, bt_ref, q_ref, c_hbm, o_ref,
                          buf, sem, m_ref, l_ref, acc_ref, *, sm_scale: float,
                          rank: int, pages_per_block: int):
    """One grid step = one slot: ALL its query heads `[H, width]` (the
    absorbed query beside the rotary one) against its live rows, a block of
    `pages_per_block` pages at a time, fetched where they lie by DMA,
    double-buffered, under a dynamic trip count (`_paged_decode_kernel`). A
    row is key and value at once: the score takes all `width` lanes, the
    output the first `rank` (the latent). Online softmax, float32 statistics
    and accumulator."""
    _, T, _ = buf.shape
    page = T // pages_per_block
    max_pages = bt_ref.shape[1]
    slot = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[slot]
    live_pages = pl.cdiv(length, page)
    n_blocks = pl.cdiv(live_pages, pages_per_block)

    @pl.when(slot == 0)
    def _clear():
        # Rows past the live pages are never fetched, only masked: they have
        # to be finite (0 x NaN is NaN).
        buf[...] = jnp.zeros_like(buf)

    def each_copy(block, b, fn):
        for i in range(pages_per_block):
            idx = block * pages_per_block + i
            page_id = bt_ref[slot, jnp.minimum(idx, max_pages - 1)]

            @pl.when(idx < live_pages)
            def _():
                fn(pltpu.make_async_copy(
                    c_hbm.at[layer, page_id],
                    buf.at[b, pl.ds(i * page, page), :], sem.at[b]))

    m_ref[...] = jnp.full_like(m_ref, DEFAULT_MASK_VALUE)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _first():
        each_copy(0, 0, lambda c: c.start())

    def block_body(b, carry):
        cur = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():
            each_copy(b + 1, 1 - cur, lambda c: c.start())

        each_copy(b, cur, lambda c: c.wait())
        rows = buf[cur]                                        # [T, width]
        heads = q_ref.shape[1]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale      # [H, T]
        live = (b * T + jax.lax.broadcasted_iota(jnp.int32, (heads, T), 1)
                < length)
        s = jnp.where(live, s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block_body, 0)
    # An idle slot (length 0) walked nothing: l is 0 and so is its output.
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _latent_decode_pallas(q, arena, layer, block_table, lengths, *, rank,
                          sm_scale, pages_per_block, interpret):
    ns, H, width = q.shape
    page = arena.shape[2]
    if pages_per_block is None:
        pages_per_block = max(1, _DECODE_BLOCK_TOKENS // page)
    pages_per_block = min(pages_per_block, block_table.shape[1])
    T = pages_per_block * page
    kernel = functools.partial(_latent_decode_kernel, sm_scale=sm_scale,
                               rank=rank, pages_per_block=pages_per_block)
    out = pl.pallas_call(
        kernel,
        name="paged_latent_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,       # layer, lengths, block table
            grid=(ns,),
            in_specs=[pl.BlockSpec((1, H, width), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, rank), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, T, width), arena.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, rank), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((ns, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      block_table.astype(jnp.int32), q.astype(arena.dtype), arena)
    return out


def _latent_decode_reference(q, arena, layer, block_table, lengths, *, rank,
                             sm_scale):
    """The XLA path: gather every page of every slot's table out of the
    layer, float32 softmax over the whole context under a length mask."""
    ns = q.shape[0]
    page = arena.shape[2]
    ctx = block_table.shape[1] * page
    rows = arena[layer, block_table].astype(jnp.float32).reshape(ns, ctx, -1)
    live = jnp.arange(ctx)[None, :] < lengths[:, None]          # [ns, ctx]
    # What a dead position holds is masked out of the value too.
    rows = jnp.where(live[:, :, None], rows, 0.0)
    scores = jnp.einsum("nhw,nsw->nhs", q.astype(jnp.float32), rows) \
        * sm_scale
    scores = jnp.where(live[:, None, :], scores, DEFAULT_MASK_VALUE)
    wts = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nhs,nsr->nhr", wts, rows[..., :rank])
    return jnp.where((lengths > 0)[:, None, None], out, 0.0)


def paged_latent_decode(ql, q_r, arena, layer, block_table, lengths, *,
                        sm_scale: float,
                        pages_per_block: Optional[int] = None,
                        interpret: bool = False):
    """Latent attention's decode step in its absorbed form: ONE query token
    a slot, all its heads against the slot's cached rows, each row key and
    value at once.

    ql [ns, H, rank], each head's query with the key up-projection absorbed
    (`models.block.latent_attention_inputs`); q_r [ns, H, dr], its rotary
    part; arena [L, n_pages, page, rank + dr and zeros to a multiple of 128],
    the WHOLE latent arena (`empty_latent`) and `layer` the index into it; block_table, lengths as
    `paged_decode_attention`'s. Head h's score at position s is `sm_scale *
    (ql[h] . c_s + q_r[h] . kr_s)` where the row is `[c_s ; kr_s]`; ->
    `sum_s p_s c_s`, float32 [ns, H, rank] (`latent_attention_output` takes
    it through the value up-projection). 2 H (2 rank + dr) operations for
    every row of (rank + dr) numbers read: at 128 heads of 512 + 64 in
    bfloat16, 242 to the byte, where a v5e's peaks stand 240 to 1.

    On a TPU (or with `interpret`) the Pallas kernel `paged_latent_decode`,
    which walks only the live pages, in place; elsewhere XLA's gather of the
    whole table."""
    rank = ql.shape[-1]
    page = arena.shape[2]
    q = latent_rows(ql.astype(q_r.dtype), q_r, arena)   # zeros meet zeros
    use = interpret or (attention._on_tpu() and rank % 128 == 0
                        and page % _sublanes(arena.dtype) == 0)
    attention._path_counts["latent_decode_pallas" if use
                           else "latent_decode_reference"] += 1
    if use:
        return _latent_decode_pallas(
            q, arena, layer, block_table, lengths, rank=rank,
            sm_scale=sm_scale, pages_per_block=pages_per_block,
            interpret=interpret)
    return _latent_decode_reference(q, arena, layer, block_table, lengths,
                                    rank=rank, sm_scale=sm_scale)

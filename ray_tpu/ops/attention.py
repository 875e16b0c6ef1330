"""Attention ops: reference softmax attention, Pallas TPU flash attention.

The reference framework has no attention kernels of its own (it orchestrates
engines like vLLM — reference: python/ray/llm/_internal/serve/deployments/llm/
vllm/vllm_models.py); in the TPU-native rebuild the compute path is first-class,
so the framework ships its own kernels.

Design:
  * ``attention_reference`` — pure jnp, fp32 softmax; ground truth for tests
    and the CPU path.
  * ``_flash_fwd_pallas`` — Pallas TPU forward kernel, online-softmax over KV
    blocks with VMEM accumulators (MXU-aligned 128-multiple block shapes). A
    head of 64 (half a tile, the LFM2 family's) lies in blocks `[rows, 64]`,
    the whole head the block's last dimension: q, k and v are read as they
    are projected, no lane padded in memory; the MXU contracts over 64 and
    writes 64 columns, half of what it could (PERF.md section 5 has the
    share of the roofline that leaves). No backward at that width.
  * ``flash_attention`` — custom_vjp: Pallas forward on TPU (reference forward
    elsewhere); backward is the standard two-kernel Pallas flash backward
    (dK/dV pass + dQ pass, bf16 MXU matmuls with f32 accumulation), with a
    blockwise XLA fallback off-TPU / for unaligned shapes.

Decode attention over the paged KV cache lives with the cache, in
``ops/paged_kv.py``; it counts its path here (``attention_path_counts``).

Layout: [batch, num_heads, seq, head_dim] (BHSD).
"""

from __future__ import annotations

import collections
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.utils import get_logger

logger = get_logger("ops.attention")

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

# Which implementation each traced flash_attention or
# paged_kv.paged_decode_attention call took, counted at TRACE time
# ("fwd_pallas", "fwd_reference", "bwd_pallas", "bwd_reference",
# "decode_pallas", "decode_reference"; latent attention's "latent_fwd_*" and
# "latent_decode_*"; a mixed stack's "window_fwd_*", "full_fwd_*" and
# "window_decode_reference"; generation by blocks' "block_fwd_*", a prompt
# under the block mask, and "block_decode_*", a step of a block of rows a
# slot; a linear layer's "linear_*", its prompt's chunks and its step, and a
# block-selecting layer's prompt, "block_sparse_*", whose decode step is
# "decode_*" by a table of the selected pages): the dispatch is otherwise
# invisible
# from outside a jitted program, and a benchmark must be able to assert that
# the kernel it names is the one that ran.
_path_counts: collections.Counter = collections.Counter()


def attention_path_counts() -> Dict[str, int]:
    """Copy of the per-process trace-time path counters."""
    return dict(_path_counts)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# A head of half a tile (the LFM2 family's 64): the forward kernel takes it
# as a block whose last dimension is the whole head, which is a legal block
# (a last dimension is a multiple of 128 or the array's own); the backward
# kernels have no such form and fall back to the blockwise XLA path.
_HALF_TILE = 64


def pallas_eligible(q: jax.Array, k: jax.Array,
                    backward: bool = False) -> bool:
    """The dispatch gate: Pallas on a TPU backend for 128-aligned
    sequence lengths and head dims (the forward kernel a head of 64 too),
    the XLA reference path otherwise (CPU tests, unaligned shapes)."""
    d = q.shape[-1]
    return (_on_tpu() and q.shape[2] % 128 == 0 and k.shape[2] % 128 == 0
            and (d % 128 == 0 or (d == _HALF_TILE and not backward)))


def _use_pallas(kind: str, q: jax.Array, k: jax.Array) -> bool:
    """pallas_eligible, with the choice counted and logged."""
    use = pallas_eligible(q, k, backward=kind == "bwd")
    path = "pallas" if use else "reference"
    _path_counts[f"{kind}_{path}"] += 1
    logger.debug("flash_attention %s: %s path, q=%s kv_len=%d", kind, path,
                 q.shape, k.shape[2])
    return use


def _out_struct(shape, dtype, like: jax.Array) -> jax.ShapeDtypeStruct:
    """pallas_call output type that varies over the same manual mesh
    axes as `like` — inside a shard_map the kernel's outputs are as
    device-varying as its inputs, and shard_map's checker needs it said."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _pick_block(seq: int, pref: int) -> int:
    """Largest 128-multiple block <= pref that divides seq (seq % 128 == 0
    is guaranteed by the dispatch gate, so 128 always works)."""
    b = min(pref, seq)
    while b > 128 and seq % b != 0:
        b //= 2
    return b if seq % b == 0 else 128


# ---------------------------------------------------------------------------
# Reference implementation
# ---------------------------------------------------------------------------

def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        sm_scale: Optional[float] = None,
                        q_offset: int = 0,
                        kv_offset: int = 0, block: int = 1) -> jax.Array:
    """Plain softmax attention with fp32 accumulation.

    ``q_offset``/``kv_offset`` give the global positions of the local q/kv
    shards — needed by ring attention where each sp shard sees rotated K/V.
    ``block`` > 1: the mask of generation by blocks (`_sees`).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[2])[:, None]
        k_pos = kv_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(_sees(q_pos, k_pos, block), s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _sees(q_pos, k_pos, block: int = 1):
    """Whether the query at `q_pos` attends to the key at `k_pos`. `block` 1:
    the causal mask. `block` B > 1, generation by blocks of B positions: all
    of the query's own block, both ways, and every block before it, floor(k /
    B) <= floor(q / B). `block` is static, and 1 emits the causal
    comparison alone."""
    if block == 1:
        return q_pos >= k_pos
    return (q_pos // block + 1) * block > k_pos


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, sm_scale: float, causal: bool,
                      block_q: int, block_k: int, kv_seq_len: int,
                      block: int = 1):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _body(masked: bool):
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_sees(q_pos, k_pos, block), s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        l_ref[:] = l_new

    if causal:
        # Three block classes: fully masked (skip entirely), fully visible
        # (no mask arithmetic — the bulk below the diagonal), diagonal
        # (per-element mask). The mask of generation by blocks (`block` > 1,
        # a divisor of both tile edges) differs from the causal one inside
        # the diagonal tiles alone, so the classes are the same.
        visible = kv_idx * block_k <= q_idx * block_q + (block_q - 1)
        full = kv_idx * block_k + (block_k - 1) <= q_idx * block_q
        pl.when(visible & jnp.logical_not(full))(
            functools.partial(_body, True))
        pl.when(full)(functools.partial(_body, False))
    else:
        _body(False)

    @pl.when(kv_idx == (kv_seq_len // block_k) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[:] + jnp.log(l))[:, 0]


def _flash_fwd_pallas(q, k, v, *, causal, sm_scale, block_q=1024,
                      block_k=1024, interpret=False, block=1):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(skv, block_k)
    grid = (b * h, sq // block_q, skv // block_k)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, skv, d)
    vr = v.reshape(b * h, skv, d)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_seq_len=skv,
        **({} if block == 1 else {"block": block}))
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd" if block == 1 else "block_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            # lse kept 3-D [bh, 1, sq]: TPU needs the trailing two block dims
            # tileable (1 == full middle dim, block_q % 128 == 0).
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[
            _out_struct((b * h, sq, d), q.dtype, q),
            _out_struct((b * h, 1, sq), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _fwd_with_lse_reference(q, k, v, *, causal, sm_scale, block=1):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        q_pos = jnp.arange(q.shape[2])[:, None]
        k_pos = jnp.arange(k.shape[2])[None, :]
        s = jnp.where(_sees(q_pos, k_pos, block), s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", (p / l).astype(v.dtype), v)
    lse = (m + jnp.log(l))[..., 0]
    return out, lse


def block_flash_attention(q, k, v, block: int, *,
                          sm_scale: Optional[float] = None,
                          interpret: bool = False) -> jax.Array:
    """Attention of a prompt under the mask of generation by blocks (the SDAR
    family): position t attends to s iff floor(s / block) <= floor(t /
    block), all of its own block of `block` positions, both ways, and every
    block before it. q, k, v `[b, H, s, d]` -> `[b, H, s, d]`. No gradient:
    serving's.

    On a TPU (or with `interpret`) `_flash_fwd_kernel` with that comparison
    in its diagonal tiles, under the name `block_flash_fwd` (`block` divides
    128, so every tile edge: the tiles skipped, taken whole and masked are the
    causal kernel's); elsewhere the XLA reference. Counted in
    `attention_path_counts()` as `block_fwd_pallas` / `block_fwd_reference`."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    use = interpret or pallas_eligible(q, k)
    _path_counts["block_fwd_pallas" if use else "block_fwd_reference"] += 1
    if use:
        return _flash_fwd_pallas(q, k, v, causal=True, sm_scale=scale,
                                 interpret=interpret, block=block)[0]
    return _fwd_with_lse_reference(q, k, v, causal=True, sm_scale=scale,
                                   block=block)[0]


# ---------------------------------------------------------------------------
# A prompt's latent attention (MLA): keys in two parts, values narrower
# ---------------------------------------------------------------------------

def _latent_flash_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, sm_scale: float,
                         block_q: int, block_k: int, kv_seq_len: int):
    """`_flash_fwd_kernel`, causal, with a head's score the sum of two
    products: its own part of the key (`kn`) and the rotary part every head
    shares (`kr`, one array for all heads: the grid hands each head the same
    block of it). `qr_ref` and `kr_ref` None: a key of ONE part."""
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _body(masked: bool):
        contract = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(qn_ref[0], kn_ref[0], contract,
                                preferred_element_type=jnp.float32)
        if qr_ref is not None:
            s = s + jax.lax.dot_general(qr_ref[0], kr_ref[0], contract,
                                        preferred_element_type=jnp.float32)
        s = s * sm_scale
        if masked:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    visible = kv_idx * block_k <= q_idx * block_q + (block_q - 1)
    full = kv_idx * block_k + (block_k - 1) <= q_idx * block_q
    pl.when(visible & jnp.logical_not(full))(functools.partial(_body, True))
    pl.when(full)(functools.partial(_body, False))

    @pl.when(kv_idx == (kv_seq_len // block_k) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def _latent_flash_pallas(q_n, q_r, k_n, k_r, v, *, sm_scale, interpret,
                         block_q=1024, block_k=1024,
                         name="latent_flash_fwd"):
    """k_n and v `[b, KVH, s, d]`: query head h reads kv head h // (H // KVH)
    where it lies (no copy a query head). k_r `[b, s, dr]`, one for all
    heads, or `[b, KVH, s, dr]`, one a kv head; q_r and k_r None: a key of
    one part, q_n and k_n the whole of it."""
    b, h, s, dn = q_n.shape
    dv = v.shape[-1]
    kvh = k_n.shape[1]
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    kernel = functools.partial(
        _latent_flash_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, kv_seq_len=s)
    if q_r is None:
        two_parts = kernel

        def kernel(q_ref, k_ref, v_ref, *rest):
            two_parts(q_ref, None, k_ref, None, v_ref, *rest)

    def by_q(d):
        return pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))

    def by_k(d, heads=kvh):
        # kv head bh // groups of `heads`; 1 of them: batch row bh // h
        over = h // heads
        return pl.BlockSpec((1, block_k, d), (
            lambda bh, qi, ki: (bh, ki, 0)) if over == 1 else (
            lambda bh, qi, ki: (bh // over, ki, 0)))

    if q_r is None:
        in_specs = [by_q(dn), by_k(dn), by_k(dv)]
        operands = (q_n.reshape(b * h, s, dn), k_n.reshape(b * kvh, s, dn),
                    v.reshape(b * kvh, s, dv))
    else:
        dr = q_r.shape[-1]
        in_specs = [by_q(dn), by_q(dr), by_k(dn),
                    # the rotary key: one for all heads, or one a kv head
                    by_k(dr, 1 if k_r.ndim == 3 else kvh),
                    by_k(dv)]
        operands = (q_n.reshape(b * h, s, dn), q_r.reshape(b * h, s, dr),
                    k_n.reshape(b * kvh, s, dn), k_r.reshape(-1, s, dr),
                    v.reshape(b * kvh, s, dv))
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(b * h, s // block_q, s // block_k),
        in_specs=in_specs,
        out_specs=by_q(dv),
        out_shape=_out_struct((b * h, s, dv), v.dtype, v),
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, h, s, dv)


def latent_flash_attention(q_n, q_r, k_n, k_r, v, sm_scale: float, *,
                           interpret: bool = False):
    """Causal attention of a prompt under latent attention (MLA): head h's
    query is `[q_n[h] ; q_r[h]]` and its key `[k_n[h] ; k_r]`, the rotary part
    `k_r` `[b, s, dr]` ONE for all heads, its value `v[h]` of a width of its
    own (192, 192 and 128 at DeepSeek-V3's widths, which `flash_attention`,
    one width for all three and a multiple of 128, does not take). q_n, k_n
    `[b, H, s, dn]`, q_r `[b, H, s, dr]`, v `[b, H, s, dv]` -> `[b, H, s,
    dv]`. No gradient: serving's.

    On a TPU (or with `interpret`) the Pallas kernel `latent_flash_fwd`, to
    which the two parts of a key go apart, so `k_r` is never repeated for the
    heads in memory; elsewhere the XLA reference. Counted in
    `attention_path_counts()` as `latent_fwd_pallas` / `latent_fwd_reference`.
    """
    s, dn, dv = q_n.shape[2], q_n.shape[-1], v.shape[-1]
    use = interpret or (_on_tpu() and s % 128 == 0 and dn % 128 == 0
                        and dv % 128 == 0)
    _path_counts["latent_fwd_pallas" if use else "latent_fwd_reference"] += 1
    if use:
        return _latent_flash_pallas(q_n, q_r, k_n, k_r, v, sm_scale=sm_scale,
                                    interpret=interpret)
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q_n, k_n,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r,
                           preferred_element_type=jnp.float32)) * sm_scale
    pos = jnp.arange(s)
    scores = jnp.where(pos[:, None] >= pos[None, :], scores,
                       DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# A prompt's attention in a mixed stack: window layers with a sink beside
# full layers, keys in two parts (rotary and passed), values narrower
# ---------------------------------------------------------------------------

def _window_flash_kernel(qn_ref, qr_ref, pkn_ref, pkr_ref, pv_ref, kn_ref,
                         kr_ref, v_ref, sink_ref, o_ref, *, sm_scale: float,
                         window: int, block: int):
    """One grid step = one block of `block` queries of ALL the query heads
    of one kv head (`[g, block, d]`, run as `g * block` rows) against the
    only keys its window touches: the block on the diagonal (`kn`, `kr`,
    `v`) and the one before it (`pkn`, `pkr`, `pv`; `window <= block`). No
    loop over key blocks and no running statistics: one softmax over the 2 x
    `block` columns and the sink's, whose probability is dropped."""
    qi = pl.program_id(1)
    g, _, dn = qn_ref.shape[1:]
    rows = g * block
    qn = qn_ref[0].reshape(rows, dn)
    qr = qr_ref[0].reshape(rows, qr_ref.shape[-1])
    contract = (((1,), (1,)), ((), ()))

    def scores(kn, kr):
        return (jax.lax.dot_general(qn, kn[0], contract,
                                    preferred_element_type=jnp.float32)
                + jax.lax.dot_general(qr, kr[0], contract,
                                      preferred_element_type=jnp.float32)
                ) * sm_scale
    # a row's query is `r` rows into the block, a column's key `c`
    r = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0),
                    block)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    s_cur = jnp.where((c <= r) & (r - c < window), scores(kn_ref, kr_ref),
                      DEFAULT_MASK_VALUE)
    # the block before: `block` positions further back; none before block 0
    s_prev = jnp.where((r + block - c < window) & (qi > 0),
                       scores(pkn_ref, pkr_ref), DEFAULT_MASK_VALUE)
    sink = sink_ref[0]                                       # [rows, 1]
    m = jnp.maximum(jnp.maximum(jnp.max(s_cur, axis=-1, keepdims=True),
                                jnp.max(s_prev, axis=-1, keepdims=True)),
                    sink)
    p_cur, p_prev = jnp.exp(s_cur - m), jnp.exp(s_prev - m)
    l = (jnp.sum(p_cur, axis=-1, keepdims=True)
         + jnp.sum(p_prev, axis=-1, keepdims=True) + jnp.exp(sink - m))
    pv = (((1,), (0,)), ((), ()))
    o = (jax.lax.dot_general(p_cur.astype(v_ref.dtype), v_ref[0], pv,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(p_prev.astype(pv_ref.dtype), pv_ref[0], pv,
                               preferred_element_type=jnp.float32))
    o_ref[0] = (o / l).astype(o_ref.dtype).reshape(o_ref.shape[1:])


def _window_flash_pallas(q_n, q_r, k_n, k_r, v, sink, *, sm_scale, window,
                         interpret):
    b, h, s, dn = q_n.shape
    dr, dv = q_r.shape[-1], v.shape[-1]
    kvh = k_n.shape[1]
    g = h // kvh
    block = -(-window // 128) * 128
    kernel = functools.partial(_window_flash_kernel, sm_scale=sm_scale,
                               window=window, block=block)

    def by_q(d):
        return pl.BlockSpec((1, g, block, d), lambda bk, qi: (bk, 0, qi, 0))

    def by_k(d, back):
        return pl.BlockSpec(
            (1, block, d), lambda bk, qi: (bk, jnp.maximum(qi - back, 0), 0))

    # the sink's logit a row of a grid step: head `row // block` of the group
    sink_rows = jnp.repeat(sink.astype(jnp.float32).reshape(kvh, g), block,
                           axis=1)[:, :, None]
    kv = [k_n.reshape(b * kvh, s, dn), k_r.reshape(b * kvh, s, dr),
          v.reshape(b * kvh, s, dv)]
    out = pl.pallas_call(
        kernel,
        name="window_flash_fwd",
        grid=(b * kvh, s // block),
        in_specs=[by_q(dn), by_q(dr),
                  by_k(dn, 1), by_k(dr, 1), by_k(dv, 1),
                  by_k(dn, 0), by_k(dr, 0), by_k(dv, 0),
                  pl.BlockSpec((1, g * block, 1),
                               lambda bk, qi: (bk % kvh, 0, 0))],
        out_specs=by_q(dv),
        out_shape=_out_struct((b * kvh, g, s, dv), v.dtype, v),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(q_n.reshape(b * kvh, g, s, dn), q_r.reshape(b * kvh, g, s, dr),
      *kv, *kv, sink_rows)
    return out.reshape(b, h, s, dv)


def _window_blocks_kernel(q_ref, *refs, sm_scale: float, window: int,
                          block: int, n_back: int):
    """`_window_flash_kernel` for a window of SEVERAL blocks and a key of one
    part: one grid step = one block of `block` queries of ALL the query heads
    of one kv head (`g * block` rows) against the `n_back + 1` key blocks its
    window touches, the oldest first and the diagonal one last (`refs`: their
    keys, then their values, then the result). One softmax over their
    columns: no running statistics, the scores of a step are `g * block` by
    `(n_back + 1) * block` float32 (2.9 MB at 9 heads a group, a window of
    512 and blocks of 128). Element masks on the diagonal block (causal) and
    on those the window's far edge crosses alone; a block before position 0
    (the index map handed block 0 again) is masked whole."""
    qi = pl.program_id(1)
    k_refs, v_refs, o_ref = refs[:n_back + 1], refs[n_back + 1:-1], refs[-1]
    g, _, d = q_ref.shape[1:]
    rows = g * block
    q = q_ref[0].reshape(rows, d)
    # a row's query is `r` rows into its block, a column's key `c` into its
    r = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0),
                    block)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    scores = []
    for j, k_ref in enumerate(k_refs):
        back = n_back - j           # the key block lies `back` blocks back
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        ago = r - c + back * block  # positions from the key to the query
        if back == 0:
            live = (ago >= 0) & (ago < window)
        elif (back + 1) * block - 1 < window:
            live = qi >= back
        else:
            live = (ago < window) & (qi >= back)
        scores.append(jnp.where(live, s, DEFAULT_MASK_VALUE))
    m = functools.reduce(jnp.maximum, (jnp.max(s, axis=-1, keepdims=True)
                                       for s in scores))
    l, o = 0.0, 0.0
    for s, v_ref in zip(scores, v_refs):
        p = jnp.exp(s - m)
        l = l + jnp.sum(p, axis=-1, keepdims=True)
        o = o + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype).reshape(o_ref.shape[1:])


# The query block of `_window_blocks_kernel`: of a window of 512 it computes
# 640 columns a query where the window holds 512.
_WINDOW_BLOCK = 128


def _window_blocks_pallas(q, k, v, *, sm_scale, window, interpret,
                          block=_WINDOW_BLOCK):
    b, h, s, d = q.shape
    dv, kvh = v.shape[-1], k.shape[1]
    g = h // kvh
    block = min(block, s)
    # blocks before the diagonal one that a query block's window reaches
    n_back = min(-(-(window - 1) // block), s // block - 1)
    kernel = functools.partial(_window_blocks_kernel, sm_scale=sm_scale,
                               window=window, block=block, n_back=n_back)

    def by_q(width):
        return pl.BlockSpec((1, g, block, width),
                            lambda bk, qi: (bk, 0, qi, 0))

    def by_k(width, back):
        return pl.BlockSpec(
            (1, block, width),
            lambda bk, qi: (bk, jnp.maximum(qi - back, 0), 0))

    backs = range(n_back, -1, -1)
    k, v = k.reshape(b * kvh, s, d), v.reshape(b * kvh, s, dv)
    out = pl.pallas_call(
        kernel,
        name="window_blocks_fwd",
        grid=(b * kvh, s // block),
        in_specs=[by_q(d), *(by_k(d, back) for back in backs),
                  *(by_k(dv, back) for back in backs)],
        out_specs=by_q(dv),
        out_shape=_out_struct((b * kvh, g, s, dv), v.dtype, v),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(q.reshape(b * kvh, g, s, d), *(k for _ in backs), *(v for _ in backs))
    return out.reshape(b, h, s, dv)


def mixed_attention_reference(q_n, q_r, k_n, k_r, v, sm_scale, window=0,
                              sink=None):
    """The XLA path of `mixed_flash_attention`: every score, a mask, a
    float32 softmax with the sink's column."""
    b, h, s, _ = q_r.shape
    groups = h // k_r.shape[1]
    k_n, k_r, v = (None if t is None else repeat_kv(t, groups)
                   for t in (k_n, k_r, v))

    def dots(q, k):
        return jnp.einsum("bhqd,bhkd->bhqk", q, k,
                          preferred_element_type=jnp.float32)

    scores = dots(q_r, k_r) if q_n is None \
        else dots(q_n, k_n) + dots(q_r, k_r)
    scores = scores * sm_scale
    pos = jnp.arange(s)
    live = pos[:, None] >= pos[None, :]
    if window:
        live &= pos[:, None] - pos[None, :] < window
    scores = jnp.where(live, scores, DEFAULT_MASK_VALUE)
    if sink is None:
        p = jax.nn.softmax(scores, axis=-1)
    else:
        col = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None, None],
                               (b, h, s, 1))
        p = jax.nn.softmax(jnp.concatenate([scores, col], -1), -1)[..., :-1]
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def mixed_kernel_refusal(s: int, dn: int, dr: int, dv: int, window: int,
                         sink: bool = False) -> Optional[str]:
    """Why `mixed_flash_attention` has no Pallas kernel for a prompt of `s`
    rows whose heads pass `dn` numbers and turn `dr`, with values of `dv`, in
    a window layer (`window` > 0) or a full one; None: it has one. A key in
    two parts needs its passed part in whole tiles and `s` in whole blocks of
    the window rounded up to a tile; a key whose parts together are one or
    more whole tiles is joined and goes as ONE part, in blocks of 128 rows
    (a shorter prompt one block), with no sink. A server asks this for every
    rung it will prefill at (`models.serving.rung_refusal`): elsewhere the
    fall to `mixed_attention_reference` is silent but for the path counts."""
    kind = f"{'window ' + str(window) if window else 'full'} attention, " \
        f"{s} rows, heads of {dn} passed + {dr} turned numbers, values of " \
        f"{dv}"
    if dv % 128:
        return f"{kind}: values in whole tiles of 128"
    if dn and dn % 128 == 0:
        block = -(-window // 128) * 128 if window else 128
        return None if s % block == 0 else \
            f"{kind}: rows in whole blocks of {block}"
    if (dn + dr) % 128:
        return f"{kind}: a key of whole tiles of 128, or a passed part of " \
            "them"
    if window and sink:
        return f"{kind}: no sink beside a key of one part"
    return None if s % min(s, 128) == 0 and s % 16 == 0 else \
        f"{kind}: rows in whole blocks of 128, or one block of whole tiles"


def mixed_flash_attention(q_n, q_r, k_n, k_r, v, sm_scale: float, *,
                          window: int = 0, sink=None,
                          interpret: bool = False):
    """Causal attention of a prompt in a stack of window and full attention
    layers (`LlamaConfig.attn_pattern`). Query head h is `[q_n[h] ; q_r[h]]`
    (the part RoPE passed, the part it turned: 128 and 64 at MiMo-V2's
    widths; 64 and 64 in Laguna's full layers; q_n and k_n None where it
    turned the whole head, Laguna's window layers), its key `[k_n ; k_r]` of
    kv head `h // (H // KVH)`, its value
    that head's `v`, of a width of its own (128). q_n `[b, H, s, dn]`, q_r
    `[b, H, s, dr]`, k_n `[b, KVH, s, dn]`, k_r `[b, KVH, s, dr]`, v `[b,
    KVH, s, dv]` -> `[b, H, s, dv]`. K and V are read by kv head, never
    repeated for the query heads. No gradient: serving's.

    `window` > 0: query i attends to keys j with `0 <= i - j < window`, and
    with `sink` `[H]` one further column of that logit a head joins the
    softmax and carries no value. On a TPU (or with `interpret`) a Pallas
    kernel that visits ONLY the key blocks a query block's window touches,
    so that its work grows with `s`, not `s^2`: `window_flash_fwd` for a key
    in two parts and a window of one block (the diagonal block and the one
    before it), `window_blocks_fwd` for a key of one part and a window of
    any number of blocks of 128. Counted as `window_fwd_pallas` /
    `window_fwd_reference`.

    `window` 0: every earlier position; the kernel `full_flash_fwd`
    (`_latent_flash_kernel`'s online softmax over key blocks, the key in two
    parts or in one), counted as `full_fwd_pallas` / `full_fwd_reference`.

    A key whose passed part is no whole tile but whose parts together are
    (64 + 64, 0 + 128) goes to the kernels as ONE part, `[k_n ; k_r]` joined
    here: one product over 128 numbers where two parts of 64 are two over
    half a tile each.

    Elsewhere, or where a shape is not a kernel's (`mixed_kernel_refusal`),
    the XLA reference `mixed_attention_reference`."""
    s, dr, dv = q_r.shape[2], q_r.shape[-1], v.shape[-1]
    dn = 0 if q_n is None else q_n.shape[-1]
    kind = "window" if window else "full"
    use = (interpret or _on_tpu()) and mixed_kernel_refusal(
        s, dn, dr, dv, window, sink is not None) is None
    _path_counts[f"{kind}_fwd_pallas" if use else f"{kind}_fwd_reference"] += 1
    if not use:
        return mixed_attention_reference(q_n, q_r, k_n, k_r, v, sm_scale,
                                         window, sink)
    if dn % 128 or not dn:
        q, k = (t[1] if t[0] is None else jnp.concatenate(t, -1)
                for t in ((q_n, q_r), (k_n, k_r)))
        if window:
            return _window_blocks_pallas(q, k, v, sm_scale=sm_scale,
                                         window=window, interpret=interpret)
        return _latent_flash_pallas(q, None, k, None, v, sm_scale=sm_scale,
                                    interpret=interpret,
                                    name="full_flash_fwd")
    if window:
        if sink is None:
            sink = jnp.full((q_n.shape[1],), DEFAULT_MASK_VALUE, jnp.float32)
        return _window_flash_pallas(q_n, q_r, k_n, k_r, v, sink,
                                    sm_scale=sm_scale, window=window,
                                    interpret=interpret)
    if sink is not None:
        raise NotImplementedError("a sink on a full-attention layer")
    return _latent_flash_pallas(q_n, q_r, k_n, k_r, v, sm_scale=sm_scale,
                                interpret=interpret, name="full_flash_fwd")


# ---------------------------------------------------------------------------
# Pallas TPU backward kernels (standard two-kernel flash backward:
# one pass producing dK/dV with q innermost, one producing dQ with kv
# innermost; all MXU matmuls in bf16 with f32 accumulation)
# ---------------------------------------------------------------------------

def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *,
                          sm_scale: float, causal: bool, block_q: int,
                          block_k: int, q_seq_len: int):
    kv_idx = pl.program_id(1)
    q_idx = pl.program_id(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _body(masked: bool):
        q = q_ref[0]          # [bq, d]
        k = k_ref[0]          # [bk, d]
        v = v_ref[0]          # [bk, d]
        do = do_ref[0]        # [bq, d]
        lse = lse_ref[0, 0][:, None]     # [bq, 1]
        delta = delta_ref[0, 0][:, None]  # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if masked:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)  # [bq, bk] f32
        pb = p.astype(v.dtype)
        # dv += p^T @ do   (contract over bq)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do @ v^T    [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        # dk += ds^T @ q   (contract over bq)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        visible = q_idx * block_q + (block_q - 1) >= kv_idx * block_k
        full = q_idx * block_q >= kv_idx * block_k + (block_k - 1)
        pl.when(visible & jnp.logical_not(full))(
            functools.partial(_body, True))
        pl.when(full)(functools.partial(_body, False))
    else:
        _body(False)

    @pl.when(q_idx == (q_seq_len // block_q) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, sm_scale: float, causal: bool,
                         block_q: int, block_k: int, kv_seq_len: int):
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _body(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        # dq += ds @ k
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        visible = kv_idx * block_k <= q_idx * block_q + (block_q - 1)
        full = kv_idx * block_k + (block_k - 1) <= q_idx * block_q
        pl.when(visible & jnp.logical_not(full))(
            functools.partial(_body, True))
        pl.when(full)(functools.partial(_body, False))
    else:
        _body(False)

    @pl.when(kv_idx == (kv_seq_len // block_k) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, dout, *, causal, sm_scale,
                      block_q=1024, block_k=512):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(skv, block_k)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, skv, d)
    vr = v.reshape(b * h, skv, d)
    dor = dout.astype(q.dtype).reshape(b * h, sq, d)
    lse_r = lse.reshape(b * h, 1, sq)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * h, 1, sq)

    dkv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          q_seq_len=sq),
        name="flash_bwd_dkv",
        grid=(b * h, skv // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            _out_struct((b * h, skv, d), k.dtype, k),
            _out_struct((b * h, skv, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr, dor, lse_r, delta)
    dk, dv = dkv

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          kv_seq_len=skv),
        name="flash_bwd_dq",
        grid=(b * h, sq // block_q, skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[_out_struct((b * h, sq, d), q.dtype, q)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr, dor, lse_r, delta)[0]

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, skv, d),
            dv.reshape(b, h, skv, d))


# ---------------------------------------------------------------------------
# custom_vjp wrapper with blockwise XLA backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_k_bwd: int = 512):
    out, _ = _flash_fwd(q, k, v, causal, sm_scale)
    return out


def _flash_fwd(q, k, v, causal, sm_scale):
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if _use_pallas("fwd", q, k):
        return _flash_fwd_pallas(q, k, v, causal=causal, sm_scale=scale)
    return _fwd_with_lse_reference(q, k, v, causal=causal, sm_scale=scale)


def _flash_vjp_fwd(q, k, v, causal, sm_scale, block_k_bwd):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_k_bwd, res, dout):
    q, k, v, out, lse = res
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if _use_pallas("bwd", q, k):
        return _flash_bwd_pallas(q, k, v, out, lse, dout, causal=causal,
                                 sm_scale=scale)
    skv = k.shape[2]
    block = min(block_k_bwd, skv)
    n_blocks = skv // block if skv % block == 0 else 1
    if skv % block != 0:
        block = skv
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [b,h,sq]
    q_pos = jnp.arange(q.shape[2])[:, None]

    def kv_block(carry, idx):
        dq_acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, idx * block, block, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v, idx * block, block, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = idx * block + jnp.arange(block)[None, :]
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse[..., None])  # [b,h,q,block]
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, dout.astype(jnp.float32))
        dp = jnp.einsum("bhqd,bhkd->bhqk", dout.astype(jnp.float32),
                        vb.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds,
                                     kb.astype(jnp.float32))
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
        return dq_acc, (dk, dv)

    # (q * 0) rather than zeros: inherits q's varying-manual-axes type so the
    # scan carry is consistent when this runs inside a shard_map (e.g. pp).
    dq0 = (q * 0).astype(jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_block, dq0, jnp.arange(n_blocks))
    dk = jnp.moveaxis(dks, 0, 2).reshape(k.shape)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(v.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """Expand KV heads for grouped-query attention: [b, kvh, s, d] -> [b, kvh*n_rep, s, d]."""
    if n_rep == 1:
        return x
    b, kvh, s, d = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, kvh, n_rep, s, d)).reshape(
        b, kvh * n_rep, s, d)

"""Decayed linear attention (Lightning Attention-2, arXiv:2401.04658; the
`lightning-attn` layers of the MiniCPM-SALA family): every head keeps a state
of FIXED size, a sum of outer products under a constant decay of its own,

    S_t = lambda_h S_{t-1} + k_t^T v_t        o_t = scale * q_t S_t
    equivalently  o_t = scale * sum_{s<=t} lambda_h^(t-s) (q_t . k_s) v_s

with `lambda_h = exp(-rate_h)` (`decay_rates`: a constant of the head and of
the layer's place in the PUBLISHED stack, no parameter), S `[d, d]` float32
and as many kv heads as query heads. q and k come normed and rotated; the
scale is the caller's.

  * ``linear_prompt`` is a prompt of one program, by CHUNKS: a chunk's own
    pairs under `exp(-rate (t - s))` taken directly (never `lambda^t *
    lambda^-s`: `rate * 256` passes float32's range at the steepest head),
    its past by `lambda^(t - t0 + 1) q_t S_prev`, and the state moved on a
    chunk. Rows at and past `length` write nothing: the state handed back is
    the one after row `length - 1`. On a TPU the Pallas kernel `linear_chunk`
    (a head's chunks in order, the state in fast memory between them),
    elsewhere the same sums in `jnp`.
  * ``linear_state_step`` is a decode step, one token a slot, on the slots'
    WHOLE state `[layers, slots, heads, d, d]` where it lies: on a TPU the
    kernel `linear_step` reads each ACTIVE slot's tiles of one layer,
    decays them, adds the step's outer product, reads them against the query
    and writes them back in place (a read and a write of the state: the least
    a step can move); an idle slot's and every other layer's tiles keep their
    bytes. Elsewhere the layer's rows and their write back under a select.
  * ``linear_attention_reference`` and ``linear_recurrence`` are the two
    plain forms the tests hold both to.

Which path a program took is in `attention.attention_path_counts()` as
`linear_pallas` / `linear_reference`. Scope names `linear_chunk` and
`linear_step` lie inside the caller's `linear_attn`.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

# A prompt's chunk: the chunk's own pairs are `chunk^2` scores a head, its
# past and its state `chunk x d x d` each.
CHUNK = 256
# Heads of one slot a grid step of the decode kernel visits: their tiles are
# `_STEP_HEADS x d x d` float32 in and out (0.5 MiB each way at d = 128).
_STEP_HEADS = 8


def decay_rates(n_heads: int, layer: int, n_layers: int) -> np.ndarray:
    """`rate_h` `[n_heads]` float32 of the layer at place `layer` of a stack
    PUBLISHED with `n_layers` layers: `2^(-8 (h + 1) / n_heads) * (1 - layer /
    (n_layers - 1) + 1e-5)`, as MiniMax-Text-01 publishes its decay: the
    first head forgets in a few positions, the last in hundreds, and a later
    layer more slowly than an earlier one."""
    h = np.arange(1, n_heads + 1, dtype=np.float64)
    place = 1.0 - layer / max(n_layers - 1, 1) + 1e-5
    return (2.0 ** (-8.0 * h / n_heads) * place).astype(np.float32)


def kernel_tiles(d: int, width: int = 128) -> bool:
    """Whether the two kernels' tiling takes a head of d and a prompt of
    `width` rows: whole lanes."""
    return d % 128 == 0 and width % 128 == 0


# ---------------------------------------------------------------------------
# The plain forms
# ---------------------------------------------------------------------------

def linear_attention_reference(q, k, v, rates, scale: float) -> jax.Array:
    """The attention form, every pair: q, k, v `[H, T, d]`, rates `[H]` ->
    `[H, T, d]` float32."""
    T = q.shape[1]
    gap = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    decay = jnp.where(gap >= 0, jnp.exp(
        -rates[:, None, None] * jnp.maximum(gap, 0).astype(F32)), 0.0)
    scores = jnp.einsum("htd,hsd->hts", q.astype(F32), k.astype(F32),
                        precision=HIGHEST) * decay * scale
    return jnp.einsum("hts,hsd->htd", scores, v.astype(F32),
                      precision=HIGHEST)


def linear_recurrence(q, k, v, rates, scale: float, state=None):
    """The recurrence, a token at a time: q, k, v `[H, T, d]` from `state`
    `[H, d, d]` (None: zeros) -> (o `[H, T, d]` float32, the state after the
    last row)."""
    H, _, d = q.shape
    lam = jnp.exp(-rates.astype(F32))[:, None, None]

    def step(S, qkv):
        q_t, k_t, v_t = (t.astype(F32) for t in qkv)
        S = lam * S + k_t[:, :, None] * v_t[:, None, :]
        return S, scale * jnp.einsum("hd,hde->he", q_t, S, precision=HIGHEST)

    S, o = jax.lax.scan(step, jnp.zeros((H, d, d), F32) if state is None
                        else state, tuple(t.transpose(1, 0, 2)
                                          for t in (q, k, v)))
    return o.transpose(1, 0, 2), S


def _chunked(q, k, v, rates, scale, length, chunk):
    """`linear_prompt`'s sums in `jnp`, a scan over the chunks."""
    H, T, d = q.shape
    N = T // chunk
    rate = rates.astype(F32)[:, None, None]
    i = jnp.arange(chunk)
    gap = i[:, None] - i[None, :]
    own = jnp.where(gap >= 0, jnp.exp(-rate * jnp.maximum(gap, 0)), 0.0)
    past = scale * jnp.exp(-rate * (i + 1)[None, :, None])      # [H, C, 1]

    def body(S, xs):
        qc, kc, vc, t0 = xs
        a = jnp.einsum("hcd,hed->hce", qc, kc,
                       preferred_element_type=F32) * own * scale
        o = jnp.einsum("hce,hed->hcd", a.astype(vc.dtype), vc,
                       preferred_element_type=F32)
        o = o + jnp.einsum("hcd,hde->hce", qc.astype(F32) * past, S,
                           precision=HIGHEST)
        live = jnp.clip(length - t0, 0, chunk)
        w = jnp.where(i < live, jnp.exp(
            -rate[:, 0] * jnp.maximum(live - 1 - i, 0)), 0.0)    # [H, C]
        S = jnp.exp(-rate * live) * S + jnp.einsum(
            "hcd,hce->hde", kc.astype(F32) * w[..., None], vc.astype(F32),
            precision=HIGHEST)
        return S, o.astype(q.dtype)

    def chunks(t):
        return t.reshape(H, N, chunk, d).transpose(1, 0, 2, 3)

    S, o = jax.lax.scan(body, jnp.zeros((H, d, d), F32),
                        (chunks(q), chunks(k), chunks(v),
                         jnp.arange(N) * chunk))
    return o.transpose(1, 0, 2, 3).reshape(H, T, d), S


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _chunk_kernel(len_ref, q_ref, kt_ref, v_ref, r_ref, o_ref, so_ref, s_ref,
                  *, chunk: int, scale: float):
    """Grid (heads, chunks), the chunks innermost and in order: `q_ref`, `v_ref`
    `[C, d]` a head's rows of this chunk, `kt_ref` `[d, C]` its keys
    transposed (positions are lanes: the state's sum needs no transpose),
    `r_ref` `[8, max(C, d)]` the head's rate in every entry (a row of it
    spreads down the rows of whatever it meets); `s_ref` `[d, d]` the state
    after the chunk before, which stays in fast memory."""
    c = pl.program_id(1)
    d = q_ref.shape[-1]

    @pl.when(c == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    rate_c, rate_d = r_ref[0:1, :chunk], r_ref[0:1, :d]         # [1, C], [1, d]
    q, kt, v = q_ref[...], kt_ref[...], v_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    gap = row - col
    own = jnp.where(gap >= 0, jnp.exp(
        -rate_c * jnp.maximum(gap, 0).astype(F32)), 0.0) * scale
    a = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                            preferred_element_type=F32) * own   # [C, C]
    o = jax.lax.dot_general(a.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)
    at = jax.lax.broadcasted_iota(jnp.int32, (chunk, d), 0)
    past = q.astype(F32) * (scale * jnp.exp(-rate_d * (at + 1).astype(F32)))
    o = o + jax.lax.dot_general(past, s_ref[...], (((1,), (0,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=F32)
    o_ref[...] = o.astype(o_ref.dtype)
    # The state after this chunk's live rows: a row at or past `length`
    # weighs nothing, and a chunk wholly past it leaves the state as it is.
    live = jnp.clip(len_ref[0] - c * chunk, 0, chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    w = jnp.where(lane < live, jnp.exp(
        -rate_c * jnp.maximum(live - 1 - lane, 0).astype(F32)), 0.0)
    s_ref[...] = jnp.exp(-rate_d * live.astype(F32)) * s_ref[...] \
        + jax.lax.dot_general(kt.astype(F32) * w, v.astype(F32),
                              (((1,), (0,)), ((), ())), precision=HIGHEST,
                              preferred_element_type=F32)

    @pl.when(c == pl.num_programs(1) - 1)
    def _end():
        so_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def _chunk_pallas(q, k, v, rates, length, *, scale, chunk, interpret):
    """Under a `jit` of its own: a prefill program's layers trace it once."""
    H, T, d = q.shape
    rows = pl.BlockSpec((None, chunk, d), lambda h, c, n: (h, c, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, scale=scale),
        name="linear_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # length
            grid=(H, T // chunk),
            in_specs=[rows,
                      pl.BlockSpec((None, d, chunk), lambda h, c, n: (h, 0, c)),
                      rows,
                      pl.BlockSpec((None, 8, max(chunk, d)),
                                   lambda h, c, n: (h, 0, 0))],
            out_specs=[rows,
                       pl.BlockSpec((None, d, d), lambda h, c, n: (h, 0, 0))],
            scratch_shapes=[pltpu.VMEM((d, d), F32)]),
        out_shape=[jax.ShapeDtypeStruct((H, T, d), q.dtype),
                   jax.ShapeDtypeStruct((H, d, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(jnp.asarray(length, jnp.int32).reshape(1), q, k.transpose(0, 2, 1), v,
      jnp.broadcast_to(rates.astype(F32)[:, None, None],
                       (H, 8, max(chunk, d))))


def linear_prompt(q, k, v, rates, scale: float, length=None, *,
                  chunk: int = CHUNK, interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """A prompt of one program: q, k, v `[H, T, d]`, rates `[H]` float32,
    `length` a traced scalar (None: T) -> (o `[H, T, d]` in q's dtype, rows
    at and past `length` not meaningful and finite; S `[H, d, d]` float32
    after row `length - 1`). A prompt narrower than a chunk is one chunk."""
    H, T, d = q.shape
    chunk = min(chunk, T)
    if T % chunk:
        raise ValueError(f"a prompt of {T} rows is no whole chunks of {chunk}")
    length = T if length is None else length
    use = interpret or (attention._on_tpu() and kernel_tiles(d, chunk))
    attention._path_counts["linear_pallas" if use else "linear_reference"] += 1
    with jax.named_scope("linear_chunk"):
        if use:
            return _chunk_pallas(q, k, v, rates, length, scale=float(scale),
                                 chunk=chunk, interpret=interpret)
        return _chunked(q, k, v, rates, scale, length, chunk)


def _column(row, diagonal):
    """`[1, d]` laid down the rows, `[d, 1]`, under a mask."""
    return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)


def _step_kernel(layer_ref, slots_ref, q_ref, k_ref, v_ref, lam_ref, s_ref,
                 y_ref, so_ref):
    """Grid (ACTIVE slots, blocks of heads): a grid step holds `_STEP_HEADS`
    heads of one slot: `q_ref` (scaled), `k_ref`, `v_ref` `[heads, d]` the
    step's rows, `lam_ref` `[heads, d]` each head's decay in every lane,
    `s_ref` `[heads, d, d]` the tiles of the layer's state, which the block
    specs read from where they lie and write back there."""
    del layer_ref, slots_ref
    heads, d = q_ref.shape
    diagonal = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    for j in range(heads):
        at = slice(j, j + 1)
        s = lam_ref[at, :] * s_ref[j] \
            + _column(k_ref[at, :], diagonal) * v_ref[at, :]    # [d, d]
        so_ref[j] = s
        y_ref[at, :] = jnp.sum(s * _column(q_ref[at, :], diagonal), axis=0,
                               keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(S, layer, active, q, k, v, lam, *, interpret):
    """Under a `jit` of its own, as `ops/retention.py::_step_pallas` is."""
    ns, H, d = q.shape
    hb = min(_STEP_HEADS, H)
    # The active slots' indices, in order, then zeros; only the first
    # `count` are visited (`ops/ssm.py::_state_step_pallas`).
    at = jnp.arange(ns, dtype=jnp.int32)
    rank = jnp.cumsum(active, dtype=jnp.int32) - 1
    slots = jnp.sum(jnp.where(active & (rank == at[:, None]), at, 0), axis=1)
    vectors = pl.BlockSpec((None, hb, d),
                           lambda i, j, layer, slots: (slots[i], j, 0))
    tile = pl.BlockSpec((None, None, hb, d, d),
                        lambda i, j, layer, slots: (layer[0], slots[i], j, 0,
                                                    0))
    y, S = pl.pallas_call(
        _step_kernel,
        name="linear_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                    # layer, slots
            grid=(rank[-1] + 1, H // hb),
            in_specs=[vectors, vectors, vectors,
                      pl.BlockSpec((hb, d), lambda i, j, layer, slots: (j, 0)),
                      tile],
            out_specs=[vectors, tile]),
        out_shape=[jax.ShapeDtypeStruct((ns, H, d), F32),
                   jax.ShapeDtypeStruct(S.shape, F32)],
        # The state out is the state in: a tile never visited (an idle
        # slot's, another layer's) keeps its bytes.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, q, k, v,
      jnp.broadcast_to(lam[:, None], (H, d)), S)
    return y, S


def linear_state_step(S, layer, active, q, k, v, rates, scale: float, *,
                      interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """A decode step of ONE layer's ACTIVE slots on the slots' whole state
    `S` `[L, ns, H, d, d]` float32 where it lies (the kernel's input AND its
    output; donate it). It is handed the whole array and `layer` (a traced
    scalar), never `S[layer]`: a custom call handed a slice is first handed a
    copy. q, k, v `[ns, H, d]`, rates `[H]`, `active` `[ns]` -> (o `[ns, H,
    d]` float32, zeros for an idle slot; S, an idle slot's and every other
    layer's tiles as they were, to the bit)."""
    ns, H, d = q.shape
    lam = jnp.exp(-rates.astype(F32))
    qs, kf, vf = q.astype(F32) * scale, k.astype(F32), v.astype(F32)
    use = interpret or (attention._on_tpu() and kernel_tiles(d)
                        and H % min(_STEP_HEADS, H) == 0)
    attention._path_counts["linear_pallas" if use else "linear_reference"] += 1
    with jax.named_scope("linear_step"):
        if use:
            y, S = _step_pallas(S, layer, active, qs, kf, vf, lam,
                                interpret=interpret)
        else:
            rows = lam[None, :, None, None] * S[layer] \
                + kf[..., :, None] * vf[..., None, :]
            y = jnp.einsum("nhd,nhde->nhe", qs, rows, precision=HIGHEST)
            S = S.at[layer].set(jnp.where(active[:, None, None, None], rows,
                                          S[layer]))
        return jnp.where(active[:, None, None], y, 0.0), S

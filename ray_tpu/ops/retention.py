"""Power retention of degree 2 (arXiv:2507.04239): attention whose score is
`(q . k / sqrt(d))^2` under a learned scalar gate a kv head, normalised by
the scores' sum. An even power is a plain inner product of EXPANDED vectors,
so the same layer is a linear recurrence over a state of fixed size, whatever
the context. With `g_t <= 0` the log-gate of position t (one a kv head), `c_t`
its running sum, query head h reading kv head `h // G`:

  attention form   a_ij = exp(c_i - c_j) (q_i . k_j)^2 / d          (j <= i)
                   y_i  = sum_j a_ij v_j / (sum_j a_ij + eps)
  recurrent form   S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T / d
                   z_t = e^{g_t} z_{t-1} + phi(k_t) / d
                   y_t = phi(q_t)^T S_t / (phi(q_t) . z_t + eps)
  chunked form     a chunk's own pairs by the attention form, its past by
                   e^{c_t} phi(q_t)^T S_prev (and z_prev), then the chunk's
                   state from the last one's

with `phi(u) . phi(w) = (u . w)^2` exactly. Every `a_ij >= 0`, so the sum
normalises without a softmax and without a running maximum. Running sums,
the state, the numerator and the denominator are float32.

THE EXPANSION'S LAYOUT. The distinct products `u_a u_b` (a <= b) are d (d +
1) / 2 = 8,256 at d = 128, which is 64.5 rows of 128 lanes. `phi(u)` is
`[d / 2 + 1, d]` here, 65 BLOCKS of d lanes (8,320 numbers: 64 lanes, 0.78%,
over the least, and those lanes stay 0):

  block j < d/2, lane l > j    sqrt(2) u_j u_l        (row j of the triangle)
  block j < d/2 - 1, lane l <= j
                               sqrt(2) u_a u_{l + d - 1 - j}, a = d - 2 - j
                               (row a, whose j + 1 entries fill what row j
                               leaves of the block: a ROTATION of u by j + 1)
  block d/2 - 1, lane l <= j   0
  block d/2                    u_l^2                  (the diagonal)

so a block is two scalars of u times u and u rotated: nothing of `phi` is
ever gathered. A kv head's state is `S [d / 2 + 1, d, d]`, block j's `[v,
lane]` = sum_t w_t v_t[v] phi(k_t)[j, lane] (4.26 MB float32 at d = 128,
34.08 MB over 8 kv heads), and `z [d / 2 + 1, d]` (held in `z_rows`: whole
tiles of 8 rows).

On a TPU `phi` exists only in fast memory, inside two kernels:

  * ``retention_prompt``: a prompt of ONE program. Its output rows are the
    attention form (plain XLA, a block of query rows at a time: no `phi`
    there at all), its final state ONE build, `S_j = (w v)^T Phi_j(k)`, by
    the kernel `retention_state`: a kv head a grid step, block j's `Phi_j^T
    [d, W]` made from `k^T` by a row broadcast and a rotation of the rows,
    then one matrix product over the prompt's positions.
  * ``retention_state_step``: one token a slot. The kernel is handed the
    slots' WHOLE state, the layer's index and the active slots; a grid step
    reads one (slot, kv head)'s tile where it lies, decays it, adds `v
    phi(k)^T`, writes it back in place (aliased: an idle slot's and another
    layer's bytes never move) and reduces it against the group's expanded
    queries: a read and a write of the state, the least a step can move.

Elsewhere (and for the tests) the same forms in plain `jnp`, where `phi` is
an array like any other.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6
_SQRT2 = math.sqrt(2.0)
# Query rows the attention form scores at once: `[heads, rows, W]` float32
# is 84 MB at 40 heads and a bucket of 2,048.
_ROWS = 256


def n_blocks(d: int) -> int:
    """Blocks of d lanes a head's expansion takes (the module's layout)."""
    return d // 2 + 1


def z_rows(d: int) -> int:
    """Rows a head's normaliser is held in: its blocks, up to whole tiles
    of 8 rows (72 for 65 at d = 128; the rows past the blocks stay 0). Held
    in 65, the compiler pads the rows to 72 itself or lays the array out
    with its axes swapped, and then copies it whole around every decode
    chunk to hand the kernel its rows (compiled for a v5e, PR 59)."""
    return -(-n_blocks(d) // 8) * 8


def state_shapes(n_layers: int, n_slots: int, kv_heads: int, d: int):
    """(S, z) of a model's retention layers, every slot's."""
    lead = (n_layers, n_slots, kv_heads)
    return lead + (n_blocks(d), d, d), lead + (z_rows(d), d)


def _in_rows(x, rows: int):
    """x `[..., n, d]` with zero rows after its n, up to `rows`."""
    pad = [(0, 0)] * (x.ndim - 2) + [(0, rows - x.shape[-2]), (0, 0)]
    return jnp.pad(x, pad)


def phi(u: jax.Array) -> jax.Array:
    """u `[..., d]` -> `[..., d / 2 + 1, d]` float32, the layout above: `phi(u)
    . phi(w)`, summed over both axes, is `(u . w)^2`."""
    d = u.shape[-1]
    u = u.astype(F32)
    lane = jnp.arange(d)
    blocks = []
    for j in range(d // 2):
        lo = 0.0 if j == d // 2 - 1 else \
            u[..., d - 2 - j:d - 1 - j] * jnp.roll(u, j + 1, axis=-1)
        blocks.append(_SQRT2 * jnp.where(lane > j, u[..., j:j + 1] * u, lo))
    blocks.append(u * u)
    return jnp.stack(blocks, axis=-2)


def _sums(gamma, length=None):
    """gamma `[W, KVH]` -> (c `[KVH, W]` its running sums; w `[KVH, W]` what
    position t's write is worth in the state after row `length - 1` (W if
    None): exp(c_end - c_t), and 0 at and past `length`)."""
    c = jnp.cumsum(gamma.astype(F32), axis=0).T
    W = c.shape[1]
    if length is None:
        return c, jnp.exp(c[:, -1:] - c)
    end = jax.lax.dynamic_slice_in_dim(c, length - 1, 1, axis=1)
    return c, jnp.where(jnp.arange(W)[None] < length, jnp.exp(end - c), 0.0)


def _attention_parts(q, k, v, c, rows: int = _ROWS):
    """The attention form's numerator `[H, W, d]` and denominator `[H, W]`,
    float32, NOT divided: q `[H, W, d]`, k, v `[KVH, W, d]`, c `[KVH, W]`."""
    H, W, d = q.shape
    KVH = k.shape[0]
    R = next(r for r in (rows, 128, 64, 32, 16, 8, 4, 2, 1) if W % r == 0
             and r <= W)
    qg = q.reshape(KVH, H // KVH, W, d)
    vf = v.astype(F32)
    keys = jnp.arange(W)

    def block(i):
        at = i * R + jnp.arange(R)
        qc = jax.lax.dynamic_slice_in_dim(qg, i * R, R, axis=2)
        s = jnp.einsum("kgqd,ksd->kgqs", qc, k, preferred_element_type=F32)
        cq = jax.lax.dynamic_slice_in_dim(c, i * R, R, axis=1)
        seen = (keys[None] <= at[:, None])[None]                # [1, R, W]
        a = jnp.exp(jnp.where(seen, cq[:, :, None] - c[:, None, :],
                              -jnp.inf))[:, None] * (s * s) * (1.0 / d)
        return (jnp.einsum("kgqs,ksd->kgqd", a, vf, precision=HIGHEST),
                jnp.sum(a, axis=-1))

    num, den = jax.lax.map(block, jnp.arange(W // R))
    # [n, KVH, G, R, ...] -> [H, W, ...]
    return (num.transpose(1, 2, 0, 3, 4).reshape(H, W, d),
            den.transpose(1, 2, 0, 3).reshape(H, W))


def retention_attention(q, k, v, gamma) -> jax.Array:
    """The attention form over one sequence: q `[H, W, d]`, k, v `[KVH, W,
    d]`, gamma `[W, KVH]` -> y `[H, W, d]` float32. No state and no `phi`."""
    c, _ = _sums(gamma)
    num, den = _attention_parts(q, k, v, c)
    return num / (den[..., None] + EPS)


def _state_reference(k, v, w):
    """The state after a sequence by `phi` as an array: k, v `[KVH, W, d]`,
    w `[KVH, W]` -> (S `[KVH, NB, d, d]`, z `[KVH, z_rows, d]`)."""
    d = k.shape[-1]
    pk = phi(k.astype(F32) * d ** -0.5)                    # [KVH, W, NB, d]
    wv = v.astype(F32) * w[..., None]
    return (jnp.einsum("ktv,ktjl->kjvl", wv, pk, precision=HIGHEST),
            _in_rows(jnp.einsum("kt,ktjl->kjl", w, pk, precision=HIGHEST),
                     z_rows(d)))


def _read(pq, S, z):
    """Expanded queries `[..., G, NB, d]` against a state `[..., NB, d, d]`
    and `[..., z_rows, d]` -> (numerator `[..., G, d]`, denominator `[...,
    G]`)."""
    return (jnp.einsum("...gjl,...jvl->...gv", pq, S, precision=HIGHEST),
            jnp.einsum("...gjl,...jl->...g", pq, z[..., :pq.shape[-2], :],
                       precision=HIGHEST))


def retention_step(S, z, q, k, v, gamma):
    """The recurrent form, one token a slot: S `[ns, KVH, NB, d, d]`, z `[ns,
    KVH, z_rows, d]` float32, q `[ns, H, d]`, k, v `[ns, KVH, d]`, gamma `[ns,
    KVH]` -> (y `[ns, H, d]` float32, S, z). The reference of
    `retention_state_step`, and the path off a TPU."""
    ns, H, d = q.shape
    KVH = k.shape[1]
    decay = jnp.exp(gamma.astype(F32))
    pk = phi(k.astype(F32) * d ** -0.5)                     # [ns, KVH, NB, d]
    S = decay[..., None, None, None] * S \
        + v.astype(F32)[:, :, None, :, None] * pk[:, :, :, None, :]
    z = decay[..., None, None] * z + _in_rows(pk, z.shape[-2])
    num, den = _read(phi(q.reshape(ns, KVH, H // KVH, d)), S, z)
    return (num / (den[..., None] + EPS)).reshape(ns, H, d), S, z


def retention_chunked(q, k, v, gamma, chunk: int):
    """The chunked form over one sequence (layouts as `retention_attention`;
    `chunk` divides W) -> (y `[H, W, d]`, the final S, z): a chunk's own
    pairs by the attention form, its past through the state carried between
    chunks. What a prompt longer than one program, or carried across
    programs, would run; here it is the tests' (no program uses it yet)."""
    H, W, d = q.shape
    KVH = k.shape[0]
    S, z = (jnp.zeros(shape[2:], F32)
            for shape in state_shapes(1, 1, KVH, d))
    ys = []
    for lo in range(0, W, chunk):
        rows = slice(lo, lo + chunk)
        qc, kc, vc = q[:, rows], k[:, rows], v[:, rows]
        c, w = _sums(gamma[rows])
        num, den = _attention_parts(qc, kc, vc, c)
        pq = phi(qc.reshape(KVH, H // KVH, chunk, d))       # [KVH, G, Q, ..]
        past_n, past_d = _read(pq.transpose(0, 2, 1, 3, 4), S[:, None],
                               z[:, None])                  # [KVH, Q, G, ..]
        grow = jnp.exp(c)[:, :, None]                       # [KVH, Q, 1]
        num = num + (grow[..., None] * past_n).transpose(0, 2, 1, 3) \
            .reshape(H, chunk, d)
        den = den + (grow * past_d).transpose(0, 2, 1).reshape(H, chunk)
        ys.append(num / (den[..., None] + EPS))
        own_S, own_z = _state_reference(kc, vc, w)
        last = jnp.exp(c[:, -1])
        S = last[:, None, None, None] * S + own_S
        z = last[:, None, None] * z + own_z
    return jnp.concatenate(ys, axis=1), S, z


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def kernel_tiles(d: int) -> bool:
    """Whether the two kernels' tiling takes a head of d: whole lanes."""
    return d % 128 == 0


def _roll(x, shift: int, axis: int, interpret: bool):
    return jnp.roll(x, shift, axis) if interpret \
        else pltpu.roll(x, shift, axis)


def _state_kernel(kt_ref, vw_ref, s_ref, z_ref, *, interpret: bool):
    """One kv head: `kt_ref` `[d, W]` its keys transposed (over sqrt(d)),
    `vw_ref` `[d + 8, W]` the weighted values transposed, then the weights
    themselves in 8 equal rows -> `s_ref` `[NB, d, d]`, `z_ref` `[z_rows,
    d]`.
    Block j's expansion transposed, `[d, W]`, is a row of `kt` broadcast
    down its rows and `kt` with its rows rotated: lanes are positions."""
    kt, vw = kt_ref[...], vw_ref[...]
    d = kt.shape[0]
    half = d // 2
    row = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)
    z_ref[...] = jnp.zeros_like(z_ref)
    for j in range(half + 1):
        if j == half:
            p = kt * kt
        else:
            lo = 0.0 if j == half - 1 else \
                _roll(kt, j + 1, 0, interpret) * kt[d - 2 - j:d - 1 - j, :]
            p = _SQRT2 * jnp.where(row > j, kt * kt[j:j + 1, :], lo)
        r = jax.lax.dot_general(vw, p, (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=F32)     # [d + 8, d]
        s_ref[j] = r[:d]
        z_ref[j:j + 1, :] = r[d:d + 1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_pallas(k, v, w, *, interpret):
    """Under a `jit` of its own: a prefill program's layers trace it once."""
    KVH, W, d = k.shape
    NB = n_blocks(d)
    pad = -W % 128                      # positions are lanes: whole tiles
    kt = (k.astype(F32) * d ** -0.5).transpose(0, 2, 1)
    vw = jnp.concatenate(
        [(v.astype(F32) * w[..., None]).transpose(0, 2, 1),
         jnp.broadcast_to(w[:, None, :], (KVH, 8, W))], axis=1)
    if pad:                             # a position of weight 0 writes nothing
        kt, vw = (jnp.pad(t, ((0, 0), (0, 0), (0, pad))) for t in (kt, vw))
    return pl.pallas_call(
        functools.partial(_state_kernel, interpret=interpret),
        name="retention_state",
        grid=(KVH,),
        in_specs=[pl.BlockSpec((None, d, W + pad), lambda h: (h, 0, 0)),
                  pl.BlockSpec((None, d + 8, W + pad), lambda h: (h, 0, 0))],
        out_specs=[pl.BlockSpec((None, NB, d, d), lambda h: (h, 0, 0, 0)),
                   pl.BlockSpec((None, z_rows(d), d), lambda h: (h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((KVH, NB, d, d), F32),
                   jax.ShapeDtypeStruct((KVH, z_rows(d), d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(kt, vw)


def retention_prompt(q, k, v, gamma, length=None, *, interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A prompt of one program: q `[H, W, d]`, k, v `[KVH, W, d]`, gamma `[W,
    KVH]` float32, `length` a traced scalar (None: W) -> (y `[H, W, d]`
    float32 by the attention form, rows at and past `length` not meaningful
    and finite; S `[KVH, NB, d, d]`, z `[KVH, z_rows, d]` float32 after row
    `length - 1`, by ONE build: on a TPU, or with `interpret`, the kernel
    `retention_state`, elsewhere `phi` as an array). The path taken is
    counted in `attention.attention_path_counts()` as `retention_state_pallas`
    / `retention_state_reference`."""
    c, w = _sums(gamma, length)
    num, den = _attention_parts(q, k, v, c)
    use = interpret or (attention._on_tpu() and kernel_tiles(q.shape[-1]))
    attention._path_counts[
        "retention_state_pallas" if use else "retention_state_reference"] += 1
    S, z = _state_pallas(k, v, w, interpret=interpret) if use \
        else _state_reference(k, v, w)
    return num / (den[..., None] + EPS), S, z


def _column(row, diagonal):
    """`[1, d]` laid down the rows, `[d, 1]`, under a mask."""
    return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)


def _phi_block(u, j: int, lane, interpret: bool):
    """Block j of `phi` of each row of u `[rows, d]` -> `[rows, d]`."""
    d = u.shape[-1]
    half = d // 2
    if j == half:
        return u * u

    def entry(i):   # u's lane i, a column
        return jnp.sum(jnp.where(lane == i, u, 0.0), axis=1, keepdims=True)

    lo = 0.0 if j == half - 1 else \
        entry(d - 2 - j) * _roll(u, j + 1, 1, interpret)
    return _SQRT2 * jnp.where(lane > j, entry(j) * u, lo)


def _step_kernel(layer_ref, slots_ref, q_ref, kvg_ref, s_ref, z_ref, y_ref,
                 so_ref, zo_ref, acc_ref, *, groups: int, interpret: bool):
    """Grid (kv heads, ACTIVE slots), slots innermost: a grid step holds one
    slot's one kv head: `q_ref` `[8, d]` the group's query heads (rows past
    `groups` zeros), `kvg_ref` `[8, d]` rows k over sqrt(d), v, the decay in
    every lane; `s_ref` `[NB, d, d]` and `z_ref` `[z_rows, d]` the tile of the
    layer's state, which the block specs read from where it lies and write
    back there. `acc_ref` `[groups, d, d]`: a head's products before their
    lanes are summed."""
    del layer_ref, slots_ref
    q = q_ref[...]
    d = q.shape[-1]
    kvg = kvg_ref[...]
    k8 = jnp.broadcast_to(kvg[0:1], (8, d))
    decay = kvg[2:3]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    diagonal = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    vcol = _column(kvg[1:2], diagonal)                          # [d, 1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    zo_ref[...] = z_ref[...]            # (the rows past the blocks: as is)
    den = jnp.zeros((8, d), F32)
    for j in range(s_ref.shape[0]):
        pk = _phi_block(k8, j, lane, interpret)[0:1]            # [1, d]
        pq = _phi_block(q, j, lane, interpret)                  # [8, d]
        s = decay * s_ref[j] + vcol * pk                        # [d, d]
        so_ref[j] = s
        zj = decay * z_ref[j:j + 1, :] + pk
        zo_ref[j:j + 1, :] = zj
        den = den + pq * zj
        for h in range(groups):
            acc_ref[h] += s * pq[h:h + 1]
    y_ref[...] = jnp.zeros_like(y_ref)
    for h in range(groups):
        num = jnp.sum(acc_ref[h], axis=1, keepdims=True)        # [d(v), 1]
        num = jnp.sum(jnp.where(diagonal, num, 0.0), axis=0, keepdims=True)
        y_ref[h:h + 1, :] = num / (
            jnp.sum(den[h:h + 1], axis=1, keepdims=True) + EPS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(S, z, layer, active, q, k, v, gamma, *, interpret):
    """Under a `jit` of its own, as `ops/ssm.py::_state_step_pallas` is."""
    ns, H, d = q.shape
    KVH, NB = S.shape[2], S.shape[3]
    G = H // KVH
    # The active slots' indices, in order, then zeros; only the first
    # `count` are visited (`ops/ssm.py::_state_step_pallas`).
    at = jnp.arange(ns, dtype=jnp.int32)
    rank = jnp.cumsum(active, dtype=jnp.int32) - 1
    slots = jnp.sum(jnp.where(active & (rank == at[:, None]), at, 0), axis=1)
    q8 = jnp.pad(q.astype(F32).reshape(ns, KVH, G, d),
                 ((0, 0), (0, 0), (0, 8 - G), (0, 0)))
    decay = jnp.broadcast_to(jnp.exp(gamma.astype(F32))[..., None],
                             (ns, KVH, d))
    kvg = jnp.pad(jnp.stack([k.astype(F32) * d ** -0.5, v.astype(F32),
                             decay], axis=2),
                  ((0, 0), (0, 0), (0, 5), (0, 0)))             # [ns,KVH,8,d]
    vectors = pl.BlockSpec((None, None, 8, d),
                           lambda h, i, layer, slots: (slots[i], h, 0, 0))
    tile = pl.BlockSpec(
        (None, None, None, NB, d, d),
        lambda h, i, layer, slots: (layer[0], slots[i], h, 0, 0, 0))
    norm = pl.BlockSpec(
        (None, None, None, z.shape[3], d),
        lambda h, i, layer, slots: (layer[0], slots[i], h, 0, 0))
    y, S, z = pl.pallas_call(
        functools.partial(_step_kernel, groups=G, interpret=interpret),
        name="retention_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                    # layer, slots
            grid=(KVH, rank[-1] + 1),
            in_specs=[vectors, vectors, tile, norm],
            out_specs=[vectors, tile, norm],
            scratch_shapes=[pltpu.VMEM((G, d, d), F32)]),
        out_shape=[jax.ShapeDtypeStruct((ns, KVH, 8, d), F32),
                   jax.ShapeDtypeStruct(S.shape, F32),
                   jax.ShapeDtypeStruct(z.shape, F32)],
        # The state out is the state in: a tile never visited (an idle
        # slot's, another layer's) keeps its bytes.
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, q8, kvg, S, z)
    return y[:, :, :G].reshape(ns, H, d), S, z


def retention_state_step(S, z, layer, active, q, k, v, gamma, *,
                         interpret: bool = False):
    """`retention_step` of ONE layer's ACTIVE slots on the slots' whole state
    `S` `[L, ns, KVH, NB, d, d]`, `z` `[L, ns, KVH, z_rows, d]` float32 where it
    lies, each tile crossed once (both are the kernel's inputs AND its
    outputs; donate them). It is handed the whole arrays and `layer` (a
    traced scalar), never `S[layer]`: a custom call handed a slice is first
    handed a copy. q `[ns, H, d]`, k, v `[ns, KVH, d]`, gamma `[ns, KVH]`,
    `active` `[ns]` -> (y `[ns, H, d]` float32, zeros for an idle slot; S, z,
    an idle slot's and every other layer's tiles as they were, to the bit).
    Needs `kernel_tiles(d)` on a TPU."""
    y, S, z = _step_pallas(S, z, layer, active, q, k, v, gamma,
                           interpret=interpret)
    return jnp.where(active[:, None, None], y, 0.0), S, z

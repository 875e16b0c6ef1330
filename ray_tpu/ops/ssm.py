"""State-space ops: the causal depthwise convolution with a carried window,
and the two recurrences, each over a sequence and as the one-token state
update of a decode step: Mamba-1's (`selective_scan`, `ssm_step`: a decay of
its own for every channel and state) and Mamba-2's (`ssd_scan`, `ssd_step`:
ONE scalar decay a head of channels, at the end of this text).

Layouts, chosen so that nothing is padded on a TPU (the channel axis `Di` is
always the minor one; a state axis of 16 as the minor one would be padded to
128 lanes, eight times its size):

  x, dt, z, y   [S, Di]        a sequence's rows ([ns, Di]: one token a slot)
  B, C          [S, N]         the input and output maps of every row
  A             [N, Di]        negative reals (`-exp(A_log)`)
  D             [Di]
  state         [N, Di]        float32, always ([ns, N, Di] for a decode step)
  conv weights  [K, Di], bias [Di]; the window is the last K - 1 inputs,
                [K - 1, Di]

The recurrence, in float32 whatever the inputs' dtype:

  s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * x_t) (x) B_t
  y_t = s_t . C_t + D * x_t            (times silu(z_t), where z is given)

`selective_scan` is a Pallas kernel on a TPU (named `selective_scan`: one
grid step holds a block of channels' state `[N, block]` in fast memory and
walks a block of the sequence 16 rows at a time; nothing of size S x Di x N
is ever written) and a `lax.scan` elsewhere, which is the reference path and
is differentiable. The path taken is counted at trace time in
`attention.attention_path_counts()` as `scan_pallas` / `scan_reference`.

Mamba-2 is the same recurrence with `A[n, c] = a[head(c)]` and `dt[t, c] =
dt[t, head(c)]`, heads of `P = Di / H` channels next to each other:

  dt  [S, H] float32   A, D  [H]   x, y [S, Di]   B, C [S, N]   state [N, Di]

so `selective_scan` fed the broadcast `A` and `dt` computes it too (the tests'
cross-check), at `S x Di x N` vector operations. With G GROUPS of B and C
(`[S, G, N]`; `[ns, G, N]` a decode step) channel c reads group `c // (Di /
G)`, its head's: G Mamba-2 mixers of H / G heads side by side, which share
nothing but the layout. One group may come without the axis, `[S, N]`, and
is then computed by the text it always was. `ssd_scan` is the chunked
dual form, plain XLA: within a chunk of Q rows the outputs are matrix
products on the matrix unit, and the state moves once a chunk (counted as
`ssd_chunked`).

A decode step's update is bound by the state's bytes (`[ns, N, Di]` float32
a layer: 268 MB at 64 slots x 128 x 8,192), so what counts is how often it
crosses them. `ssd_step`, plain XLA on a layer's rows, is the reference and
the path off a TPU; compiled for one it crosses them three times (the update
in place reads and writes; `y = sum_n s * C` is a fusion of its own that
reads them again and recomputes the update). `ssd_state_step` is a Pallas
kernel (named `ssd_state_step`) handed the slots' WHOLE state `[L, ns, N,
Di]` and the layer's index, which visits each ACTIVE slot's state ONCE: a
grid step reads a `[N, block]` tile from where it lies, updates it, reduces
it to y and writes it back in its place (the state is the kernel's input and
its output). `ops/slot_state.py::step_layer` chooses between the two and
counts the path (`ssd_step_pallas` / `ssd_step_reference`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The module, not its names: the path counters are one per process, and
# tests steer `_on_tpu` by patching it there.
from ray_tpu.ops import attention

F32 = jnp.float32

# Rows of a chunk of the dual form (`ssd_scan`): the published
# `mamba_chunk_size`. What a chunk makes is `[H, Q, Q]` float32, 33 MB at 128
# heads.
_SSD_CHUNK = 256

# Rows the kernel walks between two loads: a packed bfloat16 tile's.
_CHUNK = 16
# Channels a grid step holds (its state is `[N, block]` float32) and rows of
# the sequence it is handed at a time. On a v5e, 4,096 rows of 5,120 channels
# and 16 states take 1.15 ms at 1,024 x 512, 1.22 at 512 x 512, 1.53 at
# 256 x 512 (my chip run, PR 35); 1,024 x 512 is 10 of the 16 MiB of fast
# memory a kernel may use.
_BLOCK_CHANNELS = 1024
_BLOCK_ROWS = 512
# Channels of one slot's state a grid step of `ssd_state_step` holds, `[N,
# block]` float32 (512 KB at 128 states; in and out, two buffers each, 2 MB
# of the 16 MiB a kernel may use, beside 1.5 MB of the slots' vectors and
# y). On a v5e, nine layers of 64 slots x 128 states x 8,192 channels, 4.83
# GB in and out, take 7.36 ms at 1,024 (80.1% of HBM's rate), 7.41 at 2,048,
# 7.48 at 4,096 and 8.64 at 512 (my chip run, PR 50).
_STEP_BLOCK_CHANNELS = 1024


def causal_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array],
                window: Optional[jax.Array] = None,
                length=None) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over x `[S, Di]`:
    y_t = b + sum_k w[k] * x_{t-K+1+k} (`b` None: no bias, a gated short
    convolution's), the K - 1 inputs before the first row
    taken from `window` `[K - 1, Di]` (zeros if None: a prompt's start).
    -> (y `[S, Di]` float32, the window to carry on: the last K - 1 inputs
    before row `length` (S if None), so a bucket's padding past `length`
    never enters it)."""
    S, Di = x.shape
    K = w.shape[0]
    if window is None:
        window = jnp.zeros((K - 1, Di), x.dtype)
    xp = jnp.concatenate([window.astype(x.dtype), x], axis=0)   # [S+K-1, Di]
    # (the bias is converted BEFORE the taps: the order of the hybrid stack's
    # pinned programs, tests/test_dots.py)
    bias = None if b is None else b.astype(F32)
    y = sum(w[k].astype(F32) * xp[k:k + S].astype(F32) for k in range(K))
    if bias is not None:
        y = bias + y
    start = S if length is None else length
    carried = jax.lax.dynamic_slice_in_dim(xp, start, K - 1, axis=0)
    return y, carried


def ssm_step(x, dt, A, B, C, D, state):
    """One token a slot: x, dt `[ns, Di]`, B, C `[ns, N]`, state `[ns, N,
    Di]` float32 -> (y `[ns, Di]` float32, the new state)."""
    dt = dt.astype(F32)
    x = x.astype(F32)
    decay = jnp.exp(dt[:, None, :] * A.astype(F32)[None])
    state = decay * state + (dt * x)[:, None, :] * B.astype(F32)[:, :, None]
    y = jnp.sum(state * C.astype(F32)[:, :, None], axis=1)
    return y + D.astype(F32) * x, state


def _scan_reference(x, dt, A, B, C, D, state0, z):
    """The recurrence a row at a time: `lax.scan` over `ssm_step`, float32."""
    def row(s, xs):
        y, s = ssm_step(*(a[None] for a in xs[:2]), A,
                        *(a[None] for a in xs[2:]), D, s[None])
        return s[0], y[0]

    state, y = jax.lax.scan(row, state0, (x, dt, B, C))
    if z is not None:
        y = y * jax.nn.silu(z.astype(F32))
    return y.astype(x.dtype), state


def _scan_kernel(len_ref, x_ref, dt_ref, *rest, gated: bool):
    """Grid (blocks of channels, blocks of rows), rows innermost and in
    order. The state's output block is the same for every block of rows, so
    it stays in fast memory from the first to the last and is the carry."""
    if gated:
        z_ref, *rest = rest
    b_ref, c_ref, a_ref, d_ref, s0_ref, y_ref, s_ref, ybuf = rest
    ts = x_ref.shape[0]
    j = pl.program_id(1)
    length = len_ref[0]

    @pl.when(j == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    def chunk(c, carry):
        t0 = pl.multiple_of(c * _CHUNK, _CHUNK)
        rows = pl.ds(t0, _CHUNK)
        base = j * ts + t0

        @pl.when(base < length)
        def _live():
            xc = x_ref[rows, :].astype(F32)                    # [T, bd]
            # A row at or past `length` moves nothing: exp(0 * A) = 1 and
            # 0 * x (x) B = 0.
            row = base + jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, 1), 0)
            dc = jnp.where(row < length, dt_ref[rows, :], 0.0)
            dtx = dc * xc
            a = a_ref[...]                                     # [N, bd]
            bt = b_ref[c]                                      # [N, T]
            ct = c_ref[c]
            s = s_ref[...]
            for i in range(_CHUNK):
                s = jnp.exp(dc[i:i + 1, :] * a) * s \
                    + dtx[i:i + 1, :] * bt[:, i:i + 1]
                ybuf[i:i + 1, :] = jnp.sum(s * ct[:, i:i + 1], axis=0,
                                           keepdims=True)
            s_ref[...] = s
            y = ybuf[...] + d_ref[...] * xc
            if gated:
                zc = z_ref[rows, :].astype(F32)
                y = y * (zc * jax.nn.sigmoid(zc))
            y_ref[rows, :] = y.astype(y_ref.dtype)

        @pl.when(base >= length)
        def _dead():
            # Finite, whatever the buffer held: a later layer's K and V of
            # these rows are written into pages that attention reads masked.
            y_ref[rows, :] = jnp.zeros((_CHUNK, y_ref.shape[1]), y_ref.dtype)

        return carry

    jax.lax.fori_loop(0, ts // _CHUNK, chunk, 0)


def _scan_pallas(x, dt, A, B, C, D, state0, length, z, *, interpret,
                 block_channels, block_rows):
    S, Di = x.shape
    N = A.shape[0]
    bd = next(b for b in (block_channels, 512, 256, 128) if Di % b == 0)
    ts = min(block_rows, S)
    while S % ts:
        ts //= 2
    # B and C by chunk of rows, transposed: `[S / T, N, T]`, so that the
    # kernel reads a chunk's `[N, T]` by its index and a row's column from it.
    by_chunk = lambda m: m.astype(F32).reshape(
        S // _CHUNK, _CHUNK, N).transpose(0, 2, 1)
    rows = lambda i, j, *_: (j, i)
    channels = lambda i, j, *_: (0, i)
    seq = pl.BlockSpec((ts, bd), rows)
    maps = pl.BlockSpec((ts // _CHUNK, N, _CHUNK), lambda i, j, *_: (j, 0, 0))
    state = pl.BlockSpec((N, bd), channels)
    gated = z is not None
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, gated=gated),
        name="selective_scan",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                    # length
            grid=(Di // bd, S // ts),
            in_specs=[seq] * (3 if gated else 2)
            + [maps, maps, state, pl.BlockSpec((1, bd), channels), state],
            out_specs=[seq, state],
            scratch_shapes=[pltpu.VMEM((_CHUNK, bd), F32)]),
        out_shape=[jax.ShapeDtypeStruct((S, Di), x.dtype),
                   jax.ShapeDtypeStruct((N, Di), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(length, jnp.int32).reshape(1), x, dt,
      *((z,) if gated else ()), by_chunk(B), by_chunk(C), A.astype(F32),
      D.astype(F32).reshape(1, Di), state0)
    return y, s


def selective_scan(x, dt, A, B, C, D, state0=None, length=None, *, z=None,
                   interpret: bool = False,
                   block_channels: int = _BLOCK_CHANNELS,
                   block_rows: int = _BLOCK_ROWS):
    """The selective scan over x `[S, Di]` (layouts and the recurrence at the
    top) from `state0` (zeros if None) -> (y `[S, Di]` in x's dtype, the
    state `[N, Di]` float32 after the last row). `dt` is float32. With `z`,
    y is gated: y * silu(z). With `length` (a traced scalar), rows at and
    past it leave the state as it was (their dt is taken as 0), so the state
    returned is the one after row `length - 1` whatever S is; y there is not
    meaningful (and finite).

    On a TPU (or with `interpret`, for tests on the CPU) the Pallas kernel,
    where the shapes allow it (Di a multiple of 128, S of 16); elsewhere the
    `lax.scan` reference, which is differentiable."""
    S, Di = x.shape
    N = A.shape[0]
    dt = dt.astype(F32)
    if state0 is None:
        state0 = jnp.zeros((N, Di), F32)
    use = (interpret or attention._on_tpu()) and Di % 128 == 0 \
        and S % _CHUNK == 0
    attention._path_counts["scan_pallas" if use else "scan_reference"] += 1
    if use:
        return _scan_pallas(
            x, dt, A, B, C, D, state0, S if length is None else length, z,
            interpret=interpret, block_channels=block_channels,
            block_rows=block_rows)
    if length is not None:
        dt = jnp.where(jnp.arange(S)[:, None] < length, dt, 0.0)
    return _scan_reference(x, dt, A, B, C, D, state0, z)


def _by_head(v, channels: int):
    """A head's scalar `[..., H]` at each of its channels `[..., Di]`."""
    return jnp.repeat(v, channels // v.shape[-1], axis=-1)


def _step_vectors(x, dt, A):
    """What a step's update takes of a slot beside B and C, float32 `[ns,
    Di]` each: x, the decay (`ns x H` exponentials, each broadcast over its
    head's channels) and `dt * x`."""
    Di = x.shape[-1]
    dt = dt.astype(F32)
    x = x.astype(F32)
    return x, _by_head(jnp.exp(dt * A.astype(F32)), Di), _by_head(dt, Di) * x


def _one_group(m):
    """B or C as given, `[rows, N]` or `[rows, G, N]`: without the group
    axis where there is one group."""
    return m[:, 0] if m.ndim == 3 and m.shape[1] == 1 else m


def groups_of(m) -> int:
    """The groups of a B or C as given: 1 without the group axis."""
    return m.shape[1] if m.ndim == 3 else 1


def _by_group(m, channels: int):
    """A slot's B or C `[ns, N]` (one group) or `[ns, G, N]` against the
    state's `[ns, N, Di]`: `[ns, N, 1]`, or a group's row at each of its
    channels, `[ns, N, Di]`."""
    if m.ndim == 2:
        return m[:, :, None]
    return jnp.repeat(jnp.swapaxes(m, 1, 2), channels // m.shape[1], axis=-1)


def ssd_step(x, dt, A, B, C, D, state):
    """Mamba-2, one token a slot: x `[ns, Di]`, dt `[ns, H]` float32, A, D
    `[H]`, B, C `[ns, N]` or `[ns, G, N]`, state `[ns, N, Di]` float32 -> (y
    `[ns, Di]` float32, the new state). The decay is broadcast over the N
    states too. The reference of `ssd_state_step`, and the path off a TPU."""
    x, decay, dtx = _step_vectors(x, dt, A)
    Di = x.shape[-1]
    B, C = _one_group(B.astype(F32)), _one_group(C.astype(F32))
    state = decay[:, None, :] * state + dtx[:, None, :] * _by_group(B, Di)
    y = jnp.sum(state * _by_group(C, Di), axis=1)
    return y + _by_head(D.astype(F32), Di) * x, state


def _state_step_kernel(layer_ref, slots_ref, decay_ref, dtx_ref, b_ref, c_ref,
                       s_ref, y_ref, o_ref):
    """Grid (blocks of channels, ACTIVE slots), slots innermost: a grid step
    holds one slot's `[N, block]` tile of the layer's state, which the block
    specs read from where it lies and write back there. The slots' vectors
    of the block of channels (`[ns, block]`) and y stay in fast memory while
    the slots go by, and so do B and C (`[ns, N]`) of the ONE group the block
    of channels lies in (a block never crosses a group's edge): a slot's row
    of each is read by its index."""
    del layer_ref
    row = pl.ds(slots_ref[pl.program_id(1)], 1)
    N = s_ref.shape[0]
    diagonal = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)

    # The slot's row `[1, N]` laid along the state's rows, `[N, 1]`, under a
    # mask (16 vector registers at 128 states; a tile is 128). B and C handed
    # over transposed would do without it, and XLA then lays their producer's
    # input, the decode program's window, with the slots as the minor axis:
    # 58 MB of copies around every chunk (compiled for a v5e, PR 50).
    def column(ref):
        return jnp.sum(jnp.where(diagonal, ref[row, :], 0.0), axis=1,
                       keepdims=True)

    s = decay_ref[row, :] * s_ref[...] + dtx_ref[row, :] * column(b_ref)
    o_ref[...] = s
    y_ref[row, :] = jnp.sum(s * column(c_ref), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def _state_step_pallas(ssm, layer, active, decay, dtx, B, C, *, bd,
                       interpret):
    """Under a `jit` of its own, as `ops/moe.py::_grouped_pallas` is: a
    decode program's segments of state-space layers trace and lower it
    once. B, C `[ns, G N]`, group g's in columns g N..: block j of `bd`
    channels, which divides a group's, reads the columns of the group it
    lies in."""
    _, ns, N, Di = ssm.shape
    width = Di // (B.shape[1] // N)     # a group's channels
    # The active slots' indices, in order, then zeros; only the first
    # `count` are visited. (A compare of every slot with every rank: 4,096
    # pairs at 64 slots, where a sort would be a program of its own.)
    at = jnp.arange(ns, dtype=jnp.int32)
    rank = jnp.cumsum(active, dtype=jnp.int32) - 1
    slots = jnp.sum(jnp.where(active & (rank == at[:, None]), at, 0), axis=1)
    vectors = pl.BlockSpec((ns, bd), lambda j, i, *_: (0, j))
    maps = pl.BlockSpec((ns, N), (lambda j, i, *_: (0, 0)) if width == Di
                        else (lambda j, i, *_: (0, j * bd // width)))
    tile = pl.BlockSpec((None, None, N, bd),
                        lambda j, i, layer, slots: (layer[0], slots[i], 0, j))
    return pl.pallas_call(
        _state_step_kernel,
        name="ssd_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                    # layer, slots
            grid=(Di // bd, rank[-1] + 1),
            in_specs=[vectors, vectors, maps, maps, tile],
            out_specs=[vectors, tile]),
        out_shape=[jax.ShapeDtypeStruct((ns, Di), F32),
                   jax.ShapeDtypeStruct(ssm.shape, F32)],
        # The state out is the state in: a tile never visited (an idle
        # slot's, another layer's) keeps its bytes.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, decay, dtx, B, C, ssm)


def state_step_tiles(ssm_shape, groups: int = 1) -> bool:
    """Whether `ssd_state_step`'s tiling takes a state `[L, ns, N, Di]`:
    whole float32 tiles of 8 x 128 a slot and, with `groups` > 1 of B and C,
    a group's states and its channels in whole lanes (a block of channels is
    cut at the groups' edges)."""
    N, Di = ssm_shape[2:]
    return N % 8 == 0 and Di % 128 == 0 and (
        groups == 1 or (N % 128 == 0 and Di % (128 * groups) == 0))


def ssd_state_step(ssm, layer, active, x, dt, A, B, C, D, *,
                   interpret: bool = False,
                   block_channels: int = _STEP_BLOCK_CHANNELS):
    """`ssd_step` of ONE layer's ACTIVE slots on the slots' whole state `ssm`
    `[L, ns, N, Di]` float32 where it lies, each slot's state crossed once:
    the Pallas kernel `ssd_state_step` reads a tile, updates it, reduces it
    to y and writes it back in its place (`ssm` is the kernel's input AND its
    output; donate it). It is handed the whole array and `layer` (a traced
    scalar), never `ssm[layer]`: a custom call handed a slice is first
    handed a copy. x `[ns, Di]`, dt `[ns, H]`, A, D `[H]`, B, C `[ns, N]` or
    `[ns, G, N]` (G groups: a block of channels is a group's or part of
    one's, 512 of 4,096 channels in 8 groups where one group takes 1,024),
    `active` `[ns]` -> (y `[ns, Di]` float32, zeros for an idle slot; the
    state, an idle slot's and every other layer's rows as they were, to the
    bit). The decay and `dt * x` are made here, a row a slot, by `ssd_step`'s
    own ops, and `D * x` is added here; the arithmetic on the state is
    `ssd_step`'s, in its order, in float32. Needs `state_step_tiles`."""
    ns, Di = x.shape
    x, decay, dtx = _step_vectors(x, dt, A)
    width = Di // groups_of(B)      # a group's channels
    bd = next(b for b in (block_channels, 512, 256, 128) if width % b == 0)
    y, ssm = _state_step_pallas(ssm, layer, active, decay, dtx,
                                B.astype(F32).reshape(ns, -1),
                                C.astype(F32).reshape(ns, -1), bd=bd,
                                interpret=interpret)
    y = y + _by_head(D.astype(F32), Di) * x
    return jnp.where(active[:, None], y, 0.0), ssm


def ssd_scan(x, dt, A, B, C, D, state0=None, length=None, *,
             chunk: int = _SSD_CHUNK):
    """Mamba-2 over x `[S, Di]` (layouts at the top) from `state0` (zeros if
    None) -> (y `[S, Di]` in x's dtype, NOT gated: the family's gate sits
    before a norm, the mixer's; the state `[N, Di]` float32 after the last
    row). With `length` (a traced scalar) rows at and past it take dt = 0
    (decay 1, no input), as `selective_scan`'s do, so the state returned is
    the one after row `length - 1`; y there is not meaningful (and finite).
    B, C `[S, N]`, or `[S, G, N]`: G groups, each the B and C of its H / G
    heads and of nothing else, so the G groups are G scans side by side
    (`jax.vmap` of one group's, whose matrix products then carry a batch axis
    of G).

    The chunked dual form, chunks of `chunk` rows in order under one loop
    that carries the state. `chunk` is no option of a model: the mixer never
    passes it, and it is here for the tests of a chunk's edges, which need
    several chunks at tiny widths. Within a chunk, with c_t the running sum
    of dt_t A a head (float32; every exponent below is <= 0):

      G = C B^T                         [Q, Q], once for all a group's heads
      y = (G * L^h) (dt x)^h            L^h[t, s] = exp(c_t - c_s), s <= t
        + exp(c_t) * (C S_prev) + D x
      S_next = exp(c_Q) S_prev + B^T (exp(c_Q - c_s) dt x)

    Decays, running sums and the state are float32; every matrix product
    accumulates in float32, its operands in x's dtype (bfloat16 where the
    model computes in it: B, C, `dt x`, `G * L`, the decayed `dt x` and, for
    `C S_prev` alone, the carried state's rounded COPY; the state carried on
    is never rounded). Nothing of size `S x Di x N` is made, and nothing
    larger than `[H, Q, Q]` float32 a chunk."""
    S, Di = x.shape
    N = B.shape[-1]
    attention._path_counts["ssd_chunked"] += 1
    if state0 is None:
        state0 = jnp.zeros((N, Di), F32)
    dt = dt.astype(F32)
    if length is not None:
        dt = jnp.where(jnp.arange(S)[:, None] < length, dt, 0.0)
    B, C = _one_group(B), _one_group(C)
    if B.ndim == 2:
        return _ssd_chunks(x, dt, A, B, C, D, state0, chunk)
    G = B.shape[1]
    y, state = jax.vmap(
        functools.partial(_ssd_chunks, chunk=chunk),
        in_axes=(1, 1, 0, 1, 1, 0, 1), out_axes=1)(
        x.reshape(S, G, -1), dt.reshape(S, G, -1), A.reshape(G, -1), B, C,
        D.reshape(G, -1), state0.reshape(N, G, -1))
    return y.reshape(S, Di), state.reshape(N, Di)


def _ssd_chunks(x, dt, A, B, C, D, state0, chunk):
    """`ssd_scan` of ONE group: x `[S, Di]`, dt `[S, H]` float32 (dead rows'
    0), B, C `[S, N]`, state0 `[N, Di]`."""
    S, Di = x.shape
    H, N = dt.shape[-1], B.shape[-1]
    P = Di // H
    dtype = x.dtype
    Q = min(chunk, S)
    pad = -S % Q
    if pad:     # rows that move nothing: dt = 0
        x, dt, B, C = (jnp.pad(a, ((0, pad), (0, 0))) for a in (x, dt, B, C))
    n = (S + pad) // Q
    a, d = A.astype(F32), D.astype(F32)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(state, xs):
        xc, dc, bc, cc = xs                     # [Q, Di], [Q, H], [Q, N] x 2
        c = jnp.cumsum(dc * a, axis=0)                          # [Q, H]
        xf = xc.astype(F32).reshape(Q, H, P)
        dtx = dc[:, :, None] * xf                               # [Q, H, P]
        g = jnp.einsum("tn,sn->ts", cc, bc, preferred_element_type=F32)
        ct = c.T                                                # [H, Q]
        seg = jnp.where(causal, ct[:, :, None] - ct[:, None, :], -jnp.inf)
        m = (g * jnp.exp(seg)).astype(dtype)                    # [H, Q, Q]
        y = jnp.einsum("hts,shp->thp", m, dtx.astype(dtype),
                       preferred_element_type=F32)
        inter = jnp.dot(cc, state.astype(dtype), preferred_element_type=F32)
        y = y + jnp.exp(c)[:, :, None] * inter.reshape(Q, H, P) \
            + d[:, None] * xf
        last = c[-1]                                            # [H]
        fed = (jnp.exp(last - c)[:, :, None] * dtx).astype(dtype)
        state = _by_head(jnp.exp(last), Di) * state + jnp.einsum(
            "sn,sd->nd", bc, fed.reshape(Q, Di), preferred_element_type=F32)
        return state, y.reshape(Q, Di).astype(dtype)

    state, y = jax.lax.scan(
        one, state0, tuple(m.reshape(n, Q, -1) for m in (
            x, dt, B.astype(dtype), C.astype(dtype))))
    return y.reshape(n * Q, Di)[:S], state

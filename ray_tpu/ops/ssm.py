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
cross-check), at `S x Di x N` vector operations. `ssd_scan` is the chunked
dual form, plain XLA: within a chunk of Q rows the outputs are matrix
products on the matrix unit, and the state moves once a chunk (counted as
`ssd_chunked`).
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


def ssd_step(x, dt, A, B, C, D, state):
    """Mamba-2, one token a slot: x `[ns, Di]`, dt `[ns, H]` float32, A, D
    `[H]`, B, C `[ns, N]`, state `[ns, N, Di]` float32 -> (y `[ns, Di]`
    float32, the new state). The decay is `ns x H` exponentials, each
    broadcast over its head's channels and the N states."""
    Di = x.shape[-1]
    dt = dt.astype(F32)
    x = x.astype(F32)
    decay = _by_head(jnp.exp(dt * A.astype(F32)), Di)           # [ns, Di]
    state = decay[:, None, :] * state \
        + (_by_head(dt, Di) * x)[:, None, :] * B.astype(F32)[:, :, None]
    y = jnp.sum(state * C.astype(F32)[:, :, None], axis=1)
    return y + _by_head(D.astype(F32), Di) * x, state


def ssd_scan(x, dt, A, B, C, D, state0=None, length=None, *,
             chunk: int = _SSD_CHUNK):
    """Mamba-2 over x `[S, Di]` (layouts at the top) from `state0` (zeros if
    None) -> (y `[S, Di]` in x's dtype, NOT gated: the family's gate sits
    before a norm, the mixer's; the state `[N, Di]` float32 after the last
    row). With `length` (a traced scalar) rows at and past it take dt = 0
    (decay 1, no input), as `selective_scan`'s do, so the state returned is
    the one after row `length - 1`; y there is not meaningful (and finite).

    The chunked dual form, chunks of `chunk` rows in order under one loop
    that carries the state. `chunk` is no option of a model: the mixer never
    passes it, and it is here for the tests of a chunk's edges, which need
    several chunks at tiny widths. Within a chunk, with c_t the running sum
    of dt_t A a head (float32; every exponent below is <= 0):

      G = C B^T                         [Q, Q], once for all heads
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
    H, N = dt.shape[-1], B.shape[-1]
    P = Di // H
    dtype = x.dtype
    attention._path_counts["ssd_chunked"] += 1
    if state0 is None:
        state0 = jnp.zeros((N, Di), F32)
    dt = dt.astype(F32)
    if length is not None:
        dt = jnp.where(jnp.arange(S)[:, None] < length, dt, 0.0)
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

"""Mixture-of-experts routing and experts, for training and for serving.

The reference framework only passes expert-parallel sizes through to vLLM
(SURVEY.md §2.3 — EP row: "Not in Ray"); here MoE is a native layer.

One path for every expert's form (a gated expert, `act(x W_gate) * (x W_up)`
through `W_down`: three grouped matmuls; or an ungated one, `act(x W_up)
W_down`: two; `act` silu or relu squared: `expert_mlp`; the up and gate
matrices `[in, out]` or, where a stack's width is off the lanes, `[out, in]`:
`up_out_in`), and it computes the
published mixture exactly: router logits and softmax in float32, top-k, the weights either left as the softmax over ALL
experts gives them (OLMoE, `norm_topk_prob: false`) or renormalised over the
selected k (Mixtral), and EVERY assignment computed. There is no capacity and
so no dropped token: a token's output depends on that token alone, never on
who shares its batch. Shapes stay static without a capacity because the
`tokens * k` assignments are sorted by expert and the experts run as grouped
matmuls over the sorted rows (`grouped_matmul`: group e is the
`group_sizes[e]` rows after those of the experts before it), so compute is
O(tokens * k * d * f) whatever the skew. The experts' weight leading axis
carries the logical "expert" axis which the sharding rules map onto `ep`.

The grouped matmul has two implementations behind `grouped_matmul`, chosen
in one place (`_tiling`) from static shapes and the platform: on a TPU a
Pallas kernel (`_grouped_kernel`: row tiles of 256 visited by halves, K
never cut and a group's `[K, tn]` block read once a run of its visits, only
(row tile, group) pairs that hold a row visited, the stack of all layers
indexed where it lies);
everywhere else, and under differentiation, `jax.lax.ragged_dot` and its
VJP. Same operands, same float32 accumulation, a row's result its own.

The router has the published variants as arguments (`top_k_routing`): softmax
scores (OLMoE, Mixtral, the Qwen3 family) or sigmoid scores with a selection
bias that chooses and does not weigh, group-limited top-k and a scaling factor
(the DeepSeek-V3 family). And a layer may hold a SHARE of the experts (`held`:
one chip of an expert-parallel deployment): it routes over every expert,
computes its own experts' part of the mixture, and a token's assignments to
absent experts weigh nothing here. No capacity, no dropped token, nothing in
the place of the absent chips or of their exchange. A share's combine has two
implementations behind `local_combine`, chosen as the grouped matmul's are
(`_combine_tiling`): on a TPU a Pallas kernel that adds the block's LOCAL rows
to their tokens' rows, one in sixteen of the `tokens x k` assignments on a
share of 16 of 256 experts; elsewhere a gather back to token-major. Either
way a token's sum is float32 in an order its own routing fixes.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention


def top_k_routing(gate_logits: jax.Array, k: int, norm_topk_prob: bool = True,
                  *, score: str = "softmax",
                  bias: Optional[jax.Array] = None, n_group: int = 1,
                  topk_group: int = 1, scale: float = 1.0,
                  norm_eps: float = 1e-20) -> Tuple[jax.Array, jax.Array]:
    """gate_logits: [tokens, n_experts] -> (weights [tokens, k], idx [tokens, k]).

    `score` "softmax": the weights are the float32 softmax over all experts at
    the k largest; `norm_topk_prob` renormalises them to sum to one (which
    equals the softmax over the selected k, Mixtral's; OLMoE publishes False);
    times `scale` where it is not 1 (the Laguna family's 2.5).

    `score` "sigmoid" (DeepSeek-V3's `noaux_tc`): s = sigmoid(logits) in
    float32; the CHOICE is made on s' = s + `bias` (the selection bias, which
    chooses and does not weigh); with `n_group` > 1 the experts lie in
    `n_group` groups of equal size, a group scores the sum of its two largest
    s', the `topk_group` best groups stay and the k largest s' are taken among
    their experts alone (ties to the smaller index, here and there); the
    weights are s (NOT s') at the chosen k, renormalised if `norm_topk_prob`
    (over their sum + `norm_eps`: DeepSeek-V3 publishes 1e-20, the LFM2 family
    1e-6), times `scale` (`routed_scaling_factor`).
    """
    if score == "softmax":
        probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
        weights, idx = jax.lax.top_k(probs, k)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return (weights if scale == 1.0 else weights * scale), idx
    if score != "sigmoid":
        raise ValueError(f"router score {score!r}: 'softmax' or 'sigmoid'")
    s = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    choice = s if bias is None else s + bias.astype(jnp.float32)
    if n_group > 1:
        tokens, n = choice.shape
        grouped = choice.reshape(tokens, n_group, n // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, topk_group)       # [t, topk_group]
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None],
                       axis=1)                                  # [t, n_group]
        choice = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(
            tokens, n)
    _, idx = jax.lax.top_k(choice, k)
    weights = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + norm_eps)
    return weights * scale, idx


def activation(act: str):
    """An expert's activation by name: "silu", or "relu2", relu(x)^2."""
    if act == "silu":
        return jax.nn.silu
    if act == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"feed-forward activation {act!r}: 'silu' or 'relu2'")


def _dense_matmul(x, w, transposed=False):
    return x @ (jnp.swapaxes(w, -1, -2) if transposed else w)


def up_out_in(width: int) -> bool:
    """THE layout of an expert's up and gate matrices, from the expert's
    width alone: `[F, D]` (`[out, in]`, as an `nn.Linear` weight is
    published, the hidden size minor as in the down matrix) where the width
    is not whole lanes, `[D, F]` else. 1,856 = 14.5 x 128: as a minor axis
    the TPU's compiler lays such a stack out with the hidden size minor all
    the same, and a grouped matmul that asks for `[in, out]` rows (the Pallas
    kernel, and XLA's own `ragged-dot`) is first handed a copy of the whole
    stack: 4.5 GB at 7 layers of 64 such experts, compiled for a v5e, PR 55.
    The stack that is drawn by this rule (`LlamaConfig.up_out_in`) says so to
    `moe_ffn`; gated or not, and whatever the activation, is another
    matter."""
    return width % 128 != 0


def expert_mlp(x: jax.Array, w_gate: Optional[jax.Array], w_up: jax.Array,
               w_down: jax.Array, act: str = "silu", matmul=_dense_matmul,
               out_in: bool = False) -> jax.Array:
    """One expert's form over its rows, dense (`matmul` the plain one) or
    grouped (`matmul(xs, w, transposed)` a grouped matmul over sorted rows):
    (act(x w_gate) * (x w_up)) w_down, or with no gate (`w_gate` None)
    act(x w_up) w_down. Every matrix is `[in, out]`; with `out_in` the up and
    gate matrices are `[F, D]` and multiplied by their transpose
    (`up_out_in` has why)."""
    up = activation(act)(matmul(x, w_up if w_gate is None else w_gate,
                                out_in))
    if w_gate is not None:
        up = up * matmul(x, w_up, out_in)
    return matmul(up, w_down)


# The grouped matmul's row tile on a TPU, visited by HALVES: a (row tile,
# group) pair costs the half it touches, 128 rows, however few of them are the
# group's. XLA's kernel pays a tile of 512 a pair: 1.6-1.9 ms a call over a
# share's 16 groups of 30-130 rows where this one takes 0.8-0.9 (PERF.md,
# PR 43, the probe's table).
_ROW_TILE = 256
# VMEM the kernel's blocks may fill, two buffers each: under the 16 MiB every
# program's kernels are given unasked. Asking for more (a group's whole
# 7168 x 2048 matrix as one block read 12% faster alone) takes the room XLA
# keeps its own buffers in: the gather `moe_combine` then was (its operand
# 112 MiB at 7,168 tokens) ran 15 ms a prefill slower beside such a kernel than
# beside `ragged_dot` (PERF.md, PR 43). The local combine's blocks keep to it
# too.
_BLOCKS_VMEM = 13 << 20
# Rows of a share's block that the local combine's kernel fetches a visit,
# one bfloat16 sublane tile: the run it wants is about (token tile) x k /
# experts rows of them, 8 at a tile of 256, and chunks of 32 and 64 read as
# fast at best and a third slower at worst (PERF.md, PR 48, the probe's
# table). And the most rows a block may have:
# their tokens and weights lie in scalar memory, 8 bytes a row of its 1 MiB
# (the cells' widest block has 40,960, Laguna's 8,192 rows x 10 experts a
# token at a share of an eighth; 65,536 still compile for a v5e).
_COMBINE_CHUNK = 16
_COMBINE_ROWS = 1 << 16


def _tiling(m: int, k: int, n: int) -> Optional[Tuple[int, int]]:
    """THE choice between the two grouped matmuls, from static shapes: the
    Pallas kernel's (row tile, column tile), or None for `ragged_dot`. The
    kernel won at every shape the probe tried, a decode step's 64 and 128
    rows included, so all it asks is shapes it can tile: the rows in whole
    tiles (one tile of all the rows under `_ROW_TILE`) whose halves are whole
    bfloat16 sublane tiles, K in whole packed sublane tiles (it is never cut:
    a group's `[K, tn]` block is read once a run of its visits, and a block
    that takes the whole of an axis need not be whole lanes of it) and N a
    whole tile of 64 columns at least. The column tile is the widest the
    blocks' room allows that tiles N ROUNDED UP to lanes (N = 1,856 = 14.5 x
    128: tiles of 640 over 1,920, the last one's columns past N read as
    anything and never written back), and a row tile of half the rows is
    taken where that lets it be wider (K = 7168: a tile's rows are read once
    a column tile)."""
    best, lanes = None, -(-n // 128) * 128
    for tm in (min(_ROW_TILE, m), min(_ROW_TILE // 2, m)):
        if m % tm or tm % 32 or k % 16 or n % 64:
            continue
        tn = max((t for t in range(128, lanes + 1, 128) if lanes % t == 0
                  and 4 * (k * t + tm * k + tm * t) + 4 * tm * min(t, 512)
                  <= _BLOCKS_VMEM), default=0)
        if tn and (best is None or tn > best[1]):
            best = (tm, tn)
    return best


@functools.partial(jax.jit, static_argnames=("m", "tm"))
def _visits(groups, *, m, tm):
    """The (row tile, group) pairs that hold a grouped row, in row order:
    -> (group [V], tile [V], lo [V], hi [V], count), V = tiles + groups - 1
    the most there can be; visit v computes rows lo[v] <= r < hi[v] of its
    tile, its group's. An empty group has no visit."""
    ends = jnp.cumsum(groups)
    starts = ends - groups
    first = starts // tm
    tiles = jnp.where(groups > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)
    v = jnp.arange(-(-m // tm) + groups.shape[0] - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1,
                                dtype=jnp.int32), groups.shape[0] - 1)
    # a visit's tile: its group's first, and one on for each visit since
    tile, lo, hi = jnp.stack([first - (upto - tiles), starts, ends])[:, group]
    return group, tile + v, lo, hi, upto[-1]


def _grouped_kernel(group, tile, lo, hi, x_ref, w_ref, o_ref, *, tm, nc,
                    transposed):
    """One visit: the tile's rows against the group's `[K, tn]` block (`[tn,
    K]` where `transposed`: the product with its transpose) BY HALVES, a
    half the group has no row in skipped, `nc` columns at a time.
    Only the group's rows are stored, so the tile's other rows keep what
    their own groups' visits (consecutive: the block stays in VMEM) left
    there, and a row in no group keeps what the buffer held."""
    del group
    v = pl.program_id(1)
    base, half = tile[v] * tm, tm // 2

    def rows(h, _):
        r = pl.ds(pl.multiple_of(h * half, half), half)
        at = base + h * half + jax.lax.broadcasted_iota(
            jnp.int32, (half, 1), 0)
        mine = (at >= lo[v]) & (at < hi[v])
        x = x_ref[r, :]

        def columns(j, _):
            c = pl.ds(pl.multiple_of(j * nc, nc), nc)
            if transposed:
                y = jax.lax.dot_general(
                    x, w_ref[c, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            else:
                y = jnp.dot(x, w_ref[:, c],
                            preferred_element_type=jnp.float32)
            o_ref[r, c] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[r, c])

        jax.lax.fori_loop(0, o_ref.shape[1] // nc, columns, None)

    # halves 0 and 1: from the one the group's first row here lies in, up to
    # the one its last row does
    jax.lax.fori_loop((lo[v] >= base + half).astype(jnp.int32),
                      1 + (hi[v] > base + half).astype(jnp.int32), rows, None)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "transposed",
                                               "interpret"))
def _grouped_pallas(xs, w, groups, *, tm, tn, interpret, transposed=False):
    """Both under a `jit` of their own: a sparse layer's three matmuls share
    one list of visits and its gate and up one kernel, traced and lowered
    once a program (a start-up pays every program's tracing: PERF.md,
    PR 41)."""
    m, k = xs.shape
    n = w.shape[1 if transposed else 2]
    *meta, count = _visits(groups, m=m, tm=tm)
    nc = next(c for c in (512, 384, 256, 128) if tn % c == 0)
    return pl.pallas_call(
        functools.partial(_grouped_kernel, tm=tm, nc=nc,
                          transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(-(-n // tn), count),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, g, t, lo, hi: (t[v], 0)),
                pl.BlockSpec((None, tn, k),
                             lambda j, v, g, t, lo, hi: (g[v], j, 0))
                if transposed else
                pl.BlockSpec((None, k, tn),
                             lambda j, v, g, t, lo, hi: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, g, t, lo, hi: (t[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="grouped_matmul",
    )(*meta, xs, w)


def _ragged_dot(xs, w, groups, transposed=False):
    attention._path_counts["experts_ragged_dot"] += 1
    return jax.lax.ragged_dot(
        xs, jnp.swapaxes(w, 1, 2) if transposed else w, groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped(xs, w, groups, tiling, interpret, transposed):
    attention._path_counts["experts_grouped_pallas"] += 1
    return _grouped_pallas(xs, w, groups, tm=tiling[0], tn=tiling[1],
                           transposed=transposed, interpret=interpret)


def _grouped_fwd(xs, w, groups, tiling, interpret, transposed):
    return jax.vjp(lambda a, b: _ragged_dot(a, b, groups, transposed), xs, w)


def _grouped_bwd(tiling, interpret, transposed, vjp, dy):
    return (*vjp(dy), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(xs: jax.Array, w: jax.Array, groups: jax.Array, *,
                   transposed: bool = False,
                   interpret: bool = False) -> jax.Array:
    """xs [m, K] sorted by group, w [G, K, N] (with `transposed` [G, N, K]:
    every group's matrix as an `nn.Linear` weight, the product with its
    transpose), groups [G] int32 -> [m, N]:
    rows sum(groups[:g]) .. sum(groups[:g + 1]) - 1 times w[g], float32
    accumulation over K, a row's result that row's alone; rows past
    sum(groups) hold anything. `w` may be the stack of every layer with the
    other layers' groups empty: it is indexed where it lies, and an empty
    group costs nothing.

    On a TPU (or with `interpret`, for tests on the CPU), at the shapes
    `_tiling` takes, a Pallas kernel; elsewhere, and under differentiation,
    `jax.lax.ragged_dot` and its VJP. Which one is counted at trace time in
    `attention.attention_path_counts()` as `experts_grouped_pallas` /
    `experts_ragged_dot`."""
    tiling = _tiling(*xs.shape, w.shape[1 if transposed else 2])
    if tiling is None or not (interpret or attention._on_tpu()):
        return _ragged_dot(xs, w, groups, transposed)
    return _grouped(xs, w, groups, tiling, interpret, transposed)


def _over_groups(groups: jax.Array):
    """`expert_mlp`'s `matmul` over rows sorted by group: the grouped matmul
    as it stands on this module now (a test puts its own there)."""
    return lambda xs, w, transposed=False: grouped_matmul(
        xs, w, groups, transposed=transposed)


def _combine_tiling(tokens: int, rows: int, d: int,
                    itemsize: int) -> Optional[int]:
    """THE choice between a share's two combines, from static shapes: the
    Pallas kernel's token tile, or None for the gather. The kernel won at
    every shape the probe tried, a decode step's 32 tokens included (PERF.md,
    PR 48), so all it asks is shapes it can tile: `d` in lanes, the rows in
    whole chunks of `_COMBINE_CHUNK` and few enough for their two tables to
    lie in scalar memory, the tokens in whole tiles of whole float32 sublane
    tiles; the tile is the tallest whose float32 block, two buffers, fits the
    blocks' room beside a chunk's (256 at 4,096 columns, 128 at 7,168)."""
    rc = _COMBINE_CHUNK
    if d % 128 or rows % rc or rows > _COMBINE_ROWS:
        return None
    return next((t for t in (256, 128, 64, 32, 16, 8) if tokens % t == 0
                 and (8 * t + (2 * itemsize + 4) * rc) * d <= _BLOCKS_VMEM),
                None)


def _runs(token, groups, tokens, tt):
    """The (token tile, group, row chunk) triples that hold a grouped row,
    tile by tile and within a tile by group: -> (tile [V], chunk [V], lo [V],
    hi [V], count); visit v meets rows lo[v] <= r < hi[v], which lie in ITS
    chunk: the rows of its group whose tokens lie in its tile. Inside a group
    the rows' tokens ascend (the sort is stable), so those are one run of
    rows, cut where it crosses a chunk's edge. A tile's first group is
    visited even where it has no row there: every tile's block is written."""
    rows, n_groups, n_tiles = token.shape[0], groups.shape[0], tokens // tt
    rc, pairs = _COMBINE_CHUNK, n_tiles * n_groups
    ends = jnp.cumsum(groups)
    r = jnp.arange(rows, dtype=jnp.int32)
    group_of = jnp.sum(r[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    # ascending over the grouped rows: where pair p's run starts is a count
    key = jnp.where(r < ends[-1], group_of * n_tiles + token // tt, pairs)
    edges = jnp.sum(key[None, :] < jnp.arange(pairs + 1)[:, None], axis=1,
                    dtype=jnp.int32)
    lo, hi = (e.reshape(n_groups, n_tiles).T.reshape(-1)
              for e in (edges[:-1], edges[1:]))       # tile-major
    chunks = jnp.where(hi > lo, (hi - 1) // rc - lo // rc + 1, 0)
    chunks = jnp.maximum(chunks, jnp.arange(pairs) % n_groups == 0)
    upto = jnp.cumsum(chunks)
    # a run more or a chunk's edge crossed is a visit more: no more than these
    v = jnp.arange(pairs + rows // rc - 1, dtype=jnp.int32)
    pair = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1,
                               dtype=jnp.int32), pairs - 1)
    chunk = jnp.minimum(lo[pair] // rc + v - (upto - chunks)[pair],
                        rows // rc - 1)
    return (pair // n_groups, chunk, jnp.maximum(lo[pair], chunk * rc),
            jnp.minimum(hi[pair], (chunk + 1) * rc), upto[-1])


def _combine_kernel(tile, chunk, lo, hi, fresh, token, weight, ys_ref,
                    prev_ref, o_ref, rows_ref, sem, *, tt):
    """One visit: the run's rows, one by one, each times its float32 weight
    added to its token's row of the tile's float32 block. A tile's visits
    are consecutive, its groups ascend and a group's rows ascend, so a
    token's sum is taken in the order of its own groups, whoever shares its
    tile; a row outside the run is never read. The block starts as zeros,
    or as what the blocks before this one left (`fresh` 0: read once a tile,
    where it lies)."""
    v = pl.program_id(0)
    base = tile[v] * tt

    @pl.when((v == 0) | (tile[v] != tile[jnp.maximum(v - 1, 0)]))
    def _():
        @pl.when(fresh[0] != 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(fresh[0] == 0)
        def _():
            dma = pltpu.make_async_copy(
                prev_ref.at[pl.ds(pl.multiple_of(base, tt), tt), :], o_ref,
                sem)
            dma.start()
            dma.wait()

    @pl.when(hi[v] > lo[v])
    def _():
        # a row is read alone from 32-bit sublanes, not from packed ones
        rows_ref[...] = ys_ref[...].astype(jnp.float32)
        first = chunk[v] * _COMBINE_CHUNK

        def row(r, _):
            at = pl.ds(token[r] - base, 1)
            o_ref[at, :] = o_ref[at, :] \
                + weight[r] * rows_ref[pl.ds(r - first, 1), :]

        jax.lax.fori_loop(lo[v], hi[v], row, None)


@functools.partial(jax.jit, static_argnames=("tt", "interpret"))
def _combine_pallas(out, fresh, ys, token, weight, groups, *, tt, interpret):
    """Under a `jit` of its own, as `_grouped_pallas` is: traced and lowered
    once a program. The run tables carry `moe_dispatch`'s scope, the kernel
    the caller's."""
    tokens, d = out.shape
    with jax.named_scope("moe_dispatch"):
        *meta, count = _runs(token, groups, tokens, tt)
    return pl.pallas_call(
        functools.partial(_combine_kernel, tt=tt),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(count,),
            in_specs=[
                pl.BlockSpec((_COMBINE_CHUNK, d),
                             lambda v, t, c, *_: (c[v], 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tt, d), lambda v, t, *_: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((_COMBINE_CHUNK, d), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="local_combine",
    )(*meta, fresh.astype(jnp.int32).reshape(1), token, weight, ys, out)


def local_combine(out: jax.Array, fresh: jax.Array, ys: jax.Array,
                  assignment: jax.Array, weights: jax.Array,
                  groups: jax.Array, *,
                  interpret: bool = False) -> Optional[jax.Array]:
    """out [tokens, d] float32 + the grouped rows of ys [rows, d], row r the
    result of assignment `assignment[r]` = token * k + j, added to its
    token's row times `weights[token, j]` (float32): group g is the
    `groups[g]` rows after those of the groups before it, as
    `grouped_matmul` has them, and inside a group the tokens ascend strictly.
    A token's sum is taken in float32 in the order of its groups, which is
    its own routing's whoever shares the batch; a row in no group may hold
    anything, NaN included. `fresh` (a traced bool) says that `out` holds
    zeros, which are then not read.

    It reads the grouped rows and writes `out`, never a row an assignment:
    on a TPU (or with `interpret`) at the shapes `_combine_tiling` takes, a
    Pallas kernel, counted at trace time in
    `attention.attention_path_counts()` as `share_combine_local`; elsewhere
    None, and the caller gathers (`share_combine_gather`)."""
    tt = _combine_tiling(out.shape[0], *ys.shape, ys.dtype.itemsize)
    if tt is None or not (interpret or attention._on_tpu()):
        attention._path_counts["share_combine_gather"] += 1
        return None
    attention._path_counts["share_combine_local"] += 1
    with jax.named_scope("moe_dispatch"):
        token = assignment // weights.shape[1]
        weight = weights.reshape(-1)[assignment]
    return _combine_pallas(out, fresh, ys, token, weight, groups, tt=tt,
                           interpret=interpret)


# Rows of a share's sorted assignments that meet the grouped matmuls at once,
# as a multiple of the rows that would under even routing (`tokens x k x held
# / experts`): the held experts' rows come first in the sorted order, so a
# block of this many holds them all but for a routing four times as skewed
# towards this share, and then a second block follows (`_share_experts`).
# Walking all `tokens x k` rows, fifteen in sixteen of them in no group, took
# the grouped matmuls 20 of a 137 ms prefill (PERF.md, PR 39); the combine
# walks the block's rows too since PR 48 (`local_combine`). Nothing is dropped
# at any skew.
_SHARE_BLOCK = 4


def _share_experts(x, w_gate, w_up, w_down, layer, idx, weights, held,
                   n_experts, act="silu", out_in=False):
    """The routed part that the experts `held` = (offset, count) give: `idx`,
    `weights` [tokens, k] the router's choice over all `n_experts`. ->
    (out [tokens, d] float32, chosen [tokens * k, count] bool: which
    assignment fell to which held expert).

    The assignments are sorted so that those to held experts come first, by
    expert (the sort is stable: an expert's rows keep the order of their
    tokens), and walked in blocks of `rows` rows while a block still holds a
    local one: a dynamic trip count, one block in all but a freak routing.
    A block's rows are gathered, run through the grouped matmuls with the
    group sizes clipped to the block, and combined from the block's own rows
    (`local_combine`: each grouped row times its weight added to its token's
    row, by held expert in ascending order); where that kernel does not run,
    by a gather back to token-major and one sum over a token's k assignments
    in ascending j, an assignment outside the block, or to an absent expert,
    weighing 0. No scatter-add either way: the order of a token's sum is its
    own routing's."""
    tokens, top_k = idx.shape
    offset, n_held = held
    total = tokens * top_k
    with jax.named_scope("moe_dispatch"):
        flat = idx.reshape(-1) - offset
        flat = jnp.where((flat >= 0) & (flat < n_held), flat, n_held)
        order = jnp.argsort(flat)               # held first, by expert
        back = jnp.argsort(order).reshape(tokens, top_k)
        chosen = flat[:, None] == jnp.arange(n_held)[None, :]
        group_sizes = jnp.sum(chosen, axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(group_sizes)
        n_local = ends[-1]
        even = -(-total * n_held // n_experts)
        rows = min(total, -(-_SHARE_BLOCK * even // 8) * 8)
        stacked = None
        if layer is not None:
            stacked = w_up.shape[0] * n_held
            w_up, w_gate, w_down = (
                None if w is None else w.reshape(stacked, *w.shape[2:])
                for w in (w_up, w_gate, w_down))

    def block(carry):
        lo, out = carry
        with jax.named_scope("moe_dispatch"):
            # order[lo + r], without reading past the list's end
            take = jnp.minimum(lo + jnp.arange(rows), total - 1)
            assignment = order[take]
            xs = x[assignment // top_k]                      # [rows, d]
            groups = local = (jnp.clip(ends, lo, lo + rows)
                              - jnp.clip(ends - group_sizes, lo, lo + rows))
            if layer is not None:
                groups = jax.lax.dynamic_update_slice(
                    jnp.zeros(stacked, jnp.int32), local, (layer * n_held,))
        with jax.named_scope("experts"):
            ys = expert_mlp(xs, w_gate, w_up, w_down, act,    # [rows, d]
                            _over_groups(groups), out_in)
        with jax.named_scope("moe_combine"):
            combined = local_combine(out, lo == 0, ys, assignment, weights,
                                     local)
            if combined is not None:
                return lo + rows, combined
            # A row in no group is whatever the grouped matmul left there:
            # it is replaced, not multiplied by 0 (0 x NaN is NaN).
            ys = jnp.where((lo + jnp.arange(rows) < n_local)[:, None], ys, 0)
            here = (back >= lo) & (back < jnp.minimum(lo + rows, n_local))
            per_token = ys[jnp.clip(back - lo, 0, rows - 1)]  # [t, k, d]
            out = out + jnp.einsum(
                "tkd,tk->td", per_token, jnp.where(here, weights, 0.0),
                preferred_element_type=jnp.float32)
        return lo + rows, out

    out0 = jnp.zeros(x.shape, jnp.float32)
    if rows == total:       # one block holds every assignment
        return block((jnp.int32(0), out0))[1], chosen
    _, out = jax.lax.while_loop(lambda c: c[0] < n_local, block,
                                (jnp.int32(0), out0))
    return out, chosen


def moe_ffn(x: jax.Array, gate_w: jax.Array, w_up: jax.Array,
            w_gate: Optional[jax.Array], w_down: jax.Array, *, top_k: int = 2,
            norm_topk_prob: bool = True,
            live: Optional[jax.Array] = None,
            layer: Optional[jax.Array] = None,
            routing: Optional[Dict[str, Any]] = None,
            held: Optional[Tuple[int, int]] = None,
            shared: Optional[Tuple[Optional[jax.Array], jax.Array,
                                   jax.Array]] = None,
            act: str = "silu", out_in: bool = False,
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MoE feed-forward over sorted assignments (SwiGLU experts, or what
    `act` and a `w_gate` of None say: `expert_mlp`).

    x: [tokens, d_model]
    gate_w: [d_model, n_experts] router weights
    w_up/w_gate: [n_experts, d_model, d_ff]; w_down: [n_experts, d_ff, d_model]
    (with `out_in`, w_up/w_gate `[n_experts, d_ff, d_model]` and the shared
    expert's alike: `up_out_in`)
    live: [tokens] bool, the rows that are somebody's token (not a bucket's
    padding, not an idle slot). Every row is computed either way, each from
    itself alone; `live` only keeps the others out of the count.
    layer: with it, w_up/w_gate/w_down are the STACKS of all the model's
    layers, `[n_layers, n_experts, ...]`, and `layer` (a traced index) says
    whose experts these tokens meet. The grouped matmul then reads the stack
    where it lies, the other layers' experts as empty groups (no visit in the
    Pallas kernel); handed one layer sliced out of a scanned stack, either
    kernel is first given a copy of that layer's experts (0.8 GB a layer at
    OLMoE's widths, every step).
    routing: the router's variant, `top_k_routing`'s keyword arguments (None:
    softmax).
    held: (offset, count), static: w_up/w_gate/w_down hold experts offset ..
    offset + count - 1 of the `n_experts` the router scores, ONE share of an
    expert-parallel layer. The router still scores every expert; the
    assignments to held experts are computed (`_share_experts`) and the rest
    weigh 0. What comes back is this share's PART of the mixture: the parts
    of all shares add up to the whole layer's.
    shared: (w_gate, w_up, w_down) of a dense expert every token meets, of the
    routed experts' form (w_gate None: ungated), added to the routed part
    (once: a deployment's other shares add none).
    Returns (out [tokens, d_model], aux_loss scalar, tokens per expert
    [n_experts] int32 over the live rows; per HELD expert `[count]` with
    `held`, whose sum is the local assignments).
    """
    tokens, _ = x.shape
    n_experts = gate_w.shape[-1]
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", x, gate_w,
                            preferred_element_type=jnp.float32)
        weights, idx = top_k_routing(logits, top_k, norm_topk_prob,
                                     **(routing or {}))

    if held is not None:
        out, chosen = _share_experts(x, w_gate, w_up, w_down, layer, idx,
                                     weights, held, n_experts, act, out_in)
        group_sizes = jnp.sum(chosen, axis=0, dtype=jnp.int32)
    else:
        with jax.named_scope("moe_dispatch"):
            # Assignments token-major (t, j) -> sorted by expert; the sort is
            # stable, so an expert's rows keep the order of their tokens.
            flat_expert = idx.reshape(-1)                       # [t*k]
            order = jnp.argsort(flat_expert)
            token_of = order // top_k
            chosen = flat_expert[:, None] == jnp.arange(n_experts)[None, :]
            group_sizes = jnp.sum(chosen, axis=0, dtype=jnp.int32)   # [e]
            xs = x[token_of]                                    # [t*k, d]
            groups = group_sizes
            if layer is not None:
                stacked = w_up.shape[0] * n_experts
                groups = jax.lax.dynamic_update_slice(
                    jnp.zeros(stacked, jnp.int32), group_sizes,
                    (layer * n_experts,))
                w_up, w_gate, w_down = (
                    None if w is None else w.reshape(stacked, *w.shape[2:])
                    for w in (w_up, w_gate, w_down))

        with jax.named_scope("experts"):
            ys = expert_mlp(xs, w_gate, w_up, w_down, act,      # [t*k, d]
                            _over_groups(groups), out_in)

        with jax.named_scope("moe_combine"):
            # Back to token-major by a gather and one sum over a token's k
            # rows in a fixed order: no scatter-add, whose order of additions
            # (and so the last bit of a token's output) would be the batch's.
            back = jnp.argsort(order)
            per_token = ys[back].reshape(tokens, top_k, -1).astype(
                jnp.float32)
            out = jnp.einsum("tkd,tk->td", per_token, weights)

    if shared is not None:
        with jax.named_scope("shared_expert"):
            out = out + expert_mlp(x, *shared, act,
                                   out_in=out_in).astype(jnp.float32)

    if live is None:
        counts = group_sizes
    else:
        counts = jnp.sum(chosen & jnp.repeat(live, top_k)[:, None], axis=0,
                         dtype=jnp.int32)
    if held is not None or routing:
        # No published load-balancing loss for a share or a sigmoid router.
        return out.astype(x.dtype), jnp.zeros((), jnp.float32), counts
    # Load-balancing aux loss (Switch-style): mean prob * mean assignment frac.
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = group_sizes.astype(jnp.float32) / tokens
    aux = n_experts * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return out.astype(x.dtype), aux, counts

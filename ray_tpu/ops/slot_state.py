"""The recurrent state of a model with state-space layers, on the device:
its layout, and the only ops on it. The sibling of `ops/paged_kv.py` for
what has no pages: a state-space layer keeps, for every slot, a state of
FIXED size whatever the sequence's length, so there is nothing to page and
no block table; a slot's row is simply the slot's index.

The state is a pair of arrays, for the model's `n_layers` state-space layers
(ordinals into its `mamba` stack) and the engine's `n_slots`:

  ssm   [n_layers, n_slots, N, Di]      float32: `ops.ssm`'s state
  conv  [n_layers, K - 1, n_slots, Di]  the convolution's window, the last
                                        K - 1 inputs of each channel (Di + 2 G
                                        N channels under Mamba-2, whose
                                        convolution takes its G groups' B
                                        and C too)

The channel axis is the minor one of both and the axis before it a whole
tile (N = 16 rows of float32; 16 slots of bfloat16), so neither is padded:
with N as the minor axis the first would take eight times its size.

A gated short convolution's layers (`LlamaConfig.conv_layers`, the LFM2
family) keep the window ALONE, no recurrent state: `n_state` 0 makes the
pair (None, conv), `conv` the last K - 1 gated inputs `z = B * X` of each
channel (2 x 2,048 numbers a slot a layer at LFM2's widths, whatever the
context), and every op here passes the None through.

  * ``empty_state`` makes it; ``write_state`` puts a prefill's final state
    and window into ONE slot's rows, all layers at once, overwriting the
    whole of what the slot's previous tenant left; ``layer_state`` and
    ``update_layer`` are a decode step's read and write of one layer, the
    write only where a slot is active, so an idle slot's state never moves.
  * ``step_layer`` is a Mamba-2 layer's read, update and write of its
    recurrent state in ONE op, so that a decode step visits the state once:
    on a TPU `ops.ssm.ssd_state_step`, a kernel handed the whole `ssm`
    array and the layer's index that reads each active slot's tile where it
    lies, updates it, reduces it to the step's output and writes it back in
    place (a read and a write of the state: the least a step can move;
    `layer_state`, `ops.ssm.ssd_step` and `update_layer` compile to a read
    more); elsewhere those three. The window keeps `update_layer`.
  * Like the arena, the state rides the decode program's loop CARRY and is
    donated: `update_layer` is an in-place write of one layer's rows, and
    `step_layer`'s kernel aliases the state it is handed. Nothing outside
    this module indexes it.

A stack of POWER-RETENTION layers (`LlamaConfig.mixer` "retention") keeps
NOTHING but such a state, and a far larger one: a pair `(S, z)` of
`empty_retention`, `[n_layers, n_slots, kv_heads, d / 2 + 1, d(, d)]`
float32 (34.08 MB a slot a layer at 8 kv heads of 128), `write_retention` a
prefill's write, `retention_step_layer` a decode step's one visit.

A WINDOW layer's cache is the other thing that has no pages (a model of
window and full attention layers, `LlamaConfig.attn_pattern`): a query sees
its own position and the `window - 1` before it, so a slot keeps a RING of
the last R = `window` (rounded up to a tile's 16 rows) positions a window
layer, whatever the prompt's length, position t in row `t % R`:

  kw  [n_layers, n_slots, kv_heads, R, lanes(head_dim)]    `[k_n ; k_r ; 0]`
  vw  [n_layers, n_slots, kv_heads, R, lanes(v_head_dim)]

(`n_layers` the window layers, ordinals into the `window` stack; widths in
whole tiles of 128 lanes as the full layers' pages are, `ops/paged_kv.py`).
At MiMo-V2's widths, 8 kv heads of 256 + 128 lanes, a slot holds 786 KB a
layer, at 200 positions as at 8,000.

  * ``empty_window`` makes it; ``write_window_prompt`` puts the TAIL of a
    prefill's keys and values into one slot's rings, all layers at once
    (rows whose position the prompt never reached are zeroed: the previous
    tenant's are gone); ``write_window_token`` is a decode step's row a slot
    of one layer, written before ``window_decode_attention`` reads the ring:
    the step's own position, the `window - 1` before it, and the sink.
    A row's position is not stored: at step w, row r holds the largest
    position <= w that is r modulo R, which is live if it is >= 0 and less
    than `window` back.

A model of LINEAR and BLOCK-SPARSE layers (`LlamaConfig.mixer_types`, the
MiniCPM-SALA family) keeps two things a slot beside its sparse layers' pages:

  * each linear layer's state, ONE array `[n_layers, n_slots, heads, d, d]`
    float32 (2.1 MB a slot a layer at 32 heads of 128, whatever the
    context): ``empty_linear``, ``write_linear`` a prefill's write (the
    prompt's final state, all layers at once), ``linear_step_layer`` a decode
    step's one visit (`ops/linear_attention.py::linear_state_step`);
  * each sparse layer's POOLED keys, a pair: `[n_layers, n_slots, max_seq /
    stride, kv_heads * head_dim]`, entry i the mean of the slot's keys
    `stride * i .. stride * i + kernel - 1`, and `[n_layers, n_slots, 2,
    kv_heads * head_dim]` float32, the running sums of the last two groups
    of `stride` keys, from which a decode step finishes the pooled key that
    ends at its position (`ops/sparse_attention.py::compress_step`):
    ``empty_pooled``, ``write_pooled`` (a prompt's pooled keys over the
    slot's first rows; what the previous tenant left past them is finished
    anew before anything reads it), ``pooled_step_layer``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention
from ray_tpu.ops import linear_attention as linear_ops
from ray_tpu.ops import retention as retention_ops
from ray_tpu.ops import sparse_attention as sparse_ops
from ray_tpu.ops import ssm as ssm_ops
from ray_tpu.ops.attention import DEFAULT_MASK_VALUE
from ray_tpu.ops.paged_kv import _lanes, _to_width

State = Tuple[Optional[jax.Array], jax.Array]


def empty_state(n_layers: int, n_slots: int, n_state: int, channels: int,
                conv: int, dtype, conv_channels: Optional[int] = None
                ) -> State:
    """-> (ssm, conv), zeroed; `n_state` 0: (None, conv), a window and no
    recurrent state. `conv_channels`: the window's width where it is not the
    state's (Mamba-2's convolution runs over x, B and C together, Di + 2 G N
    channels)."""
    return (jnp.zeros((n_layers, n_slots, n_state, channels), jnp.float32)
            if n_state else None,
            jnp.zeros((n_layers, conv - 1, n_slots, conv_channels or channels),
                      dtype))


def state_bytes(state: State) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in state
               if a is not None)


def write_state(state: State, slot, ssm_rows, conv_rows) -> State:
    """A prefill's result into slot `slot` (a traced scalar): `ssm_rows`
    `[n_layers, N, Di]`, `conv_rows` `[n_layers, K - 1, Di]`."""
    ssm, conv = state
    with jax.named_scope("state_write"):
        return (None if ssm is None
                else ssm.at[:, slot].set(ssm_rows.astype(ssm.dtype)),
                conv.at[:, :, slot].set(conv_rows.astype(conv.dtype)))


def layer_state(state: State, layer) -> State:
    """Layer `layer`'s (ssm `[n_slots, N, Di]`, conv `[K - 1, n_slots,
    Di]`)."""
    return None if state[0] is None else state[0][layer], state[1][layer]


def update_layer(state: State, layer, active, ssm_rows, conv_rows) -> State:
    """A decode step's new state of one layer, kept only for the slots
    `active` `[n_slots]` marks: the others' rows stay what they were.
    Rows given as None leave their array alone (`ssm_rows`: a model that
    has no recurrent state, and a Mamba-2 layer's, which `step_layer` writes;
    `conv_rows`: `step_layer`'s own write)."""
    ssm, conv = state
    if ssm_rows is not None:
        ssm_rows = jnp.where(active[:, None, None], ssm_rows, ssm[layer])
    if conv_rows is not None:
        conv_rows = jnp.where(active[None, :, None],
                              conv_rows.astype(conv.dtype), conv[layer])
    return (ssm if ssm_rows is None else ssm.at[layer].set(ssm_rows),
            conv if conv_rows is None else conv.at[layer].set(conv_rows))


def step_layer(state: State, layer, active, x, dt, A, B, C, D, *,
               interpret: bool = False) -> Tuple[jax.Array, State]:
    """A Mamba-2 decode step's read, update and write of one layer's
    recurrent state in ONE visit (`ops.ssm.ssd_step`'s arguments, one token
    a slot; B and C `[n_slots, N]` or `[n_slots, G, N]`) -> (y `[n_slots, Di]` float32, zeros for an idle slot; the state,
    whose idle slots' rows and other layers stay what they were). On a TPU
    (or with `interpret`, for tests on the CPU), where the state is whole
    tiles, `ops.ssm.ssd_state_step`, which crosses each active slot's state
    once, where it lies; elsewhere `ssd_step` on the layer's rows and their
    write back. The path taken is counted at trace time in
    `attention.attention_path_counts()` as `ssd_step_pallas` /
    `ssd_step_reference`. The window is not this op's: `update_layer`."""
    ssm, conv = state
    use = (interpret or attention._on_tpu()) \
        and ssm_ops.state_step_tiles(ssm.shape, ssm_ops.groups_of(B))
    attention._path_counts[
        "ssd_step_pallas" if use else "ssd_step_reference"] += 1
    if use:
        y, ssm = ssm_ops.ssd_state_step(ssm, layer, active, x, dt, A, B, C,
                                        D, interpret=interpret)
        return y, (ssm, conv)
    y, rows = ssm_ops.ssd_step(x, dt, A, B, C, D, ssm[layer])
    return jnp.where(active[:, None], y, 0.0), \
        update_layer(state, layer, active, rows, None)


# ---------------------------------------------------------------------------
# A retention layer's state
# ---------------------------------------------------------------------------

def empty_retention(n_layers: int, n_slots: int, kv_heads: int,
                    head_dim: int) -> State:
    """-> (S, z), zeroed, of a stack of power-retention layers: for each
    layer, slot and kv head the state `S [d / 2 + 1, d, d]` float32 (the
    expansion's layout: `ops/retention.py`; 4.26 MB at d = 128, 34.08 MB a
    slot a layer over 8 kv heads, whatever the context) and its normaliser
    `z [d / 2 + 1, d]`, in rows up to whole tiles
    (`ops.retention.z_rows`). The slot is the second axis of both."""
    return tuple(jnp.zeros(shape, jnp.float32) for shape in
                 retention_ops.state_shapes(n_layers, n_slots, kv_heads,
                                            head_dim))


def write_retention(state: State, slot, S_rows, z_rows) -> State:
    """A prefill's result into slot `slot` (a traced scalar), all layers at
    once, the whole of what its previous tenant left overwritten: `S_rows`
    `[n_layers, KVH, NB, d, d]`, `z_rows` `[n_layers, KVH, rows, d]`."""
    S, z = state
    with jax.named_scope("state_write"):
        return (S.at[:, slot].set(S_rows.astype(S.dtype)),
                z.at[:, slot].set(z_rows.astype(z.dtype)))


def retention_step_layer(state: State, layer, active, q, k, v, gamma, *,
                         interpret: bool = False
                         ) -> Tuple[jax.Array, State]:
    """A retention layer's decode step, one token a slot, on the slots'
    whole state in ONE visit (`ops.retention.retention_step`'s arguments) ->
    (y `[n_slots, H, d]` float32, zeros for an idle slot; the state, whose
    idle slots' tiles and other layers stay what they were). On a TPU (or
    with `interpret`) `ops.retention.retention_state_step`, which crosses
    each active slot's tiles once, where they lie; elsewhere `retention_step`
    on the layer's rows and their write back under a select. Counted at
    trace time as `retention_step_pallas` / `retention_step_reference`."""
    S, z = state
    use = interpret or (attention._on_tpu()
                        and retention_ops.kernel_tiles(q.shape[-1]))
    attention._path_counts[
        "retention_step_pallas" if use else "retention_step_reference"] += 1
    if use:
        y, S, z = retention_ops.retention_state_step(
            S, z, layer, active, q, k, v, gamma, interpret=interpret)
        return y, (S, z)
    y, S_rows, z_rows = retention_ops.retention_step(S[layer], z[layer], q,
                                                     k, v, gamma)
    keep = active[:, None, None, None]
    return jnp.where(active[:, None, None], y, 0.0), (
        S.at[layer].set(jnp.where(keep[..., None], S_rows, S[layer])),
        z.at[layer].set(jnp.where(keep, z_rows, z[layer])))


# ---------------------------------------------------------------------------
# A linear layer's state, and a block-sparse layer's pooled keys
# ---------------------------------------------------------------------------

def empty_linear(n_layers: int, n_slots: int, heads: int, head_dim: int
                 ) -> State:
    """-> (S,), zeroed: `[n_layers, n_slots, heads, d, d]` float32."""
    return (jnp.zeros((n_layers, n_slots, heads, head_dim, head_dim),
                      jnp.float32),)


def write_linear(state: State, slot, S_rows) -> State:
    """A prefill's result into slot `slot` (a traced scalar), all layers at
    once, the whole of what its previous tenant left overwritten: `S_rows`
    `[n_layers, heads, d, d]`."""
    with jax.named_scope("state_write"):
        return (state[0].at[:, slot].set(S_rows.astype(jnp.float32)),)


def linear_step_layer(state: State, layer, active, q, k, v, rates,
                      scale: float, *, interpret: bool = False
                      ) -> Tuple[jax.Array, State]:
    """A linear layer's decode step, one token a slot, on the slots' whole
    state in ONE visit (`ops.linear_attention.linear_state_step`, which
    counts its path) -> (o `[n_slots, heads, d]` float32, zeros for an idle
    slot; the state, whose idle slots' tiles and other layers stay what they
    were)."""
    o, S = linear_ops.linear_state_step(state[0], layer, active, q, k, v,
                                        rates, scale, interpret=interpret)
    return o, (S,)


def empty_pooled(n_layers: int, n_slots: int, entries: int, width: int,
                 dtype) -> State:
    """-> (pooled `[n_layers, n_slots, entries, width]` in `dtype`, sums
    `[n_layers, n_slots, 2, width]` float32), zeroed."""
    return (jnp.zeros((n_layers, n_slots, entries, width), dtype),
            jnp.zeros((n_layers, n_slots, 2, width), jnp.float32))


def write_pooled(state: State, slot, pooled_rows, sum_rows) -> State:
    """A prefill's pooled keys `[n_layers, W / stride, width]` into slot
    `slot`'s first rows, and its running sums `[n_layers, 2, width]`."""
    pooled, sums = state
    with jax.named_scope("state_write"):
        return (jax.lax.dynamic_update_slice(
                    pooled, pooled_rows.astype(pooled.dtype)[:, None],
                    (0, slot, 0, 0)),
                sums.at[:, slot].set(sum_rows.astype(sums.dtype)))


def pooled_step_layer(state: State, layer, w, active, k,
                      sizes: "sparse_ops.BlockSparse"
                      ) -> Tuple[jax.Array, State]:
    """A decode step's keys `[n_slots, kv_heads, head_dim]` at positions
    `w` into one layer's pooled keys (`compress_step`; an idle slot's rows
    stay) -> (the layer's pooled keys `[n_slots, entries, width]` with this
    step's in; the state)."""
    pooled, sums = state
    with jax.named_scope("compress"):
        rows, moved = sparse_ops.compress_step(
            pooled[layer], sums[layer], k.reshape(k.shape[0], -1), w, active,
            sizes)
        return rows, (pooled.at[layer].set(rows), sums.at[layer].set(moved))


# ---------------------------------------------------------------------------
# A window layer's ring
# ---------------------------------------------------------------------------

_ROWS = 16      # a packed bfloat16 tile's rows


def empty_window(n_layers: int, n_slots: int, kv_heads: int, window: int,
                 head_dim: int, v_head_dim: int, dtype) -> State:
    """-> (kw, vw), zeroed."""
    rows = -(-window // _ROWS) * _ROWS
    return tuple(jnp.zeros((n_layers, n_slots, kv_heads, rows, _lanes(d)),
                           dtype)
                 for d in (head_dim, v_head_dim))


def write_window_prompt(state: State, slot, length, ks, vs,
                        in_bounds: bool = False) -> State:
    """A prefill's keys `[n_layers, W, kv_heads, head_dim]` and values into
    slot `slot`'s rings (`slot`, `length` traced scalars): row r takes the
    largest position under `length` that is r modulo R, or zeros where the
    prompt has none. `in_bounds`: the caller's word that `slot` is a slot,
    so that the write is an update in place that reads nothing of what it
    replaces (a scatter keeps the old rows of an index out of bounds, and to
    read ONE slot's the compiler lays ALL the rings out anew where they come
    out of a loop: 0.8 GB copied a riding prefill at Laguna's sizes, compiled
    for a v5e, PR 64)."""
    kw, vw = state
    R = kw.shape[3]
    last = length - 1
    at = last - (last - jnp.arange(R)) % R                  # [R]
    reached = (at >= 0)[None, None, :, None]

    def tail(rows, ring):       # [L, W, KVH, d] -> [L, KVH, R, lanes]
        rows = rows[:, jnp.clip(at, 0, rows.shape[1] - 1)].transpose(
            0, 2, 1, 3)
        return jnp.where(reached, _to_width(rows, ring), 0)

    def put(ring, rows):
        if in_bounds:
            return jax.lax.dynamic_update_slice(ring, rows[:, None],
                                                (0, slot, 0, 0, 0))
        return ring.at[:, slot].set(rows)

    with jax.named_scope("window_write"):
        return put(kw, tail(ks, kw)), put(vw, tail(vs, vw))


def write_window_token(state: State, layer, w, active, k, v) -> State:
    """One decode step's k `[n_slots, kv_heads, head_dim]` and v into row
    `w % R` of each ACTIVE slot's ring of one layer; the others' stay."""
    kw, vw = state
    R = kw.shape[3]
    with jax.named_scope("window_write"):
        here = ((jnp.arange(R) == (w % R)[:, None])
                & active[:, None])[:, None, :, None]
        return (kw.at[layer].set(jnp.where(
                    here, _to_width(k, kw)[:, :, None], kw[layer])),
                vw.at[layer].set(jnp.where(
                    here, _to_width(v, vw)[:, :, None], vw[layer])))


def window_decode_attention(q, state: State, layer, w, active, *,
                            window: int, sm_scale: float,
                            sink: Optional[jax.Array] = None) -> jax.Array:
    """ONE query token a slot, at position `w` `[n_slots]`, against its ring
    of one window layer and nothing else: q `[n_slots, H, lanes(head_dim)]`
    laid out as the ring's keys are, query head h reading kv head `h // (H //
    KVH)`; `sink` `[H]` one further logit a head in the softmax, which
    carries no value. -> float32 `[n_slots, H, lanes(v_head_dim)]`, zeros for
    an idle slot. An XLA program (counted as `window_decode_reference`): a
    slot's ring is R rows, a block of one."""
    kw, vw = state
    ns, H, _ = q.shape
    KVH, R = kw.shape[2], kw.shape[3]
    attention._path_counts["window_decode_reference"] += 1
    qg = _to_width(q, kw).reshape(ns, KVH, H // KVH, -1)
    scores = jnp.einsum("nkgd,nkrd->nkgr", qg, kw[layer],
                        preferred_element_type=jnp.float32) * sm_scale
    age = (w[:, None] - jnp.arange(R)) % R                  # [ns, R]
    live = (age < window) & (age <= w[:, None]) & active[:, None]
    scores = jnp.where(live[:, None, None, :], scores, DEFAULT_MASK_VALUE)
    if sink is not None:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, KVH, H // KVH, 1),
            (ns, KVH, H // KVH, 1))
        scores = jnp.concatenate([scores, col], axis=-1)
    p = jax.nn.softmax(scores, axis=-1)[..., :R]
    # What a dead row holds is masked out of v too: 0 x NaN is NaN.
    vh = jnp.where(live[:, None, :, None], vw[layer], 0).astype(jnp.float32)
    out = jnp.einsum("nkgr,nkrd->nkgd", p, vh,
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where(active[:, None, None], out.reshape(ns, H, -1), 0.0)

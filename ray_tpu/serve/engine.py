"""Continuous-batching LLM decode engine with a PAGED KV cache.

The TPU-native answer to the reference's vLLM delegation (reference:
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:170 —
engine_kwargs feed vLLM's continuous batcher + paged attention; here the
engine is OURS):

- **Paged KV cache**, kept behind two names. The DEVICE side is
  `ops/paged_kv.py`: the arena's layout, the null page, and the only ops
  on it (`empty`, `write_prompt`, `write_token`, `paged_decode_attention`).
  The HOST side is `serve/page_pool.py::PagePool`: which physical pages
  each slot holds, the reservation rule, and the block table
  `[n_slots, max_pages]` a decode chunk is handed (vLLM's block-table
  design). This module lays nothing out and does no page arithmetic. What
  it owes the cache: the decode program updates the arena IN PLACE, as a
  loop carry that nothing but those ops touches, so no copy of it (or of a
  layer's slab) is ever made (see `_step`). A model with a sparse-attention
  indexer (`mcfg.index_topk`) has a third array under the same block table,
  its indexer keys (`ic`, `paged_kv.empty_index`): the programs take and
  return it after everything else, and it is None for every other model.
  A model with state-space layers (`mcfg.ssm_state`) keeps K and V for its
  attention layers only, the arena's layers being their ordinals, and beside
  it a per-SLOT recurrent state of fixed size (`ops/slot_state.py`): no
  pages, overwritten whole by the prefill that admits a request into the
  slot, moved by a decode step only where the slot is active, carried and
  donated as the arena is; `state`, the programs' last argument and result,
  None for every other model. Its stack is not a scan over identical layers
  but SEGMENTS (`LlamaConfig.segments`): a scan over each run of state-space
  layers, the attention layers between them inline.
  A model with latent attention (`mcfg.latent`) keeps ONE row a position a
  layer (`paged_kv.empty_latent`) where the others keep K and V: its arena
  takes `kc`'s place in every program, `vc` is None, and nothing else of the
  engine knows. Its stack runs as segments too (leading dense layers, then
  the sparse ones, a scan each).
  A model of window and full attention layers (`mcfg.mixed`) has TWO caches
  of two shapes: pages under the block table for its full-attention layers
  alone (`kc`, `vc`, keys wider than values), and for its window layers a
  ring of the last `window` positions a slot (`ops/slot_state.py`), which
  takes `state`'s place in every program: written by the prefill that admits
  a request (the prompt's tail) and by each decode step, the same size at
  200 positions as at 8,000. `PagePool` reserves for the full layers alone.
  Segments by kind (`dense`, `window`, `layers`), a scan each.
- **Reservation admission**: a request is admitted when the pages
  `PagePool.pages_for` says it can ever need are free: growth can then
  never fail mid-decode, so there is no preemption/recompute path.
  Requests queue FIFO while pages are short; finishing requests return
  their pages.
- **Sync-free dispatch loop + emitter thread**: the engine loop ONLY
  dispatches device work (prefills, decode chunks, slot pokes) — every
  host<->device sync (fetching first tokens and chunk outputs) happens
  on a separate EMITTER thread consuming a FIFO. Slot/page control
  state advances deterministically on the host (token VALUES are the
  only device-dependent output), so chunks dispatch back-to-back and
  admissions slot in mid-pipeline; the host<->device round-trip is paid
  off the critical path. The pipeline's depth is a count the loop owns:
  it dispatches a decode chunk only while fewer than `_DEPTH` are in
  flight (dispatched, output not yet fetched by the emitter), and the
  wait for room is one a submit ends, so an arrival with a free slot is
  admitted at once and its prefill queues behind at most `_DEPTH` chunks.
  One fixed-shape XLA program serves every step (no recompiles).

A small fixed set of compiled programs serves all traffic: one prefill
per BUCKET width of a ladder (`prefill_widths`: doubling, and a quarter of
an octave apart in the octave under max_seq, so a short prompt pays a short
prefill — the TTFT lever — and a long one pads by a fifth at most, not by
half; the smallest and every rung wider than half of max_seq warmed before
the loop starts, the rest on a background thread, and until a width is warm
a prompt rounds UP to the next one that is: nothing compiles inside the loop),
an `adopt` twin for a PD handoff at each of the doubling widths
(`doubling_widths`) where the engine is a decode pool's (`adopts`), the
n-step decode chunk over all slots, and the slot poke.

- **Riders**: a prompt is prefilled whole while the live slots wait, and
  what they wait to do is a decode step, which is weight reads that the
  prefill of the same layers makes anyway. So where a prompt leaves
  `n_slots` rows of its bucket free, the prefill program of a riding rung
  (`rung_rides`: the octave under max_seq, of a dense or a sparse stack)
  carries ONE decode step of every live slot in those rows
  (`_make_prefill_core`); on the host the riders advance as a chunk of one
  step would (`_ride_plan`, `_place`), and the emitter streams their tokens
  after the prompt's first. Who rides is read off the stack and the shapes:
  no option, field or environment variable.
"""

from __future__ import annotations

import functools
import queue
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.serve.page_pool import PagePool
from ray_tpu.utils import get_logger, tracing

logger = get_logger("serve.engine")


# Rows of a prefill that meet the sparse feed-forward at once: its sorted
# copies are `rows x experts a token` wide (2.5 GiB of temporaries at 4,096
# rows of OLMoE's widths), so a wider bucket goes through in blocks of this
# many rows; each row is computed from itself alone, so nothing changes.
_MOE_ROWS = 4096

# Decode chunks in flight (dispatched, output not yet fetched) beyond which the
# loop dispatches no other: one executing and one queued behind it. The second
# is all that keeps the device from idling between chunks (the host's share of
# a 64 ms chunk is about 3 ms); every one beyond it buys nothing and costs each
# arrival a chunk of waiting before its prefill. PERF.md section 6, PR 33, has
# the measurement, depth 1's included.
_DEPTH = 2

# Smallest prefill width; the widths double from here (`doubling_widths`).
_MIN_BUCKET = 32
# No rung of the prefill ladder is closer to the one before than this many
# rows (`prefill_widths`), so that every rung between doubling widths is a
# multiple of it: `ops/ssm.py` walks a sequence in blocks of 512 rows, and
# `ops/attention.py::_pick_block` halves its preferred 1024 until it divides
# the width, so the flash kernels (and `ops/sparse_attention.py`'s two) keep
# blocks of 512 or 1024 rows, where a width of 1280 or 1792 would leave 256.
_MIN_RUNG_GAP = 512


def doubling_widths(max_seq: int) -> List[int]:
    """`_MIN_BUCKET` doubled while it is under `max_seq`, then `max_seq`: the
    widths a PD handoff arrives at (a `PrefillServer` pads a prompt to one of
    these) and so the widths of the engine's `adopt` programs."""
    widths = []
    b = min(_MIN_BUCKET, max_seq)
    while b < max_seq:
        widths.append(b)
        b *= 2
    return widths + [max_seq]


def prefill_widths(max_seq: int) -> List[int]:
    """The engine's ladder of prefill widths, a function of `max_seq` alone:
    `doubling_widths`, and between the last two of them (the octave under a
    `max_seq` that is a power of two) rungs a quarter of the lower one apart
    but `_MIN_RUNG_GAP` rows at least (for 4096: 2048, 2560, 3072, 3584,
    4096; for 8192: 4096, 5120, 6144, 7168, 8192; for 2048: 1024, 1536,
    2048). Every prefill operation computes the bucket's padding like any
    row, so a prompt in that octave pads by a fifth of its bucket at most (a
    third at the least gap) where doubling allowed a half. The top octave
    alone, because a rung is not free: a program more to trace, read from the
    compile cache and load at every start (0.7-1.6 s each at the benchmark's
    widths, 6-12 s where it has to compile: PERF.md section 6, PR 36) and
    8-16 MB of device memory for its executable; the longest prompts are
    where padding costs most rows, and what a deployment sizes `max_seq`
    for."""
    doubling = doubling_widths(max_seq)
    top = doubling[-2] if len(doubling) > 1 else max_seq
    step = max(top // 4, _MIN_RUNG_GAP)
    return sorted({*doubling, *range(top, max_seq, step)})


def rung_rides(max_seq: int, n_slots: int, width: int) -> bool:
    """Whether the prefill program of this width carries the live slots
    (`_make_prefill_core`, riders): the rungs of the octave under `max_seq`,
    where a prefill is long enough for a decode step's weight reads to hide
    in it and where the long prompts of a batch land, and none narrower (a
    riding program holds a decode step's attention kernel and a sampler over
    the slots' rows, traced, lowered and loaded at every start). The slots'
    rows have to fit in the rung beside a prompt."""
    return 2 * width >= max_seq and n_slots < width


def _make_prefill_core(mcfg):
    """fn(params, tokens[1, B], length) -> (first_token, ks, vs, the last
    position's logits, experts) where ks/vs are [L, B, KVH, hd] and `experts`
    is None for a dense model, else `models.block.expert_stats` of the
    prompt's tokens summed over the layers — the shared prefill pass used
    by the in-engine prefill AND the disaggregated PrefillServer (reference:
    llm/_internal/serve/deployments/prefill_decode_disagg/ — there the
    split is two vLLM pools; here both halves share one traced core). A
    model with a sparse-attention indexer adds a sixth element, its indexer
    keys [L, B, Id]; a model with state-space layers, after `experts`
    (None), its layers' final (ssm state [Lm, N, Di], convolution window
    [Lm, K - 1, Di]) after the prompt's last real token, and its ks/vs are
    those of the attention layers alone.

    RIDERS. A dense or a sparse stack's core (`core.takes_riders`; an
    indexed, a hybrid and a latent stack take nobody) also runs as
    fn(params, tokens, length, arena=(kc, vc), riders=(bt, last, pos,
    riding)): ONE decode step of the slots `riding` marks [n_slots], in the
    bucket's last n_slots rows, which the prompt has to leave free. Slot i's
    last token is embedded in row B - n_slots + i and rotated at its own
    position; per layer the tail rows' q, k and v do what a decode step does
    (the row written to the slot's page, the `paged_decode` kernel against
    the arena) and the result takes the tail of the flash output's place; the
    feed-forward and the head run over the bucket as they do anyway, so the
    step's weight reads are the prefill's. Returns (first, ks, vs, logits
    [1 + n_slots, V]: the prompt's last row, then the tail rows; experts,
    counting the riding rows; (kc, vc))."""
    if mcfg.ssm_state:
        return _make_hybrid_prefill_core(mcfg)
    if mcfg.latent:
        return _make_latent_prefill_core(mcfg)
    if mcfg.mixed:
        return _make_mixed_prefill_core(mcfg)
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (attention_inputs, expert_stacks,
                                      expert_stats, feed_forward)
    from ray_tpu.ops.attention import flash_attention, repeat_kv
    from ray_tpu.ops.norms import (apply_rope, mrope_tables, rms_norm,
                                   rope_frequencies)
    from ray_tpu.ops.paged_kv import paged_decode_attention, write_token
    from ray_tpu.ops.sparse_attention import sparse_attention

    H, KVH, hd = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim
    dt = mcfg.dtype
    sparse = mcfg.n_experts > 0
    indexed = mcfg.index_topk > 0

    def _feed_forward(lp, x, live, l):
        """`feed_forward` over at most `_MOE_ROWS` rows at a time."""
        Sq = x.shape[1]
        if not sparse or Sq <= _MOE_ROWS:
            return feed_forward(lp, x, mcfg, live, l)
        outs, counts = [], 0
        for start in range(0, Sq, _MOE_ROWS):
            rows = slice(start, start + _MOE_ROWS)
            y, (_, n) = feed_forward(lp, x[:, rows], mcfg, live[:, rows], l)
            outs.append(y)
            counts = counts + n
        return jnp.concatenate(outs, axis=1), (None, counts)

    @jax.jit
    def _token_step(kc, vc, l, bt, w, act, q, k, v):
        """One token a slot against the cache: a step's k and v `[n_slots,
        kv_heads, hd]` written at the slots' positions `w`, then each active
        slot's q `[n_slots, heads, hd]` against its positions 0..w (an idle
        slot reads nothing). A jit of its own, and ONE for the riders and for
        the decode program's layers (`_build_fns` takes it from
        `core.token_step`): no prefill width enters its shapes, so the kernel
        is traced once a process, not once a riding rung and again for
        decode (a second of every start, each, on the chip's host: PERF.md
        section 6, PR 41)."""
        kc, vc = write_token(kc, vc, l, bt, w, act, k, v)
        with jax.named_scope("attn"):
            attn = paged_decode_attention(q, kc, vc, l, bt,
                                          jnp.where(act, w + 1, 0))
            attn = attn.reshape(q.shape[0], H * hd)
        return kc, vc, attn

    def _prefill_layer(stacks, riders, carry, layer):
        # `rest`: an indexed stack's own rotary tables or, with riders (an
        # indexed stack takes none), the arena, which rides the carry as it
        # does in decode (`_build_fns`' `_step` says why).
        x, cos, sin, live, *rest = carry
        lp, l = layer if sparse or riders else (layer, None)
        lp = dict(lp, **stacks)
        B, Sq, _ = x.shape
        q, k, v, *index = attention_inputs(
            lp, x, mcfg, lambda t: apply_rope(t, cos, sin),
            (lambda t: apply_rope(t, *rest)) if indexed else None)
        with jax.named_scope("attn"):
            if indexed:
                qi, ki, w = index[0]
                attn = sparse_attention(q, k, v, qi.transpose(0, 2, 1, 3),
                                        ki[:, 0], w, mcfg.index_topk)
            else:
                attn = flash_attention(q, repeat_kv(k, H // KVH),
                                       repeat_kv(v, H // KVH), True)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, H * hd)
        if riders:
            bt, w, act = riders
            tail = slice(Sq - act.shape[0], Sq)
            *rest, rode = _token_step(
                *rest, l, bt, w, act, *(t[0, :, tail].transpose(1, 0, 2)
                                        for t in (q, k, v)))
            attn = attn.at[0, tail].set(
                jnp.where(act[:, None], rode, attn[0, tail]))
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        x, routed = _feed_forward(lp, x, live, l if sparse else None)
        # cache pre-repeat k/v: [S, KVH, hd] (B == 1 squeezed)
        ys = (k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))
        if sparse:
            ys += (expert_stats(routed[1]),)
        if indexed:
            ys += (ki[0, 0],)                                  # [S, Id]
        return (x, cos, sin, live, *rest), ys

    def core(params, tokens, length, arena=None, riders=None):
        if riders is not None:
            return riding_core(params, tokens, length, arena, riders)
        width = tokens.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        with jax.named_scope("rope"):
            cos, sin = rope_frequencies(hd, width, mcfg.rope_theta)
            if mcfg.mrope_section:      # text: the three streams are equal
                cos, sin = mrope_tables(
                    cos, sin, jnp.broadcast_to(jnp.arange(width), (3, width)),
                    mcfg.mrope_section)
            itables = rope_frequencies(mcfg.index_head_dim, width,
                                       mcfg.rope_theta) if indexed else ()
        # The bucket's padding is computed like any row, each from itself
        # alone (no capacity for it to take), and left out of the count.
        live = (jnp.arange(width)[None] < length) if sparse else None
        # The experts' stacks stay whole (`expert_stacks`): the scan slices
        # the rest, and carries the layer's index for them.
        sliced, stacks = expert_stacks(params["layers"], mcfg)
        if sparse:
            sliced = (sliced, jnp.arange(mcfg.n_layers))
        with jax.named_scope("layers"):
            (x, *_), (ks, vs, *more) = jax.lax.scan(
                functools.partial(_prefill_layer, stacks, None),
                (x, cos, sin, live, *itables), sliced)
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = jnp.einsum("bd,dv->bv", last_h,
                                params["lm_head"].astype(dt))
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        experts = jnp.sum(more[0], axis=0) if sparse else None
        out = (first, ks, vs, logits[0].astype(jnp.float32), experts)
        return out + ((more[-1],) if indexed else ())

    def riding_core(params, tokens, length, arena, riders):
        """`core` with the live slots in the bucket's tail rows (see
        `_make_prefill_core`). A row that does not ride is the prompt's or
        padding, as without riders."""
        bt, last, pos, riding = riders
        width, ns, S = tokens.shape[1], riding.shape[0], mcfg.max_seq
        tail = slice(width - ns, width)
        act = riding & (pos < S)
        w = jnp.minimum(pos, S - 1)
        rows = jnp.arange(width)
        with jax.named_scope("embed"):
            tokens = tokens.at[0, tail].set(
                jnp.where(act, last, tokens[0, tail]))
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        with jax.named_scope("rope"):
            # A row's own position: the prompt's run 0.., a rider's is its
            # slot's, anywhere under max_seq.
            at = rows.at[tail].set(jnp.where(act, w, rows[tail]))
            cos, sin = (t[at] for t in
                        rope_frequencies(hd, S, mcfg.rope_theta))
        live = ((rows < length) | jnp.zeros(width, bool).at[tail].set(act)
                )[None] if sparse else None
        sliced, stacks = expert_stacks(params["layers"], mcfg)
        with jax.named_scope("layers"):
            (x, _, _, _, kc, vc), (ks, vs, *more) = jax.lax.scan(
                functools.partial(_prefill_layer, stacks, (bt, w, act)),
                (x, cos, sin, live, *arena),
                (sliced, jnp.arange(mcfg.n_layers)))
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = jnp.einsum(
                "bd,dv->bv", jnp.concatenate([last_h, x[0, tail]]),
                params["lm_head"].astype(dt))
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        experts = jnp.sum(more[0], axis=0) if sparse else None
        return (first, ks, vs, logits.astype(jnp.float32), experts, (kc, vc))

    core.takes_riders = not indexed
    core.token_step = _token_step
    return core


def _make_hybrid_prefill_core(mcfg):
    """`_make_prefill_core` for a hybrid stack: the segments in order, a scan
    over each run of state-space layers (the stacks stay whole, the body reads
    its layer by index, as a scan reads its `xs`) and each attention layer
    inline. Rows past `length` reach no real row: attention and the
    convolution are causal, and the state-space layers are told `length`."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (attention_inputs, feed_forward,
                                      mamba_mixer)
    from ray_tpu.ops.attention import flash_attention, repeat_kv
    from ray_tpu.ops.norms import apply_rope, rms_norm, rope_frequencies

    if mcfg.n_experts or mcfg.index_topk:
        raise NotImplementedError(
            "a hybrid stack serves a dense feed-forward and plain attention")
    H, KVH, hd = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim
    dt = mcfg.dtype

    def attention_layer(lp, x, rope):
        B, Sq, _ = x.shape
        q, k, v = attention_inputs(lp, x, mcfg, rope)
        with jax.named_scope("attn"):
            attn = flash_attention(q, repeat_kv(k, H // KVH),
                                   repeat_kv(v, H // KVH), True)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, H * hd)
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        x, _ = feed_forward(lp, x, mcfg)
        return x, k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2)

    def core(params, tokens, length):
        if "wqkv" not in params["layers"]:
            raise ValueError("a serving program takes `fuse_qkv(params)`")
        width = tokens.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        if mcfg.rope:
            with jax.named_scope("rope"):
                cos, sin = rope_frequencies(hd, width, mcfg.rope_theta)
            rope = lambda t: apply_rope(t, cos, sin)
        else:
            rope = lambda t: t

        def mamba_layer(x, i):
            lp = _layer_of(params["mamba"], i)
            y, state, window = mamba_mixer(lp, x[0], mcfg, length=length)
            y, _ = feed_forward(lp, y[None], mcfg)
            return y, (state, window)

        ks, vs, states, windows = [], [], [], []
        with jax.named_scope("layers"):
            for kind, lo, hi in mcfg.segments():
                if kind == "attn":
                    x, k, v = attention_layer(
                        _layer_of(params["layers"], lo), x, rope)
                    ks.append(k)
                    vs.append(v)
                else:
                    x, (state, window) = jax.lax.scan(
                        mamba_layer, x, jnp.arange(lo, hi))
                    states.append(state)
                    windows.append(window)
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = _head_logits(params, last_h, mcfg)
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        return (first, jnp.stack(ks), jnp.stack(vs),
                logits[0].astype(jnp.float32), None,
                (jnp.concatenate(states), jnp.concatenate(windows)))

    core.takes_riders = False
    return core


def _latent_rope_tables(mcfg, width):
    """(cos, sin) [width, qk_rope_dim // 2] of a latent-attention model."""
    from ray_tpu.ops.norms import rope_frequencies, yarn_frequencies
    if mcfg.rope_yarn:
        return yarn_frequencies(mcfg.qk_rope_dim, width, mcfg.rope_theta,
                                *mcfg.rope_yarn[:4])
    return rope_frequencies(mcfg.qk_rope_dim, width, mcfg.rope_theta)


def _share_stats(counts, live, mcfg):
    """One sparse layer's routing as a latent-attention program hands it
    back, `[held + 2]` int32 that add up: `expert_stats` of the HELD experts
    (tokens per expert, then the distinct ones touched), then the
    assignments the router made of the live rows, to whichever share."""
    import jax.numpy as jnp

    from ray_tpu.models.block import expert_stats
    routed = jnp.sum(live, dtype=jnp.int32) * mcfg.top_k_experts
    return jnp.concatenate([expert_stats(counts), routed[None]])


def _make_latent_prefill_core(mcfg):
    """`_make_prefill_core` for latent attention (MLA): the segments in order
    (`LlamaConfig.segments`: the leading dense layers, then the sparse ones),
    a scan over each; `ks` is what the cache keeps, `[L, B, rank + dr]` (the
    normed latent, then the rotated shared key), and `vs` None.
    `experts` is `_share_stats` summed over the sparse layers."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (expert_stacks, feed_forward,
                                      latent_attention_inputs)
    from ray_tpu.ops.attention import latent_flash_attention
    from ray_tpu.ops.norms import apply_rope, rms_norm

    dt = mcfg.dtype
    sparse = mcfg.n_experts > 0

    def layer_fn(stacks, tables, live, x, layer):
        lp, l = layer
        routed_layer = "router" in lp
        lp = dict(lp, **stacks)
        B, Sq, _ = x.shape
        q_n, q_r, k_n, v, c, kr = latent_attention_inputs(
            lp, x, mcfg, lambda t: apply_rope(t, *tables))
        with jax.named_scope("attn"):
            attn = latent_flash_attention(q_n, q_r, k_n, kr, v,
                                          mcfg.softmax_scale)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, -1)
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        # A share's sparse half meets a quarter of `rows x experts a token`
        # rows at once (`ops.moe._share_experts`): no blocks of `_MOE_ROWS`.
        x, routed = feed_forward(lp, x, mcfg, live,
                                 l if routed_layer else None)
        ys = (c[0], kr[0])                        # [S, rank], [S, dr]
        if routed_layer:
            ys += (_share_stats(routed[1], live, mcfg),)
        return x, ys

    def core(params, tokens, length):
        width = tokens.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        with jax.named_scope("rope"):
            tables = _latent_rope_tables(mcfg, width)
        live = jnp.arange(width)[None] < length
        rows, experts = [], None
        with jax.named_scope("layers"):
            for kind, lo, hi in mcfg.segments():
                sliced, stacks = expert_stacks(params[kind], mcfg)
                x, (c, kr, *stats) = jax.lax.scan(
                    functools.partial(layer_fn, stacks, tables, live), x,
                    (sliced, jnp.arange(lo, hi)))
                rows.append(jnp.concatenate([c, kr], axis=-1))
                if stats:
                    experts = jnp.sum(stats[0], axis=0)
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = _head_logits(params, last_h, mcfg)
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        return (first, jnp.concatenate(rows), None,
                logits[0].astype(jnp.float32), experts if sparse else None)

    core.takes_riders = False
    return core


def _mixed_rope_tables(mcfg, width):
    """{stack: (cos, sin) [width, rotary_dim // 2]} of a mixed-attention
    model: each kind of attention turns at its own theta."""
    from ray_tpu.ops.norms import rope_frequencies
    return {kind: rope_frequencies(mcfg.rotary_dim, width,
                                   mcfg.attention_kind(kind)[1])
            for kind, _, _ in mcfg.segments()}


def _make_mixed_prefill_core(mcfg):
    """`_make_prefill_core` for a stack of window and full attention layers
    (`mcfg.attn_pattern`): the segments in order (`LlamaConfig.segments`:
    `dense`, `window`, `layers`), a scan over each. `ks`, `vs` are what the
    pages keep, the FULL layers' `[Lf, B, KVH, head_dim]` (a key `[k_n ;
    k_r]`) and `[Lf, B, KVH, v_head_dim]`; after `experts` (`_share_stats`
    summed over the sparse layers) come the window layers' (ks, vs) at THEIR
    kv heads, of which a slot's ring keeps the prompt's tail
    (`slot_state.write_window_prompt`)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (expert_stacks, feed_forward,
                                      mixed_attention_inputs)
    from ray_tpu.ops.attention import mixed_flash_attention
    from ray_tpu.ops.norms import apply_rope_narrow, rms_norm

    dt = mcfg.dtype
    sparse = mcfg.n_experts > 0

    def layer_fn(kind, stacks, tables, live, x, layer):
        lp, l = layer
        routed_layer = "router" in lp
        lp = dict(lp, **stacks)
        B, Sq, _ = x.shape
        _, _, window, sink = mcfg.attention_kind(kind)
        q_n, q_r, k_n, k_r, v = mixed_attention_inputs(
            lp, x, mcfg, kind, lambda t: apply_rope_narrow(t, *tables))
        with jax.named_scope("attn"):
            # The kernel and nothing else: what a roofline counts is read
            # inside the scope that times it.
            with jax.named_scope("window_attn" if window else "full_attn"):
                attn = mixed_flash_attention(
                    q_n, q_r, k_n, k_r, v, mcfg.softmax_scale, window=window,
                    sink=lp["sink"] if sink else None)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, -1)
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        x, routed = feed_forward(lp, x, mcfg, live,
                                 l if routed_layer else None)
        ys = (jnp.concatenate([k_n, k_r], -1)[0].transpose(1, 0, 2),
              v[0].transpose(1, 0, 2))            # [S, KVH, dk], [S, KVH, dv]
        if routed_layer:
            ys += (_share_stats(routed[1], live, mcfg),)
        return x, ys

    def core(params, tokens, length):
        width = tokens.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        with jax.named_scope("rope"):
            tables = _mixed_rope_tables(mcfg, width)
        live = jnp.arange(width)[None] < length
        kept = {"full": ([], []), "window": ([], [])}
        experts = 0
        with jax.named_scope("layers"):
            for kind, lo, hi in mcfg.segments():
                sliced, stacks = expert_stacks(params[kind], mcfg)
                x, (k, v, *stats) = jax.lax.scan(
                    functools.partial(layer_fn, kind, stacks, tables[kind],
                                      live), x,
                    (sliced, jnp.arange(lo, hi)))
                ks, vs = kept["window" if kind == "window" else "full"]
                ks.append(k)
                vs.append(v)
                if stats:
                    experts = experts + jnp.sum(stats[0], axis=0)
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            logits = _head_logits(params, last_h, mcfg)
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        (ks, vs), (kws, vws) = (tuple(jnp.concatenate(t) for t in kept[k])
                                for k in ("full", "window"))
        return (first, ks, vs, logits[0].astype(jnp.float32),
                experts if sparse else None, (kws, vws))

    core.takes_riders = False
    return core


def _layer_of(stack, i):
    """Layer `i` of a stack of layers (a leading axis on every leaf): what a
    scan over the stack hands its body, read by index."""
    import jax
    return jax.tree.map(lambda w: w[i], stack)


def _head_logits(params, h, mcfg):
    """h [rows, D] -> logits [rows, V]: the head, or the embedding transposed
    where the model ties them."""
    import jax.numpy as jnp
    if mcfg.tie_embeddings:
        return jnp.einsum("bd,vd->bv", h, params["embed"].astype(mcfg.dtype))
    return h @ params["lm_head"].astype(mcfg.dtype)


# Compile-time cap on per-request top_k (jax.lax.top_k needs a static
# width; requests asking for more sample from the best TOPK_CAP).
TOPK_CAP = 64


def _sample_tokens(logits, temp, topk, keys, pos, cap=TOPK_CAP):
    """Per-slot token sampling (reference: vLLM's sampler): temperature
    + top-k via Gumbel-max over the top-`cap` logits (cap is a static
    trace-time width, min(TOPK_CAP, vocab)); temp==0 slots stay greedy.
    `keys` are per-slot base PRNG keys; folding in `pos` makes a
    request's sample stream deterministic for its (seed, position)
    regardless of slot assignment or co-tenants."""
    import jax
    import jax.numpy as jnp

    cap = min(cap, logits.shape[-1])

    def one_gumbel(key, p):
        return jax.random.gumbel(jax.random.fold_in(key, p), (cap,))

    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        vals, idxs = jax.lax.top_k(logits.astype(jnp.float32), cap)
        k_eff = jnp.where(topk > 0, jnp.minimum(topk, cap), cap)
        mask = jnp.arange(cap)[None, :] < k_eff[:, None]
        scaled = jnp.where(mask, vals / jnp.maximum(temp, 1e-6)[:, None],
                           -1e30)
        g = jax.vmap(one_gumbel)(keys, pos)
        pick = jnp.argmax(scaled + g, axis=-1)
        sampled = jnp.take_along_axis(idxs, pick[:, None], axis=1)[:, 0]
        return jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)


def _build_fns(mcfg, n_slots: int, chunk: int, page: int, n_pages: int):
    """Build (prefill_jit, decode_jit, adopt_jit, poke_jit, empty_caches)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import (attention_inputs, expert_stacks,
                                      expert_stats, feed_forward,
                                      latent_attention_inputs,
                                      latent_attention_output, mamba_mixer,
                                      mixed_attention_inputs)
    from ray_tpu.ops.norms import mrope_tables, rms_norm, rope_frequencies
    from ray_tpu.ops.paged_kv import (empty, empty_index, empty_latent,
                                      latent_rows, paged_decode_attention,
                                      paged_latent_decode, write_prompt,
                                      write_prompt_rows, write_token,
                                      write_token_rows)
    from ray_tpu.ops.slot_state import (empty_state, empty_window,
                                        layer_state, update_layer,
                                        window_decode_attention, write_state,
                                        write_window_prompt,
                                        write_window_token)
    from ray_tpu.ops.sparse_attention import sparse_decode_attention

    sparse = mcfg.n_experts > 0
    indexed = mcfg.index_topk > 0
    hybrid = mcfg.ssm_state > 0
    latent = mcfg.latent
    mixed = mcfg.mixed
    S = mcfg.max_seq
    H, KVH, hd = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim
    dt = mcfg.dtype
    ns = n_slots

    def empty_caches():
        """-> (kc, vc), the arena of the layers that keep K and V; after
        them ic for a model with an indexer, or the recurrent state
        (`ops/slot_state.py`) for one with state-space layers. A model with
        latent attention: (its arena of latent rows, None); one of window
        and full attention layers: the full layers' arena, then the window
        layers' rings (`ops/slot_state.py`), which take `state`'s place."""
        if latent:
            return (empty_latent(mcfg.n_layers, n_pages, page,
                                 mcfg.latent_width, dt), None)
        if mixed:   # pages for the full layers, a ring a slot for the rest
            return empty(mcfg.kv_layers, n_pages, KVH, page, hd, dt,
                         v_head_dim=mcfg.v_head_dim) + (empty_window(
                mcfg.n_layers - mcfg.kv_layers, ns, mcfg.window_kv_heads,
                mcfg.window, hd, mcfg.v_head_dim, dt),)
        kv = empty(mcfg.kv_layers, n_pages, KVH, page, hd, dt,
                   by_token=indexed)
        if indexed:
            kv += (empty_index(mcfg.n_layers, n_pages, page,
                               mcfg.index_head_dim, dt),)
        if hybrid:
            kv += (empty_state(mcfg.n_layers - mcfg.kv_layers, ns,
                               mcfg.ssm_state, mcfg.ssm_inner, mcfg.ssm_conv,
                               dt),)
        return kv

    # ------------------------------------------------------------------
    # prefill: full causal pass over ONE padded prompt, k/v -> pages
    # ------------------------------------------------------------------
    _core = _make_prefill_core(mcfg)

    def prefill(params, kc, vc, pages, tokens, length, temp, topk, key,
                ic=None, state=None, slot=None, last=None, pos=None,
                riders=None):
        """tokens [1, B] padded to a BUCKET width (a rung of
        `prefill_widths` — jax.jit compiles one program per bucket shape, so
        a prompt pays a prefill of about its own length, not a max_seq one);
        writes the slot's pages, returns the first generated token (sampled,
        or greedy when temp == 0) and the core's `experts` (and `ic`, the
        indexer keys' arena, where the model has one; or `state`, the
        recurrent state with slot `slot`'s rows overwritten by the prompt's
        final ones, where it has state-space layers; or the window layers'
        rings with slot `slot`'s overwritten by the prompt's tail).

        With `riders` = (block table, riding [ns], the slots' temp, topk,
        keys) and the slots' `last` and `pos` (the program of a riding rung,
        `rung_rides`): the core's one decode step of the riding slots, and
        after `experts` come `last` and `pos` moved by it, as a decode chunk
        of one step would leave them, and the tokens [ns] the step sampled
        (a riding slot's is its next one; the others' rows are not slots')."""
        if riders is not None:
            return _riding_prefill(params, kc, vc, pages, tokens, length,
                                   temp, topk, key, last, pos, *riders)
        _, ks, vs, logits_row, experts, *iks = _core(params, tokens, length)
        kc, vc = write_prompt(kc, vc, pages, ks, vs)
        first = _sample_tokens(logits_row[None],
                               jnp.asarray(temp)[None],
                               jnp.asarray(topk)[None], key[None],
                               jnp.asarray(length - 1)[None])[0]
        if indexed:
            return (kc, vc, first, experts,
                    write_prompt_rows(ic, pages, iks[0]))
        if hybrid:
            return kc, vc, first, experts, write_state(state, slot, *iks[0])
        if mixed:
            return kc, vc, first, experts, write_window_prompt(
                state, slot, length, *iks[0])
        return kc, vc, first, experts

    def _riding_prefill(params, kc, vc, pages, tokens, length, temp, topk,
                        key, last, pos, bt, riding, temps, topks, keys):
        _, ks, vs, logits, experts, (kc, vc) = _core(
            params, tokens, length, (kc, vc), (bt, last, pos, riding))
        kc, vc = write_prompt(kc, vc, pages, ks, vs)
        # The prompt's row and the riders' through ONE sampler, each row at
        # its own temperature, key and position, as `_step` samples.
        toks = _sample_tokens(
            logits, jnp.concatenate([jnp.asarray(temp)[None], temps]),
            jnp.concatenate([jnp.asarray(topk)[None], topks]),
            jnp.concatenate([key[None], keys]),
            jnp.concatenate([jnp.asarray(length - 1)[None], pos]))
        act = riding & (pos < S)
        return (kc, vc, toks[0], experts, jnp.where(act, toks[1:], last),
                jnp.where(act, pos + 1, pos), toks[1:])

    def adopt(kc, vc, pages, ks, vs):
        """Write externally-prefilled k/v (a PrefillServer handoff) into
        the slot's pages."""
        return write_prompt(kc, vc, pages, ks, vs)

    # ------------------------------------------------------------------
    # decode: one token for every active slot per step, `chunk` steps
    # ------------------------------------------------------------------
    def _rope_one(x, c, s):
        # x [ns, heads, hd], c/s [ns, 1, hd//2]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
        return out.astype(x.dtype)

    def _decode_layer(x, kc, vc, ic, lp, l, bt, pos, act, cos, sin, itables):
        # x [ns, D]; kc/vc (and ic) the WHOLE arena (`ops.paged_kv`); l this
        # layer's index (traced scalar); bt the block table; a sparse model's
        # expert weights in lp are all the layers' (`expert_stacks`)
        with jax.named_scope("rope"):
            w = jnp.minimum(pos, S - 1)
            if not mcfg.rope:           # attention takes no position signal
                c = s = None
            elif mcfg.mrope_section:    # text: the three streams are equal
                c, s = mrope_tables(cos, sin, jnp.broadcast_to(w, (3, ns)),
                                    mcfg.mrope_section)
                c, s = c[:, None], s[:, None]
            else:
                c = cos[w][:, None]
                s = sin[w][:, None]
            if indexed:     # the indexer's own tables, over its own width
                ci, si = (t[w][:, None] for t in itables)
        q, k, v, *index = attention_inputs(
            lp, x, mcfg,
            (lambda t: _rope_one(t, c, s)) if mcfg.rope else (lambda t: t),
            (lambda t: _rope_one(t, ci, si)) if indexed else None)
        if _core.takes_riders:
            # The write and the kernel as the riders' step has them, traced
            # once for both (`_token_step` in `_make_prefill_core`).
            kc, vc, attn = _core.token_step(kc, vc, l, bt, w, act, q, k, v)
        else:
            kc, vc = write_token(kc, vc, l, bt, w, act, k, v)
            if indexed:
                qi, ki, iw = index[0]
                ic = write_token_rows(ic, l, bt, w, act, ki[:, 0])
            # Each active slot's query against its positions 0..w; an idle
            # slot reads nothing.
            with jax.named_scope("attn"):
                lengths = jnp.where(act, w + 1, 0)
                if indexed:
                    attn = sparse_decode_attention(
                        q, qi, iw, kc, vc, ic, l, bt, lengths,
                        mcfg.index_topk)
                else:
                    attn = paged_decode_attention(q, kc, vc, l, bt, lengths)
                attn = attn.reshape(ns, H * hd)
        with jax.named_scope("attn_out"):
            x = x + attn @ lp["wo"].astype(dt)
        # An idle slot's row is computed like any other, from itself alone,
        # and left out of the count.
        x, routed = feed_forward(lp, x, mcfg, act, l if sparse else None)
        return x, kc, vc, ic, routed

    def _hybrid_layers(params, x, kc, vc, state, bt, pos, act):
        """One token a slot through a hybrid stack's segments: the arena's
        layer is the attention layer's ordinal, the state's the state-space
        layer's; both ride the carry (see `_step`)."""
        def mamba_layer(carry, i):
            x, state = carry
            lp = _layer_of(params["mamba"], i)
            # The state's read and its write back are the update's traffic:
            # under the scope that times the update (`scan`).
            with jax.named_scope("scan"):
                ssm, window = layer_state(state, i)
            x, ssm, window = mamba_mixer(lp, x, mcfg, ssm, window, step=True)
            with jax.named_scope("scan"):
                state = update_layer(state, i, act, ssm, window)
            x, _ = feed_forward(lp, x, mcfg)
            return (x, state), None

        for kind, lo, hi in mcfg.segments():
            if kind == "attn":
                x, kc, vc, _, _ = _decode_layer(
                    x, kc, vc, None, _layer_of(params["layers"], lo), lo, bt,
                    pos, act, None, None, ())
            else:
                (x, state), _ = jax.lax.scan(mamba_layer, (x, state),
                                             jnp.arange(lo, hi))
        return x, kc, vc, state

    def _latent_layers(params, x, kc, experts, bt, pos, act, cos, sin):
        """One token a slot through a latent-attention stack's segments, a
        scan each: the absorbed query against the slot's cached rows
        (`paged_latent_decode`), the step's own row written first. The arena
        rides the carry as K and V do (see `_step`); its layer is the
        layer's place in the whole stack."""
        w = jnp.minimum(pos, S - 1)
        lengths = jnp.where(act, w + 1, 0)
        with jax.named_scope("rope"):
            c, s = cos[w][:, None], sin[w][:, None]

        def body(stacks, base, carry, layer):
            x, kc, experts = carry
            lp, l = layer
            routed_layer = "router" in lp
            lp = dict(lp, **stacks)
            ql, q_r, row, kr = latent_attention_inputs(
                lp, x, mcfg, lambda t: _rope_one(t, c, s), absorb=True)
            kc = write_token_rows(kc, base + l, bt, w, act,
                                  latent_rows(row, kr, kc))
            with jax.named_scope("attn"):
                ol = paged_latent_decode(ql, q_r, kc, base + l, bt, lengths,
                                         sm_scale=mcfg.softmax_scale)
            with jax.named_scope("attn_out"):
                x = x + latent_attention_output(lp, ol, mcfg) \
                    @ lp["wo"].astype(dt)
            x, routed = feed_forward(lp, x, mcfg, act,
                                     l if routed_layer else None)
            if routed_layer:
                experts = experts + _share_stats(routed[1], act, mcfg)
            return (x, kc, experts), None

        base = 0
        for kind, lo, hi in mcfg.segments():
            sliced, stacks = expert_stacks(params[kind], mcfg)
            (x, kc, experts), _ = jax.lax.scan(
                functools.partial(body, stacks, base), (x, kc, experts),
                (sliced, jnp.arange(lo, hi)))
            base += hi - lo
        return x, kc, experts

    def _mixed_layers(params, x, kc, vc, state, experts, bt, pos, act,
                      tables):
        """One token a slot through the segments of a stack of window and
        full attention layers, a scan each. A full layer writes the step's
        row to the slot's page and reads its live pages in place
        (`paged_decode_attention`, the arena's layer the full layer's
        ordinal); a window layer writes it to the slot's ring and reads the
        ring alone. Both caches ride the carry (see `_step`)."""
        w = jnp.minimum(pos, S - 1)
        lengths = jnp.where(act, w + 1, 0)
        dv, scale = mcfg.v_head_dim, mcfg.softmax_scale

        def body(kind, stacks, base, c, s, carry, layer):
            x, kc, vc, state, experts = carry
            lp, l = layer
            routed_layer = "router" in lp
            lp = dict(lp, **stacks)
            _, _, window, sink = mcfg.attention_kind(kind)
            q_n, q_r, k_n, k_r, v = mixed_attention_inputs(
                lp, x, mcfg, kind, lambda t: _rope_one(t, c, s))
            q, k = (jnp.concatenate(t, -1) for t in ((q_n, q_r), (k_n, k_r)))
            if window:
                state = write_window_token(state, l, w, act, k, v)
                with jax.named_scope("attn"):
                    with jax.named_scope("window_attn"):
                        attn = window_decode_attention(
                            q, state, l, w, act, window=window,
                            sm_scale=scale, sink=lp["sink"] if sink else None)
            else:
                kc, vc = write_token(kc, vc, base + l, bt, w, act, k, v)
                with jax.named_scope("attn"):
                    with jax.named_scope("full_attn"):
                        # q in the lanes a cached key lies in: zeros meet
                        # the arena's padding
                        attn = paged_decode_attention(
                            jnp.pad(q, ((0, 0), (0, 0),
                                        (0, kc.shape[-1] - q.shape[-1]))),
                            kc, vc, base + l, bt, lengths, sm_scale=scale)
            with jax.named_scope("attn_out"):
                x = x + attn[..., :dv].astype(dt).reshape(ns, -1) \
                    @ lp["wo"].astype(dt)
            x, routed = feed_forward(lp, x, mcfg, act,
                                     l if routed_layer else None)
            if routed_layer:
                experts = experts + _share_stats(routed[1], act, mcfg)
            return (x, kc, vc, state, experts), None

        for kind, lo, hi in mcfg.segments():
            sliced, stacks = expert_stacks(params[kind], mcfg)
            with jax.named_scope("rope"):
                c, s = (t[w][:, None] for t in tables[kind])
            # the arena's layer: the leading dense layers, then `layers`
            base = mcfg.first_dense if kind == "layers" else 0
            (x, kc, vc, state, experts), _ = jax.lax.scan(
                functools.partial(body, kind, stacks, base, c, s),
                (x, kc, vc, state, experts), (sliced, jnp.arange(lo, hi)))
        return x, kc, vc, state, experts

    def _step(params, sliced, stacks, kc, vc, ic, experts, bt, last, pos,
              active, cos, sin, itables, temp, topk, keys, state=None):
        # sliced, stacks: `expert_stacks` of the layers, split (and where
        # need be cast) once a chunk, outside the loop over its steps
        act = active & (pos < S)
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], last, axis=0).astype(dt)

        def body(carry, layer):
            x, kc, vc, ic, *experts = carry
            lp, l = layer
            x, kc, vc, ic, routed = _decode_layer(
                x, kc, vc, ic, dict(lp, **stacks), l, bt, pos, act, cos, sin,
                itables)
            if sparse:
                experts = [experts[0] + expert_stats(routed[1])]
            return (x, kc, vc, ic, *experts), None

        # The arena rides this scan's CARRY, and only the page write and
        # the attention kernel's reads touch it, so the layer loop, the
        # chunk loop around it and the donated entry buffers all alias ONE
        # buffer: a step rewrites `ns` pages a layer and moves nothing else
        # (the kernel is handed the arena and `l`, never `kc[l]`: a custom
        # call given a slice is first given a copy of it). It must stay out of
        # the scan's xs/ys: an xs is read-only and a ys is a freshly
        # stacked result, so the compiler would slice every layer's slab
        # out, write it into a second arena and copy that back as the next
        # step's carry (2.9 GB a step at 12 layers x 929 pages; PERF.md,
        # PR 25). The xs are the layer's weights and its index.
        with jax.named_scope("layers"):
            if hybrid:      # segments, not one scan: `_hybrid_layers`
                x, kc, vc, state = _hybrid_layers(params, x, kc, vc, state,
                                                  bt, pos, act)
            elif latent:    # segments too: `_latent_layers`
                x, kc, stats = _latent_layers(
                    params, x, kc,
                    experts[0] if sparse else jnp.zeros((), jnp.int32), bt,
                    pos, act, cos, sin)
                experts = [stats] if sparse else []
            elif mixed:     # segments by kind: `_mixed_layers` (`cos`: the
                x, kc, vc, state, stats = _mixed_layers(    # kinds' tables)
                    params, x, kc, vc, state,
                    experts[0] if sparse else jnp.zeros((), jnp.int32), bt,
                    pos, act, cos)
                experts = [stats] if sparse else []
            else:
                (x, kc, vc, ic, *experts), _ = jax.lax.scan(
                    body, (x, kc, vc, ic, *experts),
                    (sliced, jnp.arange(mcfg.n_layers)))
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], mcfg.norm_eps)
            logits = _head_logits(params, x, mcfg)         # [ns, V]
        nxt = _sample_tokens(logits, temp, topk, keys, pos)
        nxt = jnp.where(act, nxt, last)
        pos2 = jnp.where(act, pos + 1, pos)
        return kc, vc, ic, experts, nxt, pos2, state

    def decode(params, kc, vc, bt, last, pos, active, temp, topk, keys,
               ic=None, state=None):
        """-> (kc, vc, last, pos, tokens [ns, chunk], experts): `experts` is
        None for a dense model, else `expert_stats` of the live slots'
        tokens summed over the chunk's steps and the layers. With an
        indexer, `ic` follows; with state-space layers, or window layers'
        rings, `state`."""
        cos = sin = None
        itables = ()
        if latent:
            with jax.named_scope("rope"):
                cos, sin = _latent_rope_tables(mcfg, S)
        elif mixed:
            with jax.named_scope("rope"):
                cos = _mixed_rope_tables(mcfg, S)
        elif mcfg.rope:
            with jax.named_scope("rope"):
                cos, sin = rope_frequencies(hd, S, mcfg.rope_theta)
                itables = rope_frequencies(mcfg.index_head_dim, S,
                                           mcfg.rope_theta) if indexed else ()
        out0 = jnp.zeros((ns, chunk), jnp.int32)
        # `expert_stats`' width, and `_share_stats`' for a share.
        experts0 = [jnp.zeros(mcfg.n_held + 1 + (latent or mixed),
                              jnp.int32)] if sparse else []
        # A stack of segments splits each segment's (`_latent_layers`,
        # `_mixed_layers`).
        sliced, stacks = (None, None) if latent or mixed \
            else expert_stacks(params["layers"], mcfg)

        def body(i, carry):
            kc, vc, ic, state, last, pos, out, *experts = carry
            kc, vc, ic, experts, nxt, pos, state = _step(
                params, sliced, stacks, kc, vc, ic, experts, bt, last, pos,
                active, cos, sin, itables, temp, topk, keys, state)
            out = out.at[:, i].set(nxt)
            return (kc, vc, ic, state, nxt, pos, out, *experts)

        kc, vc, ic, state, last, pos, out, *experts = jax.lax.fori_loop(
            0, chunk, body, (kc, vc, ic, state, last, pos, out0, *experts0))
        experts = experts[0] if sparse else None
        if indexed:
            return kc, vc, last, pos, out, experts, ic
        if hybrid or mixed:
            return kc, vc, last, pos, out, experts, state
        return kc, vc, last, pos, out, experts

    def poke(last, pos, slot, first, length):
        """Admission bookkeeping ON DEVICE: set one slot's (last, pos).
        Keeps the decode chain free of device->host fetches — a host
        read of last/pos at admission would cost a device round-trip
        before the TTFT token could be emitted."""
        return last.at[slot].set(first), pos.at[slot].set(length)

    import jax as _jax
    prefill_jit = _jax.jit(prefill, donate_argnums=(1, 2, 9, 10, 12, 13))
    prefill_jit.takes_riders = _core.takes_riders
    decode_jit = _jax.jit(decode, donate_argnums=(1, 2, 4, 5, 10, 11))
    adopt_jit = _jax.jit(adopt, donate_argnums=(0, 1))
    poke_jit = _jax.jit(poke, donate_argnums=(0, 1))
    return prefill_jit, decode_jit, adopt_jit, poke_jit, empty_caches


def _seed_key(seed: int):
    """Threefry key = [hi, lo] words of the seed — host-side PRNGKey
    construction (no device round-trip at admit)."""
    import numpy as np
    return np.array([(seed >> 32) & 0xffffffff, seed & 0xffffffff],
                    np.uint32)


class _Request:
    __slots__ = ("ids", "max_tokens", "out", "produced", "slot",
                 "adopt_kv", "first", "temperature", "top_k", "seed",
                 "rid", "t_submit", "ctx")

    def __init__(self, ids: List[int], max_tokens: int,
                 adopt_kv: Optional[Tuple[Any, Any]] = None,
                 first: int = -1, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0):
        self.ids = ids
        self.max_tokens = max_tokens
        self.out: "queue.Queue[Optional[List[int]]]" = queue.Queue()
        self.produced = 0
        self.slot = -1
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        # Disaggregated handoff: (ks, vs) prefilled elsewhere + the first
        # generated token (already streamed to the client by the prefill
        # side, so this engine never re-emits it).
        self.adopt_kv = adopt_kv
        self.first = first
        # Set by Engine._enqueue on the submitting thread: the request's
        # number, when it joined the queue, and the replica call's trace
        # context, which the engine loop's spans carry for it.
        self.rid = -1
        self.t_submit = 0.0
        self.ctx = None


class Engine:
    """One continuous-batching decode loop over a paged KV cache.
    submit() from any thread; each request streams token chunks through
    its own queue."""

    def __init__(self, params, mcfg, *, n_slots: int = 8,
                 decode_chunk: int = 8, page_size: int = 64,
                 n_pages: Optional[int] = None, adopts: bool = False):
        """`params` is the published tree, on the device; its `wq`, `wk` and
        `wv` stacks are consumed (see below), the rest is shared. `adopts`:
        the engine is a decode pool's, handed prompts prefilled elsewhere
        (`submit_prefilled`), and warms an `adopt` program a doubling width;
        what the server class that builds it says, not a deployment's
        choice: one that serves whole requests is never sent a hand-off."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.block import fuse_qkv
        from ray_tpu.ops.slot_state import state_bytes

        self._np = np
        self._jnp = jnp
        self.mcfg = mcfg
        self.n_slots = n_slots
        self.chunk = decode_chunk
        # The weights as the programs read them: one q/k/v stack, the experts
        # in the compute dtype, made once, here. The caller's three projection
        # stacks are TAKEN OVER, as a donated argument is: deleted once fused,
        # whoever holds them (`self.params` gives them back). A caller still
        # holds its tree while this constructor warms up, and may still when
        # the warm-up thread allocates its scratch arena: the projections
        # twice over are 0.6 GB at 12 Mistral layers, 0.2 on OLMoE, whose
        # warm-up peaks within 0.9 GB of the chip's memory (PERF.md, §4).
        self._params = fuse_qkv(self._experts_in_compute_dtype(params, mcfg),
                                mcfg)
        for stack in ("layers", "dense", "window"):
            for name in set(params.get(stack, ())) - set(
                    self._params.get(stack, ())):
                params[stack][name].delete()
        self.pool = PagePool(n_slots, mcfg.max_seq, page_size, n_pages)
        self.n_pages = self.pool.n_pages
        (self._prefill, self._decode, self._adopt, self._poke,
         self._empty) = _build_fns(mcfg, n_slots, decode_chunk,
                                   self.pool.page, self.n_pages)
        # `_ic`: the indexer keys' arena of a model with sparse attention,
        # under the same block table. `_state`: the per-slot recurrent state
        # of a model with state-space layers (`ops/slot_state.py`). Each None
        # for every other model, and no model has both.
        self._hybrid = mcfg.ssm_state > 0
        self._latent = mcfg.latent
        # Window layers' rings (`ops/slot_state.py`) ride where a hybrid's
        # recurrent state does: `_state`, per slot, written by the prefill
        # that admits a request into the slot.
        self._mixed = mcfg.mixed
        self._by_slot = self._hybrid or self._mixed
        # Stacks whose programs count a SHARE's routing (`_share_stats`).
        self._shares = self._latent or self._mixed
        self._kc, self._vc, *more = self._empty()
        self._ic, self._state = self._third(more)
        # Prefill shape buckets (`prefill_widths`): a 50-token prompt
        # prefills 64 wide and, under a max_seq of 4096, a 2,100-token one
        # 2,560 wide, not max_seq wide — the TTFT lever, and most of the
        # padding, that the reference gets from vLLM's chunked prefill. A PD
        # handoff is adopted at the doubling widths alone, its sender's.
        self.buckets: List[int] = prefill_widths(mcfg.max_seq)
        self._adopt_widths = doubling_widths(mcfg.max_seq) if adopts else []
        # host-side slot state (control flow is host-predicted; only token
        # VALUES come back from the device)
        self._slot_req: List[Optional[_Request]] = [None] * n_slots
        self._pos = np.zeros(n_slots, np.int32)
        self._active = np.zeros(n_slots, bool)
        # Per-slot sampling state (temp 0 = greedy; key seeded per
        # request so streams are reproducible wherever the slot lands).
        self._temp = np.zeros(n_slots, np.float32)
        self._topk = np.zeros(n_slots, np.int32)
        self._skeys = np.zeros((n_slots, 2), np.uint32)
        self._last_d = jnp.zeros(n_slots, jnp.int32)
        self._pos_d = jnp.zeros(n_slots, jnp.int32)
        self.peak_pages_used = 0
        # Running totals since start (`counters()`; what the engine's spans
        # say a request or a chunk at a time).
        self.admitted = 0
        self.queue_wait_s_sum = 0.0
        self.admit_chunks_ahead = 0        # chunks in flight, summed over admits
        self.admit_decoding_slots = 0      # slots live, summed over admits
        self.admit_pending = 0             # requests left waiting, summed
        # When each slot was last freed (`_finish_state`; None until it has
        # had a tenant), and the time the slots then stood empty, summed
        # over the admissions that refilled them.
        self._slot_freed: List[Optional[float]] = [None] * n_slots
        self.slot_idle_s_sum = 0.0
        self.prefill_tokens = 0
        self.prefill_padded_tokens = 0     # bucket width less the prompt
        self.decode_chunks = 0
        self.decode_useful_tokens = 0
        # Tokens decoded inside prefills (a riding slot's one step in a
        # prompt's padding rows), and the prefills that carried any.
        self.rider_tokens = 0
        self.rider_steps = 0
        # Positions the active slots held when each chunk was dispatched:
        # what decode attention had to read, a layer, at the chunk's first
        # step (against n_slots * max_seq, what a whole-table gather moves).
        self.live_kv_tokens = 0
        # A sparse-attention model: the keys its decode steps selected, over
        # the chunks' steps and the active slots (min(positions, index_topk)
        # a slot a step; a layer reads that many K and V rows), against
        # `decode_live_keys`, the positions those slots held (what dense
        # attention would read). Host arithmetic on the positions.
        self._index_topk = mcfg.index_topk
        self.decode_selected_keys = 0
        self.decode_live_keys = 0
        # A model with state-space layers: the bytes of recurrent state held
        # on the device, and the admissions that overwrote a slot's.
        self.state_bytes = state_bytes(self._state) if self._hybrid else 0
        self.state_writes = 0
        # A sparse model's routing, as the programs count it on the device
        # (`models.block.expert_stats`) and the emitter thread adds it up:
        # tokens per expert over prefills and decode steps, and the distinct
        # experts touched, summed over a chunk's steps and the layers.
        self._sparse = mcfg.n_experts > 0
        self.expert_tokens = np.zeros(mcfg.n_held, np.int64)
        self.decode_experts_touched = 0
        self._touched_last_chunk = 0
        # A latent-attention model: the bytes of its arena of latent rows
        # (all it caches); and, where it is sparse, its share of the routing
        # (`_share_stats`): the assignments its routers made and those that
        # fell to the experts held here, `expert_tokens` being per HELD
        # expert. The last chunk's local assignments ride the next dispatch
        # span as `experts_touched` does.
        self.latent_cache_bytes = int(self._kc.nbytes) if self._latent else 0
        # A model of window and full attention layers: the bytes of its two
        # caches (the full layers' pages; the window layers' rings, which no
        # prompt's length moves), and the ring rows its decode steps read,
        # over the chunks' steps and the active slots (min(position + 1,
        # window) a slot a step, a layer), against `live_kv_tokens`. Host
        # arithmetic on the positions, as `decode_selected_keys` is.
        self._window = mcfg.window if self._mixed else 0
        self.full_cache_bytes = int(self._kc.nbytes + self._vc.nbytes) \
            if self._mixed else 0
        self.window_cache_bytes = state_bytes(self._state) \
            if self._mixed else 0
        self.window_kv_tokens = 0
        self.routed_assignments = 0
        self.local_assignments = 0
        self._local_last_chunk = 0
        self._routed_last_chunk = 0
        self._next_rid = 0
        self._pending: deque = deque()
        # What the loop waits on (`_stand`), under one lock: the pending
        # queue and its `_next_rid` (a submit notifies), and the decode
        # chunks in flight, which the loop raises at dispatch and the
        # emitter lowers, and notifies, once a chunk's output is fetched.
        self._cv = threading.Condition()
        self._in_flight = 0
        self._stop = False
        self.error: Optional[str] = None
        # Traceback of a prefill bucket that failed to compile in the
        # background warm: that width never becomes available, so the
        # replica is degraded and its health check must say so.
        self.warm_error: Optional[str] = None
        # Warm the decode program + the SMALLEST prefill bucket and every
        # one WIDER THAN HALF OF max_seq before serving (serve's startup
        # grace covers the XLA compiles); the buckets between warm in a
        # BACKGROUND thread — until one is ready, prompts round UP to the
        # next warmed bucket, so an unwarmed shape never compiles inside
        # the engine loop (which would freeze every in-flight decode
        # stream). Warm writes target the null page (pages = zeros), so
        # they never touch real KV state. Why the split is at half: the
        # thread warms against a SCRATCH arena, and a wide prefill's
        # temporaries do not fit beside a second arena (at OLMoE's widths
        # weights 6.64 GiB, two arenas 8.00 and a 2048-wide prefill's
        # temporaries are 15.41 of the 15.75 the chip gives, and a
        # 4096-wide prefill has 2.54 of them: PERF.md section 4); here the
        # live arena is the only one, as for the widest bucket, which fits
        # if serving does. Each is a real first call, not
        # `lower().compile()`: only that fills jit's dispatch cache, and a
        # cache read at the first request is a compilation inside the loop.
        self._warm = {self.buckets[0]} | {
            b for b in self.buckets if 2 * b > mcfg.max_seq}
        for width in sorted(self._warm):
            self._kc, self._vc, self._ic, self._state, first = \
                self._warm_width(self._kc, self._vc, self._ic, self._state,
                                 width)
        with tracing.compile_span("serve.engine.warm", program="decode",
                                  width=n_slots):
            (self._kc, self._vc, self._last_d, self._pos_d, out, _,
             *more) = self._decode(
                    self._params, self._kc, self._vc,
                    jnp.asarray(self.pool.block_table),
                    self._last_d, self._pos_d, jnp.zeros(n_slots, bool),
                    jnp.asarray(self._temp), jnp.asarray(self._topk),
                    jnp.asarray(self._skeys), self._ic, self._state)
            self._ic, self._state = self._third(more)
        # Warm both poke variants: host-int `first` (adopt path) and
        # device-scalar `first` (prefill path).
        with tracing.compile_span("serve.engine.warm", program="poke",
                                  width=n_slots):
            self._last_d, self._pos_d = self._poke(
                self._last_d, self._pos_d, 0, 0, 0)
            self._last_d, self._pos_d = self._poke(
                self._last_d, self._pos_d, 0, first, 0)
            self._last_d, self._pos_d = self._poke(
                self._last_d, self._pos_d, 0, 0, 0)
        int(first)
        # Emission FIFO: the dispatch loop enqueues device arrays; the
        # emitter thread performs the host syncs. No `put` blocks: it holds
        # at most `_DEPTH` chunks (the loop's own count, `_in_flight`) and a
        # "first" item a slot.
        self._emit_q: "queue.Queue" = queue.Queue()
        self._emitter = threading.Thread(target=self._emit_loop,
                                         daemon=True, name="llm-emit")
        self._emitter.start()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="llm-engine")
        self._thread.start()
        self._warm_thread: Optional[threading.Thread] = None
        middles = [b for b in self.buckets if b not in self._warm]
        if middles:
            self._warm_thread = threading.Thread(
                target=self._warm_buckets, args=(middles,), daemon=True,
                name="llm-bucket-warm")
            self._warm_thread.start()

    def _third(self, more):
        """(ic, state) from what a program returned after its fixed results:
        the one further cache this model has, if it has one."""
        if not more:
            return None, None
        return (None, more[0]) if self._by_slot else (more[0], None)

    def _warm_width(self, kc, vc, ic, state, width: int):
        """First call of the prefill program of one bucket width and, at a
        doubling width, of its adopt twin, writing to the null page of the
        arena given (pages = zeros: never real KV state; a recurrent state's
        slot 0, before any request holds it, or a scratch one's). Returns
        (kc, vc, ic, state, first token on the device)."""
        jnp, m = self._jnp, self.mcfg
        null_pages = jnp.zeros(self.pool.maxp, jnp.int32)
        # A riding rung's program with nobody riding, on slots' state of its
        # own: the live `_last_d` and `_pos_d` are the loop's to donate.
        rides = self._rides(width)
        slots = (None, None, None) if not rides else (
            jnp.zeros(self.n_slots, jnp.int32),
            jnp.zeros(self.n_slots, jnp.int32),
            self._riders(self._np.zeros(self.n_slots, bool)))
        with tracing.compile_span("serve.engine.warm", program="prefill",
                                  width=width):
            kc, vc, first, _, *more = self._prefill(
                self._params, kc, vc, null_pages,
                jnp.zeros((1, width), jnp.int32), 1, 0.0, 0,
                jnp.zeros(2, jnp.uint32), ic, state,
                0 if self._by_slot else None, *slots)
        if more and not rides:
            # no PD handoff carries an indexer's keys or a state
            return (kc, vc, *self._third(more), first)
        # no handoff has this width (none at all is sent an engine that
        # serves whole requests), or carries latent rows
        if width not in self._adopt_widths or self._latent or self._mixed:
            return kc, vc, ic, state, first
        # The PD adopt program for this width too (a first cross-pool
        # handoff must not compile in the loop).
        with tracing.compile_span("serve.engine.warm", program="adopt",
                                  width=width):
            kv = jnp.zeros((m.n_layers, width, m.n_kv_heads, m.head_dim),
                           m.dtype)
            kc, vc = self._adopt(kc, vc, null_pages, kv, kv)
        return kc, vc, ic, state, first

    def _rides(self, width: int) -> bool:
        """Whether the prefill program of this width takes the live slots
        along: read off the stack (`_make_prefill_core`) and the rung."""
        return self._prefill.takes_riders and rung_rides(
            self.mcfg.max_seq, self.n_slots, width)

    def _riders(self, riding):
        """A riding program's last argument: the block table and the slots
        `riding` marks (host arrays, copied: the loop mutates them while the
        program is queued), with the slots' sampling state."""
        jnp = self._jnp
        return (jnp.asarray(self.pool.block_table.copy()),
                jnp.asarray(riding), jnp.asarray(self._temp.copy()),
                jnp.asarray(self._topk.copy()),
                jnp.asarray(self._skeys.copy()))

    def _warm_buckets(self, widths: List[int]) -> None:
        """Warm intermediate prefill buckets off the engine loop; each
        becomes eligible the moment its compile lands. Runs real calls
        (the only way to reliably populate jit's dispatch cache) against
        a SCRATCH kv arena (and recurrent state) — the live ones are donated
        on every engine call and must never be touched from this thread.
        Costs one transient extra arena while warming."""
        try:
            kc, vc, *more = self._empty()
            ic, state = self._third(more)
            for width in widths:
                if self._stop:
                    return
                kc, vc, ic, state, first = self._warm_width(kc, vc, ic,
                                                            state, width)
                int(first)  # host sync: compile fully landed
                self._warm.add(width)
        except Exception:
            # Prompts keep rounding up to the buckets that did warm, but
            # a width the compiler refuses is a fault, not a detail.
            import traceback
            self.warm_error = traceback.format_exc()
            logger.error("prefill bucket warm-up failed; widths %s stay "
                         "unavailable:\n%s",
                         [w for w in widths if w not in self._warm],
                         self.warm_error)

    def lowered_prefill_text(self, width: int) -> str:
        """StableHLO text of the prefill program for one bucket width —
        what a check reads to see which attention path (a Pallas
        `tpu_custom_call` or the XLA reference) that width compiled to."""
        import jax
        import jax.numpy as jnp

        def shape_of(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        slots = riders = None
        if self._rides(width):     # as `_place` calls it
            slots = shape_of(self._last_d)
            riders = jax.tree.map(shape_of, self._riders(
                self._np.zeros(self.n_slots, bool)))
        return self._prefill.lower(
            jax.tree.map(shape_of, self._params), shape_of(self._kc),
            jax.tree.map(shape_of, self._vc),
            jax.ShapeDtypeStruct((self.pool.maxp,), jnp.int32),
            jax.ShapeDtypeStruct((1, width), jnp.int32), 1, 0.0, 0,
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            None if self._ic is None else shape_of(self._ic),
            jax.tree.map(shape_of, self._state),
            0 if self._by_slot else None, slots, slots, riders).as_text()

    # ------------------------------------------------------------------
    @property
    def params(self):
        """The model's parameters as they are published (`wq`, `wk`, `wv` a
        matrix each: what a checkpoint or a plain reference reads), split
        from the engine's fused stack at every call: the engine holds no
        second copy of the projections, and the caller holds this one only
        as long as it keeps it."""
        from ray_tpu.models.block import split_qkv
        return split_qkv(self._params, self.mcfg)

    @staticmethod
    def _experts_in_compute_dtype(params, mcfg):
        """A sparse model's expert stacks are read whole by every layer of
        every program (`models.block.expert_stacks`), so they are held in the
        compute dtype: stored otherwise, they are cast here, once, and the
        log says so (the engine then holds that copy of the experts; the
        caller may drop its own)."""
        cast = {stack: [k for k in ("w_gate", "w_up", "w_down")
                        if params[stack][k].dtype != mcfg.dtype]
                for stack in ("layers", "window")
                if mcfg.n_experts > 0 and "router" in params.get(stack, ())}
        if not any(cast.values()):
            return params
        logger.warning(
            "expert weights are stored as %s and computed in %s: the engine "
            "casts its own copy once", mcfg.param_dtype, mcfg.dtype)
        out = dict(params)
        for stack, names in cast.items():
            out[stack] = dict(params[stack], **{
                k: params[stack][k].astype(mcfg.dtype) for k in names})
        return out

    def submit(self, ids: List[int], max_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0,
               seed: int = 0) -> "queue.Queue":
        """Enqueue a request; returns its stream of token-chunk lists
        (None terminates the stream). temperature 0 = greedy; top_k
        bounds sampling to the best k logits (capped at TOPK_CAP); seed
        makes the sample stream reproducible."""
        if self.error is not None or not self._thread.is_alive():
            raise RuntimeError(f"LLM engine died:\n{self.error}")
        req = _Request(ids[: self.mcfg.max_seq - 1], max_tokens,
                       temperature=temperature, top_k=top_k, seed=seed)
        if max_tokens <= 0:
            req.out.put(None)  # nothing to generate; skip the prefill too
            return req.out
        return self._enqueue(req)

    def submit_prefilled(self, ks: Any, vs: Any, length: int, first: int,
                         max_tokens: int, *, temperature: float = 0.0,
                         top_k: int = 0, seed: int = 0) -> "queue.Queue":
        """Adopt an externally-prefilled request (PD disaggregation): the
        KV [L, B, KVH, hd] was produced by a PrefillServer and handed
        over via DeviceRefs; this engine continues decoding from token
        `first` at position `length` with the given sampling params
        (`first` was chosen by the PREFILL side — sampled there with the
        same seed derivation when temperature > 0). The stream yields
        only tokens AFTER `first`."""
        if self.error is not None or not self._thread.is_alive():
            raise RuntimeError(f"LLM engine died:\n{self.error}")
        if self._ic is not None or self._hybrid or self._latent \
                or self._mixed:
            raise NotImplementedError(
                "a PD handoff carries K and V, not a sparse-attention "
                "indexer's keys nor a state-space layer's recurrent state "
                "nor latent attention's rows (kv_lora_rank > 0) nor mixed "
                "attention's two caches (attn_pattern: pages and window "
                "rings): this model serves from one engine")
        if not self._adopt_widths:
            raise RuntimeError(
                "this engine warmed no `adopt` program and would compile one "
                "inside its loop: an engine that is handed prefilled prompts "
                "is built with `adopts=True`, as `DecodeServer` builds it")
        req = _Request([0] * min(length, self.mcfg.max_seq - 1),
                       max_tokens, adopt_kv=(ks, vs), first=first,
                       temperature=temperature, top_k=top_k, seed=seed)
        if max_tokens <= 1:
            req.out.put(None)  # prefill's first token was the whole ask
            return req.out
        return self._enqueue(req)

    def _enqueue(self, req: _Request) -> "queue.Queue":
        req.ctx = tracing.context()
        with self._cv:
            req.rid = self._next_rid
            self._next_rid += 1
            req.t_submit = time.monotonic()
            self._pending.append(req)
            self._cv.notify_all()    # the loop admits it now, room or none
        return req.out

    def counters(self) -> Dict[str, Any]:
        """Running totals since the engine started: the operator's view of
        what `serve.engine.admit` and `serve.engine.decode_dispatch` spans
        say one at a time. `admit_chunks_ahead` over `admitted` is the
        decode chunks that were in flight when a request was admitted, which
        its prefill queued behind (`_DEPTH` at most).
        `admit_decoding_slots` over `admitted` is the slots that were live
        when a request was admitted, whose next chunk waited on the device
        through its prefill (times a prefill's length: the decode time a
        prefill stalls). `admit_pending` over `admitted` is the requests
        still waiting in `_pending` behind the one admitted: the depth of
        the queue a saturated engine holds. `slot_idle_s_sum` over
        `admitted` is how long the slot a request was given had stood
        empty since its last tenant finished (nothing for a slot's first
        tenant); over `n_slots` times the seconds elapsed it is the share
        of slot-time left unfilled.
        Occupancy is
        `decode_useful_tokens` over
        `decode_chunks * n_slots * chunk`; `rider_tokens` are the tokens
        decoded outside the chunks, a live slot's one step inside another
        request's prefill (`rider_steps`: the prefills that carried any), so
        the tokens decoded are the two added; padding is
        `prefill_padded_tokens` over it plus `prefill_tokens`.
        `live_kv_tokens` over `decode_chunks * n_slots * max_seq` is the
        share of the block tables that was live at dispatch. A sparse
        model adds `expert_tokens` (assignments per expert, all layers,
        prefills and decode steps, as far as the emitter has fetched them)
        and `decode_experts_touched` (distinct experts, summed over decode
        steps and layers: over `decode_chunks * chunk * n_layers` it is the
        experts whose weights a layer reads in a step). A sparse-attention
        model adds `decode_selected_keys` over `decode_live_keys`: the share
        of the live positions its decode steps read K and V of. A model with
        state-space layers adds `state_bytes`, the recurrent state it holds
        on the device for all slots, and `state_writes`, the admissions that
        overwrote a slot's (a decode chunk moves the active slots' share of
        `state_bytes` once a step). A latent-attention model adds
        `latent_cache_bytes`, its arena of latent rows, and, where it is
        sparse, `routed_assignments` (what its routers assigned of live
        rows, to any share) and `local_assignments` (those that fell to the
        experts held here, the sum of `expert_tokens`, which is then per
        HELD expert): their ratio is this share's part of the routed work. A
        model of window and full attention layers adds `full_cache_bytes`
        (the pages of its full layers, all `PagePool` hands out),
        `window_cache_bytes` (its window layers' rings: `window` positions a
        slot a layer whatever the prompts) and `window_kv_tokens`, the ring
        rows a window layer's decode steps read (min(position + 1, window) a
        live slot a step) against `live_kv_tokens`; and, holding a share of
        its experts, the same two assignment counts."""
        out = {k: getattr(self, k) for k in (
            "admitted", "queue_wait_s_sum", "admit_chunks_ahead",
            "admit_decoding_slots", "admit_pending", "slot_idle_s_sum",
            "prefill_tokens",
            "prefill_padded_tokens", "decode_chunks",
            "decode_useful_tokens", "rider_tokens", "rider_steps",
            "live_kv_tokens", "peak_pages_used", "n_slots", "chunk")}
        if self._sparse:
            out["expert_tokens"] = [int(n) for n in self.expert_tokens]
            out["decode_experts_touched"] = self.decode_experts_touched
        if self._index_topk:
            out["decode_selected_keys"] = self.decode_selected_keys
            out["decode_live_keys"] = self.decode_live_keys
        if self._hybrid:
            out["state_bytes"] = self.state_bytes
            out["state_writes"] = self.state_writes
        if self._latent:
            out["latent_cache_bytes"] = self.latent_cache_bytes
        if self._mixed:
            out["full_cache_bytes"] = self.full_cache_bytes
            out["window_cache_bytes"] = self.window_cache_bytes
            out["window_kv_tokens"] = self.window_kv_tokens
        if self._shares and self._sparse:
            out["routed_assignments"] = self.routed_assignments
            out["local_assignments"] = self.local_assignments
        return out

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=10)
        self._emit_q.put(None)  # sentinel: drain + exit
        self._emitter.join(timeout=30)
        # Join the background bucket warmer too: a daemon thread still
        # inside an XLA compile at interpreter shutdown aborts the
        # process (C++ exception with no Python frame to land in).
        if self._warm_thread is not None:
            self._warm_thread.join(timeout=60)

    def pages_in_use(self) -> int:
        return self.pool.in_use()

    # ------------------------------------------------------------------
    def _admit(self) -> None:
        """Admit pending requests into free slots while their page
        reservations fit (FIFO: the head waits — for a finish to free a
        slot or pages — rather than being overtaken). Safe to call with
        chunks in flight: an in-flight chunk saw the new slot as
        inactive and never touches its freshly-allocated pages; the
        prefill + poke ops simply queue behind it on the device.
        Prefills for a BURST of admissions are all dispatched (and their
        first-token transfers started) before any is handed to the
        emitter, so N admissions cost ~one round-trip, not N."""
        emits: List[Tuple] = []  # (req, first, done, experts, rode, riders)
        while True:
            with self._cv:
                req = self._pending[0] if self._pending else None
            if req is None:
                break
            slot = next((i for i in range(self.n_slots)
                         if not self._active[i]
                         and self._slot_req[i] is None), None)
            need = self.pool.pages_for(len(req.ids), req.max_tokens)
            if slot is None or self.pool.free < need:
                break  # head-of-line waits for a finish

            with self._cv:
                self._pending.popleft()
                left = len(self._pending)
                ahead = self._in_flight
                # Their next chunk queues behind this request's prefill.
                decoding = int(self._active.sum())
            adopting = req.adopt_kv is not None
            width = req.adopt_kv[0].shape[1] if adopting else len(req.ids)
            # Only WARMED buckets are eligible (round up until the
            # background warm lands) — never compile in the engine loop.
            # A handoff rounds within the widths that have an adopt program.
            bucket = next(b for b in (self._adopt_widths if adopting
                                      else self.buckets)
                          if b >= width and b in self._warm)
            now = time.monotonic()
            waited = now - req.t_submit
            freed = self._slot_freed[slot]
            slot_idle = 0.0 if freed is None else now - freed
            self.admitted += 1
            self.queue_wait_s_sum += waited
            self.admit_chunks_ahead += ahead
            self.admit_decoding_slots += decoding
            self.admit_pending += left
            self.slot_idle_s_sum += slot_idle
            if not adopting:
                self.prefill_tokens += width
                self.prefill_padded_tokens += bucket - width
            # The live slots ride a riding rung's prefill where the prompt
            # leaves them their rows (`_ride_plan`); the span says how many.
            riders = None
            if not adopting and self._rides(bucket):
                riders = self._ride_plan(bucket - width)
                self.rider_tokens += len(riders)
                self.rider_steps += bool(riders)
            with tracing.span(
                    "serve.engine.admit", ctx=req.ctx, rid=req.rid,
                    kind="adopt" if adopting else "prefill",
                    prompt_tokens=len(req.ids), bucket=bucket,
                    queue_wait_us=int(waited * 1e6), pending=left,
                    pages_free=self.pool.free - need, chunks_ahead=ahead,
                    decoding=decoding, slot_idle_us=int(slot_idle * 1e6),
                    **({} if riders is None else {"riders": len(riders)})):
                emits.append(self._place(req, slot, need, bucket, riders))
        # Start EVERY device->host copy first (async), THEN enqueue: a
        # burst overlaps all its transfers.
        for _, first, _, _, rode, _ in emits:
            for out in (first, rode):
                try:
                    out.copy_to_host_async()
                except AttributeError:
                    pass  # host int (adopt path); nobody rode
        for item in emits:
            # The emitter thread performs the int(first) sync — the
            # dispatch loop never blocks on the device.
            self._emit_q.put(("first",) + item)

    def _ride_plan(self, free_rows: int) -> List[Tuple]:
        """[(slot, request, finishes)] of the slots that ride a prefill whose
        prompt leaves `free_rows` of its bucket: every live slot, where their
        rows fit. Each takes one token, as in a decode chunk of one step
        (`_run_inner`'s plan): a live slot has one to take and a position
        under max_seq, or it would have finished."""
        if free_rows < self.n_slots:
            return []
        plan = []
        for slot in self._np.flatnonzero(self._active):
            req = self._slot_req[slot]
            req.produced += 1
            plan.append((int(slot), req,
                         req.produced >= req.max_tokens
                         or self._pos[slot] + 1 >= self.mcfg.max_seq))
        return plan

    def _place(self, req: _Request, slot: int, need: int, bucket: int,
               riders: Optional[List[Tuple]]
               ) -> Tuple[_Request, Any, bool, Any, Any, List[Tuple]]:
        """Grant `need` pages and the slot, dispatch the prefill (or the
        adopt) at width `bucket` and the poke. `riders`: `_ride_plan` for a
        riding rung's program (None: it takes nobody), whose slots move a
        step with it. Returns the emitter's item: (req, first token, finished
        already, the prefill's `experts`, the riders' tokens [n_slots] on the
        device, `riders`)."""
        np, jnp = self._np, self._jnp
        S = self.mcfg.max_seq
        rode = None
        pages_arr = jnp.asarray(self.pool.grant(slot, need))
        self.peak_pages_used = max(self.peak_pages_used,
                                   self.pool.in_use())
        if req.adopt_kv is not None:
            # Disaggregated handoff: write the external KV into the
            # slot's pages; `first` was already streamed by the prefill
            # side. An UNWARMED handoff width is host-padded to the next
            # warmed bucket (a zero tail is never attended — the mask
            # stops at pos) instead of compiling a fresh adopt program
            # inside the loop.
            ks, vs = req.adopt_kv
            req.adopt_kv = None
            width = ks.shape[1]
            if width != bucket:
                pk = np.zeros((ks.shape[0], bucket) + ks.shape[2:],
                              np.asarray(ks).dtype)
                pv = np.zeros_like(pk)
                pk[:, :width] = np.asarray(ks)
                pv[:, :width] = np.asarray(vs)
                ks, vs = jnp.asarray(pk), jnp.asarray(pv)
            self._kc, self._vc = self._adopt(
                self._kc, self._vc, pages_arr, ks, vs)
            first, experts = req.first, None
        else:
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :len(req.ids)] = req.ids
            slots = (None, None, None)
            if riders is not None:
                riding = np.zeros(self.n_slots, bool)
                riding[[s for s, _, _ in riders]] = True
                slots = (self._last_d, self._pos_d, self._riders(riding))
            self._kc, self._vc, first, experts, *more = self._prefill(
                self._params, self._kc, self._vc, pages_arr,
                jnp.asarray(toks), len(req.ids),
                float(req.temperature), int(req.top_k),
                jnp.asarray(_seed_key(req.seed)), self._ic, self._state,
                slot if self._by_slot else None, *slots)
            if riders is None:
                self._ic, self._state = self._third(more)
            else:
                self._last_d, self._pos_d, rode = more
                self._pos[riding] += 1
                for s, _, fin in riders:
                    if fin:     # its slot and pages are free at once
                        self._finish_state(s)
            self.state_writes += self._hybrid
        req.slot = slot
        self._slot_req[slot] = req
        self._pos[slot] = len(req.ids)
        self._active[slot] = True
        # Sampling state applies on BOTH branches (a PD handoff
        # continues decoding with the request's params).
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._skeys[slot] = _seed_key(req.seed)
        req.produced = 1
        # Device-side slot bookkeeping (async — never a host round-trip;
        # `first` stays a device scalar on the prefill path).
        self._last_d, self._pos_d = self._poke(
            self._last_d, self._pos_d, slot, first, int(self._pos[slot]))
        done = bool(req.produced >= req.max_tokens
                    or self._pos[slot] >= S)
        if done:
            self._finish_state(slot)
        return req, first, done, experts, rode, riders or []

    def _finish_state(self, slot: int) -> None:
        """Free the slot + pages (host control state only — the stream's
        terminating None is emitted by the emitter thread, AFTER the
        slot's final tokens)."""
        self._slot_req[slot] = None
        self._active[slot] = False
        self._slot_freed[slot] = time.monotonic()
        self.pool.release(slot)
        self._temp[slot] = 0.0
        self._topk[slot] = 0

    def _finish(self, slot: int) -> None:
        req = self._slot_req[slot]
        self._finish_state(slot)
        if req is not None:
            req.out.put(None)

    def _run(self) -> None:
        try:
            self._run_inner()
        except BaseException:
            # A dead engine must not strand consumers on silent queues.
            import traceback
            self.error = traceback.format_exc()
            for slot in range(self.n_slots):
                self._finish(slot)
            while True:
                with self._cv:
                    req = self._pending.popleft() if self._pending else None
                if req is None:
                    break
                req.out.put(None)

    def _emit_loop(self) -> None:
        """The only place host<->device syncs happen on the serving
        path: fetch first tokens / chunk outputs and emit them to each
        request's stream, in dispatch order (per-request FIFO is
        preserved because the dispatch loop enqueues a request's "first"
        before any of its chunks)."""
        np = self._np
        while True:
            item = self._emit_q.get()
            if item is None:
                return
            try:
                if item[0] == "first":
                    _, req, first, done, experts, rode, riders = item
                    # Ends at the engine's first-token instant.
                    with tracing.span(
                            "serve.engine.emit", ctx=req.ctx, rid=req.rid,
                            kind="first" if req.first < 0 else "adopt"):
                        if req.first < 0:
                            req.out.put([int(first)])
                        if done:
                            req.out.put(None)
                    # The token each rider's step made, after the prompt's.
                    if riders:
                        rode = np.asarray(rode)
                    for slot, rider, fin in riders:
                        rider.out.put([int(rode[slot])])
                        if fin:
                            rider.out.put(None)
                    if experts is not None:
                        # After the token is out. A span of no length: the
                        # profiler fixes a span's arguments when it opens.
                        touched, local, routed = self._count_experts(experts)
                        share = {"local": local, "routed": routed} \
                            if self._shares else {}
                        with tracing.span("serve.engine.prefill_experts",
                                          ctx=req.ctx, rid=req.rid,
                                          touched=touched, **share):
                            pass
                else:  # ("chunk", out_d, plan, experts)
                    _, out_d, plan, experts = item
                    with tracing.span("serve.engine.emit", kind="chunk"):
                        try:
                            out_h = self._fetch(out_d)
                        finally:
                            # The chunk has left the device (or failed):
                            # room for the next, before the streams are fed.
                            with self._cv:
                                self._in_flight -= 1
                                self._cv.notify_all()
                        for slot, req, take, fin in plan:
                            toks = [int(t) for t in out_h[slot, :take]]
                            if toks:
                                req.out.put(toks)
                            if fin:
                                req.out.put(None)
                    if experts is not None:
                        (self._touched_last_chunk, self._local_last_chunk,
                         self._routed_last_chunk) = self._count_experts(experts)
                        self.decode_experts_touched += self._touched_last_chunk
            except BaseException:
                import traceback
                self.error = self.error or traceback.format_exc()
                # Terminate the affected streams rather than stranding
                # their consumers.
                if item[0] == "first":
                    for req in [item[1]] + [r for _, r, _ in item[6]]:
                        req.out.put(None)
                else:
                    for _, req, _, _ in item[2]:
                        req.out.put(None)

    def _fetch(self, out_d):
        """A decode chunk's tokens on the host: the emitter's wait for the
        device (a test holds the emitter here)."""
        return self._np.asarray(out_d)

    def _count_experts(self, experts) -> Tuple[int, int, int]:
        """Emitter thread: add one program's `expert_stats` (a share's
        `_share_stats`) to the running totals; returns its distinct experts
        touched, its assignments to experts held here, and all the
        assignments its routers made (the same, for a model that holds every
        expert)."""
        stats = self._np.asarray(experts)
        routed = None
        if self._shares:
            routed, stats = int(stats[-1]), stats[:-1]
        local = int(stats[:-1].sum())
        routed = local if routed is None else routed
        # `routed_assignments` last: a reader that sees it moved sees all.
        self.expert_tokens = self.expert_tokens + stats[:-1]
        self.local_assignments += local
        self.routed_assignments += routed
        return int(stats[-1]), local, routed

    def _stand(self, ready) -> None:
        """The loop's one wait: admit what has arrived, then stand until
        `ready()` (asked under `_cv`) or `stop()`. A submit ends the wait for
        another round of admission first, so an arrival with a free slot and
        pages is admitted whether or not the pipeline has room; the emitter
        ends it when it has fetched a chunk's output. `_run_inner` stands
        here twice a round and names each wait for the trace:
        `serve.engine.idle` encloses the stand for a live slot, opened only
        when none is live; `serve.engine.emit_block` the stand for pipeline
        room after a dispatch. `serve.engine.admit` spans nest inside
        either, so what the loop waited is the span less those."""
        while not self._stop:
            with self._cv:
                seen = self._next_rid
            self._admit()
            with self._cv:
                self._cv.wait_for(lambda: self._stop or ready()
                                  or self._next_rid != seen)
                if ready():
                    return

    def _run_inner(self) -> None:
        np, jnp = self._np, self._jnp
        S = self.mcfg.max_seq
        while True:
            # Idle until an admission makes a slot live. Admission is
            # pipeline-safe: an in-flight chunk saw the new slot as
            # inactive, and its prefill/poke queue behind that chunk on
            # the device. A saturated engine finds a slot live here and
            # opens no span: `serve.engine.idle` is nothing to do.
            if self._active.any():
                self._stand(self._active.any)
            else:
                with tracing.span("serve.engine.idle"):
                    self._stand(self._active.any)
            if self._stop:
                return
            # Predict this chunk's control outcome on the host: per-slot
            # emit counts and finishes depend only on pos/produced, never
            # on token values — so the chunk's finishes free slots/pages
            # IMMEDIATELY (the freed pages are safe to reuse: a later
            # request always writes a position before reading it, and
            # its device ops queue behind this chunk).
            plan = []
            for slot in range(self.n_slots):
                req = self._slot_req[slot]
                if req is None or not self._active[slot]:
                    continue
                valid = int(max(0, min(self.chunk, S - self._pos[slot])))
                take = int(min(valid, req.max_tokens - req.produced))
                fin = (req.produced + take >= req.max_tokens
                       or self._pos[slot] + valid >= S)
                req.produced += take
                plan.append((slot, req, take, fin))
            # COPIES, not views: jnp.asarray may alias numpy memory
            # (zero-copy on the CPU backend), and this loop mutates the
            # block table and _active in place while the dispatched chunk
            # is still queued — an aliased buffer would let those mutations
            # reach into the in-flight computation.
            useful = sum(take for _, _, take, _ in plan)
            live_kv = int(self._pos[self._active].sum())
            self.decode_chunks += 1
            self.decode_useful_tokens += useful
            self.live_kv_tokens += live_kv
            # What the emitter has fetched so far: the chunk before's distinct
            # experts (over its steps and the layers) and the running tokens
            # per expert, `:`-joined (the profiler splits arguments at `,`);
            # put together only where a span is recorded.
            routed = {"experts_touched": self._touched_last_chunk,
                      "expert_tokens": ":".join(map(str, self.expert_tokens))
                      } if self._sparse and tracing.recording() else {}
            if routed and self._shares:
                routed.update(local_assignments=self._local_last_chunk,
                              routed_assignments=self._routed_last_chunk)
            if self._index_topk or self._window:
                # What each step of the chunk reads, a layer: a slot at
                # position p attends to p + 1 positions.
                reads = (self._pos[self._active][:, None] + 1
                         + np.arange(self.chunk)[None, :]).clip(max=S)
            if self._index_topk:    # the indexer's index_topk of them at most
                picked = int(np.minimum(reads, self._index_topk).sum())
                self.decode_selected_keys += picked
                self.decode_live_keys += int(reads.sum())
                routed.update(selected_keys=picked, live_keys=int(reads.sum()))
            if self._window:
                # Of a window layer's ring: a slot's own row and the window - 1
                # before it, p + 1 rows while it has fewer.
                ring = int(np.minimum(reads, self._window).sum())
                self.window_kv_tokens += ring
                routed.update(window_kv_tokens=ring)
            with tracing.span("serve.engine.decode_dispatch", useful=useful,
                              capacity=self.n_slots * self.chunk,
                              active=len(plan), live_kv_tokens=live_kv,
                              **routed):
                (self._kc, self._vc, self._last_d, self._pos_d, out_d,
                 experts_d, *more) = \
                    self._decode(self._params, self._kc, self._vc,
                                 jnp.asarray(self.pool.block_table.copy()),
                                 self._last_d, self._pos_d,
                                 jnp.asarray(self._active.copy()),
                                 jnp.asarray(self._temp.copy()),
                                 jnp.asarray(self._topk.copy()),
                                 jnp.asarray(self._skeys.copy()), self._ic,
                                 self._state)
                self._ic, self._state = self._third(more)
                self._pos = np.where(
                    self._active, np.minimum(self._pos + self.chunk, S),
                    self._pos).astype(np.int32)
                for slot, req, take, fin in plan:
                    if fin and self._slot_req[slot] is req:
                        self._finish_state(slot)
                try:
                    out_d.copy_to_host_async()
                except AttributeError:
                    pass
            with self._cv:
                self._in_flight += 1
            self._emit_q.put(("chunk", out_d, plan, experts_d))
            # How long the loop then stood for want of pipeline room: fewer
            # than `_DEPTH` chunks in flight (or no slot left to decode for).
            with tracing.span("serve.engine.emit_block"):
                self._stand(lambda: self._in_flight < _DEPTH
                            or not self._active.any())

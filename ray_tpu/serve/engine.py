"""Continuous-batching LLM decode engine with a PAGED KV cache.

The TPU-native answer to the reference's vLLM delegation (reference:
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:170 —
engine_kwargs feed vLLM's continuous batcher + paged attention; here the
engine is OURS):

- **The model's programs and caches are not this module's.** It is the
  SCHEDULER: slots, pages, the ladder of prefill widths, admission, riders,
  the emitter. What runs on the chip is built by `models/serving.py`
  (`build_programs` -> `Programs`), and what a model keeps between programs
  is ONE bundle (`Caches`) that `Engine` holds, hands to every program first
  after the parameters, gets back first, and never opens; what the scheduler
  has to know of a model it asks of `Programs` (`takes_riders`, `adopts`,
  `by_slot`, `block`); what a model alone counts its `Books` keep, and the
  weights' layout is `serving_params`'. No program is built here, no
  architecture is named, and of the configuration `max_seq` alone is read.
- **Paged KV cache**, kept behind two names. The DEVICE side is
  `ops/paged_kv.py` (the arena's layout, the null page, the ops on it), which
  only the programs touch. The HOST side is `serve/page_pool.py::PagePool`:
  which physical pages each slot holds, the reservation rule, and the block
  table `[n_slots, max_pages]` a decode chunk is handed (vLLM's block-table
  design). This module lays nothing out and does no page arithmetic.
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
  (`rung_rides`: the octave under max_seq, of a stack whose programs take
  riders, `Programs.takes_riders`: a dense, a sparse, a state-space hybrid, a
  stack of short-convolution layers beside attention, one of
  latent-attention layers or one of window and full attention layers)
  carries ONE decode step of every live slot in
  those rows (`models/serving.py`); on the host the riders advance as a chunk
  of one step would (`_ride_plan`, `_place`), and the emitter streams their
  tokens after the prompt's first. Who rides is read off the stack and the
  shapes: no option, field or environment variable.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.serve.page_pool import PagePool
from ray_tpu.utils import get_logger, tracing

logger = get_logger("serve.engine")


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
    (`models/serving.py`, riders): the rungs of the octave under `max_seq`,
    where a prefill is long enough for a decode step's weight reads to hide
    in it and where the long prompts of a batch land, and none narrower (a
    riding program holds a decode step's attention kernel (a latent stack's
    the absorbed form's), a hybrid's its state's step, a conv stack's its
    windows' and a mixed stack's its rings' too, and a sampler over the slots'
    rows, traced, lowered and loaded at every start). The slots' rows have to fit in the rung beside a
    prompt. Whether the stack's programs take riders at all is the stack's
    answer (`Programs.takes_riders`), not the rung's."""
    return 2 * width >= max_seq and n_slots < width


def _seed_key(seed: int):
    """Threefry key = [hi, lo] words of the seed — host-side PRNGKey
    construction (no device round-trip at admit)."""
    import numpy as np
    return np.array([(seed >> 32) & 0xffffffff, seed & 0xffffffff],
                    np.uint32)


class _Request:
    __slots__ = ("ids", "max_tokens", "out", "produced", "slot",
                 "adopt_kv", "first", "temperature", "top_k", "seed",
                 "rid", "t_submit", "ctx", "tail")

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
        # A model of blocks: the prompt ids that open the slot's first block
        # (`_place`), which its first chunk hands back before its tokens.
        self.tail = 0


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

        from ray_tpu.models import serving

        serving.check_rungs(mcfg, prefill_widths(mcfg.max_seq))
        self._np = np
        self._jnp = jnp
        self.mcfg = mcfg
        self.n_slots = n_slots
        self.chunk = decode_chunk
        # The weights as the programs read them, made before anything else
        # is allocated; the caller's projections are TAKEN OVER (`params`).
        self._serving = serving
        self._params = serving.serving_params(params, mcfg)
        self.pool = PagePool(n_slots, mcfg.max_seq, page_size, n_pages)
        self.n_pages = self.pool.n_pages
        # The model's programs, and its caches: one bundle that every
        # program takes and hands back, and that nothing here opens
        # (`models/serving.py::Programs`, `Caches`).
        self._programs = serving.build_programs(
            mcfg, n_slots, decode_chunk, self.pool.page, self.n_pages)
        self._caches = self._programs.empty()
        # What this model alone counts, its caches' bytes among it.
        self._books = self._programs.books(self._caches)
        # Positions a slot's step yields (`Programs.block`): 1, or a block.
        self._block = self._programs.block
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
        # The slots' state on the device: the last token (a model of blocks:
        # the pending block and the open one, `[n_slots, 2 * block]`) and the
        # position.
        self._last_d = self._slots_last()
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
        self.decode_chunks_sampling = 0    # those with a slot at temp > 0
        self.decode_useful_tokens = 0
        # Tokens decoded inside prefills (a riding slot's one step in a
        # prompt's padding rows), and the prefills that carried any.
        self.rider_tokens = 0
        self.rider_steps = 0
        # Positions the active slots held when each chunk was dispatched:
        # what decode attention had to read, a layer, at the chunk's first
        # step (against n_slots * max_seq, what a whole-table gather moves).
        self.live_kv_tokens = 0
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
        if not self._scratch_fits():
            # No room for the thread's scratch caches (a model whose slots
            # hold gigabytes of state): every bucket warms here, against the
            # live ones, before the loop starts.
            self._warm = set(self.buckets)
        for width in sorted(self._warm):
            self._caches, first = self._warm_width(self._caches, width)
        with tracing.compile_span("serve.engine.warm", program="decode",
                                  width=n_slots):
            self._caches, self._last_d, self._pos_d, _, _ = \
                self._programs.decode(
                    self._params, self._caches,
                    jnp.asarray(self.pool.block_table),
                    self._last_d, self._pos_d, jnp.zeros(n_slots, bool),
                    jnp.asarray(self._temp), jnp.asarray(self._topk),
                    jnp.asarray(self._skeys))
        # Warm both poke variants: host-int `first` (adopt path) and
        # device-scalar `first` (prefill path).
        with tracing.compile_span("serve.engine.warm", program="poke",
                                  width=n_slots):
            self._last_d, self._pos_d = self._programs.poke(
                self._last_d, self._pos_d, 0, 0, 0)
            self._last_d, self._pos_d = self._programs.poke(
                self._last_d, self._pos_d, 0, first, 0)
            self._last_d, self._pos_d = self._programs.poke(
                self._last_d, self._pos_d, 0, 0, 0)
        jax.block_until_ready(first)
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

    def _scratch_fits(self) -> bool:
        """Whether a second set of caches, which `_warm_buckets` holds while
        it warms, fits on the device beside what is there now (the weights
        and the live caches), by the device's own count of its memory; a
        device that keeps none (the CPU) is taken to have the room."""
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        if not stats.get("bytes_limit"):
            return True
        scratch = sum(leaf.nbytes for leaf in jax.tree.leaves(self._caches))
        return stats["bytes_in_use"] + scratch <= stats["bytes_limit"]

    def _slots_last(self):
        """The slots' `last` as the programs take it, zeroed."""
        shape = (self.n_slots,) + (
            (2 * self._block,) if self._block > 1 else ())
        return self._jnp.zeros(shape, self._jnp.int32)

    def _warm_width(self, caches, width: int):
        """First call of the prefill program of one bucket width and, at a
        doubling width, of its adopt twin, writing to the null page of the
        caches given (pages = zeros: never real KV state; a per-slot state's
        slot 0, before any request holds it, or a scratch one's). Returns
        (caches, first token on the device)."""
        jnp = self._jnp
        null_pages = jnp.zeros(self.pool.maxp, jnp.int32)
        # A riding rung's program with nobody riding, on slots' state of its
        # own: the live `_last_d` and `_pos_d` are the loop's to donate.
        rides = self._rides(width)
        slots = (None, None, None) if not rides else (
            self._slots_last(),
            jnp.zeros(self.n_slots, jnp.int32),
            self._riders(self._np.zeros(self.n_slots, bool)))
        with tracing.compile_span("serve.engine.warm", program="prefill",
                                  width=width):
            caches, first, *_ = self._programs.prefill(
                self._params, caches, null_pages,
                jnp.zeros((1, width), jnp.int32), 1, 0.0, 0,
                jnp.zeros(2, jnp.uint32),
                0 if self._programs.by_slot else None, *slots)
        # no handoff has this width (none at all is sent an engine that
        # serves whole requests), or carries what this model caches
        if width not in self._adopt_widths or not self._programs.adopts:
            return caches, first
        # The PD adopt program for this width too (a first cross-pool
        # handoff must not compile in the loop).
        with tracing.compile_span("serve.engine.warm", program="adopt",
                                  width=width):
            caches = self._programs.adopt(
                caches, null_pages,
                *self._serving.empty_handoff(self.mcfg, width))
        return caches, first

    def _rides(self, width: int) -> bool:
        """Whether the prefill program of this width takes the live slots
        along: the stack's answer (`Programs.takes_riders`) and the rung."""
        return self._programs.takes_riders and rung_rides(
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
        SCRATCH caches — the live ones are donated on every engine call and
        must never be touched from this thread. Costs one transient extra
        arena while warming."""
        try:
            caches = self._programs.empty()
            for width in widths:
                if self._stop:
                    return
                caches, first = self._warm_width(caches, width)
                first.block_until_ready()   # compile fully landed
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

    def prefill_shapes(self, width: int) -> Tuple:
        """The prefill program's arguments for one bucket width as `_place`
        passes them, riders and all: shapes for arrays."""
        riding = (self._last_d, self._pos_d, self._riders(
            self._np.zeros(self.n_slots, bool))) if self._rides(width) \
            else (None, None, None)
        return self._shapes((
            self._params, self._caches,
            self._np.zeros(self.pool.maxp, self._np.int32),
            self._np.zeros((1, width), self._np.int32), 1, 0.0, 0,
            _seed_key(0), 0 if self._programs.by_slot else None, *riding))

    def decode_shapes(self) -> Tuple:
        """The decode program's arguments as the loop passes them."""
        return self._shapes((
            self._params, self._caches, self.pool.block_table, self._last_d,
            self._pos_d, self._active, self._temp, self._topk, self._skeys))

    @staticmethod
    def _shapes(args):
        import jax
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape") else x, args)

    def lowered_prefill_text(self, width: int) -> str:
        """StableHLO text of the prefill program for one bucket width —
        what a check reads to see which attention path (a Pallas
        `tpu_custom_call` or the XLA reference) that width compiled to."""
        return self._programs.prefill.lower(
            *self.prefill_shapes(width)).as_text()

    def lowered_decode_text(self) -> str:
        """StableHLO text of the decode program."""
        return self._programs.decode.lower(*self.decode_shapes()).as_text()

    # ------------------------------------------------------------------
    @property
    def params(self):
        """The model's parameters as they are published (`wq`, `wk`, `wv` a
        matrix each: what a checkpoint or a plain reference reads), split
        from the engine's fused stack at every call: the engine holds no
        second copy of the projections."""
        return self._serving.published_params(self._params, self.mcfg)

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
        if not self._programs.adopts:
            raise NotImplementedError(
                "a PD handoff carries K and V, not a sparse-attention "
                "indexer's keys nor a state-space layer's recurrent state "
                "nor latent attention's rows (kv_lora_rank > 0) nor mixed "
                "attention's two caches (attn_pattern: pages and window "
                "rings) nor a short-convolution layer's window (conv_layers)"
                " nor a block that a prompt's tail opens (block_length > 1)"
                " nor a power-retention layer's state (mixer 'retention')"
                " nor a linear layer's state beside a block-sparse layer's "
                "pooled keys (mixer_types): this model serves from one "
                "engine")
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
        of slot-time left unfilled. Occupancy is `decode_useful_tokens` over
        `decode_chunks * n_slots * chunk`; `rider_tokens` are the tokens
        decoded outside the chunks, a live slot's one step inside another
        request's prefill (`rider_steps`: the prefills that carried any), so
        the tokens decoded are the two added; padding is
        `prefill_padded_tokens` over it plus `prefill_tokens`.
        `decode_chunks_sampling` are the chunks dispatched with a live slot
        at a temperature above 0 (the span's `sampling` counts the slots):
        the others' steps took no top-k (`serving.sample_tokens`).
        `live_kv_tokens` over `decode_chunks * n_slots * max_seq` is the
        share of the block tables that was live at dispatch. What a model
        alone counts its `Books` merge in (`models/serving.py`), where each
        such counter's paragraph stands beside its cause."""
        return {**{k: getattr(self, k) for k in (
            "admitted", "queue_wait_s_sum", "admit_chunks_ahead",
            "admit_decoding_slots", "admit_pending", "slot_idle_s_sum",
            "prefill_tokens",
            "prefill_padded_tokens", "decode_chunks",
            "decode_chunks_sampling", "decode_useful_tokens",
            "rider_tokens", "rider_steps",
            "live_kv_tokens", "peak_pages_used", "n_slots", "chunk")},
            **self._books.counters()}

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
            # (a model that pages nothing reserves nothing: a free slot is
            # all its request waits for)
            need = self.pool.pages_for(len(req.ids), req.max_tokens) \
                if self._programs.paged else 0
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
                    chunks_ahead=ahead, decoding=decoding,
                    slot_idle_us=int(slot_idle * 1e6),
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
            self._caches = self._programs.adopt(self._caches, pages_arr,
                                                ks, vs)
            first, experts = req.first, None
        else:
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :len(req.ids)] = req.ids
            slots = (None, None, None)
            if riders is not None:
                riding = np.zeros(self.n_slots, bool)
                riding[[s for s, _, _ in riders]] = True
                slots = (self._last_d, self._pos_d, self._riders(riding))
            self._caches, first, experts, *more = self._programs.prefill(
                self._params, self._caches, pages_arr,
                jnp.asarray(toks), len(req.ids),
                float(req.temperature), int(req.top_k),
                jnp.asarray(_seed_key(req.seed)),
                slot if self._programs.by_slot else None, *slots)
            if riders is not None:
                self._last_d, self._pos_d, rode = more
                self._pos[riding] += 1
                for s, _, fin in riders:
                    if fin:     # its slot and pages are free at once
                        self._finish_state(s)
        req.slot = slot
        self._slot_req[slot] = req
        # A model of blocks kept the prompt's whole blocks: the rest, its
        # tail, opens the slot's first block, at that block's first position.
        req.tail = len(req.ids) % self._block
        self._books.placed(req.tail, prefilled=req.first < 0)
        self._pos[slot] = len(req.ids) - req.tail
        self._active[slot] = True
        # Sampling state applies on BOTH branches (a PD handoff
        # continues decoding with the request's params).
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._skeys[slot] = _seed_key(req.seed)
        # The prefill (or the hand-off) made the first token; a model of
        # blocks makes it with its first block.
        req.produced = int(self._block == 1)
        # Device-side slot bookkeeping (async — never a host round-trip;
        # `first` stays a device scalar on the prefill path).
        self._last_d, self._pos_d = self._programs.poke(
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
                    # Ends at the engine's first-token instant. A model of
                    # blocks has no token yet (its `first` is the block the
                    # prompt's tail opens): the span ends when the prefill
                    # has left the device, and the first token's instant is
                    # the end of the `opening` span of the slot's first chunk.
                    with tracing.span(
                            "serve.engine.emit", ctx=req.ctx, rid=req.rid,
                            kind="first" if req.first < 0 else "adopt"):
                        if self._block > 1:
                            first.block_until_ready()
                        elif req.first < 0:
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
                        with tracing.span("serve.engine.prefill_experts",
                                          ctx=req.ctx, rid=req.rid,
                                          **self._books.routed(experts)):
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
                        for slot, req, take, fin, skip, opens in plan:
                            toks = [int(t)
                                    for t in out_h[slot, skip:skip + take]]
                            if opens:
                                # A model of blocks: the request's first
                                # token comes with its first block.
                                with tracing.span(
                                        "serve.engine.emit", ctx=req.ctx,
                                        rid=req.rid, kind="opening"):
                                    req.out.put(toks)
                            elif toks:
                                req.out.put(toks)
                            if fin:
                                req.out.put(None)
                    if experts is not None:
                        self._books.routed(experts, chunk=True)
            except BaseException:
                import traceback
                self.error = self.error or traceback.format_exc()
                # Terminate the affected streams rather than stranding
                # their consumers.
                if item[0] == "first":
                    for req in [item[1]] + [r for _, r, _ in item[6]]:
                        req.out.put(None)
                else:
                    for _, req, *_ in item[2]:
                        req.out.put(None)

    def _fetch(self, out_d):
        """A decode chunk's tokens on the host: the emitter's wait for the
        device (a test holds the emitter here)."""
        return self._np.asarray(out_d)

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
                # A model of blocks: the first `skip` of the chunk's
                # positions are the prompt's tail (the slot's first chunk).
                skip, req.tail = req.tail, 0
                take = int(min(valid - skip, req.max_tokens - req.produced))
                fin = (req.produced + take >= req.max_tokens
                       or self._pos[slot] + valid >= S)
                opens = self._block > 1 and req.produced == 0
                req.produced += take
                plan.append((slot, req, take, fin, skip, opens))
            # COPIES, not views: jnp.asarray may alias numpy memory
            # (zero-copy on the CPU backend), and this loop mutates the
            # block table and _active in place while the dispatched chunk
            # is still queued — an aliased buffer would let those mutations
            # reach into the in-flight computation.
            useful = sum(p[2] for p in plan)
            live_kv = int(self._pos[self._active].sum()) \
                if self._programs.paged else 0
            # Live slots that ask for a sample: with none, the chunk's steps
            # take their argmax alone (`serving.sample_tokens`).
            sampling = int((self._active & (self._temp > 0)).sum())
            self.decode_chunks += 1
            self.decode_chunks_sampling += int(sampling > 0)
            self.decode_useful_tokens += useful
            self.live_kv_tokens += live_kv
            # What the model counts of the chunk, its totals advanced.
            model = self._books.dispatch(self._pos, self._active, self.chunk,
                                         plan, tracing.recording())
            with tracing.span("serve.engine.decode_dispatch", useful=useful,
                              capacity=self.n_slots * self.chunk,
                              active=len(plan), sampling=sampling,
                              live_kv_tokens=live_kv, **model):
                (self._caches, self._last_d, self._pos_d, out_d,
                 experts_d) = self._programs.decode(
                    self._params, self._caches,
                    jnp.asarray(self.pool.block_table.copy()),
                    self._last_d, self._pos_d,
                    jnp.asarray(self._active.copy()),
                    jnp.asarray(self._temp.copy()),
                    jnp.asarray(self._topk.copy()),
                    jnp.asarray(self._skeys.copy()))
                self._pos = np.where(
                    self._active, np.minimum(self._pos + self.chunk, S),
                    self._pos).astype(np.int32)
                for slot, req, _, fin, *_ in plan:
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

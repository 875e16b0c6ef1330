"""What Serve runs on the chip: the model's serving programs.

`serve/engine.py` is the scheduler (slots, pages, the ladder, admission,
riders, the emitter) and builds no program; this module builds them all, from
`models/block.py`'s layers and `ops/`'s kernels and caches, and imports
nothing from `serve/`.

A stack is SEGMENTS (`LlamaConfig.segments`): runs of layers of one kind, each
kind a stack of parameters. ONE function walks them for a prompt
(`_prefill_walk`) and one for a decode step (`build_programs`' `_step`): embed,
the rotary tables, the live mask, each segment's layers with the caches in the
carry, the head, the sampler, the routing counts. What differs between kinds
of layer is `_stack`'s table: a kind's prefill body, its decode body, and
what the walk cannot guess of how to run them. A new architecture is a kind's
two bodies, its cache's ops in `ops/` (empty, a prompt's write, a token's
write, the read), its books (`_Counts`, beside the kind) and its fields in
`LlamaConfig`. The weights' serving layout is `serving_params`'.

A model that generates by BLOCKS (`LlamaConfig.block_length` B > 1) has a
prefill and a decode program of its own under the same names (`block_prefill`,
`block_decode`; the same walks). A block is `denoise_steps` T forwards: no
forward is a commit's own. The K and V the cache keeps of block b are written
by the FIRST forward of block b + 1, which carries b's final ids beside its
own rows (2B rows a slot; the others B), so a chunk of `chunk / B` blocks
runs `chunk / B x T` forwards, which its dispatch span calls `forwards`.

The caches travel as one bundle (`Caches`) that the scheduler never opens. A
decode program updates them IN PLACE, as a loop carry that nothing but
`ops/`'s writes and reads touches, so no copy of an arena (or of a layer's
slab) is ever made (see `_over`).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import block
from ray_tpu.ops import (attention, linear_attention, norms, paged_kv,
                         slot_state, sparse_attention)
from ray_tpu.utils import get_logger

logger = get_logger("models.serving")

# Rows of a prefill that meet the sparse feed-forward at once: its sorted
# copies are `rows x experts a token` wide (2.5 GiB of temporaries at 4,096
# rows of OLMoE's widths), so a wider bucket goes through in blocks of this
# many rows; each row is computed from itself alone, so nothing changes.
_MOE_ROWS = 4096

# Compile-time cap on per-request top_k (jax.lax.top_k needs a static
# width; requests asking for more sample from the best TOPK_CAP).
TOPK_CAP = 64


class Caches(NamedTuple):
    """What a model keeps between programs, ONE pytree: `Programs.empty()`
    makes it, every program takes it first after `params` and hands it back
    first, donated, and the scheduler never opens it. `kc`, `vc`: the arena of
    the layers that keep K and V under the block table (`ops/paged_kv.py`; a
    latent-attention model's arena of latent rows is `kc`, and its `vc` None).
    `ic`: the indexer keys' arena of a model with sparse attention, under the
    same block table. `state`: what a model keeps a SLOT, no pages
    (`ops/slot_state.py`): the recurrent state of state-space layers, the
    rings of window layers, the windows of short-convolution layers, or the
    state of power-retention layers (whose model keeps nothing else: `kc`
    and `vc` are None). None where the model has no such cache."""
    kc: Any = None
    vc: Any = None
    ic: Any = None
    state: Any = None


class Programs(NamedTuple):
    """A model's serving programs and what a scheduler has to ask of it
    without knowing it (`build_programs`).

    `empty() -> caches`; `prefill(params, caches, pages, tokens, length, temp,
    topk, key, slot, last, pos, riders) -> (caches, first, experts[, last,
    pos, tokens])`; `decode(params, caches, bt, last, pos, active, temp, topk,
    keys) -> (caches, last, pos, out, experts)`; `adopt(caches, pages, ks, vs)
    -> caches`; `poke(last, pos, slot, first, length) -> (last, pos)`.

    A model that generates by BLOCKS (`block` B > 1): the slots' `last` is
    `[n_slots, 2B]`, a slot's PENDING block (its final ids, whose K and V the
    next forward keeps; -1s: none) then its open block (an id, or -1 for a
    row still masked); `prefill` keeps the prompt's whole blocks, `B *
    floor(length / B)` rows, yields NO token, and its `first` is the slot's
    state as it opens `[2B]`, nothing pending then the prompt's tail then
    -1s, which `poke` sets beside `pos`, the open block's first position;
    `decode` is `chunk / B` blocks of `denoise_steps` forwards each (the
    denoising steps: the first, of 2B rows a slot, commits the block before;
    no forward is the commit's own), and `out [n_slots, chunk]` holds every
    position of those blocks, a slot's first chunk the prompt's tail too."""
    empty: Callable
    prefill: Callable
    decode: Callable
    adopt: Callable
    poke: Callable
    # Whether the prefill program of a riding rung carries the live slots
    # (`_stack`'s answer: a dense, a sparse, a state-space, a conv, a latent
    # and a mixed stack do; an indexed, a retention stack and one that
    # generates by blocks take nobody).
    takes_riders: bool
    # Whether a hand-off's K and V can be adopted (`adopts`).
    adopts: bool
    # Whether a prefill writes per-slot state, so `slot` is passed.
    by_slot: bool
    # Whether any layer keeps pages under the block table: a request of a
    # model that has none reserves no page, and the table is read by nobody.
    paged: bool
    # caches -> an engine's `Books`: what this model alone counts.
    books: Callable[[Caches], "Books"]
    # Positions a slot's step yields: 1, a token a forward; B > 1, a block of
    # B positions in `denoise_steps` forwards (see above).
    block: int = 1


class _Kind(NamedTuple):
    """One kind of layer, as the walks run it.

    `prefill(lp, x, caches, l, ctx) -> (x, caches, kept, counts)`: a prompt's
    rows `x [1, B, D]` through the layer `lp` (its ordinal `l`, where the walk
    passes one); `kept`, a tuple, is what the caches keep of the rows,
    `counts` the layer's routing (None: it routes nothing), `caches` what
    riders ride against (None without them): under `ctx["riders"]` = (bt, w,
    act) the rows' last n_slots are one token a slot, and a kind of a stack
    that takes riders gives them its decode step there, against `caches` (its
    pages, its state), and hands the caches back moved; what a kind does row
    by row needs no word of them. `decode(lp, x, caches, l, ctx)
    -> (x, caches, counts)`: one token a slot, `x [n_slots, D]`, the step's
    rows written and the cached ones read. `ctx` is the walk's: the rotary
    tables, the live mask, the block table, the positions.

    The rest is what the walk cannot guess. The programs are held to the
    text they lowered to before there was one walk (tests/test_dots.py,
    tests/test_prefill_riders.py), so the fields say, a kind, exactly how
    its layers ran there."""
    prefill: Callable
    decode: Callable
    # The cache each array of `kept` is a prompt's write to (`_KEEP`).
    keeps: Tuple[str, ...]
    # The stack of parameters its layers are; None: the kind's name.
    stack: Optional[str] = None
    # How a segment's layers run. "scan": a scan over the stack's weights,
    # sliced a layer at a time, and the layers' ordinals (a segment that is
    # part of its stack: as "index"; the experts' stacks
    # stay whole, `block.expert_stacks`, read by ordinal); "slices": over the
    # weights alone, the ordinals too only under riders; "index": over the
    # ordinals alone, the body reading its layer from the whole stack (so a
    # segment may be PART of its stack; the experts' stacks stay whole here
    # too); "inline": no scan, one layer where it stands.
    over: str = "scan"
    # Entries of `ctx` that ride a prompt's scan as CARRY, not closed over.
    rides: Tuple[str, ...] = ()
    # The caches that ride a decode step's scan (all of them, or a state
    # alone: an arena that a segment never touches stays out of its loop).
    carries: Tuple[str, ...] = Caches._fields
    # Decode: `begin(ctx)` before a segment's scan puts into `ctx` what a
    # step or a segment reads of the tables once for all its layers (`ctx` is
    # the step's own dict: what one segment leaves there the next one finds).
    begin: Optional[Callable] = None
    # Prefill: `pack(kept) -> kept` of a segment's stacked rows.
    pack: Optional[Callable] = None
    # What its layers make the model count (`_Counts`); None: nothing.
    counts: Optional[_Counts] = None


class _Stack(NamedTuple):
    """A model's stack as the walks read it (`_stack`)."""
    kinds: Dict[str, _Kind]
    # tables(n, rows) -> entries of `ctx`: the rotary tables over n
    # positions; `rows`: as a prompt's rows 0..n-1 read them (a decode step
    # reads them at its slots' positions, in the layer's body or `begin`).
    tables: Callable
    # empty(n_slots, page, n_pages) -> Caches, zeroed.
    empty: Callable
    # caches -> the counters `Engine.counters()` shows of them (a mixed
    # model's rings are `window` positions a slot a layer, whatever it serves).
    cache_bytes: Callable
    takes_riders: bool = False
    adopts: bool = False
    # The routing counts are a SHARE's (`_share_stats`).
    shares: bool = False
    # How a prompt's counts add up over its segments: "late" (the one
    # segment's, summed after the head), "first" (a segment's sum as it is:
    # one routed segment), "zero" (each segment's sum added on, from 0).
    tally: str = "late"


def _layer_of(stack, i):
    """Layer `i` of a stack of layers (a leading axis on every leaf): what a
    scan over the stack hands its body, read by index."""
    return jax.tree.map(lambda w: w[i], stack)


def _head_logits(params, h, mcfg):
    """h [rows, D] -> logits [rows, V]: the head, or the embedding transposed
    where the model ties them, times `mcfg.logit_scale` where it has one."""
    if mcfg.tie_embeddings:
        logits = jnp.einsum("bd,vd->bv", h,
                            params["embed"].astype(mcfg.dtype))
    else:
        logits = h @ params["lm_head"].astype(mcfg.dtype)
    if mcfg.logit_scale != 1.0:
        logits = logits * jnp.asarray(mcfg.logit_scale, logits.dtype)
    return logits


def _embed(params, tokens, mcfg):
    """The tokens' rows of the embedding in the compute dtype, times
    `mcfg.embed_scale` where the model has one."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(mcfg.dtype)
    if mcfg.embed_scale != 1.0:
        x = x * jnp.asarray(mcfg.embed_scale, x.dtype)
    return x


def _rope_one(x, c, s):
    """One token a slot rotated: x [ns, heads, hd], c/s [ns, 1, hd//2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


def latent_rope_tables(mcfg, width):
    """(cos, sin) [width, qk_rope_dim // 2] of a latent-attention model."""
    if mcfg.rope_yarn:
        return norms.yarn_frequencies(mcfg.qk_rope_dim, width,
                                      mcfg.rope_theta, *mcfg.rope_yarn[:4])
    return norms.rope_frequencies(mcfg.qk_rope_dim, width, mcfg.rope_theta)


def expert_stats(counts: jax.Array) -> jax.Array:
    """What a serving program hands back of one layer's routing, `[E + 1]`
    int32 that add up over layers and steps: tokens per expert, then the
    number of distinct experts touched."""
    return jnp.concatenate(
        [counts, jnp.sum(counts > 0, dtype=jnp.int32)[None]])


def _share_stats(counts, live, mcfg):
    """One sparse layer's routing as a program that holds a SHARE of the
    experts hands it back, `[held + 2]` int32 that add up: `expert_stats` of
    the HELD experts (tokens per expert, then the distinct ones touched),
    then the assignments the router made of the live rows, to whichever
    share."""
    routed = jnp.sum(live, dtype=jnp.int32) * mcfg.top_k_experts
    return jnp.concatenate([expert_stats(counts), routed[None]])


def _routing_stats(mcfg):
    """(counts, live) -> what a program hands back of one sparse layer's
    routing: `_share_stats` where the model holds a share of its experts,
    `expert_stats` where it holds them all."""
    if mcfg.experts_held:
        return lambda counts, live: _share_stats(counts, live, mcfg)
    return lambda counts, live: expert_stats(counts)


class _Counts(NamedTuple):
    """What ONE cause (a kind of layer, `block_decode`) makes a model count
    that not every model counts, beside its cause: `Books` adds them up."""
    # Of the arguments `dispatch` returns, the counter each is added to.
    advances: Dict[str, str] = {}
    # dispatch(pos [n_slots], active [n_slots], chunk, plan) -> its arguments
    # of a `serve.engine.decode_dispatch` span, from the slots' positions and
    # the loop's plan (a live slot's `(slot, ..., opens)` each).
    dispatch: Optional[Callable] = None
    keeps: Dict[str, int] = {}      # its other counters, as they start


def _reads(S, cap, **advances) -> _Counts:
    """Counts the positions a chunk's decode steps read, a layer, over the
    active slots (a slot at p attends to p + 1): under the first name `cap`
    of them at most a slot a step, under a second one all of them."""
    def dispatch(pos, active, chunk, plan):
        reads = (pos[active][:, None] + 1 + np.arange(chunk)).clip(max=S)
        return dict(zip(advances, (int(np.minimum(reads, cap).sum()),
                                   int(reads.sum()))))
    return _Counts(advances, dispatch)


def _state_moves(S, slot_bytes: int) -> _Counts:
    """Counts what a stack whose decode steps move a slot's WHOLE state of
    fixed size counts: `state_writes`, the admissions that overwrote a slot's
    state, and `state_bytes_moved`, the bytes of state the decode chunks'
    steps read and wrote (`slot_bytes`, a slot's state of all its layers,
    twice a step a live slot; a chunk's are its dispatch span's
    `state_bytes`)."""
    def dispatch(pos, active, chunk, plan):
        steps = int(np.clip(S - pos[active], 0, chunk).sum())
        return {"state_bytes": 2 * slot_bytes * steps}
    return _Counts({"state_bytes": "state_bytes_moved"}, dispatch,
                   {"state_writes": 0})


class Books:
    """What ONE engine's model counts beyond what every model counts
    (`Programs.books(caches)`), in numpy and plain Python. The scheduler asks
    four things and knows no counter's name: `dispatch` and `placed` (its
    loop), `routed` (its emitter), `counters` (anyone). A counter is an entry
    of `totals` that `_add` alone advances; its paragraph stands with its
    cause. The routing's: a sparse model's `expert_tokens` (assignments per
    expert, all layers, prefills and decode steps, as far as the emitter has
    fetched them) and `decode_experts_touched` (distinct experts, summed over
    decode steps and SPARSE layers: over `decode_chunks * chunk *
    mcfg.sparse_layers`, the experts a sparse layer reads in a step); one
    that holds a SHARE of its experts adds `routed_assignments` (what its
    routers assigned of live rows, to any share) and `local_assignments`
    (those that fell to the experts held here, the sum of `expert_tokens`,
    then per HELD expert): their ratio is this share's part of the work."""

    def __init__(self, mcfg, counts: Tuple[_Counts, ...], share: bool,
                 cache_bytes: Dict[str, int]):
        self.share = share
        self._counts = counts
        self._sparse = mcfg.n_experts > 0
        # The last chunk's (touched, local, routed): the next dispatch span's.
        self._last = (0, 0, 0)
        t = self.totals = {}
        for c in counts:
            t.update(dict.fromkeys(c.advances.values(), 0), **c.keeps)
        if self._sparse:
            t.update(expert_tokens=np.zeros(mcfg.n_held, np.int64),
                     decode_experts_touched=0)
            if share:
                t.update(routed_assignments=0, local_assignments=0)
        t.update(cache_bytes)               # under the counters' names

    def _add(self, **by) -> None:
        """Each increment onto the counter of its name, in the order given,
        where this model keeps one (rebound, never changed in place)."""
        for name, n in by.items():
            if name in self.totals:
                self.totals[name] = self.totals[name] + n

    def dispatch(self, pos, active, chunk, plan, recording) -> Dict[str, Any]:
        """Loop thread: the MODEL's arguments of a chunk's
        `serve.engine.decode_dispatch` span, its totals advanced. Of the
        routing, what the emitter has fetched (the chunk before's figures;
        the tokens per expert `:`-joined, as the profiler splits arguments
        at `,`), put together only where a span is `recording`."""
        args: Dict[str, Any] = {}
        if self._sparse and recording:
            touched, local, routed = self._last
            args.update(experts_touched=touched, expert_tokens=":".join(
                map(str, self.totals["expert_tokens"])))
            if self.share:
                args.update(local_assignments=local,
                            routed_assignments=routed)
        for c in self._counts:
            if c.dispatch is not None:
                own = c.dispatch(pos, active, chunk, plan)
                args.update(own)
                self._add(**{c.advances[a]: n for a, n in own.items()
                             if a in c.advances})
        return args

    def routed(self, experts, chunk: bool = False) -> Dict[str, int]:
        """Emitter thread: one program's fetched `experts` (`expert_stats`,
        a share's `_share_stats`) taken apart and onto the totals
        (`routed_assignments` last: a reader that sees it moved sees all) ->
        the arguments of a prefill's `serve.engine.prefill_experts` span. A
        decode `chunk`'s figures are kept for the next dispatch span."""
        stats = np.asarray(experts)
        held = stats[:-1 - self.share]      # tokens per held expert
        touched, local = int(stats[-1 - self.share]), int(held.sum())
        # all the routers assigned: the same, where every expert is held
        routed = int(stats[-1]) if self.share else local
        self._add(expert_tokens=held, local_assignments=local,
                  routed_assignments=routed)
        if chunk:
            self._last = (touched, local, routed)
            self._add(decode_experts_touched=touched)
        return dict(touched=touched, **(
            dict(local=local, routed=routed) if self.share else {}))

    def placed(self, tail: int, prefilled: bool) -> None:
        """Loop thread, a request placed: `tail` prompt ids open its slot's
        first block; a prefill (no hand-off) wrote its slot's state over."""
        self._add(tail_tokens=tail, state_writes=int(prefilled))

    def counters(self) -> Dict[str, Any]:
        """The model's part of `Engine.counters()`."""
        return {k: v.tolist() if isinstance(v, np.ndarray) else v
                for k, v in self.totals.items()}


def sample_tokens(logits, temp, topk, keys, pos, cap=TOPK_CAP):
    """Per-slot token sampling (reference: vLLM's sampler): temperature
    + top-k via Gumbel-max over the top-`cap` logits (cap is a static
    trace-time width, min(TOPK_CAP, vocab)); temp==0 slots stay greedy.
    `keys` are per-slot base PRNG keys; folding in `pos` makes a
    request's sample stream deterministic for its (seed, position)
    regardless of slot assignment or co-tenants. The top-`cap`, the draws
    and the gather run only where some row of the call asks for a sample
    (a branch on the device, from `temp`): a call whose rows are all greedy
    takes its argmax alone, and the tokens are the same either way."""
    cap = min(cap, logits.shape[-1])

    def one_gumbel(key, p):
        return jax.random.gumbel(jax.random.fold_in(key, p), (cap,))

    def draw(greedy):
        vals, idxs = jax.lax.top_k(logits.astype(jnp.float32), cap)
        k_eff = jnp.where(topk > 0, jnp.minimum(topk, cap), cap)
        mask = jnp.arange(cap)[None, :] < k_eff[:, None]
        scaled = jnp.where(mask, vals / jnp.maximum(temp, 1e-6)[:, None],
                           -1e30)
        g = jax.vmap(one_gumbel)(keys, pos)
        pick = jnp.argmax(scaled + g, axis=-1)
        sampled = jnp.take_along_axis(idxs, pick[:, None], axis=1)[:, 0]
        return jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)

    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.lax.cond(jnp.any(temp > 0), draw, lambda g: g, greedy)


# ---------------------------------------------------------------------------
# The kinds of layer
# ---------------------------------------------------------------------------

def _attention_kind(mcfg, ridden: bool, **how) -> _Kind:
    """The uniform stack's layer: attention over K and V under the block
    table (flash, or the indexer's sparse attention), a dense or a sparse
    feed-forward (a hybrid's may be a share of the experts, whose routing
    comes back as `_share_stats`). `ridden`: its prefill takes riders, and
    its decode step is the riders' (`token_step`: ONE jit for both). In a
    stack of one-part layers (`mcfg.layer_parts`) the layer is attention
    ALONE: no feed-forward follows, and it routes nothing."""
    H, KVH, hd, S = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim, mcfg.max_seq
    dt = mcfg.dtype
    alone = mcfg.layer_parts is not None
    sparse = mcfg.n_experts > 0 and not alone
    indexed = mcfg.index_topk > 0
    # Generation by blocks: the prompt's mask, and a step of B rows a slot.
    B = mcfg.block_length
    stats = _routing_stats(mcfg)
    # The model's own scale of q . k, where it publishes one; else nothing is
    # passed and the kernels take head_dim^-1/2.
    scaled = {"sm_scale": mcfg.attn_scale} if mcfg.attn_scale else {}
    # What a program is built with is the function as it stands on its
    # module now (a test puts an interpreted kernel there first).
    paged_decode = paged_kv.paged_decode_attention
    sparse_decode = sparse_attention.sparse_decode_attention

    def _feed_forward(lp, x, live, l):
        """`feed_forward` over at most `_MOE_ROWS` rows at a time."""
        Sq = x.shape[1]
        if alone:
            return x, None
        if not sparse or Sq <= _MOE_ROWS:
            return block.feed_forward(lp, x, mcfg, live, l)
        outs, counts = [], 0
        for start in range(0, Sq, _MOE_ROWS):
            rows = slice(start, start + _MOE_ROWS)
            y, (_, n) = block.feed_forward(lp, x[:, rows], mcfg,
                                           live[:, rows], l)
            outs.append(y)
            counts = counts + n
        return jnp.concatenate(outs, axis=1), (None, counts)

    @jax.jit
    def _token_step(kc, vc, l, bt, w, act, q, k, v):
        """One token a slot against the cache: a step's k and v `[n_slots,
        kv_heads, hd]` written at the slots' positions `w`, then each active
        slot's q `[n_slots, heads, hd]` against its positions 0..w (an idle
        slot reads nothing). A jit of its own, and ONE for the riders and for
        the decode program's layers: no prefill width enters its shapes, so
        the kernel is traced once a process, not once a riding rung and again
        for decode (a second of every start, each, on the chip's host:
        PERF.md section 6, PR 41)."""
        kc, vc = paged_kv.write_token(kc, vc, l, bt, w, act, k, v)
        with jax.named_scope("attn"):
            attn = paged_decode(q, kc, vc, l, bt, jnp.where(act, w + 1, 0),
                                **scaled)
            attn = attn.reshape(q.shape[0], H * hd)
        return kc, vc, attn

    def prefill(lp, x, caches, l, ctx):
        nb, Sq, _ = x.shape
        q, k, v, *index = block.attention_inputs(
            lp, x, mcfg,
            (lambda t: norms.apply_rope(t, *ctx["tables"])) if mcfg.rope
            else (lambda t: t),
            (lambda t: norms.apply_rope(t, *ctx["itables"])) if indexed
            else None)
        with jax.named_scope("attn"):
            if indexed:
                qi, ki, w = index[0]
                attn = sparse_attention.sparse_attention(
                    q, k, v, qi.transpose(0, 2, 1, 3), ki[:, 0], w,
                    mcfg.index_topk)
            elif B > 1:
                attn = attention.block_flash_attention(
                    q, attention.repeat_kv(k, H // KVH),
                    attention.repeat_kv(v, H // KVH), B, **scaled)
            else:
                attn = attention.flash_attention(
                    q, attention.repeat_kv(k, H // KVH),
                    attention.repeat_kv(v, H // KVH), True, **scaled)
            attn = attn.transpose(0, 2, 1, 3).reshape(nb, Sq, H * hd)
        if ctx["riders"]:
            # ONE decode step of the riding slots in the bucket's tail rows:
            # their q, k and v do what a decode step does, and the result
            # takes the tail of the flash output's place.
            bt, w, act = ctx["riders"]
            tail = slice(Sq - act.shape[0], Sq)
            kc, vc, rode = _token_step(
                caches.kc, caches.vc, l, bt, w, act,
                *(t[0, :, tail].transpose(1, 0, 2) for t in (q, k, v)))
            caches = caches._replace(kc=kc, vc=vc)
            attn = attn.at[0, tail].set(
                jnp.where(act[:, None], rode, attn[0, tail]))
        with jax.named_scope("attn_out"):
            x = x + block.scaled(
                jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt)), mcfg)
        x, routed = _feed_forward(lp, x, ctx["live"], l if sparse else None)
        # cache pre-repeat k/v: [S, KVH, hd] (B == 1 squeezed)
        kept = (k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))
        counts = stats(routed[1], ctx["live"]) if sparse else None
        if indexed:
            kept += (ki[0, 0],)                                # [S, Id]
        return x, caches, kept, counts

    def decode(lp, x, caches, l, ctx):
        # x [ns, D]; the caches WHOLE (`ops.paged_kv`); l this layer's index
        # (traced scalar); a sparse model's expert weights in lp are all the
        # layers' (`expert_stacks`)
        ns = x.shape[0]
        bt, pos, act = ctx["bt"], ctx["pos"], ctx["act"]
        kc, vc, ic, _ = caches
        with jax.named_scope("rope"):
            w = jnp.minimum(pos, S - 1)
            if not mcfg.rope:           # attention takes no position signal
                c = s = None
            elif mcfg.mrope_section:    # text: the three streams are equal
                c, s = norms.mrope_tables(
                    *ctx["tables"], jnp.broadcast_to(w, (3, ns)),
                    mcfg.mrope_section)
                c, s = c[:, None], s[:, None]
            else:
                c, s = (t[w][:, None] for t in ctx["tables"])
            if indexed:     # the indexer's own tables, over its own width
                ci, si = (t[w][:, None] for t in ctx["itables"])
        q, k, v, *index = block.attention_inputs(
            lp, x, mcfg,
            (lambda t: _rope_one(t, c, s)) if mcfg.rope else (lambda t: t),
            (lambda t: _rope_one(t, ci, si)) if indexed else None)
        if ridden:
            # The write and the kernel as the riders' step has them, traced
            # once for both.
            kc, vc, attn = _token_step(kc, vc, l, bt, w, act, q, k, v)
        else:
            kc, vc = paged_kv.write_token(kc, vc, l, bt, w, act, k, v)
            if indexed:
                qi, ki, iw = index[0]
                ic = paged_kv.write_token_rows(ic, l, bt, w, act, ki[:, 0])
            # Each active slot's query against its positions 0..w; an idle
            # slot reads nothing.
            with jax.named_scope("attn"):
                lengths = jnp.where(act, w + 1, 0)
                if indexed:
                    attn = sparse_decode(q, qi, iw, kc, vc, ic, l, bt,
                                         lengths, mcfg.index_topk)
                else:
                    attn = paged_decode(q, kc, vc, l, bt, lengths, **scaled)
                attn = attn.reshape(ns, H * hd)
        with jax.named_scope("attn_out"):
            x = x + block.scaled(attn @ lp["wo"].astype(dt), mcfg)
        # An idle slot's row is computed like any other, from itself alone,
        # and left out of the count.
        if not alone:
            x, routed = block.feed_forward(lp, x, mcfg, act,
                                           l if sparse else None)
        return x, caches._replace(kc=kc, vc=vc, ic=ic), \
            stats(routed[1], act) if sparse else None

    def decode_rows(lp, x, caches, l, ctx):
        """`decode` for a step of B rows a slot, x [ns * B, D], slot s's
        block in rows s * B..: `ctx["pos"]` [ns] the blocks' first positions
        (multiples of B). The block's K and V are written over the slot's
        rows pos..pos + B - 1, then every row of the block attends to
        positions 0..pos + B - 1, its own block's rows both ways: the mask of
        a block is its length.

        Or of 2B rows a slot, x [ns * 2B, D]: the block BEFORE the open one
        (its final ids; positions pos - B..pos - 1) then the open one, ONE
        forward of the two streams. Both blocks' K and V are written before
        the attention (the first's only where `ctx["rides"]` [ns] says the
        slot has such a block: a slot's first block has the prompt's last
        whole block there, which stays), the first block's rows attend to
        0..pos - 1 and the open one's to 0..pos + B - 1, the first's K and V
        among them."""
        bt, pos, act = ctx["bt"], ctx["pos"], ctx["act"]
        ns = pos.shape[0]
        R = x.shape[0] // ns
        lag = R - B         # 0, or B: the rows that go before the open block's
        with jax.named_scope("rope"):
            w = jnp.minimum(pos, S - B)
            at = w[:, None] - lag + jnp.arange(R)
            if lag:     # a slot at 0 has no block before it: its rows' are 0
                at = jnp.maximum(at, 0)
            at = at.reshape(-1)
            c, s = (t[at][:, None] for t in ctx["tables"])
        q, k, v = block.attention_inputs(lp, x, mcfg,
                                         lambda t: _rope_one(t, c, s))
        k, v = k.reshape(ns, R, KVH, hd), v.reshape(ns, R, KVH, hd)
        kc, vc = caches.kc, caches.vc
        live = jnp.repeat(act[:, None], B, axis=1)
        if lag:
            rides = ctx["rides"]
            kc, vc = paged_kv.write_token(
                kc, vc, l, bt, jnp.maximum(w - B, 0), rides,
                k[:, :B], v[:, :B])
            live = jnp.concatenate(
                [jnp.repeat(rides[:, None], B, axis=1), live], axis=1)
        kc, vc = paged_kv.write_token(kc, vc, l, bt, w, act,
                                      k[:, lag:], v[:, lag:])
        with jax.named_scope("attn"):
            attn = paged_decode(q.reshape(ns, R, H, hd), kc, vc, l, bt,
                                jnp.where(act, w + B, 0), lag=lag, **scaled)
            attn = attn.reshape(ns * R, H * hd)
        with jax.named_scope("attn_out"):
            x = x + block.scaled(attn @ lp["wo"].astype(dt), mcfg)
        # An idle slot's rows (and the rows before a slot's first block) are
        # computed like any others and left out of the count.
        live = live.reshape(-1)
        x, routed = block.feed_forward(lp, x, mcfg, live,
                                       l if sparse else None)
        return x, caches._replace(kc=kc, vc=vc), \
            stats(routed[1], live) if sparse else None

    # The keys an indexer's decode steps select (a layer reads that many K
    # and V rows) over the positions dense attention would read.
    return _Kind(prefill, decode_rows if B > 1 else decode,
                 keeps=("pages", "pages") + ("index",) * indexed,
                 counts=_reads(S, mcfg.index_topk,
                               selected_keys="decode_selected_keys",
                               live_keys="decode_live_keys")
                 if indexed else None, **how)


def _mixer_riders(ctx, caches, layer):
    """What a mixer of `models/block.py` is handed beside a prompt's rows
    under `ctx["riders"]` (nothing without them): ONE decode step of the
    riding slots in the bucket's tail rows, from and to their own state of
    layer `layer`, which rides the scan's carry; the layer's weights are read
    once, for the prompt and for them."""
    if not ctx["riders"]:
        return {}
    return dict(riders=caches.state, layer=layer, active=ctx["riders"][2])


def _mamba_kind(mcfg) -> _Kind:
    """A hybrid's state-space layer (`block.mamba_mixer`, or Mamba-2's
    `block.mamba2_mixer` where the model has `ssm_heads`) over a dense or a
    sparse feed-forward: no K and V, a recurrent state a slot
    (`ops/slot_state.py`), whose layer is the layer's ordinal among the
    state-space layers. Rows past `length` reach no real row: the convolution
    is causal, and the mixer is told `length`. A sparse layer hands back its
    routing counts. In a stack of one-part layers (`mcfg.layer_parts`) the
    layer is the mixer ALONE: its stack holds no feed-forward's leaves, none
    runs, and it routes nothing. Its prefill takes RIDERS (`mixer(riders=)`):
    the tail rows' convolution against each riding slot's own window and
    their state's step where it lies, both written back as a decode step
    writes them (under the mixer's `conv` and `scan` scopes), between
    projections, a norm, a gate and a feed-forward that run once over the
    bucket; the step is the one `decode` runs, written once in the mixer."""
    mixer = block.mamba2_mixer if mcfg.ssm_heads else block.mamba_mixer
    stats = _routing_stats(mcfg)
    alone = mcfg.layer_parts is not None

    def prefill(lp, x, caches, l, ctx):
        routed_layer = "router" in lp
        y, state, window, *rode = mixer(lp, x[0], mcfg, length=ctx["length"],
                                        **_mixer_riders(ctx, caches, l))
        if rode:
            caches = caches._replace(state=rode[0])
        if alone:
            return y[None], caches, (state, window), None
        y, routed = block.feed_forward(lp, y[None], mcfg, ctx["live"],
                                       l if routed_layer else None)
        return y, caches, (state, window), \
            stats(routed[1], ctx["live"]) if routed_layer else None

    def decode(lp, x, caches, l, ctx):
        routed_layer = "router" in lp
        state, act = caches.state, ctx["act"]
        # The state's read and its write back are the update's traffic:
        # under the scope that times the update (`scan`).
        with jax.named_scope("scan"):
            ssm, window = slot_state.layer_state(state, l)
        if mcfg.ssm_heads:
            # Mamba-2's step is handed the slots' whole state and visits the
            # layer's rows where they lie, once (`slot_state.step_layer`);
            # the slice above is never read, so never made.
            x, state, window = mixer(lp, x, mcfg, state, window, step=True,
                                     layer=l, active=act)
            ssm = None
        else:
            x, ssm, window = mixer(lp, x, mcfg, ssm, window, step=True)
        with jax.named_scope("scan"):
            state = slot_state.update_layer(state, l, act, ssm, window)
        if alone:
            return x, caches._replace(state=state), None
        x, routed = block.feed_forward(lp, x, mcfg, ctx["act"],
                                       l if routed_layer else None)
        return x, caches._replace(state=state), \
            stats(routed[1], ctx["act"]) if routed_layer else None

    # `state_writes`: the admissions that overwrote a slot's state. (What
    # moves a slot's share of `state_bytes` is counted by the scheduler: a
    # decode chunk its active slots' once a step, `decode_dispatch`'s
    # `active`; a riding step its riders' once, the admit span's `riders`,
    # `rider_tokens`. No counter of the model's own.)
    return _Kind(prefill, decode, keeps=("state", "state"), over="index",
                 carries=("state",), counts=_Counts(keeps={"state_writes": 0}))


def _retention_kind(mcfg) -> _Kind:
    """A power-retention layer (`block.retention_mixer`; `mcfg.mixer`
    "retention") over the dense feed-forward: no K and V and no page, for
    each slot a state of fixed size (`ops/slot_state.py::empty_retention`),
    whose layer is the layer's place in the stack. A prompt's rows go through
    the attention form and leave the state after row `length - 1` (rows past
    it write nothing: the operator is told `length`); a decode step is handed
    the slots' WHOLE state and visits the layer's tiles of the active slots
    where they lie, once. It takes no riders and routes nothing."""
    S = mcfg.max_seq

    def prefill(lp, x, caches, l, ctx):
        x, state, norm = block.retention_mixer(
            lp, x, mcfg, lambda t: norms.apply_rope(t, *ctx["tables"]),
            length=ctx["length"])
        x, _ = block.feed_forward(lp, x, mcfg)
        return x, caches, (state, norm), None

    def decode(lp, x, caches, l, ctx):
        with jax.named_scope("rope"):
            w = jnp.minimum(ctx["pos"], S - 1)
            c, s = (t[w][:, None] for t in ctx["tables"])
        x, state = block.retention_mixer(
            lp, x, mcfg, lambda t: _rope_one(t, c, s), caches.state,
            step=True, layer=l, active=ctx["act"])
        x, _ = block.feed_forward(lp, x, mcfg, ctx["act"])
        return x, caches._replace(state=state), None

    # A slot's state, all layers: what one of its decode steps reads, and
    # writes back.
    slot_bytes = slot_state.state_bytes(jax.eval_shape(
        lambda: slot_state.empty_retention(mcfg.n_layers, 1, mcfg.n_kv_heads,
                                           mcfg.head_dim)))

    # (three quarters of a step's traffic at Brumby's widths)
    return _Kind(prefill, decode, keeps=("retention", "retention"),
                 carries=("state",), counts=_state_moves(S, slot_bytes))


def _experts_kind(mcfg) -> _Kind:
    """A layer of a stack of one-part layers that is the feed-forward ALONE
    (`block.feed_forward`: its norm, the router, the experts held, the shared
    expert): no mixer before it, nothing kept of a prompt and no cache read or
    written in a step. It hands back its routing counts."""
    stats = _routing_stats(mcfg)

    def prefill(lp, x, caches, l, ctx):
        x, routed = block.feed_forward(lp, x, mcfg, ctx["live"], l)
        return x, caches, (None, None), stats(routed[1], ctx["live"])

    def decode(lp, x, caches, l, ctx):
        x, routed = block.feed_forward(lp, x, mcfg, ctx["act"], l)
        return x, caches, stats(routed[1], ctx["act"])

    return _Kind(prefill, decode, keeps=(), over="index", carries=())


def _conv_kind(mcfg, first: int) -> _Kind:
    """A gated short-convolution layer (`block.conv_mixer`; the LFM2 family)
    over a dense or a sparse feed-forward: no K and V, for each slot the
    convolution's window and nothing else (`ops/slot_state.py`: a state with
    no recurrent part), whose layer is the layer's ordinal among ALL the conv
    layers, the leading dense ones first. Rows past `length` reach no real
    row: the convolution is causal, and the operator is told `length`. A
    sparse layer hands back its routing counts. Its prefill takes RIDERS
    (`conv_mixer(riders=)`): the tail rows' convolution against each riding
    slot's own window, written back as a decode step writes it (under the
    operator's `conv` scope), between projections and a feed-forward that
    run once over the bucket; the step is the one `decode` runs, written once
    in the operator. `first`: the ordinal among all the conv layers of this
    kind's layer 0 (what the decode walk's `ctx["base"]` comes to)."""
    def prefill(lp, x, caches, l, ctx):
        routed_layer = "router" in lp
        rides = _mixer_riders(ctx, caches, first + l)
        y, window, *rode = block.conv_mixer(lp, x[0], mcfg,
                                            length=ctx["length"], **rides)
        if rode:
            caches = caches._replace(state=rode[0])
        y, routed = block.feed_forward(lp, y[None], mcfg, ctx["live"],
                                       l if routed_layer else None)
        return y, caches, (None, window), \
            expert_stats(routed[1]) if routed_layer else None

    def decode(lp, x, caches, l, ctx):
        routed_layer = "router" in lp
        state, at = caches.state, first + l
        # The window's read and its write back are the convolution's
        # traffic: under the scope that times it.
        with jax.named_scope("conv"):
            _, window = slot_state.layer_state(state, at)
        x, window = block.conv_mixer(lp, x, mcfg, window, step=True)
        with jax.named_scope("conv"):
            state = slot_state.update_layer(state, at, ctx["act"], None,
                                            window)
        x, routed = block.feed_forward(lp, x, mcfg, ctx["act"],
                                       l if routed_layer else None)
        return x, caches._replace(state=state), \
            expert_stats(routed[1]) if routed_layer else None

    return _Kind(prefill, decode, keeps=("state", "state"), over="index",
                 carries=("state",))


def _latent_kind(mcfg) -> _Kind:
    """A latent-attention (MLA) layer: a prompt through
    `latent_flash_attention`; a decode step's absorbed query against the
    slot's cached rows (`paged_latent_decode`), the step's own row written
    first. What the cache keeps of a token is ONE row, the normed latent then
    the rotated shared key; its layer is the layer's place in the whole
    stack (`ctx["base"] + l`). Its prefill takes RIDERS: the tail rows'
    absorbed inputs, their rows written at the slots' positions and the
    kernel against the arena, which is the step `decode` runs (`_token_step`:
    ONE jit for both), before a `wo` and a feed-forward that run once over
    the bucket."""
    dt, S = mcfg.dtype, mcfg.max_seq
    latent_decode = paged_kv.paged_latent_decode

    @jax.jit
    def _token_step(kc, l, bt, w, act, ql, q_r, c, kr):
        """One token a slot against the latent arena: a step's row (`c`
        `[n_slots, rank]`, `kr` `[n_slots, dr]`) written at the slots'
        positions `w` into layer `l`, then each active slot's absorbed query
        (`ql` `[n_slots, heads, rank]`, `q_r` `[n_slots, heads, dr]`) against
        its rows 0..w (an idle slot reads nothing) -> (arena, ol `[n_slots,
        heads, rank]`). A jit of its own, and ONE for the riders and for the
        decode program's layers, as `_attention_kind`'s is (which see): the
        kernel is traced once a process, not once a riding rung."""
        kc = paged_kv.write_token_rows(kc, l, bt, w, act,
                                       paged_kv.latent_rows(c, kr, kc))
        with jax.named_scope("attn"):
            ol = latent_decode(ql, q_r, kc, l, bt, jnp.where(act, w + 1, 0),
                               sm_scale=mcfg.softmax_scale)
        return kc, ol

    def prefill(lp, x, caches, l, ctx):
        routed_layer = "router" in lp
        B, Sq, _ = x.shape
        q_n, q_r, k_n, v, c, kr = block.latent_attention_inputs(
            lp, x, mcfg, lambda t: norms.apply_rope(t, *ctx["tables"]))
        with jax.named_scope("attn"):
            attn = attention.latent_flash_attention(q_n, q_r, k_n, kr, v,
                                                    mcfg.softmax_scale)
        if ctx["riders"]:
            # ONE decode step of the riding slots in the bucket's tail rows,
            # as `decode` takes it: the absorbed form's inputs of those rows
            # ALONE, from `x`, and the result put into the kernel's own
            # output `[1, heads, rows, dv]` before its transpose, by an update
            # of n_slots rows a head. (A second reader of the bucket's `q_n`
            # and `q_r`, and an update after the transpose, make the compiler
            # lay them and the output out anew: 5.2 ms of a 3,584-row prefill
            # together, my chip runs, PR 61; the 105 MB of projections read
            # again a layer cost less.)
            bt, w, act = ctx["riders"]
            ns = act.shape[0]
            tail = slice(Sq - ns, Sq)
            cos, sin = (t[tail][:, None] for t in ctx["tables"])
            kc, ol = _token_step(
                caches.kc, ctx["base"] + l, bt, w, act,
                *block.latent_attention_inputs(
                    lp, x[0, tail], mcfg, lambda t: _rope_one(t, cos, sin),
                    absorb=True))
            caches = caches._replace(kc=kc)
            rode = block.latent_attention_output(lp, ol, mcfg).reshape(
                ns, attn.shape[1], -1).transpose(1, 0, 2)
            attn = attn.at[0, :, tail].set(
                jnp.where(act[None, :, None], rode, attn[0, :, tail]))
        with jax.named_scope("attn"):
            attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, -1)
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        # A share's sparse half meets a quarter of `rows x experts a token`
        # rows at once (`ops.moe._share_experts`): no blocks of `_MOE_ROWS`.
        x, routed = block.feed_forward(lp, x, mcfg, ctx["live"],
                                       l if routed_layer else None)
        return x, caches, (c[0], kr[0]), \
            _share_stats(routed[1], ctx["live"], mcfg) if routed_layer \
            else None                             # [S, rank], [S, dr]

    def begin(ctx):
        if "w" not in ctx:
            ctx["w"] = w = jnp.minimum(ctx["pos"], S - 1)
            with jax.named_scope("rope"):
                ctx["c"], ctx["s"] = (t[w][:, None] for t in ctx["tables"])

    def decode(lp, x, caches, l, ctx):
        routed_layer = "router" in lp
        act = ctx["act"]
        # The write and the kernel as the riders' step has them, traced once
        # for both.
        kc, ol = _token_step(
            caches.kc, ctx["base"] + l, ctx["bt"], ctx["w"], act,
            *block.latent_attention_inputs(
                lp, x, mcfg, lambda t: _rope_one(t, ctx["c"], ctx["s"]),
                absorb=True))
        with jax.named_scope("attn_out"):
            x = x + block.latent_attention_output(lp, ol, mcfg) \
                @ lp["wo"].astype(dt)
        x, routed = block.feed_forward(lp, x, mcfg, act,
                                       l if routed_layer else None)
        return x, caches._replace(kc=kc), \
            _share_stats(routed[1], act, mcfg) if routed_layer else None

    return _Kind(prefill, decode, keeps=("pages", "pages"), begin=begin,
                 pack=lambda c, kr: (jnp.concatenate([c, kr], axis=-1), None))


def _mixed_steps(mcfg) -> Tuple[Callable, Callable]:
    """One token a slot against each of a mixed stack's two caches -> (ring,
    pages): the step's k and v `[n_slots, kv_heads, d]` written at the slots'
    positions `w`, then each active slot's q `[n_slots, heads, head_dim]`
    against what the cache holds of it (an idle slot reads nothing) -> the
    cache moved and the kernel's own result, float32 or padded as it comes.
    `ring(state, l, w, act, q, k, v, sink)`: a window layer's, the row into
    the slot's ring and the ring alone read. `pages(kc, vc, base, l, bt, w,
    act, lengths, q, k, v)`: a full layer's (the arena's layer `base + l`),
    the row into the slot's page and its live pages read in place. A jit
    each, and ONE for the riders, for the decode program's layers and for
    both stacks of full layers, as `_attention_kind`'s `_token_step` is
    (which see): no prefill width enters their shapes, so each is traced once
    a process, not once a riding rung and again for decode."""
    scale = mcfg.softmax_scale
    window = mcfg.attention_kind("window").window
    # What a program is built with is the function as it stands on its
    # module now (a test puts an interpreted kernel there first).
    paged_decode = paged_kv.paged_decode_attention

    @jax.jit
    def ring(state, l, w, act, q, k, v, sink):
        state = slot_state.write_window_token(state, l, w, act, k, v)
        with jax.named_scope("attn"):
            with jax.named_scope("window_attn"):
                attn = slot_state.window_decode_attention(
                    q, state, l, w, act, window=window, sm_scale=scale,
                    sink=sink)
        return state, attn

    @jax.jit
    def pages(kc, vc, base, l, bt, w, act, lengths, q, k, v):
        kc, vc = paged_kv.write_token(kc, vc, base + l, bt, w, act, k, v)
        with jax.named_scope("attn"):
            with jax.named_scope("full_attn"):
                # q in the lanes a cached key lies in: zeros meet the
                # arena's padding (none where a key is whole tiles)
                lanes = kc.shape[-1] - q.shape[-1]
                attn = paged_decode(
                    jnp.pad(q, ((0, 0), (0, 0), (0, lanes))) if lanes else q,
                    kc, vc, base + l, bt, lengths, sm_scale=scale)
        return kc, vc, attn

    return ring, pages


def _mixed_kind(mcfg, kind: str, steps: Tuple[Callable, Callable]) -> _Kind:
    """A layer of a stack of window and full attention layers
    (`mcfg.attn_pattern`), of the stack `kind`. A full layer (`dense`,
    `layers`) writes a step's row to the slot's page and reads its live pages
    in place (the arena's layer its ordinal among the full layers); a window
    layer writes it to the slot's ring and reads the ring alone
    (`ops/slot_state.py`). Each kind of attention has its own query heads
    and turns its own part of a head at its own frequencies
    (`LlamaConfig.attention_kind`, `rope_tables`); with `mcfg.attn_gate` a
    head's output is gated before `wo` (`block.gated`). Its prefill takes
    RIDERS: the tail rows' q, k and v from those rows alone, turned at the
    slots' positions, through the step `decode` runs (`steps`, the stack's
    `_mixed_steps`), before a gate, a `wo` and a feed-forward that run once
    over the bucket."""
    dt, S = mcfg.dtype, mcfg.max_seq
    dv, scale = mcfg.v_head_dim, mcfg.softmax_scale
    _, _, window, sink, _, rotary = mcfg.attention_kind(kind)
    ring, pages = steps
    # (a part of a head narrower than a tile is turned by a small matmul)
    prompt_rope = norms.apply_rope_narrow if rotary < 128 else norms.apply_rope

    def joined(n, r):       # a head's two parts as the caches hold them
        return r if n is None else jnp.concatenate([n, r], -1)

    def token_step(lp, caches, base, l, bt, w, act, lengths, q_n, q_r, k_n,
                   k_r, v):
        """One token a slot through this layer's attention against its
        cache, from a step's `block.mixed_attention_inputs` -> (caches, attn
        `[n_slots, heads, dv]`)."""
        q, k = joined(q_n, q_r), joined(k_n, k_r)
        if window:
            state, attn = ring(caches.state, l, w, act, q, k, v,
                               lp["sink"] if sink else None)
            caches = caches._replace(state=state)
        else:
            kc, vc, attn = pages(caches.kc, caches.vc, base, l, bt, w, act,
                                 lengths, q, k, v)
            caches = caches._replace(kc=kc, vc=vc)
        return caches, attn[..., :dv].astype(dt)

    def prefill(lp, x, caches, l, ctx):
        routed_layer = "router" in lp
        B, Sq, _ = x.shape
        q_n, q_r, k_n, k_r, v, gate = block.mixed_attention_inputs(
            lp, x, mcfg, kind,
            lambda t: prompt_rope(t, *ctx["tables"][kind]))
        with jax.named_scope("attn"):
            # The kernel and nothing else: what a roofline counts is read
            # inside the scope that times it.
            with jax.named_scope("window_attn" if window else "full_attn"):
                attn = attention.mixed_flash_attention(
                    q_n, q_r, k_n, k_r, v, scale, window=window,
                    sink=lp["sink"] if sink else None)
        if ctx["riders"]:
            # ONE decode step of the riding slots in the bucket's tail rows,
            # as `decode` takes it: those rows' inputs from `x` ALONE, turned
            # at the slots' positions (the walk's tables hold a row's own),
            # and the result put into the kernel's own output `[1, heads,
            # rows, dv]` before its transpose and the gate, by an update of
            # n_slots rows a head (`_latent_kind`, which see). The gate's
            # rows there are the bucket's: the same rows of `x`.
            bt, w, act = ctx["riders"]
            tail = slice(Sq - act.shape[0], Sq)
            cos, sin = (t[tail][:, None] for t in ctx["tables"][kind])
            caches, rode = token_step(
                lp, caches, ctx["base"], l, bt, w, act,
                jnp.where(act, w + 1, 0), *block.mixed_attention_inputs(
                    lp, x[0, tail], mcfg, kind,
                    lambda t: _rope_one(t, cos, sin))[:5])
            attn = attn.at[0, :, tail].set(jnp.where(
                act[None, :, None], rode.transpose(1, 0, 2),
                attn[0, :, tail]))
        with jax.named_scope("attn"):
            attn = block.gated(attn.transpose(0, 2, 1, 3), gate).reshape(
                B, Sq, -1)
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt))
        x, routed = block.feed_forward(lp, x, mcfg, ctx["live"],
                                       l if routed_layer else None)
        kept = (joined(k_n, k_r)[0].transpose(1, 0, 2),
                v[0].transpose(1, 0, 2))          # [S, KVH, dk], [S, KVH, dv]
        return x, caches, kept, \
            _share_stats(routed[1], ctx["live"], mcfg) if routed_layer \
            else None

    def begin(ctx):
        if "w" not in ctx:
            ctx["w"] = jnp.minimum(ctx["pos"], S - 1)
            ctx["lengths"] = jnp.where(ctx["act"], ctx["w"] + 1, 0)
        with jax.named_scope("rope"):
            ctx["c"], ctx["s"] = (t[ctx["w"]][:, None]
                                  for t in ctx["tables"][kind])

    def decode(lp, x, caches, l, ctx):
        routed_layer = "router" in lp
        ns = x.shape[0]
        act = ctx["act"]
        # The write and the kernel as the riders' step has them, traced once
        # for both.
        *qkv, gate = block.mixed_attention_inputs(
            lp, x, mcfg, kind, lambda t: _rope_one(t, ctx["c"], ctx["s"]))
        caches, attn = token_step(lp, caches, ctx["base"], l, ctx["bt"],
                                  ctx["w"], act, ctx["lengths"], *qkv)
        with jax.named_scope("attn_out"):
            x = x + block.gated(attn, gate).reshape(ns, -1) \
                @ lp["wo"].astype(dt)
        x, routed = block.feed_forward(lp, x, mcfg, act,
                                       l if routed_layer else None)
        return x, caches, \
            _share_stats(routed[1], act, mcfg) if routed_layer else None

    # The ring rows a window layer's decode steps read (a slot's own and the
    # window - 1 before it, p + 1 while it has fewer), of `live_kv_tokens`.
    return _Kind(prefill, decode,
                 keeps=("ring", "ring") if window else ("pages", "pages"),
                 begin=begin, counts=_reads(
                     S, window, window_kv_tokens="window_kv_tokens")
                 if window else None)


def _linear_kind(mcfg) -> _Kind:
    """A decayed linear-attention layer (`block.linear_mixer`;
    `mcfg.mixer_types` "lightning-attn") over the dense feed-forward: no K
    and V and no page, for each slot a state of fixed size
    (`ops/slot_state.py::empty_linear`), whose layer is the layer's ordinal
    among the linear layers, which also says its decay (`mcfg.linear_rates`).
    A prompt's rows go through the chunked form and leave the state after row
    `length - 1` (rows past it write nothing: the operator is told
    `length`); a decode step is handed the slots' WHOLE state and visits the
    layer's tiles of the active slots where they lie, once. It takes no
    riders and routes nothing."""
    S = mcfg.max_seq
    table = mcfg.linear_rates()

    def rates(l):       # the layer's, by its ordinal (a traced scalar)
        return jnp.asarray(table)[l]

    def prefill(lp, x, caches, l, ctx):
        x, state = block.linear_mixer(
            lp, x, mcfg, lambda t: norms.apply_rope(t, *ctx["tables"]),
            rates(l), length=ctx["length"])
        x, _ = block.feed_forward(lp, x, mcfg)
        return x, caches, (state, None), None

    def begin(ctx):
        if "c" not in ctx:
            with jax.named_scope("rope"):
                w = jnp.minimum(ctx["pos"], S - 1)
                ctx["c"], ctx["s"] = (t[w][:, None] for t in ctx["tables"])

    def decode(lp, x, caches, l, ctx):
        x, state = block.linear_mixer(
            lp, x, mcfg, lambda t: _rope_one(t, ctx["c"], ctx["s"]),
            rates(l), caches.state, step=True, layer=l,
            active=ctx["act"])
        x, _ = block.feed_forward(lp, x, mcfg, ctx["act"])
        return x, caches._replace(state=state), None

    # A slot's state, all linear layers: what one of its decode steps reads,
    # and writes back.
    slot_bytes = slot_state.state_bytes(jax.eval_shape(
        lambda: slot_state.empty_linear(mcfg.state_layers, 1,
                                        mcfg.lightning_heads,
                                        mcfg.lightning_head_dim)))

    return _Kind(prefill, decode, keeps=("linear", "linear"), over="index",
                 carries=("state",), begin=begin,
                 counts=_state_moves(S, slot_bytes))


def _block_sparse_kind(mcfg) -> _Kind:
    """A layer that selects BLOCKS (`mcfg.mixer_types` "minicpm4";
    `ops/sparse_attention.py::BlockSparse`) over the dense feed-forward:
    grouped-query attention with no rotation, K and V under the block table
    with ONE kv head a layer of the arena (kv head g of the layer's ordinal l
    its layer `l * kv_heads + g`: a selection is a kv head's own, and each
    reads by a table of its own) and a page a block, and the slot's pooled
    keys beside them (`ops/slot_state.py::empty_pooled`). A prompt: plain
    flash attention where the bucket is under `dense_len`, else the pooled
    keys, the selection and `block_flash` under the mask by blocks
    (`block_sparse_attention`; a row under `dense_len` reads every block up
    to its own there). A decode step writes K and V to the page, its key into
    the slot's pooled keys, and reads every live page or the selected ones
    alone, by `pos`, on the device (`block_sparse_decode`). The output is
    gated before `wo` (`block.output_gated`). It takes no riders and routes
    nothing."""
    H, KVH, hd, S = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim, mcfg.max_seq
    dt = mcfg.dtype
    sizes = mcfg.block_sparse
    scale = mcfg.softmax_scale
    # What a program is built with is the function as it stands on its
    # module now (a test puts an interpreted kernel there first).
    paged_decode = paged_kv.paged_decode_attention
    block_sparse = sparse_attention.block_sparse_attention

    def prefill(lp, x, caches, l, ctx):
        nb, Sq, _ = x.shape
        q, k, v, gate = block.block_sparse_attention_inputs(lp, x, mcfg)
        with jax.named_scope("attn"):
            with jax.named_scope("compress"):
                pooled, sums = sparse_attention.compress(k[0], sizes,
                                                         ctx["length"])
            if Sq < sizes.dense_len:    # no row's context reaches it
                attn = attention.flash_attention(
                    q, attention.repeat_kv(k, H // KVH),
                    attention.repeat_kv(v, H // KVH), True, scale)[0]
            else:
                attn = block_sparse(q[0], k[0], v[0], pooled, sizes,
                                    sm_scale=scale)
            attn = block.output_gated(
                attn.transpose(1, 0, 2).reshape(nb, Sq, H * hd), gate)
        with jax.named_scope("attn_out"):
            x = x + block.scaled(
                jnp.einsum("bsh,hd->bsd", attn, lp["wo"].astype(dt)), mcfg)
        x, _ = block.feed_forward(lp, x, mcfg)
        kept = (k[0][:, :, None], v[0][:, :, None],     # [KVH, S, 1, hd]
                pooled.transpose(1, 0, 2).reshape(pooled.shape[1], KVH * hd),
                sums.transpose(1, 0, 2).reshape(2, KVH * hd))
        return x, caches, kept, None

    def begin(ctx):
        if "w" not in ctx:
            ctx["w"] = jnp.minimum(ctx["pos"], S - 1)

    def decode(lp, x, caches, l, ctx):
        ns = x.shape[0]
        bt, w, act = ctx["bt"], ctx["w"], ctx["act"]
        kc, vc, ic, _ = caches
        q, k, v, gate = block.block_sparse_attention_inputs(lp, x, mcfg)
        for g in range(KVH):
            kc, vc = paged_kv.write_token(kc, vc, l * KVH + g, bt, w, act,
                                          k[:, g:g + 1], v[:, g:g + 1])
        with jax.named_scope("attn"):
            pooled, ic = slot_state.pooled_step_layer(ic, l, w, act, k, sizes)
            attn = sparse_attention.block_sparse_decode(
                q, pooled, kc, vc, l, bt, w, act, sizes, paged_decode,
                sm_scale=scale)
            attn = block.output_gated(attn.reshape(ns, H * hd), gate)
        with jax.named_scope("attn_out"):
            x = x + block.scaled(attn @ lp["wo"].astype(dt), mcfg)
        x, _ = block.feed_forward(lp, x, mcfg, act)
        return x, caches._replace(kc=kc, vc=vc, ic=ic), None

    def dispatch(pos, active, chunk, plan):
        # a slot at p reads min(blocks up to its own, topk) once its context
        # reaches dense_len, every one of them under it
        at = (pos[active][:, None] + np.arange(chunk)).clip(max=S - 1)
        visible = at // sizes.block + 1
        dense = at + 1 < sizes.dense_len
        return {"blocks_selected": int(np.where(
                    dense, visible, np.minimum(visible, sizes.topk)).sum())
                * KVH,
                "blocks_visible": int(visible.sum()) * KVH,
                "dense_rows": int(dense.sum())}

    def pack(ks, vs, pooled, sums):
        # the arena's layers are (layer, kv head): [L, KVH, S, 1, hd] ->
        return (ks.reshape(-1, *ks.shape[2:]), vs.reshape(-1, *vs.shape[2:]),
                pooled, sums)

    # `blocks_selected` / `blocks_visible`: the pages a kv head's decode steps
    # read over those its slot held (their ratio: the share of the context
    # read); `dense_rows`: the steps served under `dense_len`.
    return _Kind(prefill, decode,
                 keeps=("pages", "pages", "pooled", "pooled"), over="index",
                 carries=("kc", "vc", "ic"), begin=begin, pack=pack,
                 counts=_Counts({"blocks_selected": "decode_blocks_selected",
                                 "blocks_visible": "decode_blocks_visible",
                                 "dense_rows": "decode_dense_rows"},
                                dispatch))


def _stack(mcfg) -> _Stack:
    """The table of a model's kinds of layer, keyed as `mcfg.segments()`
    names them, and what the walks ask of the stack as a whole. The ONE place
    that reads which architecture a configuration is."""
    hd, KVH, dt = mcfg.head_dim, mcfg.n_kv_heads, mcfg.dtype
    unpaged = mcfg.n_layers - mcfg.kv_layers    # layers that keep no pages

    def uniform_tables(n, rows):
        if not mcfg.rope:
            return dict(tables=None, itables=())
        cos, sin = norms.rope_frequencies(hd, n, mcfg.rope_theta)
        if rows and mcfg.mrope_section:  # text: the three streams are equal
            cos, sin = norms.mrope_tables(
                cos, sin, jnp.broadcast_to(jnp.arange(n), (3, n)),
                mcfg.mrope_section)
        return dict(tables=(cos, sin), itables=norms.rope_frequencies(
            mcfg.index_head_dim, n, mcfg.rope_theta) if mcfg.index_topk
            else ())

    if mcfg.ssm_state:
        kinds = {"attn": _attention_kind(mcfg, True, stack="layers",
                                         over="inline"),
                 "mamba": _mamba_kind(mcfg)}
        if mcfg.layer_parts is not None:    # each of the three a part alone
            kinds["experts"] = _experts_kind(mcfg)
        return _Stack(
            kinds, uniform_tables,
            lambda ns, page, n_pages: Caches(
                *paged_kv.empty(mcfg.kv_layers, n_pages, KVH, page, hd, dt),
                state=slot_state.empty_state(
                    mcfg.state_layers, ns, mcfg.ssm_state, mcfg.ssm_inner,
                    mcfg.ssm_conv, dt, mcfg.ssm_conv_channels)),
            lambda c: {"state_bytes": slot_state.state_bytes(c.state)},
            takes_riders=True, shares=bool(mcfg.experts_held), tally="zero")
    if mcfg.retention:
        # Nothing is paged: there is no arena (an arena of no layer would be
        # arrays of no byte for every program to carry), the block table is
        # read by no program, and a slot costs the same at every position.
        return _Stack(
            {"layers": _retention_kind(mcfg)}, uniform_tables,
            lambda ns, page, n_pages: Caches(
                state=slot_state.empty_retention(mcfg.n_layers, ns, KVH, hd)),
            lambda c: {"state_bytes": slot_state.state_bytes(c.state)})
    if mcfg.sala:
        lh, ld = mcfg.lightning_heads, mcfg.lightning_head_dim
        sizes = mcfg.block_sparse

        def empty(ns, page, n_pages):
            if mcfg.kv_layers and page != sizes.block:
                raise ValueError(
                    f"page_size {page}: a block-sparse layer's selected "
                    f"block IS a page of the slot's table (sparse_block "
                    f"{sizes.block})")
            return Caches(
                *paged_kv.empty(mcfg.kv_layers * KVH, n_pages, 1, page, hd,
                                dt),
                ic=slot_state.empty_pooled(
                    mcfg.kv_layers, ns, mcfg.max_seq // sizes.stride,
                    KVH * hd, dt) if mcfg.kv_layers else None,
                state=slot_state.empty_linear(mcfg.state_layers, ns, lh, ld)
                if mcfg.state_layers else None)

        return _Stack(
            # (a kind the list does not have has no stack, and no kind)
            {name: kind(mcfg) for name, kind, n in (
                ("sparse", _block_sparse_kind, mcfg.kv_layers),
                ("linear", _linear_kind, mcfg.state_layers)) if n},
            # the linear layers' tables (the sparse layers turn nothing)
            lambda n, rows: dict(tables=norms.rope_frequencies(
                ld, n, mcfg.rope_theta)),
            # pages for the sparse layers, a kv head a layer of the arena;
            # their pooled keys and the linear layers' state a slot
            empty,
            lambda c: {"linear_state_bytes": slot_state.state_bytes(
                           c.state or ()),
                       "pooled_key_bytes": slot_state.state_bytes(
                           c.ic or ())})
    if mcfg.conv:
        return _Stack(
            # (the leading dense layers are conv layers: the windows' first)
            {"dense": _conv_kind(mcfg, 0),
             "conv": _conv_kind(mcfg, mcfg.first_dense),
             "layers": _attention_kind(mcfg, True, over="index",
                                       carries=("kc", "vc"))},
            uniform_tables,
            # pages for the attention layers, a window a slot for the rest
            lambda ns, page, n_pages: Caches(
                *paged_kv.empty(mcfg.kv_layers, n_pages, KVH, page, hd, dt),
                state=slot_state.empty_state(unpaged, ns, 0, mcfg.d_model,
                                             mcfg.conv_taps, dt)),
            lambda c: {"conv_state_bytes": slot_state.state_bytes(c.state)},
            takes_riders=True, tally="zero")
    if mcfg.latent:
        kind = _latent_kind(mcfg)
        return _Stack(
            {"dense": kind, "layers": kind},
            lambda n, rows: dict(tables=latent_rope_tables(mcfg, n)),
            lambda ns, page, n_pages: Caches(kc=paged_kv.empty_latent(
                mcfg.n_layers, n_pages, page, mcfg.latent_width, dt)),
            lambda c: {"latent_cache_bytes": slot_state.state_bytes([c.kc])},
            takes_riders=True, shares=True, tally="first")
    if mcfg.mixed:
        steps = _mixed_steps(mcfg)
        return _Stack(
            {kind: _mixed_kind(mcfg, kind, steps)
             for kind in ("dense", "window", "layers")},
            lambda n, rows: dict(tables={
                kind: mcfg.rope_tables(kind, n)
                for kind, _, _ in mcfg.segments()}),
            # pages for the full layers, a ring a slot for the rest
            lambda ns, page, n_pages: Caches(
                *paged_kv.empty(mcfg.kv_layers, n_pages, KVH, page, hd, dt,
                                v_head_dim=mcfg.v_head_dim),
                state=slot_state.empty_window(
                    unpaged, ns, mcfg.window_kv_heads, mcfg.window, hd,
                    mcfg.v_head_dim, dt)),
            lambda c: {"full_cache_bytes": slot_state.state_bytes(c[:2]),
                       "window_cache_bytes": slot_state.state_bytes(c.state)},
            takes_riders=True, shares=True, tally="zero")
    indexed = mcfg.index_topk > 0
    # Riders are a decode step of one row a slot, a hand-off a first token:
    # a stack that generates by blocks has neither.
    plain = not indexed and mcfg.block_length == 1
    return _Stack(
        {"layers": _attention_kind(
            mcfg, plain, over="scan" if mcfg.n_experts else "slices",
            rides=("tables", "live", "itables"))},
        uniform_tables,
        lambda ns, page, n_pages: Caches(
            *paged_kv.empty(mcfg.kv_layers, n_pages, KVH, page, hd, dt,
                            by_token=indexed),
            ic=paged_kv.empty_index(mcfg.n_layers, n_pages, page,
                                    mcfg.index_head_dim, dt)
            if indexed else None),
        lambda c: {}, takes_riders=plain, adopts=plain)


def rung_refusal(mcfg, width: int) -> Optional[str]:
    """Why a prefill of `width` rows would run its attention in XLA on a TPU,
    not in a Pallas kernel; None: it would not. A mixed stack's kernels take
    some shapes and `ops.attention.mixed_flash_attention` falls to its
    reference for the rest, which from outside shows in the path counts
    alone: a rung of a block or more (128 rows: a prompt under one is a few
    score matrices in XLA, by design) that no kernel takes is a
    configuration to refuse when its server is built (`Engine`). A stack of
    linear and block-sparse layers likewise: its three kernels take whole
    tiles, and fall to `jnp` for the rest."""
    if width < 128:
        return None
    if mcfg.sala:
        # a prompt is one program: its linear layers in whole chunks of whole
        # tiles, its sparse layers under `dense_len` in the flash kernel and
        # from there on in `block_flash`
        chunk = min(linear_attention.CHUNK, width)
        sizes = mcfg.block_sparse
        if mcfg.state_layers and (width % chunk or not
                                  linear_attention.kernel_tiles(
                                      mcfg.lightning_head_dim, chunk)):
            return (f"stack `linear`: {width} rows of heads of "
                    f"{mcfg.lightning_head_dim} are no whole chunks of whole "
                    "tiles")
        if mcfg.kv_layers and (
                width % 128 or mcfg.head_dim % 128
                or (width >= sizes.dense_len and not
                    sparse_attention.block_flash_tiles(
                        width, mcfg.head_dim, sizes.block))):
            return (f"stack `sparse`: {width} rows of heads of "
                    f"{mcfg.head_dim} under blocks of {sizes.block} are no "
                    "whole tiles")
        return None
    if not mcfg.mixed:
        return None
    for name in ("layers", "window"):       # the full kind, the window kind
        kind = mcfg.attention_kind(name)
        why = attention.mixed_kernel_refusal(
            width, mcfg.head_dim - kind.rotary_dim, kind.rotary_dim,
            mcfg.v_head_dim, kind.window, kind.sink)
        if why:
            return f"stack `{name}`: {why}"
    return None


def check_rungs(mcfg, widths) -> None:
    """On a TPU, refuse a model whose prefills of these widths would fall
    to XLA's attention (`rung_refusal`), naming each shape."""
    refused = [why for why in (rung_refusal(mcfg, w) for w in widths) if why] \
        if attention._on_tpu() else []
    if refused:
        raise ValueError("this model's prompts would run their attention in "
                         "XLA, not in a kernel: " + "; ".join(refused))


def adopts(mcfg) -> bool:
    """Whether a PD hand-off can carry what this model caches: K and V of
    one shape a layer under the block table, and nothing else (no indexer's
    keys, recurrent state, latent rows or window rings). A function of the
    configuration alone: a `PrefillServer` builds no engine."""
    return _stack(mcfg).adopts


def _keep_pages(c, pages, slot, length, ks, vs=None):
    kc, vc = paged_kv.write_prompt(c.kc, c.vc, pages, ks, vs)
    return c._replace(kc=kc, vc=vc)


# A prompt's write to each cache, (caches, pages, slot, length, *rows) ->
# caches: every model pages something; what else it keeps follows. A kind
# names the cache of each array it keeps (`_Kind.keeps`).
_KEEP = {
    "pages": _keep_pages,
    "index": lambda c, pages, slot, length, ik: c._replace(
        ic=paged_kv.write_prompt_rows(c.ic, pages, ik)),
    "state": lambda c, pages, slot, length, ssm, conv: c._replace(
        state=slot_state.write_state(c.state, slot, ssm, conv)),
    "ring": lambda c, pages, slot, length, ks, vs, **how: c._replace(
        state=slot_state.write_window_prompt(c.state, slot, length, ks, vs,
                                             **how)),
    "retention": lambda c, pages, slot, length, S, z: c._replace(
        state=slot_state.write_retention(c.state, slot, S, z)),
    "pooled": lambda c, pages, slot, length, pooled, sums: c._replace(
        ic=slot_state.write_pooled(c.ic, slot, pooled, sums)),
    "linear": lambda c, pages, slot, length, S, _: c._replace(
        state=slot_state.write_linear(c.state, slot, S)),
}


def _segments(mcfg, stack: _Stack):
    """`mcfg.segments()` as the walks run them -> (name, kind, lo, hi, base)
    a segment: `base + l` is the layer of its cache (the kind's first
    `keeps`) that the segment's ordinal `l` writes, a prompt's rows and a
    step's alike; None for a kind that keeps nothing."""
    at = dict.fromkeys(_KEEP, 0)    # layers so far, a cache
    for name, lo, hi in mcfg.segments():
        kind, base = stack.kinds[name], None
        if kind.keeps:
            base = at[kind.keeps[0]] - lo
            at[kind.keeps[0]] += hi - lo
        yield name, kind, lo, hi, base


def _over(kind: _Kind, layers, mcfg, body, ordinals=True):
    """How a kind's segments run (`_Kind.over`) -> run(lo, hi, carry) ->
    (carry, ys) over the layers `lo..hi-1` of the stack `layers`, where
    `body(lp, l, carry) -> (carry, ys)` is one layer. ONE scan body a kind a
    walk, so a kind's second segment is traced from its first's.

    In a decode step the caches ride the scan's CARRY, and only `ops/`'s
    writes and the attention kernels' reads touch them, so the layer loop,
    the chunk loop around it and the donated entry buffers all alias ONE
    buffer: a step rewrites `ns` pages a layer and moves nothing else (a
    kernel is handed the arena and `l`, never `kc[l]`: a custom call given a
    slice is first given a copy of it). They must stay out of the scan's
    xs/ys: an xs is read-only and a ys is a freshly stacked result, so the
    compiler would slice every layer's slab out, write it into a second arena
    and copy that back as the next step's carry (2.9 GB a step at 12 layers x
    929 pages; PERF.md, PR 25). The xs are the layer's weights and its
    index."""
    sliced, whole = block.expert_stacks(layers, mcfg)
    if kind.over == "inline":
        return lambda lo, hi, carry: body(
            dict(_layer_of(sliced, lo), **whole), lo, carry)
    by_index = kind.over == "index"
    stacked = jax.tree.leaves(sliced)[0].shape[0]

    def layer(carry, xs):
        lp, l = xs if ordinals else (xs, None)
        return body(dict(lp, **whole), l, carry)

    def indexed(carry, l):
        return body(dict(_layer_of(sliced, l), **whole),
                    l if ordinals or by_index else None, carry)

    def run(lo, hi, carry):
        # A segment that is PART of its stack (a kind whose layers another
        # kind's interrupt: Laguna's window layers) reads its layers by
        # index too: a slice of the stack as a scan's xs is a copy of those
        # layers' weights every call (1.3 GB a decode chunk at Laguna's
        # widths, compiled for a v5e, PR 62).
        if by_index or hi - lo != stacked:
            return jax.lax.scan(indexed, carry, jnp.arange(lo, hi))
        return jax.lax.scan(layer, carry, (sliced, jnp.arange(lo, hi))
                            if ordinals else sliced)

    return run


# ---------------------------------------------------------------------------
# A prompt
# ---------------------------------------------------------------------------

def _prefill_walk(mcfg, stack: _Stack):
    """fn(params, tokens [1, B], length, caches=None, riders=None) -> (first
    token, kept, logits, experts, caches): ONE walk over the stack's segments
    (`LlamaConfig.segments`) for a prompt: embed, the rotary tables, the live
    mask, each segment's layers by its kind's body, the head. `kept` is what
    each cache keeps of the prompt, {cache of `_KEEP`: its arrays, a leading
    axis over the layers that write to it}; `logits` the last position's,
    float32; `experts` None for a dense model, else the routing counts of the
    prompt's tokens summed over the layers (`expert_stats`, or a
    share's `_share_stats`). The bucket's padding is computed like any row,
    each from itself alone (no capacity for it to take), and left out of the
    counts; rows past `length` reach no real row (attention and a
    convolution are causal, and a state-space layer is told `length`).

    RIDERS. Where the stack takes them (`_Stack.takes_riders`): with `caches`
    and `riders` = (bt, last, pos, riding), ONE decode step of the slots
    `riding` marks [n_slots], in the bucket's last n_slots rows, which the
    prompt has to leave free. Slot i's last token is embedded in row B -
    n_slots + i and rotated at its own position; per layer the tail rows' q,
    k and v do what a decode step does (the row written to the slot's page,
    the `paged_decode` kernel against the arena, which rides the scan's carry
    as it does in decode) and the result takes the tail of the flash output's
    place; a state-space layer's tail rows take the mixer's step from and to
    the slots' own state, in the same carry (`_mamba_kind`), a
    short-convolution layer's the operator's from and to the slots' own
    windows (`_conv_kind`), a latent-attention layer's the absorbed form's
    step (the slot's ONE row written, `paged_latent_decode` against the arena
    of latent rows: `_latent_kind`), a mixed stack's window layers' the row
    written to the slot's ring and the ring alone read, its full layers' the
    page's write and `paged_decode` (`_mixed_kind`); the projections,
    the feed-forward (a one-part stack's expert layers: `live` holds the
    riders) and the head run over the bucket as they do anyway, so the
    step's weight reads are the prefill's. Then logits is [1 +
    n_slots, V]: the prompt's last row, then the tail rows; and `experts`
    counts the riding rows."""
    dt, S = mcfg.dtype, mcfg.max_seq
    sparse = mcfg.n_experts > 0
    segments = {name for name, _, _ in mcfg.segments()}

    def walk(params, tokens, length, caches=None, riders=None):
        width = tokens.shape[1]
        if riders is not None:
            # A row that does not ride is the prompt's or padding, as without
            # riders.
            bt, last, pos, riding = riders
            tail = slice(width - riding.shape[0], width)
            act = riding & (pos < S)
            w = jnp.minimum(pos, S - 1)
            rows = jnp.arange(width)
        with jax.named_scope("embed"):
            if riders is not None:
                tokens = tokens.at[0, tail].set(
                    jnp.where(act, last, tokens[0, tail]))
            x = _embed(params, tokens, mcfg)
        with jax.named_scope("rope"):
            if riders is None:
                tables = stack.tables(width, True)
            else:
                # A row's own position: the prompt's run 0.., a rider's is
                # its slot's, anywhere under max_seq.
                at = rows.at[tail].set(jnp.where(act, w, rows[tail]))
                tables = jax.tree.map(lambda t: t[at], stack.tables(S, True))
        live = None
        if sparse and riders is None:
            live = jnp.arange(width)[None] < length
        elif sparse:
            live = ((rows < length)
                    | jnp.zeros(width, bool).at[tail].set(act))[None]
        ctx = dict(tables, live=live, length=length,
                   riders=None if riders is None else (bt, w, act))

        def layer(kind):
            def body(lp, l, carry):
                x, ride, caches = carry
                x, caches, kept, counts = kind.prefill(
                    lp, x, caches, l,
                    dict(ctx, **dict(zip(kind.rides, ride))))
                return (x, ride, caches), (*kept[:2], counts, *kept[2:])
            return body

        runs = {name: _over(kind, params[kind.stack or name], mcfg,
                            layer(kind),
                            kind.over != "slices" or riders is not None)
                for name, kind in stack.kinds.items() if name in segments}
        kept = {cache: [] for cache in _KEEP}
        experts = 0 if stack.tally == "zero" else None
        with jax.named_scope("layers"):
            for name, kind, lo, hi, base in _segments(mcfg, stack):
                ctx["base"] = base      # (read by a kind's riders alone)
                (x, _, caches), (k, v, counts, *more) = runs[name](
                    lo, hi, (x, tuple(ctx[c] for c in kind.rides), caches))
                ys = (k, v, *more)
                if kind.pack:
                    ys = kind.pack(*ys)
                for cache in set(kind.keeps):
                    kept[cache].append((kind.over == "inline", tuple(
                        y for y, to in zip(ys, kind.keeps) if to == cache)))
                if counts is not None and stack.tally != "late":
                    # (an inline layer's counts are its own, not a stack's)
                    if kind.over != "inline":
                        counts = jnp.sum(counts, axis=0)
                    counts = counts if experts is None else experts + counts
                experts = experts if counts is None else counts
        with jax.named_scope("head"):
            x = norms.rms_norm(x, params["final_norm"], mcfg.norm_eps)
            last_h = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                                  keepdims=False)
            if riders is not None:
                last_h = jnp.concatenate([last_h, x[0, tail]])
            logits = _head_logits(params, last_h, mcfg)
            first = jnp.argmax(logits[0]).astype(jnp.int32)
        if sparse and stack.tally == "late":
            experts = jnp.sum(experts, axis=0)

        def joined(*caches):    # the segments' rows, one after the other
            return {cache: tuple(
                None if part[0] is None else jnp.concatenate(part)
                for part in zip(*(
                    tuple(jnp.expand_dims(y, 0) for y in ys) if inline else ys
                    for inline, ys in kept[cache])))
                for cache in caches if kept[cache]}

        # (K and V are put together before the logits are cut out and the
        # rest after, as the pinned programs have it.)
        out = joined("pages", "ring")
        logits = (logits[0] if riders is None else logits).astype(jnp.float32)
        out.update(joined("index", "state", "retention", "pooled", "linear"))
        return first, out, logits, experts if sparse else None, caches

    return walk


def prefill_core(mcfg):
    """fn(params, tokens[1, B], length) -> (first_token, ks, vs, the last
    position's logits, experts, *more): `_prefill_walk` without riders, its
    `kept` spread: ks/vs are what the pages keep ([L, B, KVH, hd] of the
    layers that keep K and V; a latent-attention model's rows [L, B, rank +
    dr], and vs None), `more` what each further cache keeps, in `_KEEP`'s
    order: an indexer's keys [L, B, Id]; a hybrid's state-space layers' final
    (ssm state [Lm, N, Di], convolution window [Lm, K - 1, Di]) after the
    prompt's last real token; the window layers' (ks, vs) of a stack of
    window and full attention, of which a slot's ring keeps the prompt's
    tail. The shared prefill pass used by the in-engine prefill AND the
    disaggregated PrefillServer (reference: llm/_internal/serve/deployments/
    prefill_decode_disagg/ — there the split is two vLLM pools; here both
    halves share one traced walk)."""
    stack = _stack(mcfg)
    walk = _prefill_walk(mcfg, stack)

    def core(params, tokens, length):
        first, kept, logits, experts, _ = walk(params, tokens, length)
        (ks, vs), *more = kept.values()
        return (first, ks, vs, logits, experts,
                *(m[0] if len(m) == 1 else m for m in more))

    core.takes_riders = stack.takes_riders
    return core


# ---------------------------------------------------------------------------
# The programs
# ---------------------------------------------------------------------------

def build_programs(mcfg, n_slots: int, chunk: int, page: int,
                   n_pages: int) -> Programs:
    """The serving programs of one model at one engine's sizes."""
    stack = _stack(mcfg)
    sparse = mcfg.n_experts > 0
    S, dt, ns = mcfg.max_seq, mcfg.dtype, n_slots
    segments = {name for name, _, _ in mcfg.segments()}
    walk = _prefill_walk(mcfg, stack)

    # ------------------------------------------------------------------
    # prefill: full causal pass over ONE padded prompt, k/v -> pages
    # ------------------------------------------------------------------
    def prefill(params, caches, pages, tokens, length, temp, topk, key,
                slot=None, last=None, pos=None, riders=None):
        """tokens [1, B] padded to a BUCKET width (a rung of the engine's
        ladder — jax.jit compiles one program per bucket shape, so a prompt
        pays a prefill of about its own length, not a max_seq one); writes
        what each cache keeps of the prompt (`_KEEP`: the slot's pages; the
        indexer's keys, where the model has them; slot `slot`'s recurrent
        state, overwritten by the prompt's final one; or its window layers'
        rings, by the prompt's tail), returns the caches, the first generated
        token (sampled, or greedy when temp == 0) and the walk's `experts`.

        With `riders` = (block table, riding [ns], the slots' temp, topk,
        keys) and the slots' `last` and `pos` (the program of a riding rung):
        the walk's one decode step of the riding slots, and after `experts`
        come `last` and `pos` moved by it, as a decode chunk of one step
        would leave them, and the tokens [ns] the step sampled (a riding
        slot's is its next one; the others' rows are not slots')."""
        temps = topks = keys = at = None
        if riders is None:
            _, kept, logits, experts, _ = walk(params, tokens, length)
        else:
            bt, riding, temps, topks, keys = riders
            _, kept, logits, experts, caches = walk(
                params, tokens, length, caches, (bt, last, pos, riding))
            at = pos

        def rows(prompts, slots):
            prompts = jnp.asarray(prompts)[None]
            return prompts if slots is None \
                else jnp.concatenate([prompts, slots])

        # The pages first: the sampler follows that write. The prompt's row
        # and the riders' go through ONE sampler, each row at its own
        # temperature, key and position, as `_step` samples.
        if "pages" in kept:     # (a stack of retention layers pages nothing)
            caches = _keep_pages(caches, pages, slot, length,
                                 *kept.pop("pages"))
        toks = sample_tokens(logits[None] if riders is None else logits,
                             rows(temp, temps), rows(topk, topks),
                             rows(key, keys), rows(length - 1, at))
        if riders is not None:
            act = riding & (pos < S)
        first = toks[0]
        # (rings that riders moved come out of the layers' loops, where a
        # scatter's read of the rows it replaces costs a copy of them all:
        # `write_window_prompt`)
        how = {"ring": {"in_bounds": True}} if riders is not None else {}
        for cache, rest in kept.items():
            caches = _KEEP[cache](caches, pages, slot, length, *rest,
                                  **how.get(cache, {}))
        if riders is None:
            return caches, first, experts
        return (caches, first, experts, jnp.where(act, toks[1:], last),
                jnp.where(act, pos + 1, pos), toks[1:])

    def adopt(caches, pages, ks, vs):
        """Write externally-prefilled k/v (a PrefillServer handoff) into
        the slot's pages."""
        return _keep_pages(caches, pages, None, None, ks, vs)

    # ------------------------------------------------------------------
    # decode: one token for every active slot per step, `chunk` steps
    # ------------------------------------------------------------------
    def _step(params, caches, counts, ctx, x):
        """ONE walk over the stack's segments for a decode step: the step's
        rows `x` (a token a slot, or a block's rows a slot) through the
        layers, the caches in the carry -> (x, caches, counts). `ctx`: the
        step's own dict (the rotary tables, `bt`, `pos`, `act`)."""
        def layer(kind):
            def body(lp, l, carry):
                x, caches, *counts = carry
                x, caches, n = kind.decode(lp, x, caches, l, ctx)
                return (x, caches, *(counts if n is None
                                     else [counts[0] + n])), None
            return body

        runs = {name: _over(kind, params[kind.stack or name], mcfg,
                            layer(kind))
                for name, kind in stack.kinds.items() if name in segments}
        with jax.named_scope("layers"):
            for name, kind, lo, hi, base in _segments(mcfg, stack):
                ctx["base"] = base
                if kind.begin:
                    kind.begin(ctx)
                # The caches of this kind alone ride its scan: an arena a
                # segment never touches stays out of its loop.
                (x, rode, *counts), _ = runs[name](lo, hi, (
                    x, Caches(**{c: getattr(caches, c)
                                 for c in kind.carries}), *counts))
                caches = caches._replace(
                    **{c: getattr(rode, c) for c in kind.carries})
        return x, caches, counts

    def _token(params, caches, counts, tables, bt, last, pos, active, temp,
               topk, keys):
        """A decode step of one token a slot: embed, `_step`, the head, the
        sampler."""
        act = active & (pos < S)
        with jax.named_scope("embed"):
            x = _embed(params, last, mcfg)
        x, caches, counts = _step(
            params, caches, counts, dict(tables, bt=bt, pos=pos, act=act), x)
        with jax.named_scope("head"):
            x = norms.rms_norm(x, params["final_norm"], mcfg.norm_eps)
            logits = _head_logits(params, x, mcfg)         # [ns, V]
        nxt = sample_tokens(logits, temp, topk, keys, pos)
        nxt = jnp.where(act, nxt, last)
        return caches, counts, nxt, jnp.where(act, pos + 1, pos)

    def decode(params, caches, bt, last, pos, active, temp, topk, keys):
        """-> (caches, last, pos, tokens [ns, chunk], experts): `experts` is
        None for a dense model, else the routing counts of the live slots'
        tokens summed over the chunk's steps and the layers."""
        with jax.named_scope("rope"):
            tables = stack.tables(S, False)
        out0 = jnp.zeros((ns, chunk), jnp.int32)
        # `expert_stats`' width, and `_share_stats`' for a share.
        counts0 = [jnp.zeros(mcfg.n_held + 1 + stack.shares,
                             jnp.int32)] if sparse else []

        def body(i, carry):
            caches, last, pos, out, *counts = carry
            caches, counts, nxt, pos = _token(
                params, caches, counts, tables, bt, last, pos, active, temp,
                topk, keys)
            return (caches, nxt, pos, out.at[:, i].set(nxt), *counts)

        caches, last, pos, out, *counts = jax.lax.fori_loop(
            0, chunk, body, (caches, last, pos, out0, *counts0))
        return caches, last, pos, out, counts[0] if sparse else None

    # ------------------------------------------------------------------
    # generation by blocks: a prompt's whole blocks kept, then a block of
    # B positions a slot in T denoising forwards, the first of which commits
    # the block before
    # ------------------------------------------------------------------
    B, T = mcfg.block_length, mcfg.denoise_steps

    def block_prefill(params, caches, pages, tokens, length, temp, topk, key,
                      slot=None, last=None, pos=None, riders=None):
        """`prefill` of a model that generates by blocks: the prompt's whole
        blocks, P = B * floor(length / B) rows, under the block mask, their K
        and V to the slot's pages (the bucket's rows past P are written too,
        computed from what they hold, and every one is written again by its
        block's forwards before anything reads it). No token: a position
        predicts its OWN token, so the prompt's last row predicts nothing
        new. -> (caches, the slot's state as it opens [2B]: B -1s, no block
        pending, then the opening block: the prompt's tail `tokens[P: length]`
        then -1s; `experts`)."""
        whole = length // B * B
        _, kept, _, experts, _ = walk(params, tokens, whole)
        caches = _keep_pages(caches, pages, slot, length, *kept.pop("pages"))
        at = whole - B + jnp.arange(2 * B)
        first = jnp.where(
            (at >= whole) & (at < length),
            jnp.take(tokens[0], jnp.clip(at, 0, tokens.shape[1] - 1)), -1)
        return caches, first.astype(jnp.int32), experts

    def unmask(logits, z, masked, n, temp, topk, keys, at):
        """The commit rule of one denoising step. logits [ns * B, V] of the
        blocks' rows, z [ns, B] their ids and `masked` [ns, B] the rows
        still to decide: each masked row's candidate is `sample_tokens` of
        its logits (the argmax at temperature 0; a draw keyed by its slot's
        seed and `at`, the row's position and the step), its confidence the
        float32 softmax's probability of that candidate; the `n` masked rows
        of a block with the largest confidence take their candidates, ties
        to the smaller position, all of them where fewer are left. -> (z,
        masked)."""
        with jax.named_scope("unmask"):
            x = sample_tokens(logits, temp, topk, keys, at)
            lg = logits.astype(jnp.float32)
            conf = jnp.exp(
                jnp.take_along_axis(lg, x[:, None], axis=1)[:, 0]
                - jax.nn.logsumexp(lg, axis=-1)).reshape(ns, B)
            conf = jnp.where(masked, conf, -1.0)
            i = jnp.arange(B)
            # rows of the block that go before row i: a larger confidence,
            # or the same at a smaller position
            ahead = (conf[:, None, :] > conf[:, :, None]) | (
                (conf[:, None, :] == conf[:, :, None])
                & (i[None, :] < i[:, None]))
            take = masked & (jnp.sum(ahead, axis=-1) < n)
            return jnp.where(take, x.reshape(ns, B), z), masked & ~take

    def block_decode(params, caches, bt, last, pos, active, temp, topk, keys):
        """-> (caches, last, pos, tokens [ns, chunk], experts): `chunk / B`
        blocks a live slot, T forwards each. `last` [ns, 2B] is a slot's
        PENDING block (its B final ids, whose K and V the cache does not keep
        yet; -1s: none, a slot's first block) beside its OPEN one at
        positions pos..pos + B - 1 (an id, or -1: masked, embedded as
        `mask_id`); the pending block lies at pos - B..pos - 1.

        A block's first forward is of 2B rows a slot, the pending block's
        then the open one's (`decode_rows`): the pending rows' K and V, from
        their final ids, are the rows the cache keeps of that block, written
        a layer before the layer's attention, where the open rows read them.
        The commit of a block rides the next one's first forward (the scope
        `commit` names that forward) and has none of its own; the head,
        `unmask` and the sampler take the open rows alone. Forwards 2..T are
        of the open block's B rows; each forward is followed by `unmask`
        (step s of T commits B // T rows, one more while s <= B mod T) and
        writes the open block's rows over the last one's. Then the block is
        the pending one and the next opens all masked. What the cache keeps:
        every block but a request's LAST, which no later forward commits: its
        slot is freed, and nobody reads those K and V. `experts` counts every
        forward's live rows (pending rows of a slot with no pending block
        are not)."""
        with jax.named_scope("rope"):
            tables = stack.tables(S, False)
        out0 = jnp.zeros((ns, chunk), jnp.int32)
        counts0 = [jnp.zeros(mcfg.n_held + 1 + stack.shares,
                             jnp.int32)] if sparse else []
        temps, topks, rkeys = (jnp.repeat(t, B, axis=0)
                               for t in (temp, topk, keys))

        def forward(caches, counts, z, pos, act, rides=None):
            """z [ns, B], or [ns, 2B] with `rides` -> the open rows' logits
            [ns * B, V]."""
            with jax.named_scope("embed"):
                x = _embed(params, z.reshape(-1), mcfg)
            x, caches, counts = _step(
                params, caches, counts,
                dict(tables, bt=bt, pos=pos, act=act, rides=rides), x)
            with jax.named_scope("head"):
                x = x.reshape(ns, -1, x.shape[-1])[:, -B:].reshape(ns * B, -1)
                x = norms.rms_norm(x, params["final_norm"], mcfg.norm_eps)
                return caches, counts, _head_logits(params, x, mcfg)

        def body(b, carry):
            caches, last, pos, out, *counts = carry
            act = active & (pos < S)
            masked = last[:, B:] < 0
            z = jnp.where(last < 0, mcfg.mask_id, last)
            at = (pos[:, None] + jnp.arange(B)).reshape(-1)
            with jax.named_scope("commit"):     # the pending block's rides
                caches, counts, logits = forward(
                    caches, counts, z, pos, act, act & (last[:, 0] >= 0))
            z = z[:, B:]
            for s in range(T):
                if s:
                    caches, counts, logits = forward(caches, counts, z, pos,
                                                     act)
                z, masked = unmask(logits, z, masked, B // T + (s < B % T),
                                   temps, topks, rkeys, at * T + s)
            out = jax.lax.dynamic_update_slice(out, z, (0, b * B))
            opened = jnp.concatenate([z, jnp.full_like(z, -1)], axis=1)
            return (caches, jnp.where(act[:, None], opened, last),
                    jnp.where(act, pos + B, pos), out, *counts)

        caches, last, pos, out, *counts = jax.lax.fori_loop(
            0, chunk // B, body, (caches, last, pos, out0, *counts0))
        return caches, last, pos, out, counts[0] if sparse else None

    def blocks(pos, active, chunk, plan):
        """What a chunk's blocks are: the `forwards` it runs
        (`denoise_forwards`; the widest of `rows` rows, a block's first: the
        pending block beside the open one), the live slots' blocks whose
        pending block that forward commits (`commits_rode`: all but the one
        that `opens` a slot), the positions covered in the live slots
        (`block_tokens`), the prompts' tails (`tail_tokens`) among them: a
        slot's forwards over `block_tokens - tail_tokens` is a token's cost."""
        n = chunk // B
        return dict(blocks=n, forwards=n * T, rows=2 * ns * B,
                    committed=len(plan) * chunk, commits_rode=sum(
                        min(n, int(S - pos[slot]) // B) - opens
                        for slot, *_, opens in plan))

    counts = tuple({id(k): k.counts for k in stack.kinds.values()
                    if k.counts is not None}.values())
    if B > 1:
        counts += (_Counts({"forwards": "denoise_forwards",
                            "commits_rode": "commits_rode",
                            "committed": "block_tokens"}, blocks,
                           {"block": B, "tail_tokens": 0}),)
        if chunk % B:
            raise ValueError(
                f"decode_chunk {chunk} is whole blocks of block_length {B}")
        if page % B:
            raise ValueError(
                f"block_length {B} divides the page of {page} positions, so "
                "that a block never crosses one")
        # Under the names every program of these two kinds has: a trace's
        # readers find `jit_prefill` and `jit_decode`.
        block_prefill.__name__, block_decode.__name__ = "prefill", "decode"
        prefill, decode = block_prefill, block_decode

    def poke(last, pos, slot, first, length):
        """Admission bookkeeping ON DEVICE: set one slot's (last, pos).
        Keeps the decode chain free of device->host fetches — a host
        read of last/pos at admission would cost a device round-trip
        before the TTFT token could be emitted."""
        return last.at[slot].set(first), pos.at[slot].set(length)

    # Donated: the caches, and the slots' `last` and `pos`.
    return Programs(
        empty=functools.partial(stack.empty, n_slots, page, n_pages),
        prefill=jax.jit(prefill, donate_argnums=(1, 9, 10)),
        decode=jax.jit(decode, donate_argnums=(1, 3, 4)),
        adopt=jax.jit(adopt, donate_argnums=(0,)),
        poke=jax.jit(poke, donate_argnums=(0, 1)),
        takes_riders=stack.takes_riders, adopts=stack.adopts,
        by_slot=any(cache not in ("pages", "index") for kind in
                    stack.kinds.values() for cache in kind.keeps),
        paged=mcfg.kv_layers > 0,
        books=lambda caches: Books(mcfg, counts, stack.shares,
                                   stack.cache_bytes(caches)),
        block=B)


def _experts_in_compute_dtype(params, mcfg):
    """A sparse model's expert stacks (whatever stacks hold a `router`) are
    read whole by every layer of every program (`block.expert_stacks`), so
    they are held in the compute dtype: stored otherwise they are cast here,
    once, and the log says so (the caller may drop its own copy)."""
    cast = {stack: {k: leaves[k].astype(mcfg.dtype)
                    for k in ("w_gate", "w_up", "w_down")
                    if k in leaves and leaves[k].dtype != mcfg.dtype}
            for stack, leaves in params.items()
            if isinstance(leaves, dict) and "router" in leaves}
    if not any(cast.values()):
        return params
    logger.warning(
        "expert weights are stored as %s and computed in %s: the engine "
        "casts its own copy once", mcfg.param_dtype, mcfg.dtype)
    return dict(params, **{stack: dict(params[stack], **leaves)
                           for stack, leaves in cast.items() if leaves})


def serving_params(params, mcfg):
    """The tree the programs read, made once from the published one on the
    device: the experts in the compute dtype, then one q/k/v stack
    (`block.fuse_qkv`). The caller's projections are TAKEN OVER, as a donated
    argument is: every leaf the fused tree no longer holds is deleted,
    whoever holds it (`published_params` gives them back). A caller holds its
    tree while a server warms up: the projections twice over are 0.6 GB at 12
    Mistral layers, 0.2 on OLMoE, whose warm-up peaks within 0.9 GB of the
    chip's memory (PERF.md, section 4)."""
    cast = _experts_in_compute_dtype(params, mcfg)
    fused = block.fuse_qkv(cast, mcfg)
    held = {id(leaf) for leaf in jax.tree.leaves(fused)}
    for leaf in jax.tree.leaves(cast):
        if id(leaf) not in held:
            leaf.delete()
    return fused


# (params, mcfg) -> `serving_params`' tree as the model is published (`wq`,
# `wk`, `wv` a matrix each: a checkpoint's layout), split anew at every call.
published_params = block.split_qkv


def empty_handoff(mcfg, width: int):
    """The (K, V) `adopt` takes of a hand-off of `width` rows, zeroed."""
    kv = jnp.zeros((mcfg.n_layers, width, mcfg.n_kv_heads, mcfg.head_dim),
                   mcfg.dtype)
    return kv, kv

"""Nobody else's program moved: what every stack's serving programs (and a
dense train step) lower to is letter for letter what the parent commit of the
PR that pinned them lowered them to (sha256 of the lowered text). The pins
of tests/test_dots.py (PR 39: one prefill rung that takes nobody and the
decode program of each stack) and of tests/test_prefill_riders.py (PR 41:
every rung of every stack, and who takes riders) in ONE file, read off ONE
engine a stack: an engine compiles its programs when it is built, and three
tests built each stack's engine anew to lower a rung each.
"""

import functools
import hashlib
from unittest import mock

import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from ray_tpu.models import llama, serving
from ray_tpu.models.serving import Caches, prefill_core
from ray_tpu.serve.engine import Engine
from engine_pins import GENERIC, MODEL
from test_dots import PUBLISHED
from test_lfm2 import PUBLISHED as LFM2
from test_mimo import PUBLISHED as MIMO
from test_prefill_ladder import F32, KINDS
from test_serve_llm import parents_sample_tokens

# sha256 (first 16 hex digits) of the lowered text of the other models'
# serving programs at their adapters' rehearsal widths and of a dense train
# step at `LlamaConfig.tiny`, on the parent commit of PR 39 (00d21d1; jax
# 0.9.0 on the CPU: no Mosaic payload, no source locations in the text).
# Since PR 41 a dense and a sparse stack's rungs of the octave under `max_seq`
# (64 and 128 here) carry the live slots (`engine.rung_rides`) and lower to
# another text on purpose: their pin is the 32 rung, taken on PR 41's parent
# (5481b82), as are the latent stack's own (which took nobody until PR 61).
# `PARENT_RUNGS` below pins every rung of every stack. The same two
# stacks' decode programs hand the arena to the jit they share with the riders
# (`_token_step`: the same write and kernel, one trace a process) and were
# taken anew on PR 41's tree (their parent's: 4ce2defd4ff49240 and
# 278d751dc50fcfc4); the stacks that take nobody keep the parent's.
# Since PR 58 the hybrid stack takes riders too: its pin is the 32 rung, the
# parent's (a8cc334), and its decode program, whose attention layers now go
# through `_token_step` as well, was taken anew on PR 58's tree (the
# parent's: 98e6e614b5625848, which the tree still lowers to with the
# attention kind built `ridden=False`: the mixers' steps did not move).
# Since PR 60 the conv stack (LFM2's short-convolution layers beside
# attention) is pinned here and takes riders too: its 32 rung is the text of
# PR 60's parent (0ed315d), and its decode program, whose two attention layers
# now go through `_token_step` as every riding stack's do, was taken anew on
# PR 60's tree (the parent's: bd26e37b38f9f1e5, which the tree still lowers to
# with the attention kind built `ridden=False`: the operator's step, moved
# into `block._slot_conv`, is the same text).
# Since PR 61 the latent stack (dots: MLA layers over a share of the experts)
# takes riders too: its pin is the 32 rung, the parent's (b8b4411), and its
# decode program, whose write and kernel now go through the jit they share
# with the riders (`_latent_kind`'s `_token_step`; the lengths a step reads
# are made there, from the same `act` and `w`), was taken anew on PR 61's tree
# (the parent's: 3cbcf9da23401fa5; the mathematics of a step did not move:
# tests/test_dots.py holds it to the reference).
# `mixed` (PR 42's stack) was taken on PR 45's parent (6c2c097), before that
# PR moved a line under ray_tpu/. Since PR 64 it takes riders too: its pin is
# the 32 rung, that parent's text still (and PR 64's parent's, 320e6dd), and
# its decode program, whose ring and page steps now go through the two jits
# they share with the riders (`serving._mixed_steps`), was taken anew on PR
# 64's tree (the parent's, of 6c2c097 and of 320e6dd alike: c31b6808d133c647,
# which the tree still lowers to with the steps built without the shared
# jits: `test_the_mixed_decode_without_the_shared_jits_is_the_parents`).
# Since PR 47 `serving.sample_tokens` takes its top-k behind a conditional, so
# every serving program's text moves, by design, inside `sample` and nowhere
# else: the pinned programs are lowered with the sampler of PR 47's parent
# (7f64f96; `test_serve_llm.parents_sample_tokens`, which the sampler is held
# to token for token there) in its place, and every digest stands unmoved.
PARENT_PROGRAMS = {
    "conv.decode": "e95d7ce3f111b0ea",
    "conv.prefill32": "ff719c8f70fb6d00",
    "dense.decode": "d87712c9b4ee5285",
    "dense.prefill32": "c948937b09fe2fee",
    "hybrid.decode": "112c069064bd2652",
    "hybrid.prefill32": "bf109118a1785278",
    "indexed.decode": "7f5193fdda9e8db0",
    "indexed.prefill64": "2b26fc68f7f5f898",
    "latent.decode": "d16fcac2d9f7e040",
    "latent.prefill32": "8da32aa0051287f3",
    "mixed.decode": "e89d77bc0eac84b0",
    "mixed.prefill32": "7ce5961c0ca08aab",
    "sparse.decode": "94dff0eb228ce990",
    "sparse.prefill32": "7a5fc5aa7c158c94",
    "train.tiny": "569d197c86234e93",
}

# sha256 (first 16 hex digits) of the lowered text of every prefill program
# of the five stacks at their adapters' rehearsal widths, `max_seq` 128 and
# two slots, on PR 41's parent (5481b82; jax 0.9.0 on the CPU: no Mosaic
# payload, no source locations in the text). The rungs 64 and 128 of the dense
# and the sparse stack ride since PR 41, the hybrid's since PR 58, the conv
# stack's (every rung taken on PR 60's parent, 0ed315d) since PR 60, the
# latent stack's since PR 61, the mixed stack's (every rung taken on PR 45's
# parent, 6c2c097) since PR 64: `PARENT_RIDING` below;
# `PARENT_PROGRAMS` above pins the decode programs. All three tables stand since PR 47 with
# that PR's parent's sampler in `serving.sample_tokens`' place while a
# program is lowered (`parents_prefill_text` puts it there): the one
# part of every serving program that PR moved.
PARENT_RUNGS = {
    "dense": {32: "c948937b09fe2fee"},
    "sparse": {32: "7a5fc5aa7c158c94"},
    "indexed": {32: "bfe2a2df64e53893", 64: "2b26fc68f7f5f898",
                128: "69ca4b8800557a63"},
    "hybrid": {32: "bf109118a1785278"},
    "conv": {32: "ff719c8f70fb6d00"},
    "latent": {32: "8da32aa0051287f3"},
    "mixed": {32: "7ce5961c0ca08aab"},
}
# What the riding rungs lowered to there: another text now, on purpose.
PARENT_RIDERLESS = {
    "dense": {64: "d5061fe7c8b0f160", 128: "0f8a98c45565c6ea"},
    "sparse": {64: "01d0cbc9e60958cc", 128: "6ea775ec4038bec1"},
    "hybrid": {64: "b6847a6dfe909d84", 128: "4dd7ed9434604dd1"},
    "conv": {64: "0d6746047675a641", 128: "e080b12a6d548004"},
    "latent": {64: "f96e02f0c080c3fb", 128: "4b487bf21d58472e"},
    "mixed": {64: "ef4db6b4bc528b78", 128: "d8c5fc514a414023"},
}
# What the riding rungs lower to with the riders' shapes as `_place` passes
# them (`Engine.lowered_prefill_text`): the programs `serve-batch` and
# `serve-batch-olmoe` spend their prefill time in, on PR 45's parent
# (6c2c097); the hybrid's (a Mamba-1 layer's step in the mixer's tail rows,
# `block.mamba_mixer(riders=)`: the programs `serve-batch-jamba2` spends its
# prefill time in) taken on PR 58's tree, which made them; the conv stack's
# (the operator's step in its tail rows, `block.conv_mixer(riders=)`, and
# `_token_step` in its two attention layers': the programs
# `serve-generate-lfm2` spends 0.58 of its prefills in) on PR 60's; the latent
# stack's (the absorbed form's step in the tail rows of each MLA layer,
# `_latent_kind`'s `_token_step`: the programs `serve-batch-dots-vlm1` spends
# nearly all its prefill time in) on PR 61's; the mixed stack's (a window
# layer's row into the slot's ring and the ring alone read, a full layer's
# into its page and `paged_decode`, `_mixed_steps`: the programs
# `serve-longdoc-mimo-v2` and `serve-longdoc-laguna` spend all their prefill
# time in) on PR 64's.
PARENT_RIDING = {
    "dense": {64: "8de5c32ccafe6475", 128: "3a4649dd358ebc16"},
    "sparse": {64: "c033be69300aac6b", 128: "c2b37a166224c151"},
    "hybrid": {64: "a87192cc5a56bac3", 128: "f0b6c2f60d1080fc"},
    "conv": {64: "5aef5bb379a666c0", 128: "892c8e058afa9478"},
    "latent": {64: "6c612fcd1cce9799", 128: "1b7702520c1c27e5"},
    "mixed": {64: "e2deceaccded5d52", 128: "06a1790488ab9bee"},
}
# The mixed stack's decode program on PR 64's parent (320e6dd; on 6c2c097
# too), which PR 64's tree lowers to with `_mixed_steps`' two jits taken off.
MIXED_DECODE_UNSHARED = "c31b6808d133c647"
STACKS = dict(KINDS, latent=("dots", PUBLISHED), mixed=("mimo", MIMO),
              conv=("lfm2", LFM2))


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# The pins were taken when the programs took the caches apart (`kc, vc` after
# `params`; `ic`, `state` and `slot` after `key`; the one further cache a
# model has LAST among the results), and lowered text names arguments by
# position. So a pinned program is lowered from its own function (the jit's
# `__wrapped__`) under the parent's name, argument order, result order and
# `donate_argnums`, which puts the bundle together and takes it apart again:
# what is compared is then the parent's text, or the program changed. The
# one callee that changed on purpose since (PR 47's sampler) is lowered as the
# parent had it: `_parents_sampler`.

def _parents_sampler():
    return mock.patch.object(serving, "sample_tokens", parents_sample_tokens)


def parents_prefill_text(eng, width):
    def prefill(params, kc, vc, pages, tokens, length, temp, topk, key,
                ic=None, state=None, slot=None, last=None, pos=None,
                riders=None):
        caches, first, experts, *rode = eng._programs.prefill.__wrapped__(
            params, Caches(kc, vc, ic, state), pages, tokens, length, temp,
            topk, key, slot, last, pos, riders)
        return (caches.kc, caches.vc, first, experts, *(
            c for c in (caches.ic, caches.state) if c is not None), *rode)

    params, caches, pages, tokens, length, temp, topk, key, slot, *riding = \
        eng.prefill_shapes(width)
    with _parents_sampler():
        return jax.jit(prefill, donate_argnums=(1, 2, 9, 10, 12, 13)).lower(
            params, caches.kc, caches.vc, pages, tokens, length, temp, topk,
            key, caches.ic, caches.state, slot, *riding).as_text()


def parents_decode_text(eng):
    def decode(params, kc, vc, bt, last, pos, active, temp, topk, keys,
               ic=None, state=None):
        caches, last, pos, out, experts = eng._programs.decode.__wrapped__(
            params, Caches(kc, vc, ic, state), bt, last, pos, active, temp,
            topk, keys)
        return (caches.kc, caches.vc, last, pos, out, experts, *(
            c for c in (caches.ic, caches.state) if c is not None))

    params, caches, *slots = eng.decode_shapes()
    with _parents_sampler():
        return jax.jit(decode, donate_argnums=(1, 2, 4, 5, 10, 11)).lower(
            params, caches.kc, caches.vc, *slots, caches.ic,
            caches.state).as_text()


@functools.cache
def _programs(kind):
    """(takes riders, the riding rungs, {width: digest of the rung's lowered
    text}, digest of the decode program's, the keys of its counters, digest
    of the mixed stack's decode program built without the jits its steps
    share with the riders: None for another stack) of the stack's engine at
    its adapter's rehearsal widths, built once: the tests below read their
    pins off it and leave it as it was built."""
    adapter = models.adapter(STACKS[kind][0])
    cfg = adapter.build_config(dict(adapter.REHEARSE, **STACKS[kind][1]),
                               F32, 128)
    eng = Engine(adapter.init_params(cfg, 3), cfg, n_slots=2, decode_chunk=2,
                 page_size=16)
    try:
        assert eng._programs.takes_riders is prefill_core(
            cfg).takes_riders
        riding = [w for w in eng.buckets if eng._rides(w)]
        rungs = {w: _sha(parents_prefill_text(eng, w)) for w in eng.buckets}
        decode = _sha(parents_decode_text(eng))
        keys = set(eng.counters())
        takes, unshared = eng._programs.takes_riders, None
        if kind == "mixed":
            steps = serving._mixed_steps

            def unjitted(mcfg):
                with mock.patch.object(jax, "jit", lambda f: f):
                    return steps(mcfg)

            with mock.patch.object(serving, "_mixed_steps", unjitted):
                eng._programs = serving.build_programs(cfg, 2, 2, 16, 17)
            unshared = _sha(parents_decode_text(eng))
    finally:
        eng.stop()
    return takes, riding, rungs, decode, keys, unshared


def _train_step_digest():
    cfg = llama.LlamaConfig.tiny()
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    step = jax.jit(jax.value_and_grad(
        lambda p, t: llama.loss_fn(p, t, cfg)[0]))
    return _sha(step.lower(
        params, jax.ShapeDtypeStruct((2, 64), jnp.int32)).as_text())


@pytest.mark.parametrize("kind", ["dense", "sparse", "indexed", "hybrid",
                                  "latent", "mixed", "conv", "train"])
def test_the_other_models_programs_are_the_parents(kind):
    """What a dense, a sparse (softmax router, every expert), an indexed, a
    hybrid, a latent, a mixed and a conv engine's prefill (a rung that takes no
    riders) and decode, and a dense train step, lower to is letter for letter
    what the parent commit lowers them to."""
    if kind == "train":
        got = {"train.tiny": _train_step_digest()}
    else:
        _, riding, rungs, decode, *_ = _programs(kind)
        width = 32 if 64 in riding else 64
        got = {f"{kind}.prefill{width}": rungs[width],
               f"{kind}.decode": decode}
    assert got == {k: PARENT_PROGRAMS[k] for k in got}


@pytest.mark.parametrize("kind", sorted(PARENT_RUNGS))
def test_who_takes_riders_and_every_other_program_is_the_parents(kind):
    """A dense, a sparse, a hybrid, a conv, a latent and a mixed stack's
    programs of the octave under `max_seq` take riders and hold a decode
    step's attention (a hybrid's its state-space layers' step too, a conv
    stack's its short-convolution layers'; a latent stack's is the absorbed
    form's; a mixed stack's window layers' reads their slot's ring); their
    narrow rungs, and every rung of an indexed stack, take nobody and lower
    to the parent's text, letter for letter. Asked of the built program; no
    option, field or environment variable has a say."""
    takes, riding, got, *_ = _programs(kind)
    assert takes is (kind != "indexed")
    assert riding == ([64, 128] if takes else [])
    assert {w: d for w, d in got.items()
            if w not in riding} == PARENT_RUNGS[kind]
    for w, was in PARENT_RIDERLESS.get(kind, {}).items():
        assert w in riding and got[w] != was


@pytest.mark.parametrize("kind", sorted(PARENT_RIDING))
def test_the_riding_rungs_are_the_parents(kind):
    """The riding rungs of a dense, a sparse, a hybrid, a conv, a latent and a
    mixed stack, lowered with the riders' shapes as `_place` passes them, are
    the pinned text."""
    _, riding, got, *_ = _programs(kind)
    assert {w: got[w] for w in riding} == PARENT_RIDING[kind]


def test_the_mixed_decode_without_the_shared_jits_is_the_parents():
    """The mixed stack's decode program moved by the two calls alone: with
    `_mixed_steps`' jits taken off (the steps traced in line, as the parent
    wrote them in the layer's body) the tree lowers it to the parent's text,
    letter for letter."""
    assert _programs("mixed")[-1] == MIXED_DECODE_UNSHARED


@pytest.mark.parametrize("kind", ["dense", "sparse", "indexed", "hybrid",
                                  "latent", "mixed"])
def test_what_each_stack_counts_is_what_the_parent_counted(kind):
    """The keys of `Engine.counters()`, read in the same build as the digests:
    the scheduler's own and the stack's row of tests/engine_pins.py."""
    *_, keys, _ = _programs(kind)
    assert GENERIC <= keys and keys - GENERIC == MODEL[kind], \
        sorted(keys - GENERIC)

"""What a layer of a stack of window and full attention layers does WITH
RIDERS in a prompt's pass (`models/serving.py::_mixed_kind`, PR 64), op by op
in float32 without jit (the same ops on the same values are the same bits),
as tests/mixer_riders.py holds the mixers: the bucket's last `n_slots` rows
are one token a slot, and the pass (a) leaves the prompt's output rows, what
the caches keep of them and the prompt's last-row logits to the bit what the
pass without riders leaves, and (b) gives each riding slot the output row,
the ring row and the page row that `decode` gives that slot alone, an idle
slot's ring and pages and every other layer's untouched. On a MiMo-shaped
model (a sink, a key in two parts of which RoPE turns one) and a Laguna-shaped
one (a gate a head, more window heads than full ones, YaRN's tables with a
magnitude), the window kind and both stacks of the full kind, a slot under
the window's width, one whose ring has wrapped once and one many times.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import block, serving
import test_laguna
import test_mimo

MODELS = {"mimo": test_mimo, "laguna": test_laguna}
MAX_SEQ, SLOTS, PAGE = 128, 4, 16
BUCKET, LENGTH = 64, 41
# The rehearsal's window is 16: slot 0 has not filled its ring, slot 2 has
# wrapped it twice, slot 3 four times; slot 1 holds a request and does not
# ride.
POS = np.array([5, 9, 37, 70], np.int32)
ACTIVE = np.array([True, False, True, True])
TOL = 2e-5


@pytest.fixture(scope="module", params=sorted(MODELS))
def model(request):
    """(cfg, the serving tree, the stack's table, caches that hold something
    everywhere, the slots' block table) of one tiny model."""
    _, _, cfg, params = MODELS[request.param]._tiny(max_seq=MAX_SEQ)
    stack = serving._stack(cfg)
    maxp = MAX_SEQ // PAGE
    empty = stack.empty(SLOTS, PAGE, SLOTS * maxp + 1)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 8))
    caches = jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape, a.dtype), empty)
    bt = jnp.arange(1, 1 + SLOTS * maxp, dtype=jnp.int32).reshape(SLOTS, maxp)
    return cfg, block.fuse_qkv(params, cfg), stack, caches, bt


def _same(got, want):
    return (np.asarray(got) == np.asarray(want)).all()


def _riding_ctx(stack, bt, base):
    """A prompt's `ctx` with riders, as `_prefill_walk` makes it."""
    act, w = jnp.asarray(ACTIVE), jnp.asarray(POS)
    rows = jnp.arange(BUCKET)
    tail = slice(BUCKET - SLOTS, BUCKET)
    at = rows.at[tail].set(jnp.where(act, w, rows[tail]))
    tables = jax.tree.map(lambda t: t[at], stack.tables(MAX_SEQ, True))
    live = ((rows < LENGTH) | jnp.zeros(BUCKET, bool).at[tail].set(act))[None]
    return dict(tables, live=live, length=LENGTH, riders=(bt, w, act),
                base=base)


@pytest.mark.parametrize("name", ["dense", "window", "layers"])
def test_a_layers_tail_rows_are_its_decode_step_and_the_prompt_is_untouched(
        model, name):
    cfg, fused, stack, caches, bt = model
    kind = stack.kinds[name]
    *_, hi, base = next(
        s for s in serving._segments(cfg, stack) if s[0] == name)
    l = hi - 1                      # the last layer of the kind's first run
    sliced, whole = block.expert_stacks(fused[name], cfg)
    lp = dict(serving._layer_of(sliced, l), **whole)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, BUCKET, cfg.d_model))
    tail = slice(BUCKET - SLOTS, BUCKET)
    act = jnp.asarray(ACTIVE)
    with jax.disable_jit():
        plain, none, kept, _ = kind.prefill(lp, x, None, l, dict(
            stack.tables(BUCKET, True), length=LENGTH, riders=None,
            live=jnp.arange(BUCKET)[None] < LENGTH, base=base))
        riding, rode, kept_r, _ = kind.prefill(
            lp, x, caches, l, _riding_ctx(stack, bt, base))
        ctx = dict(stack.tables(MAX_SEQ, False), bt=bt, pos=jnp.asarray(POS),
                   act=act, base=base)
        kind.begin(ctx)
        step, stepped, _ = kind.decode(lp, x[0, tail], caches, l, ctx)
    assert none is None
    # (a) the prompt's rows and what the caches keep of them: to the bit
    assert _same(riding[0, :LENGTH], plain[0, :LENGTH])
    assert all(_same(got[:LENGTH], was[:LENGTH])
               for got, was in zip(kept_r, kept))
    # (b) a riding slot's row: the step's alone ...
    err = np.abs(np.asarray(riding[0, tail]) - np.asarray(step))[ACTIVE]
    assert err.max() < TOL, err.max()
    # ... and not the prompt pass's own row there (an idle slot's row is a
    # row of padding, computed like one and read by nobody)
    assert np.abs(np.asarray(plain[0, tail])
                  - np.asarray(step))[ACTIVE].min(axis=0).max() > 100 * TOL
    # the caches: what the step leaves, to the bit (the same write; page 0
    # is nobody's, and takes an idle slot's row)
    assert _same(rode.kc[:, 1:], stepped.kc[:, 1:])
    assert _same(rode.vc[:, 1:], stepped.vc[:, 1:])
    assert all(_same(got, want) for got, want in zip(rode.state,
                                                     stepped.state))
    kw, vw = (np.asarray(a) for a in rode.state)
    kw0, vw0 = (np.asarray(a) for a in caches.state)
    if name == "window":
        assert _same(rode.kc, caches.kc) and _same(rode.vc, caches.vc)
        ring = kw.shape[3]
        for got, was in ((kw, kw0), (vw, vw0)):
            moved = (got != was).any(axis=(2, 4))       # [layers, slots, R]
            want = np.zeros_like(moved)
            want[l, ACTIVE, POS[ACTIVE] % ring] = True
            assert (moved == want).all()
    else:
        assert _same(kw, kw0) and _same(vw, vw0)
        for got, was in ((rode.kc, caches.kc), (rode.vc, caches.vc)):
            moved = (np.asarray(got) != np.asarray(was)).any(
                axis=(2, 4))[:, 1:]
            want = np.zeros_like(moved)                 # [layers, pages, row]
            pages = np.asarray(bt)[np.arange(SLOTS), POS // PAGE]
            want[base + l, pages[ACTIVE] - 1, POS[ACTIVE] % PAGE] = True
            assert (moved == want).all()


def test_a_riding_walk_leaves_the_prompts_logits_and_rows_and_counts_its_riders(
        model):
    """The whole walk: the prompt's first token, its last row's logits and
    what every cache keeps of its rows are to the bit the riderless walk's;
    the riders' rows have logits of their own, and the share's routing
    counts the riders' assignments beside the prompt's."""
    cfg, fused, stack, caches, bt = model
    walk = serving._prefill_walk(cfg, stack)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, BUCKET), 0,
                                cfg.vocab_size)
    last = jnp.asarray([17, 23, 5, 99], jnp.int32)
    with jax.disable_jit():
        first, kept, logits, experts, none = walk(fused, tokens, LENGTH)
        first_r, kept_r, logits_r, experts_r, rode = walk(
            fused, tokens, LENGTH, caches,
            (bt, last, jnp.asarray(POS), jnp.asarray(ACTIVE)))
    assert none is None and int(first) == int(first_r)
    assert logits_r.shape == (1 + SLOTS, cfg.vocab_size)
    assert _same(logits_r[0], logits)
    assert kept.keys() == kept_r.keys() == {"pages", "ring"}
    for cache in kept:
        assert all(_same(got[:, :LENGTH], was[:, :LENGTH])
                   for got, was in zip(kept_r[cache], kept[cache]))
    # a rider's logits are its own row's: not the prompt's, not another's
    tails = np.asarray(logits_r[1:])[ACTIVE]
    assert np.isfinite(tails).all()
    assert len({int(t.argmax()) for t in tails} | {int(first)}) > 1
    # every sparse layer routed the riders' rows too (`_share_stats`' last)
    sparse = sum(hi - lo for name, lo, hi in cfg.segments()
                 if "router" in fused[name])
    assert int(experts_r[-1]) - int(experts[-1]) == \
        sparse * cfg.top_k_experts * int(ACTIVE.sum())
    # and the caches moved in the riding slots' rows alone
    for got, was in zip(jax.tree.leaves(rode), jax.tree.leaves(caches)):
        got, was = np.asarray(got), np.asarray(was)
        assert (got != was).any()
        if got.shape[1] == SLOTS:       # a ring: [layers, slots, ...]
            assert _same(got[:, ~ACTIVE], was[:, ~ACTIVE])

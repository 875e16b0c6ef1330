"""SDAR's generation by blocks through the serving engine, against the
benchmark's plain reference, at the adapter's REHEARSE widths on the CPU:
sections (c) the engine in float32, (d) bfloat16, tenants and temperature and
(e) the reference's reading of a served stream of tests/test_sdar.py (which
holds the block mask, the paged cache, the adapter and the counts, and says
what the tolerances are), in a file of its own so that neither is the suite's
longest (`--dist loadfile` keeps a file on one worker).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import control
from benchmark import reference_sdar as ref
from ray_tpu.ops import attention
from ray_tpu.serve.engine import Engine
from engine_pins import Spans, pinned
from test_sdar import ADAPTER, BF16, F32, GAP_TOL, _model, _params


def _engine(model, dtypes=F32, params=None, **kw):
    cfg = ADAPTER.build_config(model, dtypes, 128)
    params = _params(cfg) if params is None else params
    kw = dict(dict(n_slots=4, decode_chunk=8, page_size=16, n_pages=40), **kw)
    return Engine(jax.tree.map(jnp.copy, params), cfg, **kw)


def _serve(eng, ids, n, **kw):
    q, out = eng.submit(list(ids), n, **kw), []
    while (toks := q.get()) is not None:
        out += toks
    return out


def _prompt(L, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, L)]


# ---------------------------------------------------------------------------
# (c) through the engine, float32
# ---------------------------------------------------------------------------

CASES = [  # (block, steps, prompt length, tokens)
    (4, 2, 8, 12), (4, 2, 9, 12), (4, 2, 10, 9), (4, 2, 11, 8),   # r = 0..3
    (4, 2, 3, 7),          # a prompt shorter than a block
    (4, 2, 13, 2),         # max_tokens ends inside the first block
    (4, 2, 12, 10),        # and inside the second chunk's first block
    (4, 1, 10, 11), (4, 4, 9, 10), (8, 2, 13, 17), (2, 2, 7, 9)]


@pytest.fixture(scope="module")
def engines():
    made = {}

    def get(B, T):
        if (B, T) not in made:
            made[B, T] = _engine(_model(B, T))
        return made[B, T]

    yield get
    for eng in made.values():
        eng.stop()


@pytest.mark.parametrize("B,T,L,n", CASES)
def test_engine_generates_the_references_tokens(engines, B, T, L, n):
    """Prefill of the prompt's whole blocks, then blocks through the cache:
    the served stream is `reference.generate`'s token for token, `n` of them
    whatever block the request ends inside; every token's gap in the state
    the reference committed it from is 0 to GAP_TOL (the logits agree to the
    order of their sums), and the step each token is held to is the step the
    reference committed it in: the positions a step commits are its n_s most
    confident."""
    model, eng = _model(B, T), engines(B, T)
    ids = _prompt(L, seed=L)
    got = _serve(eng, ids, n)
    want, _, steps = ref.generate(eng.params, model, ids, n, with_logits=True)
    assert got == want and len(got) == n
    if T <= 2:
        gaps, held = ref.served_token_gaps(eng.params, model, ids, got,
                                           with_steps=True)
        assert max(gaps) <= GAP_TOL
        assert held == steps or max(gaps) == 0.0    # (a tie of two subsets)
    quota = [B // T + (s < B % T) for s in range(T)]
    assert all(steps.count(s + 1) <= quota[s] * -(-(L % B + n) // B)
               for s in range(T))


def test_an_engine_decodes_through_the_kernel_with_the_row_lag(
        kernel_in_interpret_mode):
    """The whole engine through the interpreted `paged_decode` kernel, the
    fused forward's call with the lag and the plain forward's without: the
    reference's tokens, for a prompt whose first block has nothing pending
    and whose blocks cross a page (pages of 16; positions 8..27)."""
    model = _model()
    eng = _engine(model, n_slots=2)
    try:
        ids = _prompt(10, seed=12)
        assert _serve(eng, ids, 18) == ref.generate(eng.params, model, ids,
                                                    18)
        paths = attention.attention_path_counts()
        assert paths["block_decode_pallas"] >= 2
    finally:
        eng.stop()


def test_a_slots_next_tenant_inherits_no_pending_block():
    """One slot: the second request is admitted into the slot the first just
    left, whose stream ended inside a chunk, so the slot's state on the
    device still holds the first's pending block: `poke` sets "nothing
    pending" with the opening block, and the second is served as the
    reference generates it (a pending block inherited would be written over
    the prompt's last whole block, positions 8..11 here, before the first
    forward reads them)."""
    model = _model()
    eng = _engine(model, n_slots=1)
    try:
        assert eng._last_d.shape == (1, 8)
        _serve(eng, _prompt(9, seed=31), 6)
        assert (np.asarray(eng._last_d)[0, :4] >= 0).all()  # a block pending
        ids = _prompt(14, seed=32)
        got = _serve(eng, ids, 11)
        assert got == ref.generate(eng.params, model, ids, 11)
    finally:
        eng.stop()


def test_a_first_blocks_pending_rows_leave_the_prompts_last_block():
    """The programs alone, one slot: a prompt of 10 (two whole blocks kept,
    a tail of 2) opens with nothing pending, `first` = -1 x 4, the tail, -1
    x 2; a chunk of two blocks then leaves the K and V of positions 0..7, the
    prompt's last whole block among them, as the prefill wrote them, to the
    bit (the first fused forward's pending rows, positions 4..7, go to the
    null page), writes the rows of 8..15, and leaves the second block
    pending."""
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.serving import build_programs
    cfg = ADAPTER.build_config(_model(), F32, 128)
    built = build_programs(cfg, 1, 8, 16, 5)
    params = fuse_qkv(_params(cfg), cfg)
    ids = _prompt(10, seed=3)
    pages = jnp.asarray([2, 0, 0, 0, 0, 0, 0, 0], jnp.int32)
    caches, first, _ = built.prefill(
        params, built.empty(), pages,
        jnp.asarray([ids + [0] * 54], jnp.int32), 10, 0.0, 0,
        jnp.zeros(2, jnp.uint32), None)
    assert np.asarray(first).tolist() == [-1] * 4 + ids[8:] + [-1] * 2
    kept = [np.asarray(c)[:, 2] for c in (caches.kc, caches.vc)]
    last, pos = built.poke(jnp.zeros((1, 8), jnp.int32),
                           jnp.zeros(1, jnp.int32), 0, first, 8)
    caches, last, pos, out, _ = built.decode(
        params, caches, pages[None], last, pos, jnp.ones(1, bool),
        jnp.zeros(1, jnp.float32), jnp.zeros(1, jnp.int32),
        jnp.zeros((1, 2), jnp.uint32))
    for before, cache in zip(kept, (caches.kc, caches.vc)):
        after = np.asarray(cache)[:, 2]
        assert np.array_equal(before[:, :, :8], after[:, :, :8])
        assert (np.abs(after[:, :, 8:] - before[:, :, 8:]).max(axis=(0, 1, 3))
                > 0).all()
    out, last = np.asarray(out)[0], np.asarray(last)[0]
    assert out[:2].tolist() == ids[8:] and int(pos[0]) == 16
    assert last.tolist() == out[4:].tolist() + [-1] * 4


def test_the_engines_counters_and_paths_say_blocks(engines):
    eng = engines(4, 2)
    before = eng.counters()
    _serve(eng, _prompt(10), 9)        # tail 2; 9 tokens: two chunks of 8
    c = eng.counters()
    assert c["block"] == 4
    # two chunks of two blocks of two forwards: no forward is the commit's
    assert c["denoise_forwards"] - before["denoise_forwards"] == 2 * 2 * 2
    # every block but the slot's first committed the block before it
    assert c["commits_rode"] - before["commits_rode"] == 3
    assert c["block_tokens"] - before["block_tokens"] == 16
    assert c["tail_tokens"] - before["tail_tokens"] == 2
    assert c["decode_useful_tokens"] - before["decode_useful_tokens"] == 9
    paths = attention.attention_path_counts()
    assert paths["block_fwd_reference"] and paths["block_decode_reference"]
    programs = eng._programs
    assert programs.block == 4      # of two forwards each: counted above
    assert not programs.takes_riders and not programs.adopts
    with pytest.raises(NotImplementedError, match="block_length > 1"):
        eng.submit_prefilled(None, None, 8, 0, 4)
    text = eng.lowered_decode_text()
    assert "unmask" in eng._programs.decode.lower(
        *eng.decode_shapes()).as_text(debug_info=True)
    assert text.count("stablehlo.while") >= 2


# ---------------------------------------------------------------------------
# (d) bfloat16, tenants, temperature
# ---------------------------------------------------------------------------

def test_a_dispatch_span_says_what_the_chunks_blocks_are(engines):
    """A prompt of 10 (a tail of 2) served 9 tokens is two chunks of 8
    positions: each two blocks of two forwards, the widest of 2 x 4 slots x 4
    rows, 8 positions covered in the one live slot; the first chunk's first
    block opens the slot and commits nothing, every other block commits the
    one before it."""
    eng = engines(4, 2)
    with Spans() as spans:
        assert len(_serve(eng, _prompt(10), 9)) == 9
    chunks = spans.named("serve.engine.decode_dispatch")
    assert [[a[k] for k in ("blocks", "forwards", "rows", "committed",
                            "commits_rode")] for a in chunks] == [
        [2, 4, 32, 8, 1], [2, 4, 32, 8, 2]]


def test_a_request_is_served_alike_alone_after_another_and_beside_idle_slots(
        engines):
    eng = engines(4, 2)
    ids = _prompt(11, seed=5)
    alone = _serve(eng, ids, 14)
    # every slot has had a tenant by now; then beside three others
    others = [eng.submit(_prompt(9 + i, seed=20 + i), 20) for i in range(3)]
    beside = _serve(eng, ids, 14)
    for q in others:
        while q.get() is not None:
            pass
    assert alone == beside == _serve(eng, ids, 14)


def test_a_temperature_draws_one_stream_a_seed_whatever_the_slot(engines):
    eng = engines(4, 2)
    ids = _prompt(10, seed=6)
    kw = dict(temperature=0.9, top_k=8, seed=77)
    first = _serve(eng, ids, 12, **kw)
    blocker = eng.submit(_prompt(12, seed=7), 40)       # takes a slot
    second = _serve(eng, ids, 12, **kw)
    while blocker.get() is not None:
        pass
    assert first == second
    assert first != _serve(eng, ids, 12, **dict(kw, seed=78))
    assert first != _serve(eng, ids, 12)                # greedy


@pytest.mark.timeout(240)
def test_bfloat16_stays_inside_the_tolerance_and_int8_weights_do_not():
    """The program in bfloat16 against the float32 reference on the same
    weights: the mean gap of its served tokens stays under BF16_MEAN_TOL; the
    same program on weights rounded to int8 (benchmark/control.py's
    rounding, one scale an output channel) reads above it. The limit is
    written between the two readings of this size (0.0006 and 0.024 over
    these prompts), as the cell's is between its two."""
    BF16_MEAN_TOL = 0.004
    model = _model()
    cfg = ADAPTER.build_config(model, BF16, 128)
    params = _params(cfg)
    prompts = [_prompt(L, seed=40 + L) for L in (9, 10, 11, 12, 17, 22)]

    def mean_gap(weights):
        eng = _engine(model, BF16, weights)
        try:
            gaps = [g for ids in prompts for g in ref.served_token_gaps(
                params, model, ids, _serve(eng, ids, 24))]
        finally:
            eng.stop()
        return sum(gaps) / len(gaps)

    sound, coarse = mean_gap(params), mean_gap(control.rounded(params))
    print("bfloat16", sound, "int8", coarse)
    assert sound <= BF16_MEAN_TOL < coarse


# ---------------------------------------------------------------------------
# (e) the reference's reading of a served stream
# ---------------------------------------------------------------------------

def test_served_token_gaps_finds_the_steps_and_a_wrong_commit(engines):
    model, eng = _model(), engines(4, 2)
    params = eng.params
    ids = _prompt(9, seed=9)
    toks, logits, steps = ref.generate(params, model, ids, 15,
                                       with_logits=True)
    gaps, held = ref.served_token_gaps(params, model, ids, toks,
                                       with_steps=True)
    assert gaps == [0.0] * 15 and held == steps
    assert {1, 2} == set(steps)
    # the logits a token was committed from are its state's: their argmax
    assert [int(np.argmax(row)) for row in logits] == toks
    # benchmark/control.py reads its greedy tokens off `logits_last`
    assert control.greedy_by_reference(ref, params, model, ids, 15) == toks
    # one wrong position committed in step 1 (the LEAST confident of the
    # block's masks, with its step-1 candidate): the stream that follows is
    # another, and no subset explains it
    B = 4
    first = np.asarray(ref.forward(
        params, model, ids[:8] + [ids[8]] + [model["mask_id"]] * 3))[8:]
    cand = first.argmax(-1)
    conf = np.exp(first - first.max(-1, keepdims=True))
    conf = (conf / conf.sum(-1, keepdims=True))[np.arange(B), cand]
    worst = 1 + int(np.argmin(conf[1:]))
    assert held[worst - 1] == 2
    wrong = list(toks)
    wrong[worst - 1] = int(cand[worst])
    if wrong == toks:       # its candidate did not move in step 2
        wrong[worst - 1] = (toks[worst - 1] + 1) % 255
    bad = ref.served_token_gaps(params, model, ids, wrong)
    assert max(bad) > 0.05 and sum(bad) > 100 * GAP_TOL


def test_what_the_engine_counts_is_what_the_parent_counted(engines):
    """The keys of `Engine.counters()`: tests/engine_pins.py's row."""
    assert pinned(engines(4, 2), "sdar")

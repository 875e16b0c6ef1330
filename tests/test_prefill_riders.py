"""The live slots ride the wide prefills (`serve/engine.py`, PR 41): where a
prompt leaves `n_slots` rows of its bucket free, a riding rung's program
(`rung_rides`: the octave under `max_seq`) carries ONE decode step of every
live slot in those rows. On the CPU at the adapters' rehearsal widths in
float32, a dense and a sparse stack and, since PR 58, the three hybrids
(Mamba-1 over a dense feed-forward; Mamba-2, one group, over a share of the
experts; the stack of one-part layers, Mamba-2 with groups) and, since PR 60,
LFM2's stack of short-convolution layers beside attention and, since PR 61,
dots' stack of latent-attention (MLA) layers, whose riders take the absorbed
form's step against the arena of latent rows, and, since PR 64, the two
stacks of window and full attention layers (MiMo's: a sink, a key in two
parts; Laguna's: a gate a head, more window heads than full ones, YaRN),
whose riders write a row to their slot's ring or page and read the ring alone
or the live pages: every stream is
what the same engine serves with nobody riding, and the plain reference's
greedy tokens; the counters and the admit spans agree; a burst of admissions
moves the riders a step each; a rider that finishes on a riding step frees its
slot, its pages and its slot's recurrent state (or windows) at once, and the
next admission overwrites them. (An indexed stack takes nobody, and every
program that takes nobody lowers to the parent's text:
tests/test_parents_programs.py.)

Tolerance: program and reference compute the same mathematics in float32 and
differ in the order of their sums; LOGIT_TOL is tests/test_prefill_ladder.py's.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.serve.engine import (_DEPTH, Engine, prefill_widths,
                                  rung_rides)
import test_dots
import test_granite
import test_laguna
import test_lfm2
import test_mimo
import test_nemotron_h
from engine_pins import Spans as _Spans
from test_prefill_ladder import F32, LOGIT_TOL, _tiny, _tokens

MAX_SEQ, SLOTS, CHUNK = 256, 4, 4
# (prompt tokens, max_tokens, temperature): rungs 256 and 128 ride, 64 and 32
# do not; the prompt of 253 leaves its bucket three rows, so nobody rides it.
ASKS = [(200, 21, 0.0), (131, 14, 0.8), (253, 3, 0.0), (100, 18, 0.0),
        (230, 12, 0.8), (40, 11, 0.0), (180, 25, 0.0), (124, 7, 0.8),
        (222, 16, 0.0), (150, 10, 0.0)]


def _until(cond, seconds=60.0):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.002)


def _drain(q, seconds=120.0):
    out = []
    while (item := q.get(timeout=seconds)) is not None:
        out.extend(item)
    return out


# The hybrids beside test_prefill_ladder's "hybrid" (Jamba: Mamba-1 over a
# dense feed-forward), each as its own model's tests build it: the adapter's
# rehearsal widths, weights that decide.
HYBRIDS = {"mamba2": test_granite, "one-part": test_nemotron_h}
# ... and, since PR 60, the stack of short-convolution layers beside attention
# (LFM2: a slot keeps a window a conv layer and pages for the rest).
# ... and, since PR 61, the stack of latent-attention layers (dots: a slot
# keeps ONE row a position a layer, and the riders read them absorbed).
# ... and, since PR 64, the two stacks of window and full attention layers
# (a slot keeps a ring a window layer and pages for the full ones; the
# rehearsal's window of 16 has wrapped at every position these prompts reach).
MIXED = {"mixed": test_mimo, "mixed-gated": test_laguna}
STACKS = ["dense", "sparse", "hybrid", *HYBRIDS, "conv", "latent", *MIXED]


def _model(kind):
    if kind == "conv":
        return test_lfm2._tiny(max_seq=MAX_SEQ)
    if kind == "latent":
        return test_dots._tiny(max_seq=MAX_SEQ)
    if kind in MIXED:
        return MIXED[kind]._tiny(max_seq=MAX_SEQ)
    if kind not in HYBRIDS:
        return _tiny(kind, MAX_SEQ)
    tests = HYBRIDS[kind]
    cfg = tests.ADAPTER.build_config(tests.MODEL, F32, MAX_SEQ)
    return tests.ADAPTER, tests.MODEL, cfg, tests._params(cfg)


def _build(kind, n_slots=SLOTS):
    adapter, model, cfg, params = _model(kind)
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=n_slots,
                 decode_chunk=CHUNK, page_size=16,
                 n_pages=n_slots * MAX_SEQ // 16 + 1)   # pages never bind
    _until(lambda: sorted(eng._warm) == eng.buckets or eng.warm_error)
    assert not eng.warm_error, eng.warm_error
    return adapter, model, params, eng


def _serve(eng, asks=ASKS):
    streams = [eng.submit(_tokens(n, seed), m, temperature=t, top_k=16,
                          seed=seed)
               for seed, (n, m, t) in enumerate(asks)]
    return [_drain(q) for q in streams]


@pytest.fixture(scope="module", params=STACKS)
def served(request):
    """One engine a stack: `ASKS` served with the live slots riding, then by
    the same engine, the same programs, with nobody marked as riding."""
    adapter, model, params, eng = _build(request.param)
    try:
        before = eng.counters()
        with _Spans() as spans:
            riding = _serve(eng)
        after = eng.counters()
        eng._ride_plan = lambda free_rows: []
        plain = _serve(eng)
        last = eng.counters()
        assert eng.error is None, eng.error
    finally:
        eng.stop()
    delta = {k: after[k] - before[k] for k in (
        "rider_tokens", "rider_steps", "decode_useful_tokens", "admitted")}
    return dict(adapter=adapter, model=model, params=params, eng=eng,
                riding=riding, plain=plain, spans=spans, delta=delta,
                rode_plain=last["rider_tokens"] - after["rider_tokens"])


def test_every_stream_is_what_the_engine_serves_with_nobody_riding(served):
    """Greedy and sampled alike: a rider's token is its slot's next decode
    step's, at the same position, key and temperature."""
    assert [len(s) for s in served["riding"]] == [m for _, m, _ in ASKS]
    assert served["riding"] == served["plain"]
    assert served["delta"]["rider_tokens"] > 0 == served["rode_plain"]


def test_the_greedy_streams_are_the_plain_references(served):
    reference = served["adapter"].reference()
    for seed, (n, _, temp) in enumerate(ASKS):
        if temp:
            continue
        gaps = reference.served_token_gaps(
            served["params"], served["model"], _tokens(n, seed),
            served["riding"][seed])
        assert max(gaps) < LOGIT_TOL, (seed, gaps)


def test_the_admit_spans_riders_add_up_to_the_counters(served):
    """`riders` is on the admit span of every riding rung's prefill and on no
    other; their sum is `rider_tokens`, and with the chunks' `useful` every
    token after a request's first is accounted for."""
    admits = served["spans"].named("serve.engine.admit")
    assert len(admits) == served["delta"]["admitted"] == len(ASKS)
    for a in admits:
        assert ("riders" in a) == rung_rides(MAX_SEQ, SLOTS, a["bucket"])
        if a["bucket"] - a["prompt_tokens"] < SLOTS:
            assert not a.get("riders")
        elif "riders" in a:
            assert a["riders"] == a["decoding"]
    rode = [a["riders"] for a in admits if a.get("riders")]
    assert sum(rode) == served["delta"]["rider_tokens"]
    assert len(rode) == served["delta"]["rider_steps"]
    useful = sum(c["useful"] for c in
                 served["spans"].named("serve.engine.decode_dispatch"))
    assert useful == served["delta"]["decode_useful_tokens"]
    assert useful + sum(rode) == sum(m - 1 for _, m, _ in ASKS)


def test_the_reader_gives_the_riders_share_and_none_without_them(
        served, monkeypatch):
    """benchmark/layer_metrics/decode_rider_share_pct.py on this run's spans,
    and on the same run as a program that has no riders records it."""
    from benchmark import program_trace
    from benchmark.run import HERE, load_reader
    read = load_reader(HERE, "layer_metrics", "decode_rider_share_pct")
    spans = [program_trace.Span(name, i, i + 1, args)
             for i, (name, args) in enumerate(served["spans"].seen)]
    t = program_trace.ProgramTrace(spans, [], [])
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    d = served["delta"]
    assert read({}) == pytest.approx(100.0 * d["rider_tokens"] / (
        d["rider_tokens"] + d["decode_useful_tokens"]))
    older = program_trace.ProgramTrace(
        [program_trace.Span(s.name, s.start, s.end, {
            k: v for k, v in s.args.items() if k != "riders"})
         for s in spans], [], [])
    monkeypatch.setattr(program_trace, "load", lambda run: older)
    assert read({}) is None
    monkeypatch.setattr(program_trace, "load", lambda run: None)
    assert read({}) is None


def test_the_manifest_entry_of_the_riders_share():
    from benchmark.tests.test_benchmark import load
    from test_engine_trace import ROOT
    entry = next(m for m in load(ROOT, "BENCHMARK.json")["per_layer"]
                 if m["name"] == "decode_rider_share_pct")   # later PRs after
    assert entry == dict(
        name="decode_rider_share_pct", unit="%", better="higher",
        source="program_counter", layer="scheduler (serve)",
        moves="batch_tokens_per_s",
        workloads=["serve-batch", "serve-batch-olmoe"])


@pytest.fixture(scope="module", params=["dense", "hybrid", "conv", "latent",
                                        "mixed"])
def held(request):
    """An engine of three slots (a dense stack's; a hybrid's, whose slots hold
    a recurrent state too; a conv stack's, whose slots hold a window a conv
    layer; a latent stack's, whose pages hold latent rows; a mixed stack's,
    whose slots hold a ring a window layer) whose emitter the
    test holds at its first chunk, so that the loop stands with `_DEPTH`
    chunks in flight and nothing moves but what the test submits."""
    _, _, _, eng = _build(request.param, n_slots=3)
    gate = threading.Event()
    fetch = eng._fetch

    def hold(out_d):
        gate.wait(60)
        return fetch(out_d)

    eng._fetch = hold
    yield eng, gate
    gate.set()
    eng.stop()


def test_a_burst_moves_the_riders_a_step_an_admission_and_a_finished_rider_frees_its_slot_at_once(
        held):
    """One burst of three admissions (submitted under the loop's own lock, so
    one round of `_admit` sees all three) beside a live request A: A rides all
    three prefills, three steps; B, admitted first with two tokens to make,
    rides the second prefill, finishes on it and frees its slot and pages
    there and then, so the third of the burst is admitted into B's slot in
    the same round, on a three-slot engine, and its prefill writes the slot's
    recurrent state over what B's riding step left there. Every stream is
    what the engine serves one request at a time."""
    eng, gate = held
    asks = [(150, 40), (140, 2), (200, 6), (170, 5)]       # A, B, C, D
    gate.set()
    alone = [_drain(eng.submit(_tokens(n, 20 + seed), m))
             for seed, (n, m) in enumerate(asks)]
    _until(lambda: not eng._active.any() and eng._in_flight == 0)
    gate.clear()
    before = eng.counters()
    a = eng.submit(_tokens(150, 20), 40)
    _until(lambda: eng._in_flight == _DEPTH)    # the loop stands, A is live
    slot_a = int(np.flatnonzero(eng._active)[0])
    pos_a = int(eng._pos[slot_a])
    assert pos_a == 150 + _DEPTH * CHUNK
    # B, C, D in one round; D fits only because B's finish frees a slot.
    with eng._cv:
        rest = [eng.submit(_tokens(n, 21 + i), m)
                for i, (n, m) in enumerate(asks[1:])]
    # The emitter is handed a round's first tokens when the round is over;
    # it stands at A's first chunk, the second queued behind it.
    _until(lambda: eng._emit_q.qsize() == _DEPTH - 1 + 3)
    now = eng.counters()
    assert now["admitted"] - before["admitted"] == 4
    assert now["decode_chunks"] - before["decode_chunks"] == _DEPTH
    assert int(eng._pos[slot_a]) == pos_a + 3           # three riding steps
    # A rode B's, C's and D's prefills; B rode C's; C rode D's.
    assert now["rider_tokens"] - before["rider_tokens"] == 1 + 2 + 2
    assert now["rider_steps"] - before["rider_steps"] == 3
    assert eng._active.all() and eng.pool.in_use() == sum(
        eng.pool.pages_for(n, m) for n, m in (asks[0], asks[2], asks[3]))
    gate.set()
    assert [_drain(a)] + [_drain(q) for q in rest] == alone


RIDES = [   # (max_seq, n_slots, width) -> rides
    ((4096, 16, 4096), True), ((4096, 16, 3584), True),
    ((4096, 16, 2048), True), ((4096, 16, 1024), False),
    ((4096, 16, 32), False), ((8192, 16, 4096), True),
    ((8192, 16, 2048), False), ((2048, 16, 1536), True),
    ((3000, 8, 2048), True), ((3000, 8, 1024), False),
    ((16, 2, 16), True), ((16, 16, 16), False), ((128, 64, 64), False),
]


@pytest.mark.parametrize("case,rides", RIDES,
                         ids=["-".join(map(str, c)) for c, _ in RIDES])
def test_rung_rides_in_the_octave_under_max_seq_where_the_slots_fit(case,
                                                                    rides):
    assert rung_rides(*case) is rides


@pytest.mark.parametrize("max_seq", [2048, 4096, 8192])
def test_the_riding_rungs_are_the_top_octaves(max_seq):
    ladder = prefill_widths(max_seq)
    riding = [w for w in ladder if rung_rides(max_seq, 16, w)]
    assert riding == [w for w in ladder if w >= max_seq // 2]
    assert len(riding) == (3 if max_seq == 2048 else 5)

"""serve/engine.py's dispatch loop: the decode chunks it keeps in flight
(`_DEPTH`), the wait a submit ends, what `chunks_ahead` counts, that a
request's tokens do not depend on who shared its chunks, and that the loop and
the emitter leave when told to. A tiny engine on the CPU; the emitter is held,
slowed or broken at `Engine._fetch`, its one wait for the device."""

import sys
import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.serve.engine import _DEPTH, Engine
from test_tracing import _Profiled


def _build(n_slots=4):
    from ray_tpu.models.llama import LlamaConfig, init_params
    cfg = LlamaConfig.tiny()
    eng = Engine(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                 n_slots=n_slots, decode_chunk=4, page_size=16)
    while sorted(eng._warm) != sorted(eng.buckets):
        assert not eng.warm_error, eng.warm_error
        time.sleep(0.05)
    return eng


@pytest.fixture(scope="module")
def engine():
    eng = _build()
    yield eng
    eng.stop()


def _until(cond, seconds=20.0):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.002)


def _drain(q, seconds=60.0):
    """The stream's items up to its terminating None."""
    got = []
    while (item := q.get(timeout=seconds)) is not None:
        got.append(item)
    return got


class _Fetch:
    """`with _Fetch(engine, fn):` puts `fn(fetch, out_d)` in the place of the
    emitter's fetch of a chunk; with no `fn`, every fetch is held until
    `release()`."""

    def __init__(self, eng, fn=None):
        self.eng, self.fn = eng, fn or self._hold
        self.gate = threading.Event()

    def _hold(self, fetch, out_d):
        assert self.gate.wait(60), "never released"
        return fetch(out_d)

    def release(self):
        self.gate.set()

    def __enter__(self):
        fetch = self.eng._fetch
        self.eng._fetch = lambda out_d: self.fn(fetch, out_d)
        return self

    def __exit__(self, *exc):
        self.release()
        del self.eng._fetch


def _delta(eng, before, keys=("admitted", "admit_chunks_ahead",
                              "decode_chunks")):
    now = eng.counters()
    return {k: now[k] - before[k] for k in keys}


def test_loop_stops_at_the_depth_and_admits_an_arrival_meanwhile(
        engine, tmp_path):
    """With the emitter held at its first fetch, the loop dispatches `_DEPTH`
    chunks and no more; a request submitted then is admitted and placed (its
    prefill dispatched) behind those chunks, before anything is released."""
    assert engine._emit_q.maxsize == 0          # no `put` can block the loop
    before = engine.counters()
    with _Profiled(tmp_path) as prof, _Fetch(engine) as emitter:
        a = engine.submit(list(range(1, 9)), 40)
        _until(lambda: engine._in_flight == _DEPTH)
        time.sleep(0.3)                 # a loop that ran ahead would, now
        assert _delta(engine, before)["decode_chunks"] == _DEPTH
        b = engine.submit(list(range(1, 30)), 6)
        _until(lambda: engine._active.sum() == 2)
        # b's admit span has closed: its "first" item is queued behind the
        # `_DEPTH - 1` chunks the held emitter has not taken
        _until(lambda: engine._emit_q.qsize() == _DEPTH)
        assert _delta(engine, before) == {
            "admitted": 2, "admit_chunks_ahead": _DEPTH,
            "decode_chunks": _DEPTH}
        assert engine._in_flight == _DEPTH and b.empty()
        emitter.release()
        got_a, got_b = _drain(a), _drain(b)
    assert sum(map(len, got_a)) == 40 and sum(map(len, got_b)) == 6
    assert engine._in_flight == 0

    ahead = {s["prompt_tokens"]: (s["chunks_ahead"], end) for _, _, end, s in
             prof.events("serve.engine.admit")}
    assert ahead[8][0] == 0 and ahead[29][0] == _DEPTH
    dispatched = [start for _, start, _, _ in
                  prof.events("serve.engine.decode_dispatch")]
    fetched = [end for n, _, end, s in prof.events("serve.engine.emit")
               if n == "serve.engine.emit" and s["kind"] == "chunk"]
    # b's admit span closed (its prefill and poke dispatched) with `_DEPTH`
    # chunks dispatched and none fetched.
    assert sum(t < ahead[29][1] for t in dispatched) == _DEPTH
    assert ahead[29][1] < min(fetched)
    assert len(dispatched) == len(fetched) == len(
        [1 for n, *_ in prof.events("serve.engine.emit_block")])


def test_chunks_ahead_is_0_on_an_idle_engine_and_at_most_the_depth(
        engine, tmp_path):
    rng = np.random.default_rng(33)
    before = engine.counters()
    with _Profiled(tmp_path) as prof:
        assert sum(map(len, _drain(engine.submit([1, 2, 3], 9)))) == 9
        streams = []
        for _ in range(14):
            n, m = int(rng.integers(3, 60)), int(rng.integers(2, 30))
            streams.append((m, engine.submit(list(range(1, 1 + n)), m)))
            time.sleep(float(rng.uniform(0, 0.004)))
        for m, q in streams:
            assert sum(map(len, _drain(q))) == m
    ahead = [s["chunks_ahead"] for _, _, _, s in
             prof.events("serve.engine.admit")]
    assert len(ahead) == 15 and ahead[0] == 0
    assert all(0 <= n <= _DEPTH for n in ahead)
    assert _delta(engine, before)["admit_chunks_ahead"] == sum(ahead)


def test_slot_idle_is_the_time_a_freed_slot_stood_empty(tmp_path):
    """Two requests through the one slot of an engine: the first tenant reads
    0; the second reads the time since the first's last chunk was dispatched
    (where the slot is freed), which holds the pause this test makes between
    the first stream's end and the second submit and is held by the profile's
    own clock from that dispatch's start to the second admit's. The counter
    is the spans' sum."""
    eng = _build(n_slots=1)
    try:
        before = eng.counters()
        with _Profiled(tmp_path) as prof:
            assert sum(map(len, _drain(eng.submit([1, 2, 3], 9)))) == 9
            t_drained = time.monotonic()
            time.sleep(0.05)
            t_submit = time.monotonic()
            assert sum(map(len, _drain(eng.submit([4, 5], 6)))) == 6
        d = _delta(eng, before, ("admitted", "slot_idle_s_sum"))
    finally:
        eng.stop()
    first, second = prof.events("serve.engine.admit")
    assert first[3]["slot_idle_us"] == 0
    idle_us = second[3]["slot_idle_us"]
    assert idle_us >= (t_submit - t_drained) * 1e6 >= 50_000
    freed_in = [start for _, start, end, _ in
                prof.events("serve.engine.decode_dispatch")
                if end <= second[1]][-1]
    assert idle_us <= (second[1] - freed_in) / 1e3
    assert d["admitted"] == 2
    assert 0 <= d["slot_idle_s_sum"] * 1e6 - idle_us <= 3 * 2


def test_decoding_on_an_admit_span_is_the_slots_live_at_that_instant(
        engine, tmp_path):
    """0 on an idle engine, n after n admissions that have not finished (the
    emitter is held, so none does), and `admit_decoding_slots` is its sum."""
    before = engine.counters()
    with _Profiled(tmp_path) as prof, _Fetch(engine) as emitter:
        streams = []
        for n in range(3):
            streams.append(engine.submit(list(range(1, 6 + n)), 40))
            _until(lambda: engine._active.sum() == n + 1)
        emitter.release()
        for q in streams:
            assert sum(map(len, _drain(q))) == 40
    admits = [s for _, _, _, s in prof.events("serve.engine.admit")]
    assert [a["decoding"] for a in admits] == [0, 1, 2]
    assert _delta(engine, before, ("admitted", "admit_decoding_slots")) == {
        "admitted": 3, "admit_decoding_slots": 0 + 1 + 2}
    # every slot here has had a tenant in an earlier test of this module or
    # is a first tenant's: the refill time is never negative
    assert all(a["slot_idle_us"] >= 0 for a in admits)


def test_idle_span_is_open_only_while_no_slot_is_live_and_states_are_disjoint(
        engine, tmp_path):
    """A request alone, a pause, three at once with the emitter slowed, a
    pause, a request alone. `serve.engine.idle` covers the pauses, encloses
    the admission round that ends each (arrivals that one round takes together
    would all be inside it) and nothing of the time from there to the start
    of the dispatch that frees the last slot: a busy engine opens none. The
    stand the trace began in left no event (a span records when it closes, if
    it opened in the trace), which is what `engine_trace.edge_idles` is for.
    On the loop thread the four states' self times never overlap."""
    from benchmark import engine_trace, program_trace

    def slow(fetch, out_d):
        time.sleep(0.003)
        return fetch(out_d)

    with _Profiled(tmp_path):
        time.sleep(0.03)
        assert sum(map(len, _drain(engine.submit([1, 2, 3], 9)))) == 9
        time.sleep(0.03)
        with _Fetch(engine, slow):
            streams = [engine.submit(list(range(1, 9)), 30)]
            _until(lambda: engine._active.any())    # its own admission round
            streams += [engine.submit(list(range(1, 10 + k)), 30)
                        for k in range(2)]
            for q in streams:
                assert sum(map(len, _drain(q))) == 30
        time.sleep(0.03)
        assert sum(map(len, _drain(engine.submit([7, 8], 5)))) == 5
    t = program_trace.load_path(str(tmp_path))
    idles = t.named(engine_trace.IDLE)
    admits = t.named(engine_trace.ADMIT)
    dispatches = t.named("serve.engine.decode_dispatch")
    assert len(admits) == 5 and len(idles) == 2
    assert [a.args["decoding"] for a in admits] == [0, 0, 1, 2, 0]

    def last_dispatch_before(span):
        return [d for d in dispatches if d.start < span.start][-1]

    # a stand ends with the admission round that made a slot live
    live = [(admits[0].end, last_dispatch_before(admits[1]).start),
            (idles[0].end, last_dispatch_before(admits[4]).start),
            (idles[1].end, dispatches[-1].start)]
    own = engine_trace.self_intervals(t, engine_trace.IDLE)
    assert len(own) == 4            # each stand, either side of its admit
    for s, e in own:
        assert all(e <= a or s >= b for a, b in live), (s, e, live)
    # the stand that an arrival ends encloses its admit and no dispatch
    for idle, admit in zip(idles, (admits[1], admits[4])):
        assert idle.start <= admit.start and admit.end <= idle.end
        assert not [d for d in dispatches if idle.start < d.start < idle.end]
    assert all(e - s >= 0.03 * 1e9 for s, e in own[::2])    # the two pauses
    # the trace began and ended in a stand: its first loop span is an admit
    # that found no slot decoding, its last the wait after the last dispatch
    first, last = min(s.start for s in t.spans), max(s.end for s in t.spans)
    blocks = t.named("serve.engine.emit_block")
    assert engine_trace.edge_idles(t, (first - 5.0, last + 7.0)) == [
        (first - 5.0, admits[0].start), (blocks[-1].end, last + 7.0)]
    every = sorted(iv for state in engine_trace.STATES
                   for iv in engine_trace.self_intervals(t, state))
    assert all(b[0] >= a[1] for a, b in zip(every, every[1:]))
    assert len(blocks) == len(dispatches)


def _mix(rng, n):
    """(ids, max_tokens, sampling) a request: half greedy, half sampled."""
    asks = []
    for i in range(n):
        ids = [int(t) for t in rng.integers(1, 256, int(rng.integers(3, 70)))]
        sampling = {} if i % 2 else {
            "temperature": 0.8, "top_k": 5, "seed": int(rng.integers(1 << 30))}
        asks.append((ids, int(rng.integers(1, 40)), sampling))
    return asks


def test_a_request_streams_the_same_tokens_alone_or_mid_pipeline(engine):
    """A seeded mix on 4 slots and 16 pages: requests join while chunks are
    in flight (the emitter is slowed, so the pipeline stands at its depth),
    leave, and free pages the later ones wait for. Each stream is what the
    same request streams alone on the same engine."""
    rng = np.random.default_rng(2033)
    asks = _mix(rng, 12)
    assert sum(engine.pool.pages_for(len(ids), m) for ids, m, _ in asks) \
        > 2 * (engine.n_pages - 1)              # pages are reused
    before = engine.counters()

    def slow(fetch, out_d):
        time.sleep(0.004)
        return fetch(out_d)

    with _Fetch(engine, slow):
        streams = []
        for ids, m, sampling in asks:
            streams.append(engine.submit(ids, m, **sampling))
            time.sleep(float(rng.uniform(0, 0.01)))
        together = [_drain(q) for q in streams]
    assert _delta(engine, before)["admit_chunks_ahead"] > 0   # joins mid-pipeline
    assert engine.pages_in_use() == 0

    for (ids, m, sampling), got in zip(asks, together):
        alone = _drain(engine.submit(ids, m, **sampling))
        assert len(got[0]) == 1                 # "first" precedes the chunks
        assert all(1 <= len(c) <= engine.chunk for c in got[1:])
        flat = [t for c in got for t in c]
        assert flat == [t for c in alone for t in c]
        assert len(flat) == min(m, engine.mcfg.max_seq - len(ids))


def test_submits_from_many_threads_are_all_served_and_counted(engine):
    """More submitting threads than cores under a short switch interval: no
    submit is lost to the loop's wait, and the count in flight comes back to 0."""
    before = engine.counters()
    done, errors = [], []

    def client(k):
        try:
            for j in range(4):
                m = 2 + (k + j) % 7
                got = _drain(engine.submit([1 + k, 2 + j, 3], m))
                assert sum(map(len, got)) == m
            done.append(k)
        except Exception as e:          # reported by the test's own thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        clients = [threading.Thread(target=client, args=(k,))
                   for k in range(16)]
        for t in clients:
            t.start()
        for t in clients:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and len(done) == 16, errors
    d = _delta(engine, before)
    assert d["admitted"] == 16 * 4
    assert 0 <= d["admit_chunks_ahead"] <= _DEPTH * d["admitted"]
    assert engine._in_flight == 0 and engine.pages_in_use() == 0


def _small_mix(rng, n, least):
    """`_mix` cut to requests of 4 pages at most, `least` tokens at least: 4
    slots' worth always fit the pool, so only a slot is ever waited for."""
    return [(ids[:30], least + m % (31 - least), sampling)
            for ids, m, sampling in _mix(rng, n)]


class _Served:
    """What a replica hosts: `LLMServer.__call__` without the HTTP body, a
    generator over one request's stream. The test puts its engine here."""

    engine = None

    def generate(self, ids, max_tokens, sampling):
        q = self.engine.submit(ids, max_tokens, **sampling)
        while (item := q.get(timeout=60)) is not None:
            yield item


class _Clients:
    """`serve.replica.Replica` in this process over `eng`, with
    `max_ongoing_requests` = `n_slots` as `serve/llm.py` deploys it, and one
    thread a request iterating `handle_request_streaming` as the runtime's
    producer threads do. `send` returns when the engine has the request, so
    the order of the calls is the order of the submits."""

    def __init__(self, eng):
        import cloudpickle

        from ray_tpu.serve.replica import Replica
        _Served.engine = self.eng = eng
        self._dumps = cloudpickle.dumps
        self.replica = Replica(self._dumps(_Served), self._dumps(((), {})),
                               "llm", max_ongoing=eng.n_slots)
        self.threads, self.got = [], {}

    def send(self, k, ids, max_tokens, sampling):
        def client():
            self.got[k] = list(self.replica.handle_request_streaming(
                "generate", self._dumps(((ids, max_tokens, sampling), {}))))

        seen = self.eng._next_rid
        self.threads.append(threading.Thread(target=client))
        self.threads[-1].start()
        _until(lambda: self.eng._next_rid == seen + 1)

    def join(self):
        for t in self.threads:
            t.join(60)
        assert not any(t.is_alive() for t in self.threads)
        assert self.replica._ongoing == 0
        return [self.got[k] for k in sorted(self.got)]


def test_three_times_the_slots_stream_through_a_replica_and_queue_at_the_engine(
        engine, tmp_path):
    """Nothing between the router and `Engine.submit` holds a stream back: 12
    requests on 4 slots are all at the engine at once, 8 of them in `_pending`
    (the emitter is held, so no slot frees), the replica counts all 12 for the
    router, and they are admitted in the order they were submitted. The admit
    spans say how many still waited, `admit_pending` is their sum, and every
    stream is what its request streams alone."""
    rng = np.random.default_rng(3814)
    asks = _small_mix(rng, 12, least=12)   # outlasts the chunks in flight
    clients = _Clients(engine)
    before = engine.counters()
    with _Profiled(tmp_path) as prof, _Fetch(engine) as emitter:
        for k, ask in enumerate(asks):
            clients.send(k, *ask)
        _until(lambda: engine._active.sum() == engine.n_slots)
        assert len(engine._pending) == 8 and clients.replica._ongoing == 12
        assert clients.replica._max_ongoing == engine.n_slots
        emitter.release()
        together = clients.join()
    admits = [s for _, _, _, s in prof.events("serve.engine.admit")]
    assert [a["prompt_tokens"] for a in admits] == [
        len(ids) for ids, _, _ in asks]                 # FIFO, none overtaken
    assert [a["rid"] for a in admits] == sorted(a["rid"] for a in admits)
    waiting = [a["pending"] for a in admits]
    assert waiting[-1] == 0 and max(waiting) >= 7 and sum(waiting) > 0
    assert _delta(engine, before, ("admitted", "admit_pending")) == {
        "admitted": 12, "admit_pending": sum(waiting)}
    # the last eight waited at the engine through their predecessors' decode
    assert all(a["queue_wait_us"] > 0 for a in admits[engine.n_slots:])
    for (ids, m, sampling), got in zip(asks, together):
        alone = _drain(engine.submit(ids, m, **sampling))
        assert [t for c in got for t in c] == [t for c in alone for t in c]
        assert sum(map(len, got)) == min(m, engine.mcfg.max_seq - len(ids))
    assert engine.pages_in_use() == 0


def test_a_freed_slot_with_a_request_pending_is_refilled_within_a_chunk(
        engine, tmp_path):
    """S14: with requests waiting in `_pending`, the slot a chunk's dispatch
    frees has its next tenant admitted by the loop's next stand, not when a
    client has seen a stream end: `slot_idle_us` of every such refill is
    under the time of one chunk (the emitter's fetch is slowed to 50 ms a
    chunk, which is then the least a chunk takes), and its admit span opens
    before the freeing chunk's output has been fetched."""
    chunk_s = 0.05

    def slow(fetch, out_d):
        time.sleep(chunk_s)
        return fetch(out_d)

    rng = np.random.default_rng(3815)
    asks = _small_mix(rng, 12, least=6)
    clients = _Clients(engine)
    with _Profiled(tmp_path) as prof, _Fetch(engine, slow):
        for k, ask in enumerate(asks):
            clients.send(k, *ask)
        clients.join()
    admits = [s for _, _, _, s in prof.events("serve.engine.admit")]
    refills = [a for a in admits[engine.n_slots:]
               if a["queue_wait_us"] > a["slot_idle_us"]]   # it was waiting
    assert len(refills) >= 6, admits
    assert max(a["slot_idle_us"] for a in refills) < chunk_s * 1e6, refills
    assert max(a["chunks_ahead"] for a in refills) >= 1     # mid-pipeline


def _threads_of(eng):
    return {eng._thread, eng._emitter, eng._warm_thread}


def test_stop_ends_the_wait_for_room_and_leaves_no_thread():
    eng = _build()
    with _Fetch(eng) as emitter:
        eng.submit(list(range(1, 9)), 40)
        _until(lambda: eng._in_flight == _DEPTH)
        stopper = threading.Thread(target=eng.stop)
        stopper.start()
        eng._thread.join(5)             # the loop stood waiting for room
        assert not eng._thread.is_alive()
        emitter.release()
        stopper.join(30)
        assert not stopper.is_alive()
    assert not _threads_of(eng) & set(threading.enumerate())


def test_a_failing_fetch_ends_its_streams_and_later_submits_raise():
    eng = _build()

    def broken(fetch, out_d):
        raise RuntimeError("fetch failed")

    with _Fetch(eng, broken):
        a = eng.submit(list(range(1, 9)), 12)
        b = eng.submit(list(range(1, 20)), 7)
        got_a, got_b = _drain(a, 20), _drain(b, 20)
        # the first tokens came out before the chunk that failed
        assert [len(c) for c in got_a + got_b] == [1, 1]
        assert "fetch failed" in eng.error
        with pytest.raises(RuntimeError, match="fetch failed"):
            eng.submit([1, 2, 3], 4)
        # every chunk still leaves the count: the loop is not wedged
        _until(lambda: not eng._active.any() and eng._in_flight == 0)
    eng.stop()
    assert not _threads_of(eng) & set(threading.enumerate())


def test_a_failing_dispatch_ends_the_streams_still_pending_too():
    """The loop's own fault (`_run`'s drain): with 2 slots live and 5 more
    requests waiting in `_pending`, a decode dispatch that raises ends all
    seven streams, through the replica, and later submits raise."""
    eng = _build(n_slots=2)
    clients = _Clients(eng)

    def broken(*args):
        raise RuntimeError("dispatch failed")

    with _Fetch(eng) as emitter:
        for k in range(7):
            clients.send(k, list(range(1, 9 + k)), 40, {})
        _until(lambda: eng._in_flight == _DEPTH)
        assert eng._active.sum() == 2 and len(eng._pending) == 5
        eng._programs = eng._programs._replace(decode=broken)
        emitter.release()
        got = clients.join()
    _until(lambda: not eng._thread.is_alive())      # the drain's last None
    assert "dispatch failed" in eng.error and not eng._pending
    # the two tenants streamed at most what was dispatched before the fault
    # (the drain's None may overtake what the emitter still holds, a first
    # token included); the five that waited streamed nothing and ended
    streamed = [sum(map(len, g)) for g in got]
    assert all(n <= 1 + _DEPTH * eng.chunk for n in streamed[:2])
    assert streamed[2:] == [0] * 5
    with pytest.raises(RuntimeError, match="dispatch failed"):
        eng.submit([1, 2, 3], 4)
    eng.stop()
    assert not _threads_of(eng) & set(threading.enumerate())

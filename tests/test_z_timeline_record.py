"""benchmark/timeline_record.py and the eleven readers of PR 51 on a timeline
built here by hand (no cluster, no clock): the event list `state.timeline()`
gives, as `ray_tpu.shutdown()` leaves it in the session's directory.

The file's name sorts last on purpose: under the driver's `--dist loadfile` the
older files then reach the workers in the order they had on the seed."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import timeline_record  # noqa: E402
from benchmark.timeline_record import (ADMIT, CALL, DISPATCH, EMIT,  # noqa: E402
                                       PROXY)

RUN = {"cell": "x", "seed": 0, "t0": 100.0, "seconds": 10.0, "setup_s": 40.0}


def _reader(name):
    from benchmark.run import HERE, load_reader
    return load_reader(HERE, "layer_metrics", name)


def _ev(name, start, end, **args):
    """A program span as the controller's timeline lists it: `ts` wall us,
    `dur` us, `mono_ns` at its end."""
    return {"name": name, "cat": "program", "ph": "X", "ts": 1e12 + start * 1e6,
            "dur": (end - start) * 1e6, "pid": "worker:1", "tid": "t",
            "args": dict(args, mono_ns=int(round(end * 1e9)))}


def _request(tid, rid, proxy, call, admit, first_end, written, prompt,
             queue_ms, slot_idle_ms, **more):
    return [
        _ev(PROXY, proxy, first_end + 1.0, trace_id=tid, path="/llm",
            stream=True, first_chunk_us=int(round((written - proxy) * 1e6))),
        _ev(CALL, call, first_end + 1.0, trace_id=tid, method="__call__"),
        _ev(ADMIT, admit, admit + 0.001, trace_id=tid, rid=rid,
            kind="prefill", prompt_tokens=prompt, bucket=4096,
            queue_wait_us=int(queue_ms * 1000),
            slot_idle_us=int(slot_idle_ms * 1000), **more),
        _ev(EMIT, first_end - 0.001, first_end, trace_id=tid, rid=rid,
            kind="first"),
    ]


def _chunk(start, emit_end, useful):
    return [_ev(DISPATCH, start, start + 0.01, useful=useful, capacity=16,
                active=2),
            _ev(EMIT, emit_end - 0.05, emit_end, kind="chunk")]


def _timeline(dropped=0, dropped_until=0.0):
    """Process start at 60 s, the window 100-110 s. Four programs warmed over
    70-84 (two at once on the warm-up thread), the check's request at 90,
    three requests and three chunks in the window, the last of each across
    its close."""
    events = [
        {"name": "BenchLLMServer.__init__", "cat": "task", "ph": "X",
         "ts": 0.0, "dur": 1.0, "pid": "driver", "tid": "ab",
         "args": {"status": "finished", "trace_id": "", "parent_span": ""}},
        {"name": "rpc.wire", "cat": "native", "ph": "X", "ts": 0.0,
         "dur": 5.0, "pid": "w", "tid": "native", "args": {}},
        _ev("serve.engine.warm", 70.0, 75.0, program="prefill", width=64,
            compile_s=3.0, cache_misses=1, cache_hits=0, compiles=1),
        _ev("serve.engine.warm", 75.0, 78.0, program="decode", width=16,
            compile_s=1.5, cache_misses=1, cache_hits=0, compiles=1),
        _ev("serve.engine.warm", 78.0, 80.0, program="prefill", width=128,
            compile_s=1.0, cache_misses=0, cache_hits=1, compiles=1),
        _ev("serve.engine.warm", 79.0, 84.0, program="prefill", width=256,
            compile_s=2.0, cache_misses=1, cache_hits=0, compiles=1),
    ]
    events += _request("00", 0, 90.0, 90.001, 90.002, 90.1, 90.101, 100,
                       1.0, 0.0)
    events += _chunk(90.5, 90.6, 4)
    # its slot is the one the check's request left at 90.2: not a refill
    events += _request("aa", 1, 101.0, 101.002, 101.010, 101.110, 101.112,
                       1000, 3.0, 10_810.0)
    events += _chunk(101.2, 101.3, 6)
    events += _request("bb", 2, 103.0, 103.004, 103.020, 103.220, 103.226,
                       2000, 10.0, 4.0, riders=2)
    events += _chunk(105.0, 105.1, 10)
    events += _request("cc", 3, 109.9, 109.901, 109.950, 110.050, 110.053,
                       500, 40.0, 8.0)
    events += _chunk(109.98, 110.02, 8)
    # the harness's side channel: a replica call under no proxy span
    events.append(_ev(CALL, 110.5, 110.6, trace_id="dd", method="bench_stats"))
    events.append({"name": "program_spans", "cat": "meta", "ph": "M",
                   "pid": "controller", "tid": "timeline",
                   "args": {"kept": len(events) - 2, "dropped": dropped,
                            "dropped_until_mono_ns":
                                int(dropped_until * 1e9)}})
    return events


WANT = {
    "setup_boot_s": 10.0,                   # 60 -> 70
    "setup_warm_s": 14.0,                   # 70 -> 84, the overlap once
    "setup_compile_s": 7.5,
    "setup_check_s": 16.0,                  # 84 -> 100
    "service_ingress_p95_ms": 3.8,          # p95 of 2, 4, 1
    "service_egress_p95_ms": 5.7,           # p95 of 2, 6, 3
    "engine_queue_wait_p95_ms": 37.0,       # p95 of 3, 10, 40
    "engine_admit_to_first_p95_ms": 190.0,  # p95 of 100, 200, 100
    "decode_occupancy_window_pct": 50.0,    # 6 + 10 + 8 of 3 x 16
    "engine_slot_refill_window_ms": 6.0,    # mean(4, 8); freed before t0: out
    # 1000 + 1; 2000 + 1 + 2 riders; half of 500 + 1; chunks 6 + 10 + half of 8
    "engine_window_tokens_per_s": (1001 + 2003 + 250.5 + 20) / 10.0,
}
SETUP = [n for n in WANT if n.startswith("setup_")]


def _put(monkeypatch, events):
    rec = None if events is None else timeline_record.of_events(events)
    monkeypatch.setattr(timeline_record, "load", lambda run: rec)
    return rec


@pytest.mark.parametrize("name", list(WANT) + [
    "sum:set-up", "sum:request", "no dump"])
def test_readers_on_a_hand_built_timeline(monkeypatch, name):
    assert list(WANT) == list(timeline_record.READERS)
    if name == "no dump":
        # the parent of PR 51: `state.load_timeline` is not there
        from ray_tpu import state
        monkeypatch.delattr(state, "load_timeline")
        timeline_record._loaded.clear()
        assert timeline_record.load(RUN) is None
        assert [_reader(n)(RUN) for n in WANT] == [None] * 11
        timeline_record._loaded.clear()
        return
    rec = _put(monkeypatch, _timeline())
    assert len(rec.spans) == 4 + 4 * 4 + 2 * 4 + 1 and len(rec.tasks) == 1
    assert rec.others == {"rpc.wire": 1} and rec.dropped == 0
    if name == "sum:set-up":
        parts = timeline_record.setup_parts(RUN)
        assert parts["boot"] + parts["warm"] + parts["check"] == \
            pytest.approx(RUN["setup_s"])
        assert parts["compile"] <= parts["warm"]
        assert (parts["cache_misses"], parts["programs"]) == (3, 4)
        # a stretch between two warm spans (a train job's check) is the
        # check's, and the sum stays whole
        gap = [e for e in _timeline() if e["args"].get("program") != "decode"]
        _put(monkeypatch, gap)
        parts = timeline_record.setup_parts(RUN)
        assert (parts["boot"], parts["warm"], parts["check"]) == \
            pytest.approx((10.0, 11.0, 19.0))
        return
    if name == "sum:request":
        ps = timeline_record.paths(RUN)
        assert [p.trace_id for p in ps] == ["aa", "bb", "cc"]   # not the check's
        assert all(p.in_order() for p in ps)
        for p, first_chunk_us in zip(ps, (112_000, 226_000, 153_000)):
            cuts = (p.call - p.proxy, p.admit - p.call, p.first - p.admit,
                    p.written - p.first)
            assert sum(cuts) * 1e6 == pytest.approx(first_chunk_us)
        return
    assert _reader(name)(RUN) == pytest.approx(WANT[name])
    # a span dropped inside the interval: no number from a partial record
    _put(monkeypatch, _timeline(dropped=1, dropped_until=105.0))
    assert _reader(name)(RUN) is None
    # one dropped during the set-up, before the window: the set-up's readers
    # give nothing, the window's all but the one that pairs by order
    _put(monkeypatch, _timeline(dropped=1, dropped_until=65.0))
    if name in SETUP or name == "engine_window_tokens_per_s":
        assert _reader(name)(RUN) is None
    else:
        assert _reader(name)(RUN) == pytest.approx(WANT[name])
    # a program that writes no dump
    _put(monkeypatch, None)
    assert _reader(name)(RUN) is None
    # one whose proxy says no `first_chunk_us` (a timeline taken live from
    # the parent): the path's readers alone fall silent
    bare = [dict(e, args={k: v for k, v in e["args"].items()
                          if k != "first_chunk_us"}) for e in _timeline()]
    _put(monkeypatch, bare)
    assert (_reader(name)(RUN) is None) == name.startswith("service_")


def test_the_cut_to_a_traced_interval_keeps_what_a_profiler_would():
    rec = timeline_record.of_events(_timeline())
    # the chunk dispatched at 101.2 lies in 101-102; the request whose proxy
    # span opened at 101.0 stays open past it
    assert len(rec.named(DISPATCH, (101.0, 102.0), inside=True)) == 1
    assert len(rec.named(PROXY, (101.0, 102.0))) == 1
    assert rec.named(PROXY, (101.0, 102.0), inside=True) == []
    assert timeline_record.occupancy_pct(
        rec.named(DISPATCH, (101.0, 106.0), inside=True)) == 50.0
    assert timeline_record.slot_refill_ms(
        rec.named(ADMIT, (103.0, 104.0), inside=True, kind="prefill")) == 4.0
    # the 4 s reader's own statistic keeps a slot freed before the cut
    assert timeline_record.slot_refill_ms(
        rec.named(ADMIT, (101.0, 104.0), kind="prefill")) == \
        pytest.approx((10_810.0 + 4.0) / 2)
    assert timeline_record.occupancy_pct([]) is None
    assert timeline_record.slot_refill_ms([]) is None


def test_load_parses_once_a_run(monkeypatch, tmp_path):
    import json

    from ray_tpu import state
    path = tmp_path / state.TIMELINE_FILE
    path.write_text(json.dumps(_timeline()))
    monkeypatch.setattr(state, "_last_session_dir", str(tmp_path))
    timeline_record._loaded.clear()
    rec = timeline_record.load(RUN)
    assert rec is timeline_record.load(RUN) and len(rec.spans) == 29
    assert timeline_record.load(dict(RUN, seed=1)) is not rec
    assert _reader("setup_boot_s")(dict(RUN, seed=1)) == pytest.approx(10.0)
    timeline_record._loaded.clear()


def test_the_script_prints_a_session_by_hand(tmp_path, capsys):
    import json

    from ray_tpu import state
    (tmp_path / state.TIMELINE_FILE).write_text(json.dumps(_timeline()))
    run = tmp_path / "run-trace1.json"
    run.write_text(json.dumps(dict(RUN, outcomes=[], replica={}, marks={})))
    assert timeline_record.main(["x", str(tmp_path), str(run)]) == 0
    out = capsys.readouterr().out
    assert "span serve.engine.admit: 4 " in out
    assert "program spans 29, dropped 0" in out and "rpc.wire 1" in out
    for name, want in WANT.items():
        assert f"{name}: {want}"[:len(name) + 6] in out
    assert "boot + warm + check = 40.000 s of setup_s 40.000 (100.00%)" in out
    assert "3 requests, 3 with their five boundaries in order" in out
    timeline_record._loaded.clear()
    assert timeline_record.main(["x", str(tmp_path / "none")]) == 1


def test_manifest_entries_of_the_eleven_readers():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = {p["name"]: p for p in manifest["per_layer"]}
    cells = {w["name"] for w in manifest["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in manifest["end_to_end"]}
    layers = {p["layer"] for p in manifest["per_layer"]
              if p["name"] not in WANT}
    names = list(per_layer)
    at = names.index("setup_boot_s")
    assert names[at:at + 11] == list(WANT)      # appended, in this order
    for name in WANT:
        p = per_layer[name]
        assert p["source"] in ("program_span", "program_counter")
        assert p["layer"] in layers
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        # every cell it lists reports the end-to-end metric it moves
        assert p["workloads"] and set(p["workloads"]) <= reports[p["moves"]]
    assert {per_layer[n]["moves"] for n in SETUP} == {"setup_s"}

"""benchmark/layer_metrics/decode_rider_share_pct.py (PR 41) on traces built
here span by span: a run whose admit spans carry `riders`, the same run as a
program without riders records it (None, and nothing raised), no trace, and
the trace recorded on the chip in PR 24, whose admit spans are older still."""

import os

import pytest

from benchmark import program_trace as pt
from benchmark.run import load_reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def _admit(rid, at, **args):
    return pt.Span("serve.engine.admit", at, at + 1, dict(dict(
        rid=rid, kind="prefill", prompt_tokens=2000, bucket=2048,
        queue_wait_us=1, decoding=16, slot_idle_us=0), **args))


def _chunk(at, useful):
    return pt.Span("serve.engine.decode_dispatch", at, at + 1, dict(
        useful=useful, capacity=128, active=16))


def _read(trace, monkeypatch):
    monkeypatch.setattr(pt, "load", lambda run: trace)
    return load_reader(BENCH, "layer_metrics", "decode_rider_share_pct")({})


def test_the_share_is_riders_over_riders_and_the_chunks_useful(monkeypatch):
    # Three admissions: 16 rode, 15 rode, and a prompt that left no room (0);
    # a narrow rung's admission carries no `riders` and counts for nothing.
    spans = [_admit(1, 0, riders=16), _chunk(10, 128), _admit(2, 20, riders=15),
             _admit(3, 30, riders=0), _chunk(40, 120),
             _admit(4, 50, bucket=1024, prompt_tokens=1000)]
    t = pt.ProgramTrace(spans, [], [])
    assert _read(t, monkeypatch) == pytest.approx(100.0 * 31 / (31 + 248))


@pytest.mark.parametrize("trace", [
    None,
    pt.ProgramTrace([_admit(1, 0), _chunk(10, 128), _admit(2, 20)], [], []),
    pt.ProgramTrace([_chunk(10, 128)], [], []),
], ids=["no-trace", "a-program-without-riders", "no-admission"])
def test_a_run_without_riders_leaves_the_metric_out(trace, monkeypatch):
    assert _read(trace, monkeypatch) is None


def test_the_recorded_traces_of_older_programs_read_none(monkeypatch):
    for name in ("tiny24.xplane.pb", "tiny.xplane.pb"):
        old = pt.load_path(os.path.join(HERE, name))
        assert _read(old, monkeypatch) is None

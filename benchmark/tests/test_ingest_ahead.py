"""benchmark/layer_metrics/ingest_ahead_pct.py (PR 52) on records made here by
hand: the consumer's `data.iter.take` spans with and without a batch waiting,
a program that makes its batches inline (the parent: no such span), no trace,
and the traces recorded on the chip by older programs. And what the three
accepted ingest readers give once the iterator's spans run on a thread of
their own, beside the step and not between two of them."""

import os

import pytest

from benchmark import program_trace as pt
from benchmark.run import load_reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000      # ns


def _take(at, ready):
    return pt.Span("data.iter.take", at, at + 1, {"ready": ready})


def _inline_step(at):
    """A step of the parent's loop: the feed's spans, then the step."""
    return [pt.Span("data.iter.next_ref", at, at + 2 * MS, {"held": 2}),
            pt.Span("data.iter.get_block", at + 2 * MS, at + 3 * MS, {}),
            pt.Span("data.iter.format", at + 3 * MS, at + 4 * MS, {"rows": 1}),
            pt.Span("data.iter.device_put", at + 4 * MS, at + 5 * MS,
                    {"rows": 1}),
            pt.Span("train.step", at + 6 * MS, at + 7 * MS, {})]


def _read(name, trace, monkeypatch):
    monkeypatch.setattr(pt, "load", lambda run: trace)
    return load_reader(BENCH, "layer_metrics", name)({})


def test_the_share_is_the_takes_that_found_their_batch_waiting(monkeypatch):
    steps = [s for i in range(4) for s in _inline_step(200 * MS * i)]
    takes = [_take(200 * MS * i + 5 * MS, ready) for i, ready
             in enumerate([0, 1, 1, 1])]
    t = pt.ProgramTrace(sorted(steps + takes, key=lambda s: s.start), [], [])
    assert _read("ingest_ahead_pct", t, monkeypatch) == pytest.approx(75.0)
    t = pt.ProgramTrace([_take(0, 1), _take(10, 1)], [], [])
    assert _read("ingest_ahead_pct", t, monkeypatch) == pytest.approx(100.0)
    t = pt.ProgramTrace([_take(0, 0)], [], [])
    assert _read("ingest_ahead_pct", t, monkeypatch) == 0.0


@pytest.mark.parametrize("trace", [
    None,
    pt.ProgramTrace([s for i in range(3) for s in _inline_step(200 * MS * i)],
                    [], []),
    pt.ProgramTrace([], [], []),
], ids=["no-trace", "a-program-that-makes-its-batches-inline", "no-span"])
def test_a_program_without_the_span_leaves_the_metric_out(trace, monkeypatch):
    assert _read("ingest_ahead_pct", trace, monkeypatch) is None


def test_the_recorded_traces_of_older_programs_read_none(monkeypatch):
    for name in ("tiny24.xplane.pb", "tiny.xplane.pb"):
        old = pt.load_path(os.path.join(HERE, name))
        assert _read("ingest_ahead_pct", old, monkeypatch) is None


def test_the_ingest_readers_read_the_same_work_from_the_producers_thread(
        monkeypatch):
    """`per_step_ms` sums what ENDED between two `train.step` starts,
    whatever thread it ran on: the feed's spans moved from before the step's
    dispatch to beside the step read the same."""
    inline = pt.ProgramTrace(
        [s for i in range(4) for s in _inline_step(200 * MS * i)], [], [])
    ahead = []
    for i in range(4):
        at = 200 * MS * i
        feed, step = _inline_step(at)[:4], _inline_step(at)[4]
        for s in feed:                  # the same spans, 50 ms into the step
            ahead.append(pt.Span(s.name, s.start + 56 * MS, s.end + 56 * MS,
                                 s.args))
        ahead += [step, _take(at + 5 * MS, 1)]
    ahead = pt.ProgramTrace(sorted(ahead, key=lambda s: s.start), [], [])
    for name, ms in (("ingest_next_ref_ms", 2.0), ("ingest_get_ms", 2.0),
                     ("ingest_device_put_ms", 1.0)):
        assert _read(name, inline, monkeypatch) == pytest.approx(ms)
        assert _read(name, ahead, monkeypatch) == pytest.approx(ms)

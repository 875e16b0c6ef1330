"""The session's spans outlive the session (PR 51): `ray_tpu.shutdown()` writes
`<session_dir>/timeline.json`, `state.load_timeline` and `ray_tpu timeline
--session` read it with no cluster, and the controller keeps the program's
spans in a ring that native spans cannot push them out of, counting what it
had to let go. CPU only.

The file's name sorts last on purpose: under the driver's `--dist loadfile` the
older files then reach the workers in the order they had on the seed."""

import asyncio
import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import state


def _program(events, name):
    return [e for e in events if e["name"] == name and e["cat"] == "program"]


def _meta(events):
    (m,) = [e for e in events if e.get("ph") == "M"
            and e["name"] == "program_spans"]
    return m["args"]


@pytest.fixture(scope="module")
def ended_session():
    """A session that opened spans in a task, in an actor and in the driver,
    and was shut down at once: nothing waited for a flusher's tick."""
    from ray_tpu.core._native import graftscope
    from ray_tpu.utils import tracing
    if not (graftscope.available() and graftscope.enabled()):
        pytest.skip("graftscope recorder unavailable")
    assert not ray_tpu.is_initialized()
    ray_tpu.init(resources={"CPU": 2})
    from ray_tpu import api
    session_dir = api._global_node.session_dir

    @ray_tpu.remote
    def task(n):
        from ray_tpu.utils import tracing
        with tracing.span("test.in_task", n=n) as sp:
            sp["late"] = n * 2          # set inside the body: timeline only
        # A fresh worker's FIRST task can run before worker_main has bound
        # the public API to its core worker, and its spans then find no
        # sink (found by this test, PR 51; binding earlier broke graftlog's
        # salvage, so it stands): say whether this one was recorded.
        return tracing.context()[0].hex(), tracing._worker() is not None

    @ray_tpu.remote
    class Actor:
        def work(self, n):
            from ray_tpu.utils import tracing
            with tracing.span("test.in_actor", n=n, word="x"):
                pass
            return tracing.context()[0].hex(), tracing._worker() is not None

    a = Actor.remote()
    def recorded(call):
        for _ in range(10):
            trace_id, bound = ray_tpu.get(call())
            if bound:
                return trace_id
        raise AssertionError("no call found its worker bound")

    ids = {"task": recorded(lambda: task.remote(3)),
           "actor": recorded(lambda: a.work.remote(5))}
    b = Actor.remote()
    ids["killed"] = recorded(lambda: b.work.remote(7))
    ray_tpu.kill(b)                     # ships its spans before it dies
    # Task events ride a tick of their own (worker -> agent -> controller):
    # the dump holds those that had arrived, as `state.timeline()` does live.
    api._cw()._flush_task_events()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not any(
            t["name"] == "task" and t["event"] == "finished"
            and t.get("trace_id") == ids["task"]
            for t in state.list_task_events(limit=1000)):
        time.sleep(0.1)
    t_before = time.monotonic_ns()
    with tracing.span("test.in_driver", n=1):
        pass
    t0 = time.monotonic()
    ray_tpu.shutdown()
    return {"dir": session_dir, "ids": ids, "t_before": t_before,
            "shutdown_s": time.monotonic() - t0}


def test_shutdown_leaves_the_sessions_spans_in_its_directory(ended_session):
    path = os.path.join(ended_session["dir"], state.TIMELINE_FILE)
    assert os.path.exists(path) and not os.path.exists(path + ".tmp")
    assert not ray_tpu.is_initialized()
    events = state.load_timeline()       # None: the last this process ended
    assert events == state.load_timeline(ended_session["dir"]) \
        == state.load_timeline(path)
    (in_task,) = _program(events, "test.in_task")
    assert in_task["args"]["n"] == 3 and in_task["args"]["late"] == 6
    assert in_task["args"]["trace_id"] == ended_session["ids"]["task"]
    spans = _program(events, "test.in_actor")
    assert sorted((s["args"]["n"], s["args"]["trace_id"]) for s in spans) == [
        (5, ended_session["ids"]["actor"]),
        (7, ended_session["ids"]["killed"])]
    assert all(s["args"]["word"] == "x" for s in spans)
    (mine,) = _program(events, "test.in_driver")
    # CLOCK_MONOTONIC, the clock of this process's own stamps
    assert ended_session["t_before"] <= mine["args"]["mono_ns"] \
        <= time.monotonic_ns()
    for s in [in_task, mine] + spans:
        assert s["args"]["mono_ns"] > 0 and s["dur"] >= 0 and s["ph"] == "X"
    assert _meta(events) == {
        "kept": len([e for e in events if e["cat"] == "program"]),
        "dropped": 0, "dropped_until_mono_ns": 0}
    # task events are in the same list, as `state.timeline()` gives them live
    (ran,) = [e for e in events if e["cat"] == "task" and e["name"] == "task"
              and e["args"]["trace_id"] == ended_session["ids"]["task"]]
    # the span is homed on its task's track, as it is live
    assert (in_task["pid"], in_task["tid"]) == (ran["pid"], ran["tid"])
    assert ended_session["shutdown_s"] < 15


def test_no_dump_reads_as_none(tmp_path):
    assert state.load_timeline(str(tmp_path)) is None
    assert state.load_timeline(str(tmp_path / "timeline.json")) is None


def test_cli_reads_a_session_that_ended(ended_session, tmp_path):
    out = str(tmp_path / "t.json")
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.cli", "timeline", "--session",
         ended_session["dir"], "--native", "--out", out],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "wrote" in r.stdout
    with open(out) as f:
        assert json.load(f) == state.load_timeline(ended_session["dir"])
    chrome = str(tmp_path / "c.json")
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.cli", "timeline", "--session",
         ended_session["dir"], "--native", "--format", "chrome", "--out",
         chrome], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    with open(chrome) as f:
        assert any(e["name"] == "test.in_actor"
                   for e in json.load(f)["traceEvents"])
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.cli", "timeline", "--session",
         str(tmp_path), "--out", out], capture_output=True, text=True,
        timeout=60)
    assert r.returncode == 1 and "no timeline.json" in r.stderr


# -- the controller's two rings (no cluster: the object alone) ---------------

def _span(name, cat, mono_ns):
    return {"name": name, "cat": cat, "ph": "X", "ts": mono_ns / 1e3,
            "dur": 1.0, "pid": "w", "tid": "t", "args": {"mono_ns": mono_ns}}


@pytest.fixture
def controller():
    from ray_tpu.core.controller import Controller
    c = Controller.__new__(Controller)
    from collections import deque
    c.task_events = deque()
    c.native_spans = deque(maxlen=50000)
    c.program_spans = deque(maxlen=50000)
    c._program_lost = [0, 0]
    c._oid_trace = {}
    c.meta = None
    return c


def test_program_spans_survive_60000_native_spans_after_them(controller):
    run = asyncio.run
    run(controller.report_native_spans(
        [_span("serve.engine.admit", "program", 1000 + i)
         for i in range(3000)]))
    for k in range(12):
        run(controller.report_native_spans(
            [_span("rpc.wire", "native", 10_000 + k * 5000 + i)
             for i in range(5000)]))
    events = run(controller.timeline())
    assert len(_program(events, "serve.engine.admit")) == 3000
    assert len([e for e in events if e["cat"] == "native"]) == 50000
    assert _meta(events) == {"kept": 3000, "dropped": 0,
                             "dropped_until_mono_ns": 0}
    assert run(controller.timeline(native=False)) == []


def test_what_the_ring_lets_go_is_counted_with_its_latest_instant(controller):
    run = asyncio.run
    controller.program_spans = __import__("collections").deque(maxlen=100)
    run(controller.report_native_spans(
        [_span("a", "program", i) for i in range(1, 91)]))
    assert controller._program_lost == [0, 0]
    run(controller.report_native_spans(
        [_span("a", "program", i) for i in range(91, 121)]
        + [_span("rpc.wire", "native", 5)]))
    # 120 into a ring of 100: the 20 oldest went, the last of them at 20
    assert _meta(run(controller.timeline())) == {
        "kept": 100, "dropped": 20, "dropped_until_mono_ns": 20}
    # a worker that gave spans up itself says so with its next report
    run(controller.report_native_spans([], [3, 500]))
    assert _meta(run(controller.timeline())) == {
        "kept": 100, "dropped": 23, "dropped_until_mono_ns": 500}
    # one report wider than the ring: its own head goes too
    run(controller.report_native_spans(
        [_span("b", "program", 1000 + i) for i in range(150)]))
    meta = _meta(run(controller.timeline()))
    assert meta == {"kept": 100, "dropped": 173,
                    "dropped_until_mono_ns": 1049}


def test_a_worker_counts_the_program_spans_it_gives_up():
    from ray_tpu.core.core_worker import CoreWorker
    w = CoreWorker.__new__(CoreWorker)
    w._scope_lost = [0, 0]
    w._lost_spans([_span("rpc.wire", "native", 9)])
    assert w._scope_lost == [0, 0]
    w._lost_spans([_span("a", "program", 7), _span("a", "program", 5),
                   _span("sidecar.put", "native", 11)])
    assert w._scope_lost == [2, 7]

    class Away:
        async def call(self, *a):
            raise ConnectionError("controller is away")

    w.controller = Away()
    asyncio.run(w._send_native_spans([_span("a", "program", 30)]))
    assert w._scope_lost == [3, 30]     # kept for the report that arrives

    class Here:
        async def call(self, method, spans, lost):
            self.got = (method, len(spans), lost)

    w.controller = Here()
    asyncio.run(w._send_native_spans([_span("a", "program", 40)]))
    assert w.controller.got == ("report_native_spans", 1, [3, 30])
    assert w._scope_lost == [0, 0]

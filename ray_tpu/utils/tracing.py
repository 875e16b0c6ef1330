"""span(): the one way Serve, Train and Data time their own work.

A span has two sinks and no buffer of its own:

* the `jax.profiler` trace, when jax is already imported in this process
  (never imported for this: a driver that touches jax holds the chip). With
  no profiler session open that is a flag check; with one, the span lands on
  `/host:CPU` of the same `.xplane.pb` as the device's events, on one clock,
  its keyword arguments as the event's stats. A span given `step_num` opens a
  `StepTraceAnnotation`, which is what XProf's step view reads;
* `core_worker._scope_spans`, when this process has a core worker whose
  graftscope assembler is on: the 2 s flusher ships it to the controller and
  `state.timeline()` / `ray_tpu timeline` nests it under the task whose
  context it carries. A thread that acts for tasks submitted elsewhere (the
  engine loop for a replica call) passes that task's `context()` as `ctx`;
  one that works for ONE task all its life (the Data iterator's producer)
  runs `under(ctx)`, and the tasks it submits carry the context too.
  This sink is on in every run, in every process, from its start, and every
  span on it carries `mono_ns` (CLOCK_MONOTONIC at its end; its start is
  that less `dur`). It ends in a file: `ray_tpu.shutdown()` has every
  process ship what it still holds (an actor that is killed ships first),
  pulls the controller's timeline and writes `<session_dir>/timeline.json`,
  which `state.load_timeline()` and `ray_tpu timeline --session` read when
  the cluster is gone. The controller keeps these spans (`cat` "program")
  in a ring of their own; what a ring or a process had to let go is counted
  in the list's last event (`program_spans`).

What the profiler records is fixed when the span opens; the dict the `with`
yields is the timeline's copy, and keys set on it inside the body reach the
timeline only. `profiler=False` keeps a span out of the profiler: its
annotations nest by thread, so a span that stays open across an `await` or a
`yield` would interleave with its neighbours there.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Any, Dict, Iterator, Optional, Tuple

from ray_tpu.utils.logging import get_logger

_MAX_BUFFERED = 4096     # spans kept while the controller is away

Context = Tuple[bytes, bytes]   # (trace_id, span of the running task)


def context() -> Optional[Context]:
    """The running task's trace context, to hand to another thread."""
    cw = sys.modules.get("ray_tpu.core.core_worker")
    if cw is None:
        return None
    return getattr(cw._trace_local, "ctx", None) or cw._trace_ctxvar.get()


def _worker():
    api = sys.modules.get("ray_tpu.api")
    return getattr(api, "_core_worker", None)


@contextlib.contextmanager
def under(ctx: Optional[Context]) -> Iterator[None]:
    """Make `ctx` (another thread's `context()`) this thread's, as a task's
    exec thread has its task's: tasks submitted and spans opened inside carry
    it. For a thread that works for that task all its life; None (no task, no
    runtime) changes nothing."""
    if ctx is None:
        yield
        return
    from ray_tpu.core import core_worker as cw
    before = getattr(cw._trace_local, "ctx", None)
    cw._trace_local.ctx = ctx
    try:
        yield
    finally:
        cw._trace_local.ctx = before


def root(trace_id: bytes):
    """Make this thread the root of a trace: `under` a context with no
    parent span."""
    return under((trace_id, b""))


def recording() -> bool:
    """Whether a span opened now has a sink: a profiler session is open, or
    this process's graftscope assembler is on. For a caller whose span
    arguments cost something to put together."""
    jax = sys.modules.get("jax")
    if jax is not None and jax.profiler.TraceAnnotation.is_enabled():
        return True
    worker = _worker()
    return worker is not None and worker._scope_asm() is not None


@contextlib.contextmanager
def span(name: str, ctx: Optional[Context] = None, profiler: bool = True,
         **args: Any) -> Iterator[Dict[str, Any]]:
    jax = sys.modules.get("jax") if profiler else None
    note = None
    if jax is not None:
        kind = (jax.profiler.StepTraceAnnotation if "step_num" in args
                else jax.profiler.TraceAnnotation)
        note = kind(name, **args)
        note.__enter__()
    t0 = time.time_ns()
    try:
        yield args
    finally:
        t1 = time.time_ns()
        if note is not None:
            note.__exit__(None, None, None)
        worker = _worker()
        asm = worker._scope_asm() if worker is not None else None
        if asm is not None:
            ctx = ctx or context()
            trace_id, parent = (ctx[0].hex(), (ctx[1] or ctx[0]).hex()) \
                if ctx else ("", "")
            s = asm._span(name, t0, t1 - t0, trace_id, parent,
                          dict(args, mono_ns=time.monotonic_ns()))
            s["cat"], s["tid"] = "program", threading.current_thread().name
            buf = worker._scope_spans
            buf.append(s)
            if len(buf) > _MAX_BUFFERED:
                worker._lost_spans(buf[:len(buf) - _MAX_BUFFERED])
                del buf[:len(buf) - _MAX_BUFFERED]


# -- what JAX compiled, for the set-up spans --------------------------------

_compiled = {"cache_hits": 0, "cache_misses": 0, "compiles": 0,
             "compile_s": 0.0}
_compiled_lock = threading.Lock()   # JAX compiles on whichever thread calls
_listening = False
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}


def _on_event(event: str, **_: Any) -> None:
    key = _CACHE_EVENTS.get(event)
    if key:
        with _compiled_lock:
            _compiled[key] += 1


def _on_duration(event: str, seconds: float, **_: Any) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        with _compiled_lock:
            _compiled["compiles"] += 1
            _compiled["compile_s"] += seconds


@contextlib.contextmanager
def compile_span(name: str, **args: Any) -> Iterator[Dict[str, Any]]:
    """A span around a program's first call. On the timeline and in the
    worker's log it also says what JAX did inside it: programs read from the
    persistent cache (`cache_hits`), compiled and written to it
    (`cache_misses`), all backend compiles or cache reads (`compiles`) and
    the seconds they took (`compile_s`). Process-wide counts: a compile on
    another thread at the same time is counted too."""
    import jax

    global _listening
    with _compiled_lock:
        if not _listening:
            _listening = True
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
        before = dict(_compiled)
    with span(name, **args) as out:
        t0 = time.monotonic()
        try:
            yield out
        finally:
            out.update({k: round(v - before[k], 3)
                        for k, v in _compiled.items()})
            get_logger("tracing").info(
                "%s %.3fs %s", name, time.monotonic() - t0, out)

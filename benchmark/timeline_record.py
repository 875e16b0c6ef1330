"""The program's spans over the WHOLE run, read from the session's timeline.

`program_trace.py` reads the spans the profiler caught in the 4 s it was on.
The same spans also go, in every run and every process, to the controller's
timeline (ray_tpu/utils/tracing.py, its second sink), and since PR 51
`ray_tpu.shutdown()` writes that timeline to `<session_dir>/timeline.json`
before it stops the node. Every span on it carries `mono_ns`,
CLOCK_MONOTONIC at its end: the clock of the run record's `t0`, `marks` and
`outcomes`, on the one host. So a reader over this file cuts the window by
the run's own stamps and takes a p95 over every request of the 51 s.

`load(run)` finds the dump through the program's own function
(`ray_tpu.state.load_timeline`: the last session this process shut down),
keeps the program's spans (`cat` "program") and the task events, and says
what the record lacks: `dropped` spans, the latest of them at
`dropped_until`. A reader whose interval begins before that instant returns
None, never a number from a partial record. On a program without the dump
(the parent of PR 51) `load` returns None, raises nothing, and so does every
reader over it.

    python3 benchmark/timeline_record.py <session dir> [run-trace*.json]

prints the spans by name, the dropped count and, given the run's record (or
finding the one under benchmark/out/ whose `t0` lies inside the session),
the eleven numbers, the set-up's sum against `setup_s`, the requests' five
boundaries and, where the record has the trace's marks, the two twins cut to
the traced seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark.stats import median, percentile  # noqa: E402
from benchmark.trace import merge, total  # noqa: E402

ADMIT = "serve.engine.admit"
EMIT = "serve.engine.emit"
DISPATCH = "serve.engine.decode_dispatch"
PROXY = "serve.proxy.request"
CALL = "serve.replica.call"
WARM = ("serve.engine.warm", "train.compile")
Interval = Tuple[float, float]


@dataclasses.dataclass
class Span:
    name: str
    start: float          # s, CLOCK_MONOTONIC
    end: float
    args: Dict[str, Any]


@dataclasses.dataclass
class Record:
    spans: List[Span]                 # the program's, whole session, by start
    tasks: List[Dict[str, Any]]       # the timeline's task events, as given
    dropped: int = 0
    dropped_until: float = 0.0        # s: latest `mono_ns` among the dropped
    others: Dict[str, int] = dataclasses.field(default_factory=dict)

    def whole(self, since: float) -> bool:
        """Nothing the program recorded from `since` on is missing."""
        return not self.dropped or self.dropped_until < since

    def named(self, name: str, window: Optional[Interval] = None,
              inside: bool = False, **where: Any) -> List[Span]:
        """Spans of that name that START in `window`; with `inside`, that
        also end in it (what a profiler session over it would have kept)."""
        lo, hi = window or (float("-inf"), float("inf"))
        return [s for s in self.spans if s.name == name and lo <= s.start < hi
                and (not inside or s.end <= hi)
                and all(s.args.get(k) == v for k, v in where.items())]


def of_events(events: Iterable[Dict[str, Any]]) -> Record:
    """The record of a timeline's event list (`state.timeline()`'s, live or
    read back)."""
    rec = Record([], [])
    for e in events:
        cat = e.get("cat")
        args = e.get("args") or {}
        if cat == "program" and "mono_ns" in args:
            end = args["mono_ns"] / 1e9
            rec.spans.append(Span(e["name"], end - e.get("dur", 0.0) / 1e6,
                                  end, args))
        elif cat == "task":
            rec.tasks.append(e)
        elif e.get("ph") == "M" and e.get("name") == "program_spans":
            rec.dropped = int(args.get("dropped", 0))
            rec.dropped_until = args.get("dropped_until_mono_ns", 0) / 1e9
        else:
            rec.others[e.get("name", "?")] = \
                rec.others.get(e.get("name", "?"), 0) + 1
    rec.spans.sort(key=lambda s: s.start)
    return rec


def load_path(path: Optional[str]) -> Optional[Record]:
    """The dump of a session directory (None: the last session this process
    shut down); None where the program writes none, or wrote none."""
    try:
        from ray_tpu import state
    except ImportError:
        return None
    read = getattr(state, "load_timeline", None)
    events = read(path) if read else None
    return of_events(events) if events is not None else None


_loaded: Dict[Tuple, Optional[Record]] = {}


def load(run: Dict[str, Any]) -> Optional[Record]:
    """The record of the run this process just made; one parse for the
    eleven readers."""
    key = (run.get("cell"), run.get("seed"), run.get("t0"))
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = load_path(None)
    return _loaded[key]


def window(run: Dict[str, Any]) -> Interval:
    return run["t0"], run["t0"] + run["seconds"]


def in_window(run: Dict[str, Any]) -> Optional[Tuple[Record, Interval]]:
    """(record, the measured window), or None where a reader over the window
    has nothing whole to read."""
    rec = load(run)
    if rec is None or not rec.whole(run["t0"]):
        return None
    return rec, window(run)


# -- the set-up ---------------------------------------------------------------

def setup_parts(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """`setup_s` in three parts that add up to it. `boot`: process start
    (`t0` less `setup_s`) to the start of the first `serve.engine.warm` /
    `train.compile` span: cluster, proxy, worker, TPU runtime start, weights.
    `warm`: the union of those spans. `check`: what is left from the first
    one's start to `t0`, outside every one of them: the readiness poll and
    the benchmark's reference check after the last (a serve cell), or the
    check between the two compiles and the warm steps after them (a train
    job); it has no span of its own. `compile` (inside `warm`): the sum of
    the spans' `compile_s`, with `cache_misses` beside it."""
    rec = load(run)
    start = run["t0"] - run["setup_s"]
    if rec is None or not rec.whole(start):
        return None
    warm = [s for name in WARM
            for s in rec.named(name, (start, run["t0"]), inside=True)]
    if not warm:
        return None
    first = min(s.start for s in warm)
    union = total(merge([(s.start, s.end) for s in warm]))
    return {"boot": first - start, "warm": union,
            "check": run["t0"] - first - union,
            "compile": sum(s.args.get("compile_s", 0.0) for s in warm),
            "cache_misses": sum(s.args.get("cache_misses", 0) for s in warm),
            "programs": len(warm)}


def setup_part(run: Dict[str, Any], part: str) -> Optional[float]:
    parts = setup_parts(run)
    return None if parts is None else parts[part]


# -- a request's path ---------------------------------------------------------

@dataclasses.dataclass
class Path:
    """One request of the window by its `trace_id`: the five boundaries, in
    seconds. `written` is the proxy span's start plus its `first_chunk_us`."""
    trace_id: str
    proxy: float
    call: float
    admit: float
    first: float
    written: float

    def in_order(self) -> bool:
        return self.proxy <= self.call <= self.admit <= self.first \
            <= self.written


def paths(run: Dict[str, Any]) -> Optional[List[Path]]:
    """The streamed requests whose proxy span opened in the window and whose
    first item was written, each with the spans of its trace; None without
    a whole record or for a program whose proxy says no `first_chunk_us`."""
    got = in_window(run)
    if got is None:
        return None
    rec, win = got
    by_trace: Dict[str, Dict[str, Span]] = {}
    for s in rec.spans:
        tid = s.args.get("trace_id")
        kind = {CALL: "call", ADMIT: "admit"}.get(s.name) or (
            "first" if s.name == EMIT and s.args.get("kind") == "first"
            else None)
        if tid and kind:
            by_trace.setdefault(tid, {}).setdefault(kind, s)
    out = []
    for p in rec.named(PROXY, win):
        mine = by_trace.get(p.args.get("trace_id"), {})
        if "first_chunk_us" in p.args and len(mine) == 3:
            out.append(Path(p.args["trace_id"], p.start, mine["call"].start,
                            mine["admit"].start, mine["first"].end,
                            p.start + p.args["first_chunk_us"] / 1e6))
    return out or None


def path_p95_ms(run: Dict[str, Any], a: str, b: str) -> Optional[float]:
    """p95 over the window's requests of boundary `b` less boundary `a`."""
    ps = paths(run)
    if not ps:
        return None
    return percentile([(getattr(p, b) - getattr(p, a)) * 1e3 for p in ps],
                      95.0)


# -- the engine over the window -----------------------------------------------

def spans(run: Dict[str, Any], name: str,
          interval: Optional[Interval] = None, inside: bool = False,
          **where: Any) -> Optional[List[Span]]:
    """The spans `name` of the measured window (or of `interval`, inside
    it); None where the record is not whole from the window's start."""
    got = in_window(run)
    if got is None:
        return None
    return got[0].named(name, interval or got[1], inside, **where)


def occupancy_pct(chunks: Optional[List[Span]]) -> Optional[float]:
    capacity = sum(s.args["capacity"] for s in chunks or [])
    if not capacity:
        return None
    return 100.0 * sum(s.args["useful"] for s in chunks) / capacity


def slot_refill_ms(prefills: Optional[List[Span]],
                   since: Optional[float] = None) -> Optional[float]:
    """The MEAN of `slot_idle_us` over the admissions that refilled a slot (a
    first tenant reads 0 and is left out): `engine_slot_refill_ms`'
    statistic, see its docstring for why not the median. Given `since`, a
    slot freed before that instant is left out as a first tenant is: over the
    whole window the slots the check's requests left read the seconds since
    the check, not a refill's wait, and a few of them are the whole mean
    (48-67 ms on `serve-generate-lfm2` where the 4 s read 0.9-2.5: PERF.md,
    PR 51)."""
    refills = [s.args["slot_idle_us"] / 1e3 for s in prefills or []
               if s.args.get("slot_idle_us") and (
                   since is None
                   or s.start - s.args["slot_idle_us"] / 1e6 >= since)]
    return sum(refills) / len(refills) if refills else None


def admit_to_first_ms(run: Dict[str, Any]) -> Optional[List[float]]:
    """Per prefill admitted in the window: end of its first token's emit less
    the start of its admit span (paired by `rid`), ms."""
    got = in_window(run)
    if got is None:
        return None
    rec, win = got
    firsts = {s.args.get("rid"): s for s in rec.named(EMIT, kind="first")}
    return [(firsts[a.args["rid"]].end - a.start) * 1e3
            for a in rec.named(ADMIT, win, kind="prefill")
            if a.args.get("rid") in firsts]


def _inside(tokens: float, start: float, end: float, win: Interval) -> float:
    """The part of `tokens`, spread evenly over start..end, inside `win`."""
    if end <= start:
        return tokens if win[0] <= end < win[1] else 0.0
    return tokens * max(0.0, min(end, win[1]) - max(start, win[0])) \
        / (end - start)


def window_tokens(run: Dict[str, Any],
                  win: Optional[Interval] = None) -> Optional[float]:
    """Tokens inside the window by the program's own count, continuous: a
    prefill's `prompt_tokens`, its first token and its `riders` spread evenly
    over its admit's start to its first token's emit end; a chunk's `useful`
    over its dispatch's start to its emit's end (the n-th `kind` chunk emit
    is the n-th dispatch's: one emitter, in order). Each counted by the part
    of its interval inside the window, so no prompt lands on an instant.
    Pairing by order needs the whole session: None after any drop."""
    rec = load(run)
    if rec is None or rec.dropped:
        return None
    win = win or window(run)
    firsts = {s.args.get("rid"): s for s in rec.named(EMIT, kind="first")}
    tokens = 0.0
    for a in rec.named(ADMIT, kind="prefill"):
        first = firsts.get(a.args.get("rid"))
        if first is not None:
            tokens += _inside(a.args["prompt_tokens"] + 1
                              + a.args.get("riders", 0),
                              a.start, first.end, win)
    chunks = rec.named(DISPATCH)
    if not chunks:
        return None
    for d, e in zip(chunks, rec.named(EMIT, kind="chunk")):
        tokens += _inside(d.args["useful"], d.start, e.end, win)
    return tokens


# -- by hand -------------------------------------------------------------------

READERS = ("setup_boot_s", "setup_warm_s", "setup_compile_s", "setup_check_s",
           "service_ingress_p95_ms", "service_egress_p95_ms",
           "engine_queue_wait_p95_ms", "engine_admit_to_first_p95_ms",
           "decode_occupancy_window_pct", "engine_slot_refill_window_ms",
           "engine_window_tokens_per_s")


def _find_run(rec: Record) -> Optional[str]:
    """The run record under benchmark/out/ whose `t0` the session holds."""
    if not rec.spans:
        return None
    lo, hi = rec.spans[0].start, max(s.end for s in rec.spans)
    for path in sorted(glob.glob(os.path.join(HERE, "out", "*", "*",
                                              "run-trace*.json")),
                       key=os.path.getmtime, reverse=True):
        with open(path) as f:
            t0 = json.load(f).get("t0")
        if t0 is not None and lo <= t0 <= hi:
            return path
    return None


def _nearest(stamps: List[float], at: List[float],
             within: float) -> List[Tuple[int, int]]:
    """(i, j): `at[j]` is the nearest to `stamps[i]`, within `within` s."""
    out = []
    for i, t in enumerate(stamps):
        j = min(range(len(at)), key=lambda k: abs(at[k] - t), default=None)
        if j is not None and abs(at[j] - t) <= within:
            out.append((i, j))
    return out


def main(argv: List[str]) -> int:
    from benchmark.run import HERE as bench_dir, load_reader
    rec = load_path(argv[1])
    if rec is None:
        print(f"no timeline.json under {argv[1]} (or a program without "
              f"`state.load_timeline`)")
        return 1
    by_name: Dict[str, List[float]] = {}
    for s in rec.spans:
        by_name.setdefault(s.name, []).append((s.end - s.start) * 1e3)
    for name, d in sorted(by_name.items()):
        print(f"span {name}: {len(d)} median {median(d):.3f} ms "
              f"total {sum(d):.1f} ms")
    print(f"program spans {len(rec.spans)}, dropped {rec.dropped}"
          + (f" (the latest at {rec.dropped_until:.3f} s)" if rec.dropped
             else "") + f"; task events {len(rec.tasks)}; others "
          f"{sum(rec.others.values())}: " + ", ".join(
              f"{n} {c}" for n, c in sorted(rec.others.items(),
                                            key=lambda kv: -kv[1])[:12]))
    path = argv[2] if len(argv) > 2 else _find_run(rec)
    if not path:
        print("no run record given or found: the readers need its `t0`, "
              "`seconds` and `setup_s`")
        return 0
    with open(path) as f:
        run = json.load(f)
    print(f"run record {path}: {run['cell']} seed {run['seed']}")
    _loaded[(run.get("cell"), run.get("seed"), run.get("t0"))] = rec
    for name in READERS:
        value = load_reader(bench_dir, "layer_metrics", name)(run)
        print(f"{name}: {value}")
    parts = setup_parts(run)
    if parts:
        s = parts["boot"] + parts["warm"] + parts["check"]
        print(f"set-up: boot + warm + check = {s:.3f} s of setup_s "
              f"{run['setup_s']:.3f} ({100 * s / run['setup_s']:.2f}%), "
              f"{parts['programs']} programs warmed, cache_misses "
              f"{parts['cache_misses']}")
    ps = paths(run) or []
    if ps:
        ms = {k: median([(getattr(p, b) - getattr(p, a)) * 1e3 for p in ps])
              for k, a, b in (("ingress", "proxy", "call"),
                              ("entry_and_queue", "call", "admit"),
                              ("admit_to_first", "admit", "first"),
                              ("egress", "first", "written"))}
        print(f"{len(ps)} requests, {sum(p.in_order() for p in ps)} with "
              f"their five boundaries in order; medians, ms: {ms}")
        names = ("proxy", "call", "admit", "first", "written")
        for p in ps:
            for a, b in zip(names, names[1:]):
                if getattr(p, b) < getattr(p, a):
                    print(f"    {p.trace_id[:8]}: {b} before {a} by "
                          f"{(getattr(p, a) - getattr(p, b)) * 1e3:.3f} ms")
        firsts = sorted(o["t_first"] for o in run.get("outcomes", [])
                        if o.get("t_first"))
        pairs = _nearest([p.written for p in ps], firsts, 0.05)
        if pairs:
            print(f"client's first byte less proxy's first write, "
                  f"{len(pairs)} requests: median "
                  f"{median([(firsts[j] - ps[i].written) * 1e3 for i, j in pairs]):.3f} ms")
        stamps = sorted((run.get("replica", {}).get("stamps") or {}).values())
        pairs = _nearest([p.call for p in ps], [s[0] for s in stamps], 0.05)
        if pairs:
            print(f"stamps' entry -> first token less the spans' call -> "
                  f"first emit, {len(pairs)} requests: median "
                  f"{median([((stamps[j][1] - stamps[j][0]) - (ps[i].first - ps[i].call)) * 1e3 for i, j in pairs]):.3f} ms")
    marks = run.get("marks") or {}
    if "trace_start" in marks and "trace_stop" in marks:
        cut = (marks["trace_start"], marks["trace_stop"])
        for name, mine in (
                ("decode_occupancy_pct",
                 occupancy_pct(spans(run, DISPATCH, cut, inside=True))),
                ("engine_slot_refill_ms",
                 slot_refill_ms(spans(run, ADMIT, cut, inside=True,
                                      kind="prefill")))):
            twin = load_reader(bench_dir, "layer_metrics", name)(run)
            print(f"cut to the traced {cut[1] - cut[0]:.3f} s: {name} "
                  f"{mine} here, {twin} by the profiler's reader")
    return 0


if __name__ == "__main__":
    # The readers import `benchmark.timeline_record`: run its copy, so that
    # the record parsed here is the one they find.
    from benchmark import timeline_record
    sys.exit(timeline_record.main(sys.argv))

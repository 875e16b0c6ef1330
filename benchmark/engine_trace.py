"""The chip's idle time, put down to what the engine's loop was doing.

`trace.py` names a gap on the chip by the `bench.*` span of the HARNESS that
covers it, and a replica thread is always inside `bench.stream_get`: every
gap of every serve line reads so. The program says more (PR 37): its loop
thread (ray_tpu/serve/engine.py::_run_inner) is at every instant inside one
of four spans or in the few lines of host arithmetic between them,

    serve.engine.idle             no slot is live: nothing to do
    serve.engine.emit_block       a chunk is dispatched, the loop waits for
                                  pipeline room (the emitter's fetch)
    serve.engine.admit            a request is placed, its prefill dispatched
    serve.engine.decode_dispatch  a chunk is put together and dispatched

and `program_trace.load` has those spans and chip 0's instructions from ONE
parse, on one clock. `serve.engine.admit` spans nest inside the first two
(the loop admits while it stands), so a state's time is its SELF time: the
span less the admit spans inside it. `program_trace.Span` keeps no thread;
only the loop thread emits these four, and the emitter's `serve.engine.emit`
spans, which overlap them in time, are left alone.

A program without `serve.engine.idle` (the parent of PR 37) gives every gap
outside its other spans to `(no span)`, and the readers over this file
return None for it.

    python3 benchmark/engine_trace.py <dir or .xplane.pb> [n_slots]

prints the ten longest gaps by those names and the two shares of the traced
window: idle with no request, and idle with work somewhere in the engine;
given the engine's slots, also the share of slot-time left unfilled, by the
admit spans' `slot_idle_us` and by the dispatch spans' `active`, and the
share of it that prefills stalled.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import program_trace, trace  # noqa: E402
from benchmark.stats import median  # noqa: E402
from benchmark.trace import Interval, merge, subtract  # noqa: E402

IDLE = "serve.engine.idle"
ADMIT = "serve.engine.admit"
EMIT_BLOCK = "serve.engine.emit_block"
STATES = (IDLE, EMIT_BLOCK, ADMIT, "serve.engine.decode_dispatch")
NO_SPAN = "(no span)"
Piece = Tuple[str, float, float]        # (state, start_ns, end_ns)


def self_intervals(t: program_trace.ProgramTrace, name: str,
                   window: Optional[Interval] = None) -> List[Interval]:
    """The intervals of the spans named `name`, less the
    `serve.engine.admit` spans that fall inside them. Given the traced
    `window`, `serve.engine.idle` also gets the two stands the profiler
    cannot show (`edge_idles`)."""
    own = [(s.start, s.end) for s in t.named(name)]
    if name == ADMIT:
        return merge(own)
    if name == IDLE and window:
        own += edge_idles(t, window)
    return subtract(own, [(s.start, s.end) for s in t.named(ADMIT)])


def edge_idles(t: program_trace.ProgramTrace,
               window: Interval) -> List[Interval]:
    """A span that is open when the trace starts or stops leaves no event,
    and an idle engine's stand is the longest span there is (in `serve-chat`
    a trace starts inside one every other run). The loop's cycle says where
    it stood: an idle stand ends in an admission that found no slot decoding
    (`decoding` 0, recorded: it opened inside the trace), so a window whose
    first loop span is such an admit began in one; and a loop that leaves
    `serve.engine.emit_block` either dispatches within microseconds or has no
    slot left and stands idle, so a window whose last loop span is an
    `emit_block` ended in one."""
    loop = [s for s in t.spans if s.name in STATES]
    if not loop:
        return []
    out = []
    first, last = loop[0], max(loop, key=lambda s: s.end)
    if first.name == ADMIT and first.args.get("decoding") == 0:
        out.append((window[0], first.start))
    if last.name == EMIT_BLOCK:
        out.append((last.end, window[1]))
    return [(s, e) for s, e in out if s < e]


def window_of(t: program_trace.ProgramTrace,
              data: Optional[trace.Trace] = None) -> Interval:
    """The traced window by `trace.py`'s rule (first to last event of the
    first file: what `window_s` of the run record spans, the two parses being
    on one clock), or, without its view, first to last of what `t` holds."""
    if data is not None:
        return data.t_min, data.t_max
    edges = [(s.start, s.end) for s in t.spans] + \
        [(s, e) for _, s, e in t.modules + t.ops]
    return min(s for s, _ in edges), max(e for _, e in edges)


def idle_gaps(t: program_trace.ProgramTrace,
              window: Optional[Interval] = None) -> List[Piece]:
    """`window` (default: `window_of(t)`) less chip 0's instructions, each
    gap cut where the loop's state changes: pieces by start, their lengths
    summing to the chip's idle time in the window."""
    window = window or window_of(t)
    gaps = subtract([window], [(s, e) for _, s, e in t.ops])
    pieces: List[Piece] = []
    rest = gaps
    for state in STATES:
        mine = self_intervals(t, state, window)
        pieces += [(state, s, e) for s, e in subtract(rest, subtract(rest, mine))]
        rest = subtract(rest, mine)
    pieces += [(NO_SPAN, s, e) for s, e in rest]
    return sorted(pieces, key=lambda p: p[1])


def shares(t: Optional[program_trace.ProgramTrace],
           data: Optional[trace.Trace] = None,
           pieces: Optional[List[Piece]] = None
           ) -> Optional[Dict[str, float]]:
    """Percent of the traced window in which chip 0 ran nothing, by the
    loop's state (`pieces`: `idle_gaps` of that window, if the caller has
    them); None without a device plane, or for a program that cannot tell
    the two idles apart (no `serve.engine.idle` span and no `decoding` on its
    admit spans: the parent of PR 37)."""
    if t is None or not t.ops or not (t.named(IDLE) or any(
            "decoding" in s.args for s in t.named(ADMIT))):
        return None
    window = window_of(t, data)
    out = {state: 0.0 for state in STATES + (NO_SPAN,)}
    for state, s, e in pieces or idle_gaps(t, window):
        out[state] += 100.0 * (e - s) / (window[1] - window[0])
    return out


def with_work_pct(per_state: Dict[str, float]) -> float:
    return sum(v for state, v in per_state.items() if state != IDLE)


def longest(pieces: List[Piece], k: int = 10
            ) -> List[Tuple[float, List[Tuple[str, float]]]]:
    """The `k` longest gaps of `idle_gaps`' pieces (pieces that touch are one
    gap): (seconds, [(state, seconds inside it), longest first])."""
    out: List[Tuple[float, Dict[str, float]]] = []
    end = None
    for state, s, e in pieces:
        if s != end:
            out.append((0.0, {}))
        length, by_state = out[-1]
        by_state[state] = by_state.get(state, 0.0) + (e - s) / 1e9
        out[-1] = (length + (e - s) / 1e9, by_state)
        end = e
    return [(length, sorted(by_state.items(), key=lambda kv: -kv[1]))
            for length, by_state in sorted(out, key=lambda g: -g[0])[:k]]


def slot_refill(t: program_trace.ProgramTrace) -> List[float]:
    """`slot_idle_us` of the prefill admissions that refilled a slot (a
    slot's first tenant reads 0 and is left out), in ms."""
    return [s.args["slot_idle_us"] / 1e3
            for s in t.named(ADMIT, kind="prefill")
            if s.args.get("slot_idle_us")]


def stalled_slot_ns(t: program_trace.ProgramTrace) -> Optional[float]:
    """Over the prefills paired with their admit spans: device time of the
    `jit_prefill` execution x the slots that were decoding when it was
    admitted, summed. None where the admit spans carry no `decoding`."""
    pairs = [(admit, run) for admit, run, _ in t.prefills()
             if "decoding" in admit.args]
    if not pairs:
        return None
    return sum((e - s) * admit.args["decoding"] for admit, (_, s, e) in pairs)


def main(argv: List[str]) -> int:
    t = program_trace.load_path(argv[1])
    data = trace.load(argv[1]) if t and t.ops else None
    if t is None or data is None:
        print(f"no *.xplane.pb with a device plane under {argv[1]}")
        return 1
    window = window_of(t, data)
    seconds = (window[1] - window[0]) / 1e9
    print(f"window {seconds:.3f} s, chip 0 idle "
          f"{100.0 * (1.0 - data.busy_s / data.window_s):.3f}% "
          f"(1 - busy_s/window_s, as the run record has it)")
    pieces = idle_gaps(t, window)
    for gap_s, parts in longest(pieces):
        print(f"    gap {gap_s:10.6f} s  " + "  ".join(
            f"{state} {sec:.6f}" for state, sec in parts))
    per = shares(t, data, pieces)
    if per is None:
        print(f"no {IDLE} span, no `decoding`: a program before PR 37")
        return 0
    for state, pct in per.items():
        print(f"idle under {state:30s}{pct:8.3f}% of the window")
    print(f"no request (idle under {IDLE}): {per[IDLE]:.3f}%")
    print(f"idle_with_work_pct: {with_work_pct(per):.3f}")
    if len(argv) > 2:       # the engine's slots: S14's two views, S4's stall
        slots = int(argv[2])
        refills = slot_refill(t)
        chunks = t.named("serve.engine.decode_dispatch")
        stalled = stalled_slot_ns(t)
        by_refill = 100.0 * sum(refills) / 1e3 / (seconds * slots)
        by_active = 100.0 * (1.0 - sum(c.args["active"] for c in chunks)
                             / max(len(chunks), 1) / slots)
        print(f"{len(refills)} refills, mean "
              f"{sum(refills) / max(len(refills), 1):.3f} ms (median "
              f"{median(refills)}), sum "
              f"{sum(refills) / 1e3:.3f} s: {by_refill:.2f}% of slot-time "
              f"unfilled; by `active` of the {len(chunks)} dispatch spans "
              f"{by_active:.2f}%")
        if stalled is not None:
            print(f"prefill_stall_pct: "
                  f"{100.0 * stalled / 1e9 / (seconds * slots):.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

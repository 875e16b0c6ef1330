"""From a profiler trace (`*.xplane.pb`) to numbers. Needs no device: the
parent reads what the process that held the chip wrote.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, with lines `XLA Modules` (one event per executed program,
named `jit_<fn>(<hash>)`), `XLA Ops` (one event per HLO instruction executed,
named by its whole HLO text, control flow such as `while` enclosing its body's
events) and `Async XLA Ops` (copies and collectives in flight); and
`/host:CPU`, one line per thread, where `jax.profiler.TraceAnnotation` spans
appear under their own names. All on one clock, in nanoseconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
COLLECTIVE = re.compile(
    r"^%?(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute)")
CONTROL = re.compile(r"^%?(while|conditional|call)[.\d]* = ")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of a (merged) that no interval of b covers."""
    out: List[Interval] = []
    b = merge(b)
    j = 0
    for s, e in merge(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def short_name(hlo_text: str) -> str:
    """`%fusion.12 = bf16[..] fusion(...)` -> `fusion.12 bf16[..]`."""
    name, _, rest = hlo_text.partition(" = ")
    shape = rest.split(" ")[0] if rest else ""
    return (name.lstrip("%") + (" " + shape[:48] if shape else ""))[:96]


@dataclasses.dataclass
class Chip:
    name: str
    ops: List[Tuple[str, float, float]]        # XLA Ops: (hlo text, start, end)
    async_ops: List[Tuple[str, float, float]]
    modules: List[Tuple[str, float, float]]    # (jit name without hash, s, e)

    def busy(self) -> List[Interval]:
        return merge([(s, e) for _, s, e in self.ops])

    def self_times(self) -> Dict[str, float]:
        """Seconds per instruction, a parent's time less its children's."""
        out: Dict[str, float] = {}
        stack: List[List] = []   # [name, end, self_ns]

        def close(upto: float) -> None:
            while stack and stack[-1][1] <= upto:
                name, _, self_ns = stack.pop()
                out[name] = out.get(name, 0.0) + self_ns / 1e9

        for name, s, e in sorted(self.ops, key=lambda o: (o[1], -(o[2]))):
            close(s)
            if stack:
                stack[-1][2] -= (e - s)
            stack.append([name, e, e - s])
        close(float("inf"))
        return out


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    host_spans: List[Tuple[str, float, float]]   # bench.* annotations
    t_min: float
    t_max: float

    @property
    def window_s(self) -> float:
        return (self.t_max - self.t_min) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran on the device, averaged over the chips."""
        return sum(total(c.busy()) for c in self.chips) / len(self.chips) / 1e9

    def module_durations(self, prefix: str) -> List[float]:
        """Seconds of each execution of the programs named `prefix*`, chip 0."""
        return [(e - s) / 1e9 for n, s, e in self.chips[0].modules
                if n.startswith(prefix)]

    def top_ops(self, k: int = 10) -> List[List]:
        times = self.chips[0].self_times()
        agg: Dict[str, float] = {}
        for hlo, sec in times.items():
            agg[short_name(hlo)] = agg.get(short_name(hlo), 0.0) + sec
        return [[n, s] for n, s in sorted(agg.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The longest gaps with nothing on chip 0, each named by the bench.*
        host span that covers most of it."""
        gaps = subtract([(self.t_min, self.t_max)], self.chips[0].busy())
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            best, best_ov = "(no bench span open)", 0.0
            for name, hs, he in self.host_spans:
                ov = min(e, he) - max(s, hs)
                if ov > best_ov:
                    best, best_ov = name, ov
            out.append([best, (e - s) / 1e9])
        return out

    def collective_exposed_s(self) -> float:
        """Seconds, averaged over chips, in which a collective was in flight
        and no other instruction ran."""
        acc = 0.0
        for c in self.chips:
            coll = [(s, e) for n, s, e in c.ops + c.async_ops
                    if COLLECTIVE.match(n)]
            compute = [(s, e) for n, s, e in c.ops
                       if not COLLECTIVE.match(n) and not CONTROL.match(n)]
            acc += total(subtract(coll, compute))
        return acc / len(self.chips) / 1e9


def find_xplanes(path: str) -> List[str]:
    if os.path.isfile(path):
        return [path]
    return sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))


def load(path: str) -> Optional[Trace]:
    """Every `*.xplane.pb` under `path` (one per traced process), or None."""
    import jax  # ProfileData only: no backend is initialised

    files = find_xplanes(path)
    if not files:
        return None
    chips: List[Chip] = []
    spans: List[Tuple[str, float, float]] = []
    t_min, t_max = float("inf"), float("-inf")
    for i, f in enumerate(files):
        data = jax.profiler.ProfileData.from_file(f)
        for plane in data.planes:
            is_dev = plane.name.startswith("/device:TPU")
            if not is_dev and plane.name != "/host:CPU":
                continue
            lines: Dict[str, List[Tuple[str, float, float]]] = {}
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if not evs:
                    continue
                # The first file bounds the window and names the gaps: every
                # process's trace has its own time origin.
                if i == 0:
                    t_min = min(t_min, min(s for _, s, _ in evs))
                    t_max = max(t_max, max(e for _, _, e in evs))
                if is_dev:
                    lines.setdefault(line.name, []).extend(evs)
                elif i == 0:   # each process's trace has its own time origin
                    spans.extend(ev for ev in evs if ev[0].startswith("bench."))
            if is_dev and lines.get("XLA Ops"):
                chips.append(Chip(
                    f"{os.path.basename(f)}{plane.name}", lines["XLA Ops"],
                    lines.get("Async XLA Ops", []),
                    [(n.split("(")[0], s, e)
                     for n, s, e in lines.get("XLA Modules", [])]))
    if not chips:
        return None
    return Trace(chips, spans, t_min, t_max)


def attach(run: dict, data: Optional[Trace]) -> None:
    """Put a reduced trace into a run record: the device's busy time and the
    traced window's length, and the breakdown the ledger keeps."""
    run["trace_data"] = data
    if data is None:         # a rehearsal on the CPU has no device plane
        return
    run["device"]["busy_s"] = data.busy_s
    run["device"]["window_s"] = data.window_s
    run["breakdown"] = {"device_ops": data.top_ops(10),
                        "idle_gaps": data.idle_gaps(10)}


def plan(ctx: dict) -> Optional[Dict[str, float]]:
    """When to trace inside the window, from the mix's `trace` block; never
    more than a third of a short window. None without `--trace 1`."""
    if not ctx["trace"]:
        return None
    t = ctx["traffic"]["trace"]
    third = ctx["seconds"] / 3.0
    return {"start_s": min(float(t["start_s"]), third),
            "seconds": min(float(t["seconds"]), third)}

"""The program's own spans and scopes, read from a profiler trace.

`trace.py` reduces a trace to what the device did and keeps the harness's
`bench.*` spans. This file reads what the PROGRAM says about itself in the
same `.xplane.pb` (ray_tpu/utils/tracing.py, PR 24):

* host spans named `serve.*`, `train.*`, `data.*` on `/host:CPU`, with the
  keyword arguments they were opened with as the event's stats;
* the `jax.named_scope` path of every device instruction. On a TPU it is the
  `tf_op` stat of the event's METADATA (`jit(decode)/while/body/layers/while/
  body/attn/dot_general:`, a backward op `transpose(jvp(attn))`), which
  `jax.profiler.ProfileData` does not expose: so the file is parsed as a
  protobuf, with the few fields of tsl's `xplane.proto` that are needed
  declared here (importing tensorflow for its copy takes 8 s).

A program without the spans or scopes (the parent of PR 24) gives empty lists
and every reader over this file returns None. A CPU trace (`--rehearse`) has
the host spans and no device plane.

    python3 benchmark/program_trace.py <dir or .xplane.pb>    # what is in it
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark.stats import median  # noqa: E402
from benchmark.trace import find_xplanes  # noqa: E402

SPAN_PREFIXES = ("serve.", "train.", "data.")
# The one vocabulary of scope names in serve/engine.py, models/llama.py and
# train/spmd.py. `layers` encloses the others.
SCOPES = ("embed", "attn_norm", "qkv", "rope", "kv_write", "kv_gather",
          "attn", "attn_out", "mlp_norm", "mlp", "head", "loss", "sample",
          "optimizer", "layers")
_WORD = re.compile(r"[A-Za-z_]\w*")


@dataclasses.dataclass
class Span:
    name: str
    start: float          # ns, on the trace's clock
    end: float
    args: Dict[str, Any]


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]                            # by start
    modules: List[Tuple[str, float, float]]      # chip 0: (jit name, s, e)
    ops: List[Tuple[str, float, float]]          # chip 0: (scope path, s, e)
    _by_scope: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def named(self, name: str, **where: Any) -> List[Span]:
        return [s for s in self.spans if s.name == name
                and all(s.args.get(k) == v for k, v in where.items())]

    def whole_modules(self, prefix: str) -> List[Tuple[str, float, float]]:
        """Executions of the programs named `prefix*`, without the device's
        first and last program of the trace: the window's edges cut them."""
        return [m for m in self.modules[1:-1] if m[0].startswith(prefix)]

    def scope_ms(self, prefix: str) -> Optional[Dict[str, float]]:
        """Device self-time, in ms an execution, of the programs named
        `prefix*`, by the deepest scope of each instruction ('' for none; a
        `while` is charged what its body's instructions leave; `(idle)` is
        the time inside an execution in which nothing ran). Each value is the
        median over the executions: a fixed-shape program costs the same
        every time, so the values sum to its duration, and an execution whose
        events the profiler merged or dropped (seen at a trace's end) does
        not move them."""
        if prefix not in self._by_scope:     # several readers ask for one
            self._by_scope[prefix] = self._scope_ms(prefix)
        return self._by_scope[prefix]

    def _scope_ms(self, prefix: str) -> Optional[Dict[str, float]]:
        mods = self.whole_modules(prefix)
        if not mods or not self.ops:
            return None
        each: List[Dict[str, float]] = []
        i = 0
        for _, ms, me in mods:
            while i < len(self.ops) and self.ops[i][1] < ms:
                i += 1
            out: Dict[str, float] = {}
            stack: List[List] = []      # [scope, end, self_ns]
            busy = 0.0
            while i < len(self.ops) and self.ops[i][1] < me:
                path, s, e = self.ops[i]
                while stack and stack[-1][1] <= s:
                    scope, _, self_ns = stack.pop()
                    out[scope] = out.get(scope, 0.0) + self_ns
                if stack:
                    stack[-1][2] -= e - s
                else:
                    busy += e - s
                stack.append([deepest_scope(path), e, e - s])
                i += 1
            for scope, _, self_ns in stack:
                out[scope] = out.get(scope, 0.0) + self_ns
            out["(idle)"] = (me - ms) - busy
            each.append(out)
        return {k: median([o.get(k, 0.0) for o in each]) / 1e6
                for k in {k for o in each for k in o}}

    def per_step_ms(self, names: Iterable[str]) -> Optional[float]:
        """Median over the `train.step` spans of the trace, but the first, of
        the time inside spans of `names` that ended since the step before."""
        steps = self.named("train.step")
        mine = [s for s in self.spans if s.name in set(names)]
        if len(steps) < 2 or not mine:
            return None
        sums = [sum(s.end - s.start for s in mine
                    if before.start < s.end <= step.start)
                for before, step in zip(steps, steps[1:])]
        return median(sums) / 1e6

    def prefills(self) -> List[Tuple[Span, Tuple[str, float, float],
                                     Optional[Span]]]:
        """(admit span, its `jit_prefill` execution on the device, its first
        `serve.engine.emit` span or None), for the requests prefilled inside
        the trace. The engine admits, prefills and emits in one order, so the
        pairing is by position: prefills of requests admitted before the
        trace began (their first emits carry a smaller `rid` than any admit
        here) are skipped at the head, admits whose prefill ran after the
        trace ended fall off the tail, and a pair that is not admit <=
        prefill <= emit in time is dropped, not trusted."""
        admits = self.named("serve.engine.admit", kind="prefill")
        firsts = {s.args.get("rid"): s
                  for s in self.named("serve.engine.emit", kind="first")}
        runs = [m for m in self.modules if m[0].startswith("jit_prefill")]
        if not admits:
            return []
        earlier = sum(1 for rid in firsts if rid < admits[0].args["rid"])
        out = []
        for admit, run in zip(admits, runs[earlier:]):
            emit = firsts.get(admit.args["rid"])
            if run[1] < admit.start or (emit and emit.end < run[1]):
                continue
            out.append((admit, run, emit))
        return out


def deepest_scope(path: str) -> str:
    """`jit(f)/transpose(jvp(layers))/while/body/attn/dot_general:` -> `attn`.
    The last component is the primitive, never a scope."""
    for part in reversed(path.split("/")[:-1]):
        for word in _WORD.findall(part):
            if word in SCOPES:
                return word
    return ""


# -- the protobuf ------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    I, S, D, U = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_DOUBLE, F.TYPE_UINT64
    f = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane.proto", package="benchx", syntax="proto3")
    # (message, [(field, number, type or message name, repeated)])
    for name, fields in (
        ("XStat", [("metadata_id", 1, I, 0), ("double_value", 2, D, 0),
                   ("uint64_value", 3, U, 0), ("int64_value", 4, I, 0),
                   ("str_value", 5, S, 0), ("ref_value", 7, U, 0)]),
        ("XEvent", [("metadata_id", 1, I, 0), ("offset_ps", 2, I, 0),
                    ("duration_ps", 3, I, 0), ("stats", 4, "XStat", 1)]),
        ("XLine", [("name", 2, S, 0), ("timestamp_ns", 3, I, 0),
                   ("events", 4, "XEvent", 1)]),
        ("XEventMetadata", [("id", 1, I, 0), ("name", 2, S, 0),
                            ("stats", 5, "XStat", 1)]),
        ("XStatMetadata", [("id", 1, I, 0), ("name", 2, S, 0)]),
        ("EventEntry", [("key", 1, I, 0), ("value", 2, "XEventMetadata", 0)]),
        ("StatEntry", [("key", 1, I, 0), ("value", 2, "XStatMetadata", 0)]),
        ("XPlane", [("name", 2, S, 0), ("lines", 3, "XLine", 1),
                    ("event_metadata", 4, "EventEntry", 1),
                    ("stat_metadata", 5, "StatEntry", 1)]),
        ("XSpace", [("planes", 1, "XPlane", 1)]),
    ):
        m = f.message_type.add(name=name)
        if name == "XStat":
            m.oneof_decl.add(name="value")
        for fname, number, kind, repeated in fields:
            fd = m.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL,
                type=F.TYPE_MESSAGE if isinstance(kind, str) else kind)
            if isinstance(kind, str):
                fd.type_name = ".benchx." + kind
            if fname.endswith("_value"):
                fd.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchx.XSpace"))


def _stats(stats, stat_names: Dict[int, str]) -> Dict[str, Any]:
    out = {}
    for s in stats:
        field = s.WhichOneof("value")
        if field == "ref_value":          # a string kept once, as a name
            out[stat_names.get(s.metadata_id, "")] = \
                stat_names.get(s.ref_value, "")
        elif field:
            out[stat_names.get(s.metadata_id, "")] = getattr(s, field)
    return out


def parse(data: bytes) -> ProgramTrace:
    space = _xspace_class()()
    space.ParseFromString(data)
    spans: List[Span] = []
    modules: List[Tuple[str, float, float]] = []
    ops: List[Tuple[str, float, float]] = []
    seen_chip = False
    for plane in space.planes:
        is_chip = plane.name.startswith("/device:TPU")
        if not is_chip and plane.name != "/host:CPU":
            continue
        if is_chip and seen_chip:
            continue         # chip 0, as trace.py's per-program numbers
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        scope_of: Dict[int, str] = {}
        for line in plane.lines:
            if is_chip and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for ev in line.events:
                md = meta.get(ev.metadata_id)
                if md is None:
                    continue
                start = line.timestamp_ns + ev.offset_ps / 1e3
                end = start + ev.duration_ps / 1e3
                if not is_chip:
                    if md.name.startswith(SPAN_PREFIXES):
                        spans.append(Span(md.name, start, end,
                                          _stats(ev.stats, stat_names)))
                elif line.name == "XLA Modules":
                    modules.append((md.name.split("(")[0], start, end))
                else:
                    if ev.metadata_id not in scope_of:
                        scope_of[ev.metadata_id] = _stats(
                            md.stats, stat_names).get("tf_op", "")
                    ops.append((scope_of[ev.metadata_id], start, end))
        seen_chip = seen_chip or (is_chip and bool(ops or modules))
    # An enclosing instruction before what it encloses.
    return ProgramTrace(sorted(spans, key=lambda s: s.start),
                        sorted(modules, key=lambda m: m[1]),
                        sorted(ops, key=lambda o: (o[1], -o[2])))


@functools.lru_cache(maxsize=4)
def load_path(path: str) -> Optional[ProgramTrace]:
    """The first `*.xplane.pb` under `path` (rank 0's), or None."""
    files = find_xplanes(path)
    if not files:
        return None
    with open(files[0], "rb") as f:
        return parse(f.read())


def load(run: dict) -> Optional[ProgramTrace]:
    """The trace of a run record, from where the drivers put it (they clear
    the directory before a traced run); None if there is none."""
    return load_path(os.path.join(HERE, "out", run["cell"], str(run["seed"]),
                                  "trace"))


def scoped_ms(run: dict, program: str,
              scopes: Tuple[str, ...]) -> Optional[float]:
    """Device ms an execution of `program*` under `scopes`; None without a
    device plane, or for a program that names no scope at all."""
    t = load(run)
    per = t.scope_ms(program) if t else None
    if per is None or not any(s in per for s in SCOPES):
        return None
    return sum(per.get(s, 0.0) for s in scopes)


def main(argv: List[str]) -> int:
    t = load_path(argv[1])
    if t is None:
        print(f"no *.xplane.pb under {argv[1]}")
        return 1
    counts: Dict[str, List[float]] = {}
    for s in t.spans:
        counts.setdefault(s.name, []).append((s.end - s.start) / 1e6)
    for name, d in sorted(counts.items()):
        print(f"span {name}: {len(d)} median {median(d):.3f} ms "
              f"total {sum(d):.1f} ms")
    for prefix in sorted({m[0] for m in t.modules}):
        per = t.scope_ms(prefix)
        if per:
            n = len(t.whole_modules(prefix))
            print(f"program {prefix}: {n} whole executions, "
                  f"{sum(per.values()):.3f} ms each")
            for scope, ms in sorted(per.items(), key=lambda kv: -kv[1]):
                print(f"    {scope or '(no scope)':12s} {ms:10.3f} ms")
    for admit, run, emit in t.prefills():
        print("prefill rid", admit.args["rid"], "queue_wait_us",
              admit.args["queue_wait_us"], "pipeline_ms",
              round((run[1] - admit.start) / 1e6, 3), "prefill_emit_ms",
              round((emit.end - run[1]) / 1e6, 3) if emit else None)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

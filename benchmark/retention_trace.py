"""Device self-time by scope for a power-retention layer's own scopes.

`program_trace.py` reduces a trace by a fixed vocabulary of scope names, in
which `ret_in`, `retention` and `ret_out`
(ray_tpu/models/block.py::retention_mixer) do not appear: an instruction
under `layers/retention` is charged to `layers` there, which keeps the outer
names their meaning. The readers of the retention metrics need the deeper
name, by `ssm_trace.py`'s rule (an instruction's time less its children's,
charged to the deepest scope of its path that is in the vocabulary: the
projections under `ret_in/qkv` stay `qkv`'s); a program without these scopes
gives dictionaries without them, and every reader over this file then returns
None.

    python3 benchmark/retention_trace.py benchmark/out/<cell>/<seed>/trace

prints, for `jit_prefill` and `jit_decode`, the mean device self-time an
execution by scope under this vocabulary.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import conv_trace, moe_trace, program_trace  # noqa: E402

SCOPES = ("ret_in", "retention", "ret_out")
VOCABULARY = moe_trace.VOCABULARY + SCOPES
BYTES = conv_trace.BYTES
device_peaks = conv_trace.device_peaks
span_median = conv_trace.span_median


def deepest_scope(path: str) -> str:
    """`jit(decode)/.../layers/while/body/retention/mul:` -> `retention`.
    The last component is the primitive, never a scope."""
    for part in reversed(path.split("/")[:-1]):
        for word in conv_trace._WORD.findall(part):
            if word in VOCABULARY:
                return word
    return ""


def by_scope(t: program_trace.ProgramTrace,
             executions: Sequence[Tuple[str, float, float]]
             ) -> List[Dict[str, float]]:
    """For each execution (name, start, end) of a program on chip 0, in the
    order given (by start), nanoseconds of device self-time by scope."""
    ops, each, i = t.ops, [], 0
    for _, ms, me in executions:
        while i < len(ops) and ops[i][1] < ms:
            i += 1
        out: Dict[str, float] = {}
        stack: List[List] = []      # [scope, end, self_ns]
        while i < len(ops) and ops[i][1] < me:
            path, s, e = ops[i]
            while stack and stack[-1][1] <= s:
                scope, _, own = stack.pop()
                out[scope] = out.get(scope, 0.0) + own
            if stack:
                stack[-1][2] -= e - s
            stack.append([deepest_scope(path), e, e - s])
            i += 1
        for scope, _, own in stack:
            out[scope] = out.get(scope, 0.0) + own
        each.append(out)
    return each


def has(each: List[Dict[str, float]]) -> bool:
    return any(s in d for d in each for s in SCOPES)


def prefills(run: dict):
    """[(admit span, its `jit_prefill` execution (name, start, end), that
    execution's self-time by scope)] of a run whose programs have these
    scopes; else None."""
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    each = by_scope(t, [r for _, r, _ in pairs]) if pairs else []
    if not has(each):
        return None
    return [(admit, r, d) for (admit, r, _), d in zip(pairs, each)]


def decodes(run: dict):
    """(the whole `jit_decode` executions, each one's self-time by scope, the
    `serve.engine.decode_dispatch` spans) of a run whose programs have these
    scopes; else None."""
    t = program_trace.load(run)
    runs = t.whole_modules("jit_decode") if t else []
    each = by_scope(t, runs) if runs else []
    if not has(each):
        return None
    return runs, each, t.named("serve.engine.decode_dispatch")


def counts_of(run: dict) -> Optional[object]:
    """The adapter's counts where they count a retention layer; else None."""
    from benchmark import models
    counts = models.adapter(run["config"]["arch"]).counts
    return counts if hasattr(counts, "retention_step_ops_bytes") else None


def main(argv: List[str]) -> int:
    t = program_trace.load_path(argv[1])
    if t is None:
        print("no trace under", argv[1])
        return 1
    for program in ("jit_prefill", "jit_decode"):
        runs = t.whole_modules(program)
        each = by_scope(t, runs)
        print(f"program {program}: {len(runs)} whole executions")
        total: Dict[str, float] = {}
        for d in each:
            for scope, own in d.items():
                total[scope] = total.get(scope, 0.0) + own
        for scope, own in sorted(total.items(), key=lambda kv: -kv[1]):
            print(f"    {scope or '(no scope)':<16s}"
                  f"{own / 1e6 / max(len(runs), 1):10.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

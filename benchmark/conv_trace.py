"""Device self-time by scope for a stack of short-convolution layers beside
attention over sparse experts (`arch: lfm2`).

`program_trace.py` reduces a trace by a fixed vocabulary of scope names, in
which the conv operator's `conv_in`, `conv` and `conv_out`
(ray_tpu/models/block.py::conv_mixer) do not appear: an instruction under
`layers/conv` is charged to `layers` there, which keeps the outer names their
meaning. The readers of this stack's metrics need the deeper names beside the
sparse feed-forward's (`moe_trace.py`'s) and `attn`, and a sum over chosen
executions rather than a median over all, as the other stacks' readers do.
Same trace, same events, same rule (an instruction's time less its
children's, charged to the deepest scope of its path that is in the
vocabulary); a program without these scopes gives dictionaries without them,
and every reader over this file then returns None.

    python3 benchmark/conv_trace.py benchmark/out/<cell>/<seed>/trace

prints, for `jit_prefill` and `jit_decode`, the mean device self-time an
execution by scope under this vocabulary.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import moe_trace, peaks, program_trace  # noqa: E402
from benchmark.stats import median  # noqa: E402

SCOPES = ("conv_in", "conv", "conv_out")
VOCABULARY = moe_trace.VOCABULARY + SCOPES
BYTES = {"bfloat16": 2, "float32": 4}
_WORD = re.compile(r"[A-Za-z_]\w*")


def deepest_scope(path: str) -> str:
    """`jit(decode)/.../layers/while/body/conv/mul:` -> `conv`. The last
    component is the primitive, never a scope."""
    for part in reversed(path.split("/")[:-1]):
        for word in _WORD.findall(part):
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


def ns(per_scope: Dict[str, float], scopes: Sequence[str] = SCOPES) -> float:
    return sum(per_scope.get(s, 0.0) for s in scopes)


def has(each: List[Dict[str, float]]) -> bool:
    return any(s in d for d in each for s in SCOPES)


def prefills(run: dict):
    """(the trace, [(admit span, its `jit_prefill` execution's self-time by
    scope)]) of a run whose programs have this stack's scopes; else None."""
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    each = by_scope(t, [r for _, r, _ in pairs]) if pairs else []
    if not has(each):
        return None
    return t, [(admit, d) for (admit, _, _), d in zip(pairs, each)]


def decodes(run: dict):
    """(the trace, the self-time by scope of each whole `jit_decode`
    execution, the `serve.engine.decode_dispatch` spans) of a run whose
    programs have this stack's scopes; else None."""
    t = program_trace.load(run)
    each = by_scope(t, t.whole_modules("jit_decode")) if t else []
    if not has(each):
        return None
    return t, each, t.named("serve.engine.decode_dispatch")


def device_peaks(run: dict) -> Tuple[float, float]:
    kind = run["device"]["kind"]
    return (peaks.peak(kind, "bf16_flops_per_s"),
            peaks.peak(kind, "hbm_bytes_per_s"))


def span_median(spans, arg: str) -> Optional[float]:
    """The median of the spans' argument `arg`, None where none carries it."""
    values = [s.args[arg] for s in spans if s.args.get(arg) is not None]
    return median(values) if values else None


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

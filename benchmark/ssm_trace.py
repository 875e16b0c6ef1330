"""Device self-time by scope for a state-space layer's own scopes.

`program_trace.py` reduces a trace by a fixed vocabulary of scope names, in
which `ssm_in`, `conv`, `ssm_params`, `scan` and `ssm_out`
(ray_tpu/models/block.py::mamba_mixer) do not appear: an instruction under
`layers/scan` is charged to `layers` there, which keeps the outer names their
meaning. The readers of the state-space metrics need the deeper name, and a
sum over chosen executions rather than a median over all, as `moe_trace.py`'s
and `sparse_attn_trace.py`'s do for their layers. Same trace, same events,
same rule (an instruction's time less its children's, charged to the deepest
scope of its path that is in the vocabulary); a program without these scopes
gives dictionaries without them.

    python3 benchmark/ssm_trace.py benchmark/out/<cell>/<seed>/trace

prints, for `jit_prefill` and `jit_decode`, the mean device self-time an
execution by scope under this vocabulary.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import moe_trace, program_trace  # noqa: E402

SCOPES = ("ssm_in", "conv", "ssm_params", "scan", "ssm_out")
VOCABULARY = moe_trace.VOCABULARY + SCOPES
_WORD = re.compile(r"[A-Za-z_]\w*")


def deepest_scope(path: str) -> str:
    """`jit(decode)/.../layers/while/body/scan/exp:` -> `scan`. The last
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

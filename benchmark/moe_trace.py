"""Device self-time by scope for the sparse feed-forward's own scopes.

`program_trace.py` reduces a trace by a fixed vocabulary of scope names, in
which the sparse half's scopes do not appear (an instruction under
`mlp/experts` is charged to `mlp` there, which is what keeps `decode_mlp_ms`
its meaning). The readers of the `moe` metrics need the deeper name, and a
sum over chosen executions rather than a median over all: prefill programs
differ by bucket. Same trace, same events, same rule (an instruction's time
less its children's, charged to the deepest scope of its path that is in the
vocabulary); a program without these scopes gives dictionaries without them.

One more thing only this file knows: XLA lowers `jax.lax.ragged_dot` on a TPU
to a Mosaic kernel of its own (`%ragged-dot-none.N = ... custom-call(...)`,
beside a small `%ragged-dot-metadata`), and those events carry no `tf_op`, so
no scope (my chip run, PR 27: `experts` read 0.015 ms a chunk, the kernels
sat under no scope). They are found by their HLO name in `trace.py`'s view of
the same events and charged to `experts` where they name no scope themselves.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Sequence, Tuple

from benchmark import program_trace

MOE_SCOPES = ("router", "moe_dispatch", "experts", "moe_combine")
VOCABULARY = program_trace.SCOPES + MOE_SCOPES + ("qk_norm",)
_WORD = re.compile(r"[A-Za-z_]\w*")
_RAGGED = re.compile(r"^%?ragged-dot")


def deepest_scope(path: str) -> str:
    """`jit(decode)/while/body/layers/while/body/mlp/experts/ragged_dot:` ->
    `experts`. The last component is the primitive, never a scope."""
    for part in reversed(path.split("/")[:-1]):
        for word in _WORD.findall(part):
            if word in VOCABULARY:
                return word
    return ""


def grouped_matmuls(run: dict) -> List[float]:
    """Start times (ns, ascending) of chip 0's `ragged-dot` kernel events."""
    data = run.get("trace_data")
    if data is None:
        return []
    return sorted(s for name, s, _ in data.chips[0].ops if _RAGGED.match(name))


def _starts_at(starts: List[float], s: float) -> bool:
    """Whether an event of `starts` begins at `s`: the two readers of the
    file compute an event's start by different arithmetic, a few ns apart."""
    i = bisect.bisect_left(starts, s - 4.0)
    return i < len(starts) and starts[i] <= s + 4.0


def self_ns(t: program_trace.ProgramTrace,
            executions: Sequence[Tuple[str, float, float]],
            kernels: Sequence[float] = ()) -> List[Dict[str, float]]:
    """For each execution (name, start, end) of a program on chip 0, in the
    order given (by start), nanoseconds of device self-time by scope; an
    event with no scope that starts where one of `kernels` does
    (`grouped_matmuls`) counts as `experts`."""
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
            scope = deepest_scope(path)
            if not scope and kernels and _starts_at(kernels, s):
                scope = "experts"
            stack.append([scope, e, e - s])
            i += 1
        for scope, _, own in stack:
            out[scope] = out.get(scope, 0.0) + own
        each.append(out)
    return each


def by_scope(run: dict, t: program_trace.ProgramTrace,
             executions: Sequence[Tuple[str, float, float]]
             ) -> List[Dict[str, float]]:
    """`self_ns` of a run's executions, its grouped matmuls found by name."""
    return self_ns(t, executions, grouped_matmuls(run))


def moe_ns(per_scope: Dict[str, float]) -> float:
    return sum(per_scope.get(s, 0.0) for s in MOE_SCOPES)


def has_moe(each: List[Dict[str, float]]) -> bool:
    return any(s in d for d in each for s in MOE_SCOPES)

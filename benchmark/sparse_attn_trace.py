"""Device self-time by scope for sparse attention's own scopes.

`program_trace.py` reduces a trace by a fixed vocabulary of scope names, in
which `indexer`, `select` and `sparse_attn` (ray_tpu/ops/sparse_attention.py)
do not appear: an instruction under `attn/select` is charged to `attn` there,
which keeps the outer names their meaning. The readers of the sparse-attention
metrics need the deeper name, and a sum over chosen executions rather than a
median over all, as `moe_trace.py`'s do for the sparse feed-forward. Same
trace, same events, same rule (an instruction's time less its children's,
charged to the deepest scope of its path that is in the vocabulary); a
program without these scopes gives dictionaries without them.
"""

from __future__ import annotations

import re
from typing import Dict, List, Sequence, Tuple

from benchmark import moe_trace, program_trace

SCOPES = ("indexer", "select", "sparse_attn")
VOCABULARY = moe_trace.VOCABULARY + SCOPES
_WORD = re.compile(r"[A-Za-z_]\w*")


def deepest_scope(path: str) -> str:
    """`jit(decode)/.../attn/select/top_k:` -> `select`. The last component
    is the primitive, never a scope."""
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

"""Device self-time by scope for latent attention's own scopes and a share of
the experts' (ray_tpu/models/block.py::latent_attention_inputs,
latent_attention_output; ray_tpu/ops/moe.py).

`program_trace.py` reduces a trace by a fixed vocabulary of scope names, in
which `q_latent`, `kv_latent`, `kv_up`, `absorb` (inside `qkv` and
`attn_out`) and `shared_expert` (inside `mlp`) do not appear. The readers of
the latent-attention metrics need the deeper names, and sums over chosen
executions, as `moe_trace.py`'s and `ssm_trace.py`'s do for their layers:
same trace, same events, same rule (an instruction's time less its
children's, charged to the deepest scope of its path that is in the
vocabulary; XLA's `ragged-dot` kernels, which carry no scope, to `experts`).
A program without these scopes gives dictionaries without them.

    python3 benchmark/latent_trace.py benchmark/out/<cell>/<seed>/trace

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

from benchmark import moe_trace, program_trace  # noqa: E402

LATENT_SCOPES = ("q_latent", "kv_latent", "kv_up", "absorb")
# Everything between a block's input and its attention's residual: MLA.
MLA_SCOPES = ("attn_norm", "qkv", "rope", "kv_write", "attn", "attn_out"
              ) + LATENT_SCOPES
VOCABULARY = moe_trace.VOCABULARY + LATENT_SCOPES + ("shared_expert",)
_WORD = re.compile(r"[A-Za-z_]\w*")


def deepest_scope(path: str) -> str:
    """`jit(decode)/.../layers/while/body/qkv/absorb/dot_general:` ->
    `absorb`. The last component is the primitive, never a scope."""
    for part in reversed(path.split("/")[:-1]):
        for word in _WORD.findall(part):
            if word in VOCABULARY:
                return word
    return ""


def by_scope(run: Optional[dict], t: program_trace.ProgramTrace,
             executions: Sequence[Tuple[str, float, float]]
             ) -> List[Dict[str, float]]:
    """For each execution (name, start, end) of a program on chip 0, in the
    order given (by start), nanoseconds of device self-time by scope."""
    kernels = moe_trace.grouped_matmuls(run) if run else []
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
            if not scope and kernels and moe_trace._starts_at(kernels, s):
                scope = "experts"
            stack.append([scope, e, e - s])
            i += 1
        for scope, _, own in stack:
            out[scope] = out.get(scope, 0.0) + own
        each.append(out)
    return each


def ns(per_scope: Dict[str, float],
       scopes: Sequence[str] = MLA_SCOPES) -> float:
    return sum(per_scope.get(s, 0.0) for s in scopes)


def has(each: List[Dict[str, float]]) -> bool:
    return any(s in d for d in each for s in LATENT_SCOPES)


_OPERAND = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")


def latent_flash_calls(run: dict) -> List[Tuple[int, int, int, int, int,
                                                float]]:
    """The prompt-attention kernel's calls (`latent_flash_fwd`) on chip 0:
    (heads, positions, dn, dr, dv, seconds) each. Such a call is a
    `tpu_custom_call` event with five bfloat16 operands of rank 3: q_n
    `[heads, s, dn]`, q_r `[heads, s, dr]`, k_n, the shared rotary key `[1, s,
    dr]`, v `[heads, s, dv]`; the shapes are read from the event's text."""
    data = run.get("trace_data")
    calls = []
    for hlo, s, e in (data.chips[0].ops if data is not None else ()):
        if 'custom_call_target="tpu_custom_call"' not in hlo \
                or "custom-call(" not in hlo:
            continue
        operands = hlo.split("custom-call(", 1)[1].split(
            "), custom_call_target")[0]
        shapes = [tuple(int(x) for x in g) for g in _OPERAND.findall(operands)]
        if len(shapes) != 5 or operands.count("%") != 5:
            continue
        (h, sq, dn), (_, _, dr), _, (b, _, dr2), (_, _, dv) = shapes
        if b != 1 or dr != dr2 or shapes[2] != shapes[0]:
            continue
        calls.append((h, sq, dn, dr, dv, (e - s) / 1e9))
    return calls


def main(argv: List[str]) -> int:
    t = program_trace.load_path(argv[1])
    if t is None:
        print("no trace under", argv[1])
        return 1
    for program in ("jit_prefill", "jit_decode"):
        runs = t.whole_modules(program)
        each = by_scope(None, t, runs)
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

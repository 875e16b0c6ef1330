"""What the readers of a model that generates by BLOCKS share (`arch: sdar`).

A decode program of such a model is `decode_chunk / block_length` blocks,
each `denoise_steps` forwards of `n_slots x block_length` rows with the head
and the commit rule (the scope `unmask`, ray_tpu/models/serving.py) and one
forward without them (the scope `commit`). `program_trace.py` and
`moe_trace.py` reduce a trace by fixed vocabularies of scope names in which
`unmask` does not appear (an instruction under `unmask/sample` is charged to
`sample` there, which keeps `decode_sample_ms` its meaning; the rest of the
scope to no scope at all). This file reads the scope off the instructions'
paths itself, by the same rule as they do: an instruction's time less its
children's.

The engine says what a chunk was on its `serve.engine.decode_dispatch` span:
`blocks`, `forwards`, `rows`, `committed`. A program whose spans lack them
(every other stack's, the parent's) gives None here, and every reader over
this file then returns None.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import moe_trace, program_trace
from benchmark.conv_trace import (BYTES, device_peaks,  # noqa: F401
                                  span_median)

# A prefill's span ends at most this long after its execution (the emitter's
# wake); a wrong offset reads a refill's 105 ms, or a negative time.
_SLACK_NS = 20e6


def sizes(run: dict) -> Optional[Tuple[int, int, int]]:
    """(block_length, denoise_steps, decode_chunk) of a run's configuration;
    None for a model that yields a token a step."""
    m = run["config"]
    if not m.get("block_length") or m["block_length"] <= 1:
        return None
    return (int(m["block_length"]), int(m["denoise_steps"]),
            int(m["deployment"]["engine"]["decode_chunk"]))


def chunks(run: dict):
    """(the trace, its whole `jit_decode` executions, the
    `serve.engine.decode_dispatch` spans that carry `forwards`) of a run
    whose engine dispatched blocks; else None."""
    t = program_trace.load(run)
    if t is None or sizes(run) is None:
        return None
    spans = [s for s in t.named("serve.engine.decode_dispatch")
             if s.args.get("forwards")]
    runs = t.whole_modules("jit_decode")
    if not spans or not runs:
        return None
    return t, runs, spans


def prefills(t: program_trace.ProgramTrace):
    """[(admit span, its `jit_prefill` execution)] of the requests admitted
    AND prefilled inside the trace. The emitter's `serve.engine.emit
    kind="first"` span of a request ends when its prefill has left the device
    and the emitter has woken (2 to 9 ms later on a loaded host), and the
    emitter takes its items in the order the device runs them: so the
    executions and those spans, each in time order, match one to one from
    SOME offset, the one at which every span ends within `_SLACK_NS` after
    its execution; the offset with the most such pairs is taken (the closest
    of two with as many), and a request's admit span is found by its number. `program_trace.ProgramTrace.
    prefills` fixes the offset from the head of the trace; here a slot refills
    every 105 ms behind two chunks of 90, the device's line begins before the
    host's, and one execution at the head that has no span shifts every pair
    by one (my chip runs, PR 53: 0, 0, 16, 17, 37 and 37 of 38 paired on six
    seeds)."""
    runs = [m for m in t.modules if m[0].startswith("jit_prefill")]
    firsts = sorted(t.named("serve.engine.emit", kind="first"),
                    key=lambda s: s.end)

    def matched(offset):
        return [(emit, runs[i + offset]) for i, emit in enumerate(firsts)
                if 0 <= i + offset < len(runs)
                and 0 <= emit.end - runs[i + offset][2] <= _SLACK_NS]

    # (of two offsets with as many pairs, the one whose spans follow closest)
    best = max((matched(k) for k in range(-4, 5)), key=lambda pairs: (
        len(pairs), -sum(emit.end - run[2] for emit, run in pairs)))
    run_of = {emit.args.get("rid"): run for emit, run in best}
    return [(admit, run_of[admit.args.get("rid")])
            for admit in t.named("serve.engine.admit", kind="prefill")
            if admit.args.get("rid") in run_of
            and run_of[admit.args["rid"]][1] >= admit.start]


def under_ns(t: program_trace.ProgramTrace,
             executions: Sequence[Tuple[str, float, float]],
             scope: str) -> List[float]:
    """For each execution (name, start, end) of a program on chip 0, in the
    order given (by start): nanoseconds of device self-time of the
    instructions whose path holds the scope `scope`, whatever lies deeper."""
    ops, each, i = t.ops, [], 0
    for _, ms, me in executions:
        while i < len(ops) and ops[i][1] < ms:
            i += 1
        total = 0.0
        stack: List[List] = []      # [inside the scope, end, self_ns]
        while i < len(ops) and ops[i][1] < me:
            path, s, e = ops[i]
            while stack and stack[-1][1] <= s:
                inside, _, own = stack.pop()
                total += own if inside else 0.0
            if stack:
                stack[-1][2] -= e - s
            stack.append([scope in path.split("/")[:-1], e, e - s])
            i += 1
        total += sum(own for inside, _, own in stack if inside)
        each.append(total)
    return each


def by_scope(run: dict, t: program_trace.ProgramTrace,
             executions) -> List[Dict[str, float]]:
    """`moe_trace.by_scope`: self-time by the vocabulary that has `attn`."""
    return moe_trace.by_scope(run, t, executions)

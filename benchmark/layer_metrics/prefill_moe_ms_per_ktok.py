"""Model step (prefill): device self-time of the sparse feed-forward's scopes
(`router`, `moe_dispatch`, `experts`, `moe_combine`, all inside `mlp`) in the
`jit_prefill` executions of the trace, over the thousands of prompt tokens of
the admits paired with them (program_trace.ProgramTrace.prefills), as
`prefill_ms_per_ktok` counts the whole program. None for a program without
those scopes. device_trace."""

from benchmark import moe_trace, program_trace


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    tokens = sum(admit.args["prompt_tokens"] for admit, _, _ in pairs)
    each = moe_trace.by_scope(run, t, [r for _, r, _ in pairs]) \
        if tokens else []
    if not moe_trace.has_moe(each):
        return None
    return sum(moe_trace.moe_ns(d) for d in each) / 1e6 / (tokens / 1e3)

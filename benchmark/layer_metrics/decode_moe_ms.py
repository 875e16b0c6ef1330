"""Model step (decode): device self-time a decode step of the sparse
feed-forward's scopes (`router`, `moe_dispatch`, `experts`, `moe_combine`,
all inside `mlp`) in `jit_decode`, the median over the whole executions of
the trace. None for a program without those scopes. device_trace."""

from benchmark import moe_trace, program_trace
from benchmark.stats import median


def read(run):
    t = program_trace.load(run)
    each = moe_trace.by_scope(run, t, t.whole_modules("jit_decode")) \
        if t else []
    if not moe_trace.has_moe(each):
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return median([moe_trace.moe_ns(d) for d in each]) / 1e6 / chunk

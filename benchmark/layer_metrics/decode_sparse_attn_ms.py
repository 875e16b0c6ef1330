"""Model step (decode): device self-time a decode step of sparse attention's
scopes (`indexer`: the scores of a slot's live indexer keys; `select`: the
exact top-k; `sparse_attn`: the gather of the selected K and V rows and the
attention over them; all inside `attn`) in `jit_decode`, the median over the
whole executions of the trace. None for a program without those scopes.
device_trace."""

from benchmark import program_trace, sparse_attn_trace
from benchmark.stats import median


def read(run):
    t = program_trace.load(run)
    each = sparse_attn_trace.by_scope(t, t.whole_modules("jit_decode")) \
        if t else []
    if not sparse_attn_trace.has(each):
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return median([sparse_attn_trace.ns(d) for d in each]) / 1e6 / chunk

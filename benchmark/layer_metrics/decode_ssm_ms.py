"""Model step (decode): device self-time a decode step of the state-space
mixer's five scopes (`ssm_in`, `conv`, `ssm_params`, `scan`: the one-token
state update and the state's read and write, `ssm_out`; all the state-space
layers) in `jit_decode`, the median over the whole executions of the trace.
None for a program without those scopes. device_trace."""

from benchmark import program_trace, ssm_trace
from benchmark.stats import median


def read(run):
    t = program_trace.load(run)
    each = ssm_trace.by_scope(t, t.whole_modules("jit_decode")) if t else []
    if not ssm_trace.has(each):
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return median([ssm_trace.ns(d) for d in each]) / 1e6 / chunk

"""Model step (decode): median device duration of the `jit_decode` program in
the trace, over the `decode_chunk` steps it holds. device_trace."""

from benchmark.stats import median


def read(run):
    if run["trace_data"] is None:
        return None
    d = run["trace_data"].module_durations("jit_decode")
    if not d:
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return median(d) / chunk * 1e3

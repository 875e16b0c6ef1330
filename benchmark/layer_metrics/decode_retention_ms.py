"""Model step (decode): device self-time a decode step under the scope
`retention` (the power-retention operator of every layer: the active slots'
state read, decayed, added to, read against the queries and written back;
ray_tpu/ops/retention.py::retention_state_step) in `jit_decode`, the median
over the whole executions of the trace. None for a program without the
scope. device_trace."""

from benchmark import retention_trace
from benchmark.stats import median


def read(run):
    dec = retention_trace.decodes(run)
    if dec is None:
        return None
    _, each, _ = dec
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return median([d.get("retention", 0.0) for d in each]) / 1e6 / chunk

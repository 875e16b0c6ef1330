"""Model step (decode): device self-time a decode step of the conv operator's
three scopes (`conv_in`, `conv`: the taps and the slots' windows read and
written back, `conv_out`; all the conv layers) in `jit_decode`, the median
over the whole executions of the trace. None for a program without those
scopes. device_trace."""

from benchmark import conv_trace
from benchmark.stats import median


def read(run):
    read = conv_trace.decodes(run)
    if read is None:
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return median([conv_trace.ns(d) for d in read[1]]) / 1e6 / chunk

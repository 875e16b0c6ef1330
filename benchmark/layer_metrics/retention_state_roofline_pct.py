"""Kernel (the decode step's `retention_state_step`,
ray_tpu/ops/retention.py, scope `retention` of `jit_decode`): the least time
the chip could take to move the active slots' recurrent state of a
`jit_decode` execution, over the device self-time that execution spent under
`retention`.

The step is bound by bytes (thirteen operations an element of state read and
written), so least time is bytes over peak HBM bytes/s (benchmark/peaks.py):
the adapter's `counts.decode_state_bytes` (every layer's state of a live slot
in and out, at the 8,256 rows the equations need, whatever layout the
program pads to) for the median `active` of the trace's
`serve.engine.decode_dispatch` spans times the chunk's steps. The program's
layout is 0.78% larger and an idle slot's state never moves, so the share can
only under-read: over 100 is a fault in this reader. None for a program
without the scope or a model whose counts have no retention state.
device_trace."""

from benchmark import retention_trace
from benchmark.stats import median


def read(run):
    dec = retention_trace.decodes(run)
    counts = retention_trace.counts_of(run)
    if dec is None or counts is None:
        return None
    _, each, spans = dec
    active = retention_trace.span_median(spans, "active")
    if not active:
        return None
    m = run["config"]
    chunk = m["deployment"]["engine"]["decode_chunk"]
    byts = counts.decode_state_bytes(m, active * chunk)
    _, b_peak = retention_trace.device_peaks(run)
    took_s = median([d.get("retention", 0.0) for d in each]) / 1e9
    return 100.0 * (byts / b_peak) / took_s if took_s else None

"""Kernel (the decode step's `linear_step`, ray_tpu/ops/linear_attention.py,
scope `linear_attn` of `jit_decode`): the least time the chip could take to
move the active slots' linear state of a `jit_decode` execution, over the
device self-time that execution spent under `linear_attn`.

The step is bound by bytes (four operations an element of state read and
written), so least time is bytes over peak HBM bytes/s (benchmark/peaks.py):
the adapter's `counts.decode_state_bytes` (every linear layer's state of a
live slot in and out: heads x d x d float32, the program's own layout) for
the median `active` of the trace's `serve.engine.decode_dispatch` spans times
the chunk's steps. An idle slot's state never moves and the step's q, k and v
are not counted, so the share can only under-read: over 100 is a fault in
this reader. None for a program without the scope. device_trace."""

from benchmark import sala_trace
from benchmark.stats import median


def read(run):
    dec = sala_trace.decodes(run)
    counts = sala_trace.counts_of(run)
    if dec is None or counts is None:
        return None
    _, each, spans = dec
    active = sala_trace.span_median(spans, "active")
    if not active:
        return None
    m = run["config"]
    chunk = m["deployment"]["engine"]["decode_chunk"]
    byts = counts.decode_state_bytes(m, active * chunk)
    _, b_peak = sala_trace.device_peaks(run)
    took_s = median([sala_trace.ns(d, sala_trace.LINEAR) for d in each]) / 1e9
    return 100.0 * (byts / b_peak) / took_s if took_s else None

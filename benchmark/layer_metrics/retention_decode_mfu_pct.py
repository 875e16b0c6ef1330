"""Model step (decode): the WHOLE decode step's share of the chip's peak for
a stack of power-retention layers: the larger of a step's operations over
peak FLOP/s and its bytes over peak HBM bytes/s (the adapter's
`counts.decode_step_ops_bytes`: every weight but the embedding table once,
each live slot's state of every layer in and out) over the duration of a step
of `jit_decode` (an execution's device time over the chunk's steps, the
median over the whole executions of the trace). The step is counted at the
median `active` of the trace's `serve.engine.decode_dispatch` spans; a
slot's context changes nothing. `decode_mfu_pct` returns None for a model
that touches no expert; this is the share of the whole step that bounds a
later gain in this stack's cells. None for a program without the retention
scopes. device_trace."""

from benchmark import retention_trace
from benchmark.stats import median


def read(run):
    dec = retention_trace.decodes(run)
    counts = retention_trace.counts_of(run)
    if dec is None or counts is None:
        return None
    runs, _, spans = dec
    active = retention_trace.span_median(spans, "active")
    if not active or not runs:
        return None
    m = run["config"]
    chunk = m["deployment"]["engine"]["decode_chunk"]
    ops, byts = counts.decode_step_ops_bytes(
        m, [0] * int(round(active)),
        retention_trace.BYTES[m["dtypes"]["params"]])
    f_peak, b_peak = retention_trace.device_peaks(run)
    step_s = median([e - s for _, s, e in runs]) / 1e9 / chunk
    return 100.0 * max(ops / f_peak, byts / b_peak) / step_s

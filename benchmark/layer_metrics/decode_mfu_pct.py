"""Model step (decode): the whole decode step's share of the chip's peak:
the larger of a step's operations over peak FLOP/s and its bytes over peak
HBM bytes/s (the adapter's `counts.decode_step_ops_bytes`: every weight
outside the routed experts once, the experts TOUCHED, the live K and V, the
live slots' conv windows in and out) over the duration of a step of
`jit_decode` (an execution's device time over the chunk's steps, the median
over the whole executions of the trace). The step is counted at the medians
of the trace's `serve.engine.decode_dispatch` spans: `active` slots, each at
the mean context `live_kv_tokens / active`, `experts_touched` over the
chunk's steps and the sparse layers. None for a program without this stack's
scopes or counters. device_trace."""

from benchmark import conv_trace, models
from benchmark.stats import median


def read(run):
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    dec = conv_trace.decodes(run)
    if dec is None or not hasattr(counts, "layers"):
        return None
    t, _, spans = dec
    active = conv_trace.span_median(spans, "active")
    live = conv_trace.span_median(spans, "live_kv_tokens")
    touched = conv_trace.span_median(spans, "experts_touched")
    runs = t.whole_modules("jit_decode")
    if not active or live is None or not touched or not runs:
        return None
    chunk = m["deployment"]["engine"]["decode_chunk"]
    ops, byts = counts.decode_step_ops_bytes(
        m, [live / active] * int(round(active)),
        conv_trace.BYTES[m["dtypes"]["params"]],
        conv_trace.BYTES[m["dtypes"]["activations"]],
        experts_touched=touched / (chunk * counts.layers(m)[1]))
    f_peak, b_peak = conv_trace.device_peaks(run)
    step_s = median([e - s for _, s, e in runs]) / 1e9 / chunk
    return 100.0 * max(ops / f_peak, byts / b_peak) / step_s

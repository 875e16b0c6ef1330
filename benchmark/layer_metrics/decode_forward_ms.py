"""Model step (decode), generation by blocks: device time of ONE forward of
`n_slots x block_length` rows: the median duration of the whole `jit_decode`
executions of the trace over the forwards a chunk holds (the
`serve.engine.decode_dispatch` spans' `forwards`: `decode_chunk /
block_length` blocks of `denoise_steps` + 1). The commit's forward computes
no head, so this is the mean of the two kinds. None for a program whose spans
carry no `forwards`. device_trace."""

from benchmark import block_trace
from benchmark.stats import median


def read(run):
    found = block_trace.chunks(run)
    if found is None:
        return None
    _, runs, spans = found
    forwards = block_trace.span_median(spans, "forwards")
    return median([e - s for _, s, e in runs]) / 1e6 / forwards

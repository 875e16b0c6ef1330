"""Model step (decode), generation by blocks: device self-time of the commit
rule a denoising forward: the instructions of `jit_decode` under the scope
`unmask` (ray_tpu/models/serving.py: the candidates' argmax or draw, the
float32 softmax's probability of each, the ranking inside a block), the
median over the whole executions of the trace, over the denoising forwards a
chunk holds (`decode_chunk / block_length` x `denoise_steps`). None for a
program without that scope. device_trace."""

from benchmark import block_trace
from benchmark.stats import median


def read(run):
    found = block_trace.chunks(run)
    if found is None:
        return None
    t, runs, _ = found
    B, T, chunk = block_trace.sizes(run)
    each = block_trace.under_ns(t, runs, "unmask")
    if not any(each):
        return None
    return median(each) / 1e6 / (chunk // B * T)

"""Kernel (decode attention at a block of rows a slot, `paged_decode` in
ray_tpu/ops/paged_kv.py with `block_length` x the query heads of each kv
head, under the scope `attn` of `jit_decode`): the least time the chip could
take for a chunk's attention over the device self-time of that scope an
execution (the median over the whole executions of the trace). Least time is
the larger of operations over peak FLOP/s and bytes over peak HBM bytes/s of
the adapter's `counts.decode_attn_ops_bytes`, a layer a forward: every row
of a block against every live position of its slot, K and V of those
positions read ONCE for the block's rows. Counted at the medians of the
trace's `serve.engine.decode_dispatch` spans: `live_kv_tokens` positions at
the chunk's start in `active` slots, block j of the chunk adding `block_length`
positions a slot, each block `denoise_steps` + 1 forwards. Pages are read
whole and the count is not rounded up to them, so the share can only
under-read. None for a program whose spans carry no `forwards`.
device_trace."""

from benchmark import block_trace, models
from benchmark.stats import median


def read(run):
    found = block_trace.chunks(run)
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    if found is None or not hasattr(counts, "decode_attn_ops_bytes"):
        return None
    t, runs, spans = found
    B, T, chunk = block_trace.sizes(run)
    active = block_trace.span_median(spans, "active")
    live = block_trace.span_median(spans, "live_kv_tokens")
    took_s = median([d.get("attn", 0.0)
                     for d in block_trace.by_scope(run, t, runs)]) / 1e9
    if not active or live is None or not took_s:
        return None
    f_peak, b_peak = block_trace.device_peaks(run)
    ab = block_trace.BYTES[m["dtypes"]["activations"]]
    least = 0.0
    for j in range(chunk // B):
        ops, byts = counts.decode_attn_ops_bytes(
            m, live + active * B * (j + 1), active, ab)
        least += (T + 1) * max(ops / f_peak, byts / b_peak)
    return 100.0 * counts.attention_layers(m) * least / took_s

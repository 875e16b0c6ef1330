"""Kernel (decode attention over pages that hold two kv heads of 64 to a row,
`paged_decode` in ray_tpu/ops/paged_kv.py, under the scope `attn` of
`jit_decode`): the bytes of the live K and V a chunk's steps need (the median
`live_kv_tokens` of the trace's `serve.engine.decode_dispatch` spans x the
chunk's steps x the ATTENTION layers x 2 x kv heads x head_dim x bytes an
element: 2,048 B a token a layer at the published widths, the adapter's
`counts.decode_attn_bytes`) over peak HBM bytes/s, over the scope's device
self-time an execution. The count is of tokens at the chunk's START and is
not rounded up to pages, so the share can only under-read.
`decode_attn_roofline_pct` multiplies by `num_hidden_layers` and reads a
`head_dim` key, and is not this stack's. None for a program without this
stack's scopes. device_trace."""

from benchmark import conv_trace, models
from benchmark.stats import median


def read(run):
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    dec = conv_trace.decodes(run)
    if dec is None or not hasattr(counts, "decode_attn_bytes"):
        return None
    _, each, spans = dec
    live = conv_trace.span_median(spans, "live_kv_tokens")
    took_s = median([d.get("attn", 0.0) for d in each]) / 1e9
    if live is None or not took_s:
        return None
    byts = counts.attention_layers(m) * counts.decode_attn_bytes(
        m, live * m["deployment"]["engine"]["decode_chunk"],
        conv_trace.BYTES[m["dtypes"]["activations"]])
    return 100.0 * byts / conv_trace.device_peaks(run)[1] / took_s

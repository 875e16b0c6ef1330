"""Kernel (sparse attention of a decode step,
ray_tpu/ops/sparse_attention.py::sparse_decode_attention: scopes `indexer`,
`select`, `sparse_attn` of `jit_decode`): the least time the chip could take
for a `jit_decode` execution's sparse attention, over the device self-time
the execution spent under those scopes.

Least time is the larger of operations over peak FLOP/s and bytes over peak
HBM bytes/s (the adapter's `counts.sparse_decode_counts`,
benchmark/peaks.py); bytes bound it. Both come from the program's counters on
the trace's `serve.engine.decode_dispatch` spans, medians over the spans:
`selected_keys` (positions whose K and V rows a layer reads, summed over the
chunk's steps and the active slots) and `live_keys` (positions whose indexer
key a layer scores), times the layers. Nothing is rounded up to pages and the
gather's indices are not counted, so the share can only under-read: over 100
is a fault in this reader. None for a program whose spans carry no
`selected_keys`. device_trace."""

from benchmark import models, peaks, program_trace, sparse_attn_trace
from benchmark.stats import median

BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    t = program_trace.load(run)
    spans = [s for s in (t.named("serve.engine.decode_dispatch") if t else [])
             if "selected_keys" in s.args]
    each = sparse_attn_trace.by_scope(t, t.whole_modules("jit_decode")) \
        if spans else []
    if not sparse_attn_trace.has(each):
        return None
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    ops, byts = counts.sparse_decode_counts(
        m, median([s.args["selected_keys"] for s in spans]),
        median([s.args["live_keys"] for s in spans]),
        BYTES[m["dtypes"]["activations"]])
    kind = run["device"]["kind"]
    least_s = m["num_hidden_layers"] * max(
        ops / peaks.peak(kind, "bf16_flops_per_s"),
        byts / peaks.peak(kind, "hbm_bytes_per_s"))
    took_s = median([sparse_attn_trace.ns(d) for d in each]) / 1e9
    return 100.0 * least_s / took_s if took_s else None

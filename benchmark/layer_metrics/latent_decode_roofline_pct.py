"""Kernel (decode attention over the latent arena in its absorbed form,
`paged_latent_decode` in ray_tpu/ops/paged_kv.py, all there is under the
`attn` scope of such a model's `jit_decode`): the least time the chip could
take for the attention of a `jit_decode` execution, over the device
self-time that execution spent under `attn`.

Least time of one layer's step is the larger of operations over peak FLOP/s
and bytes over peak HBM bytes/s (the adapter's
`counts.latent_decode_ops_bytes`, benchmark/peaks.py): 128 heads score each
cached row of 576 numbers and sum its first 512, 242 operations a byte read
against the chip's 240, so neither bound is the plain one. The rows are the
median `live_kv_tokens` of the trace's `serve.engine.decode_dispatch` spans
over their median `active` slots (positions the active slots held when the
chunk was dispatched), times `decode_chunk` steps and the layers. The count
is of rows at the chunk's START (every step adds one a slot), of 1,152 B a
row where the arena holds 1,280, and no padding of a block of 512, so the
share can only under-read: over 100 is a fault in this reader. None for a
program without latent attention's scopes or a model whose counts have no
such function. device_trace."""

from benchmark import latent_trace, models, peaks, program_trace
from benchmark.stats import median

BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    t = program_trace.load(run)
    spans = [s for s in (t.named("serve.engine.decode_dispatch") if t else [])
             if "live_kv_tokens" in s.args and s.args.get("active")]
    each = latent_trace.by_scope(run, t, t.whole_modules("jit_decode")) \
        if spans else []
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    if not latent_trace.has(each) \
            or not hasattr(counts, "latent_decode_ops_bytes"):
        return None
    slots = int(round(median([s.args["active"] for s in spans])))
    rows = median([s.args["live_kv_tokens"] for s in spans])
    ops, byts = counts.latent_decode_ops_bytes(
        m, [rows / slots] * slots, BYTES[m["dtypes"]["activations"]])
    kind = run["device"]["kind"]
    least_s = (m["deployment"]["engine"]["decode_chunk"]
               * m["num_hidden_layers"]
               * max(ops / peaks.peak(kind, "bf16_flops_per_s"),
                     byts / peaks.peak(kind, "hbm_bytes_per_s")))
    took_s = median([d.get("attn", 0.0) for d in each]) / 1e9
    return 100.0 * least_s / took_s if took_s else None

"""Kernel (a decode step's read of the selected pages,
ray_tpu/ops/sparse_attention.py::block_sparse_decode: `paged_decode` by a
table of each kv head's own, scope `block_sparse_attn` of `jit_decode`): the
least time the chip could take for a `jit_decode` execution's attention over
the selected blocks, over the device self-time the execution spent under
`block_sparse_attn`.

Least time is the larger of operations over peak FLOP/s and bytes over peak
HBM bytes/s (the adapter's `counts.block_sparse_decode_ops_bytes`,
benchmark/peaks.py); bytes bound it. The blocks are the program's counter on
the trace's `serve.engine.decode_dispatch` spans, the median over the spans:
`blocks_selected` (the (kv head, block) reads of ONE layer, summed over the
chunk's steps and the active slots; a slot's own block counted whole, which
it is not yet), times the sparse layers. The scoring and the pooled keys are
`block_select`'s, not counted here. Over 100 is a fault in this reader. None
for a program whose spans carry no `blocks_selected`. device_trace."""

from benchmark import sala_trace
from benchmark.stats import median


def read(run):
    dec = sala_trace.decodes(run)
    counts = sala_trace.counts_of(run)
    if dec is None or counts is None:
        return None
    _, each, spans = dec
    spans = [s for s in spans if "blocks_selected" in s.args]
    if not spans:
        return None
    m = run["config"]
    f_peak, b_peak = sala_trace.device_peaks(run)
    ops, byts = counts.block_sparse_decode_ops_bytes(
        m, median([s.args["blocks_selected"] for s in spans]),
        sala_trace.BYTES[m["dtypes"]["activations"]])
    least_s = counts.mixer_layers(m)[0] * max(ops / f_peak, byts / b_peak)
    took_s = median([sala_trace.ns(d, sala_trace.SPARSE) for d in each]) / 1e9
    return 100.0 * least_s / took_s if took_s else None

"""Kernel (prefill's `index_select`, ray_tpu/ops/sparse_attention.py: the
indexer's scores of a block of queries against every key and the exact
top-k of each row, in fast memory, under the `select` scope of
`jit_prefill`): the least time the chip could take for the scoring of the
prefills in the trace, over the device self-time of their `select` scope.

Least time of one layer is the larger of operations over peak FLOP/s and
bytes over peak HBM bytes/s (the adapter's `counts.index_select_ops_bytes`
at the admit's `prompt_tokens`: operations of the live causal pairs, a
bucket's padding is computed by the program and is not work). The selection
itself is compares and counts and is counted as no operation, so this reads
how far the kernel's time is from the scoring alone. None for a program
without that scope. device_trace."""

from benchmark import models, peaks, program_trace, sparse_attn_trace

BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    each = sparse_attn_trace.by_scope(t, [r for _, r, _ in pairs])
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    if not sparse_attn_trace.has(each) \
            or not hasattr(counts, "index_select_ops_bytes"):
        return None
    kind = run["device"]["kind"]
    f_peak = peaks.peak(kind, "bf16_flops_per_s")
    b_peak = peaks.peak(kind, "hbm_bytes_per_s")
    least = took = 0.0
    for (admit, _, _), scopes in zip(pairs, each):
        ops, byts = counts.index_select_ops_bytes(
            m, admit.args["prompt_tokens"], BYTES[m["dtypes"]["activations"]])
        least += m["num_hidden_layers"] * max(ops / f_peak, byts / b_peak)
        took += scopes.get("select", 0.0) / 1e9
    return 100.0 * least / took if took else None

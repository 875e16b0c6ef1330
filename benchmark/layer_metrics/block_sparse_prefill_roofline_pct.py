"""Kernel (prefill's `block_flash`, ray_tpu/ops/sparse_attention.py: flash
attention under the mask by blocks, a kv head's whole group of query heads a
grid step, scope `block_sparse_attn` of `jit_prefill`): the least time the
chip could take for the sparse layers' attention of the prefills in the
trace, over the device self-time of their `block_sparse_attn` scope.

Least time of one layer is the larger of operations over peak FLOP/s and
bytes over peak HBM bytes/s (the adapter's
`counts.block_sparse_prompt_ops_bytes` at the admit's `prompt_tokens`: q . k
and p v over the keys each query READS, every earlier key under `dense_len`
and `topk` blocks from there on; q, k and v read and the rows written once),
times the sparse layers. The program scores every causal pair of a bucket
under the mask (a block of keys that no row of a block of queries selects is
still computed) and the bucket's padding: it does more work than is counted,
so the share reads low by design; over 100 is a fault in this reader. None
for a program without the scope. device_trace."""

from benchmark import sala_trace


def read(run):
    pairs = sala_trace.prefills(run)
    counts = sala_trace.counts_of(run)
    if not pairs or counts is None:
        return None
    m = run["config"]
    f_peak, b_peak = sala_trace.device_peaks(run)
    act = sala_trace.BYTES[m["dtypes"]["activations"]]
    layers = counts.mixer_layers(m)[0]
    least = took = 0.0
    for admit, _, scopes in pairs:
        ops, byts = counts.block_sparse_prompt_ops_bytes(
            m, admit.args["prompt_tokens"], act)
        least += layers * max(ops / f_peak, byts / b_peak)
        took += sala_trace.ns(scopes, sala_trace.SPARSE) / 1e9
    return 100.0 * least / took if took else None

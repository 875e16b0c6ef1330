"""Kernel (a prompt's attention under the block mask, `block_flash_fwd` in
ray_tpu/ops/attention.py, all there is under the scope `attn` of
`jit_prefill` beside the copies of K and V a query head): the least time the
chip could take for the pairs the block mask keeps of the paired prefills'
prompts (their whole blocks), q, k, v and the result crossing HBM once (the
adapter's `counts.prefill_attn_ops_bytes`, the larger of operations over peak
FLOP/s and bytes over peak HBM bytes/s) times the layers, over the scope's
device self-time in those executions. The bucket's padding rows are computed
by the kernel and not counted, so the share can only under-read. The pairs
are `block_trace.prefills`' (by the request's number). None for a
model that yields a token a step, or a trace that holds no prefill.
device_trace."""

from benchmark import block_trace, models, program_trace


def read(run):
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    t = program_trace.load(run)
    if t is None or block_trace.sizes(run) is None \
            or not hasattr(counts, "prefill_attn_ops_bytes"):
        return None
    pairs = block_trace.prefills(t)
    each = block_trace.by_scope(run, t, [r for _, r in pairs])
    f_peak, b_peak = block_trace.device_peaks(run)
    ab = block_trace.BYTES[m["dtypes"]["activations"]]
    least = took = 0.0
    for (admit, _), scopes in zip(pairs, each):
        ops, byts = counts.prefill_attn_ops_bytes(
            m, admit.args["prompt_tokens"], ab)
        least += counts.attention_layers(m) * max(ops / f_peak,
                                                  byts / b_peak)
        took += scopes.get("attn", 0.0) / 1e9
    return 100.0 * least / took if took else None

"""Kernel (a prompt's attention at a head of 64, `flash_fwd` in
ray_tpu/ops/attention.py with a block whose last dimension is the whole head,
all there is under the scope `attn` of `jit_prefill` in a stack whose
attention layers are few): the least time the chip could take for the live
causal pairs of the paired prefills' prompts, q, k, v and the result crossing
HBM once (the adapter's `counts.prefill_attn_ops_bytes`, the larger of
operations over peak FLOP/s and bytes over peak HBM bytes/s) times the
ATTENTION layers (`counts.attention_layers`), over the scope's device time in
those executions. The bucket's padding rows are computed by the kernel and
not counted, so the share can only under-read. None for a program without
this stack's scopes. device_trace."""

from benchmark import conv_trace, models


def read(run):
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    pre = conv_trace.prefills(run)
    if pre is None or not hasattr(counts, "prefill_attn_ops_bytes"):
        return None
    f_peak, b_peak = conv_trace.device_peaks(run)
    ab = conv_trace.BYTES[m["dtypes"]["activations"]]
    least = took = 0.0
    for admit, scopes in pre[1]:
        ops, byts = counts.prefill_attn_ops_bytes(
            m, admit.args["prompt_tokens"], ab)
        least += counts.attention_layers(m) * max(ops / f_peak,
                                                  byts / b_peak)
        took += scopes.get("attn", 0.0) / 1e9
    return 100.0 * least / took if took else None

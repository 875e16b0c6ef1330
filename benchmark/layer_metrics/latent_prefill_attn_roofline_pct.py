"""Kernel (a prompt's latent attention, `latent_flash_fwd` in
ray_tpu/ops/attention.py: queries and keys of 128 + 64, the 64 one rotary
key for all heads, values of 128): the least time the chip could take for
that kernel's calls in the trace, over the device time they took.

A call is found by its operands (benchmark/latent_trace.py::
latent_flash_calls: five bfloat16 operands of rank 3, the fourth the shared
key with a leading 1) and its shapes are read from the event's own text, as
`attn_kernel_roofline` reads `flash_fwd`'s. Least time is the larger of
operations over peak FLOP/s (causal: the lower triangle; scores over dn +
dr, values over dv) and bytes over peak HBM bytes/s (the adapter's
`counts.latent_flash_call_ops_bytes`, benchmark/peaks.py). The bucket's
padding is counted as the kernel computes it: this is the kernel's share,
not the prompt's. None where the trace has no such call. device_trace."""

from benchmark import latent_trace, models, peaks


def read(run):
    counts = models.adapter(run["config"]["arch"]).counts
    calls = latent_trace.latent_flash_calls(run)
    if not calls or not hasattr(counts, "latent_flash_call_ops_bytes"):
        return None
    kind = run["device"]["kind"]
    f_peak = peaks.peak(kind, "bf16_flops_per_s")
    b_peak = peaks.peak(kind, "hbm_bytes_per_s")
    least = took = 0.0
    for heads, s, dn, dr, dv, seconds in calls:
        ops, byts = counts.latent_flash_call_ops_bytes(heads, s, dn, dr, dv, 2)
        least += max(ops / f_peak, byts / b_peak)
        took += seconds
    return 100.0 * least / took if took else None

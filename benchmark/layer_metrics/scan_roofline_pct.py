"""Kernel (prefill's `selective_scan`, ray_tpu/ops/ssm.py: the recurrence of
a state-space layer over a prompt, a block of channels' state in fast memory,
under the `scan` scope of `jit_prefill`): the least time the chip could take
for the scans of the prefills in the trace, over the device self-time of
their `scan` scope.

Least time of one layer is the larger of operations over peak FLOP/s and
bytes over peak HBM bytes/s (the adapter's `counts.selective_scan_ops_bytes`
at the admit's `prompt_tokens`: the rows the kernel really walks, a bucket's
padding past the prompt's last chunk of 16 rows is skipped by the program
and is not work), times the state-space layers. benchmark/peaks.py has the
matrix unit's peak and HBM's and no vector or transcendental peak, so the
recurrence's nine operations an element weigh almost nothing against it and
the kernel's bytes bound the least time: the share reads low, and is the
yardstick all the same. None for a program without that scope.
device_trace."""

from benchmark import models, peaks, program_trace, ssm_trace

BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    each = ssm_trace.by_scope(t, [r for _, r, _ in pairs])
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    if not ssm_trace.has(each) \
            or not hasattr(counts, "selective_scan_ops_bytes"):
        return None
    kind = run["device"]["kind"]
    f_peak = peaks.peak(kind, "bf16_flops_per_s")
    b_peak = peaks.peak(kind, "hbm_bytes_per_s")
    least = took = 0.0
    for (admit, _, _), scopes in zip(pairs, each):
        ops, byts = counts.selective_scan_ops_bytes(
            m, admit.args["prompt_tokens"], BYTES[m["dtypes"]["activations"]])
        least += counts.mamba_layers(m) * max(ops / f_peak, byts / b_peak)
        took += scopes.get("scan", 0.0) / 1e9
    return 100.0 * least / took if took else None

"""Kernel (prefill's `retention_prompt`, ray_tpu/ops/retention.py: the
attention form over a prompt and the kernel `retention_state`, under the
scope `retention` of `jit_prefill`): the least time the chip could take for
the operator of the prefills in the trace, over the device self-time of
their `retention` scope.

Least time of one layer is the larger of operations over peak FLOP/s and
bytes over peak HBM bytes/s of the LEAST work of the equations for a prompt
of its length (the adapter's `counts.retention_prompt_ops_bytes` at the
admit's `prompt_tokens`: the attention form's pairs under the mask and ONE
build of S and z; q, k, v and the gates read, the rows and the state written
once), times the layers. The program scores a bucket's every pair, in
float32 at six passes of the matrix unit where the peak is bfloat16's, and
builds its state at the bucket's width: it does more work than is counted,
so the share reads low by design, as `scan_roofline_pct` does. None for a
program without the scope. device_trace."""

from benchmark import retention_trace


def read(run):
    pairs = retention_trace.prefills(run)
    counts = retention_trace.counts_of(run)
    if not pairs or counts is None:
        return None
    m = run["config"]
    f_peak, b_peak = retention_trace.device_peaks(run)
    act = retention_trace.BYTES[m["dtypes"]["activations"]]
    least = took = 0.0
    for admit, _, scopes in pairs:
        ops, byts = counts.retention_prompt_ops_bytes(
            m, admit.args["prompt_tokens"], act)
        least += m["num_hidden_layers"] * max(ops / f_peak, byts / b_peak)
        took += scopes.get("retention", 0.0) / 1e9
    return 100.0 * least / took if took else None

"""Kernel (prefill's `linear_chunk`, ray_tpu/ops/linear_attention.py, under
the scope `linear_attn` of `jit_prefill`): the least time the chip could take
for the linear layers' operator of the prefills in the trace, over the device
self-time of their `linear_attn` scope.

Least time of one layer is the larger of operations over peak FLOP/s and
bytes over peak HBM bytes/s of the LEAST work of the recurrence for a prompt
of its length (the adapter's `counts.linear_prompt_ops_bytes` at the admit's
`prompt_tokens`: a multiply-add an element of the state to move it and one to
read it, a position; q, k and v read, the rows and the final state written,
once each), times the linear layers. The program works in chunks (a chunk's
own pairs are more operations for the same numbers, the state's products run
in float32 at six passes of the matrix unit where the peak is bfloat16's) and
over the bucket's padding: it does more work than is counted, so the share
reads low by design, as `scan_roofline_pct` and
`retention_prefill_roofline_pct` do; over 100 is a fault in this reader. None
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
    layers = counts.mixer_layers(m)[1]
    least = took = 0.0
    for admit, _, scopes in pairs:
        ops, byts = counts.linear_prompt_ops_bytes(
            m, admit.args["prompt_tokens"], act)
        least += layers * max(ops / f_peak, byts / b_peak)
        took += sala_trace.ns(scopes, sala_trace.LINEAR) / 1e9
    return 100.0 * least / took if took else None

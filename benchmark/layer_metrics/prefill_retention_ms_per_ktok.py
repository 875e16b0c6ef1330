"""Model step (prefill): device self-time under the scope `retention` (the
power-retention operator over a prompt: the attention form's rows and one
build of the final state; ray_tpu/ops/retention.py::retention_prompt) in the
`jit_prefill` executions of the trace, over the thousands of prompt tokens of
the admits paired with them, as `prefill_ms_per_ktok` counts the whole
program. None for a program without the scope. device_trace."""

from benchmark import retention_trace


def read(run):
    pairs = retention_trace.prefills(run)
    if not pairs:
        return None
    tokens = sum(admit.args["prompt_tokens"] for admit, _, _ in pairs)
    if not tokens:
        return None
    return sum(d.get("retention", 0.0) for _, _, d in pairs) / 1e6 \
        / (tokens / 1e3)

"""Model step (prefill): device self-time of the FULL layers' attention
kernel (scope `full_attn` inside `attn`: `full_flash_fwd`,
ray_tpu/ops/attention.py, and nothing else) in the `jit_prefill` executions
of the trace, over the thousands of prompt tokens of the admits paired with
them: the quadratic part of a mixed stack's prefill. None for a program
without that scope. device_trace."""

from benchmark import window_trace


def read(run):
    found = window_trace.prefill_scope(run, ["full_attn"])
    if found is None or not sum(found[0]):
        return None
    tokens, took_s = found
    return took_s * 1e3 / (sum(tokens) / 1e3)

"""Model step (prefill): device self-time of the WINDOW layers' attention
kernel (scope `window_attn` inside `attn`: `window_flash_fwd`,
ray_tpu/ops/attention.py, and nothing else) in the `jit_prefill` executions
of the trace, over the thousands of prompt tokens of the admits paired with
them, as `prefill_ms_per_ktok` counts the whole program. It does not grow
with the prompt: the kernel visits two key blocks a query block. None for a
program without that scope. device_trace."""

from benchmark import window_trace


def read(run):
    found = window_trace.prefill_scope(run, ["window_attn"])
    if found is None or not sum(found[0]):
        return None
    tokens, took_s = found
    return took_s * 1e3 / (sum(tokens) / 1e3)

"""Kernel (a prompt's full attention in a mixed stack, `full_flash_fwd` in
ray_tpu/ops/attention.py: keys of 128 + 64 a kv head, values of 128, all
there is under the scope `full_attn` of `jit_prefill`): the least time the
chip could take for the causal triangle of the paired prefills' prompts at 2
x (192 + 128) operations a pair a head, q, k, v and the result crossing HBM
once (the adapter's `counts.prefill_attn_ops_bytes`), over the scope's device
time (benchmark/window_trace.py::prefill_roofline_pct). None for a program
without the scope. device_trace."""

from benchmark import window_trace


def read(run):
    return window_trace.prefill_roofline_pct(run, window=False)

"""Kernel (a prompt's window attention, `window_flash_fwd` in
ray_tpu/ops/attention.py, all there is under the scope `window_attn` of
`jit_prefill`): the least time the chip could take for the LIVE pairs of the
paired prefills' prompts, sum over queries i of min(i + 1, window) keys a
head at 2 x (192 + 128) operations a pair, q, k, v and the result crossing
HBM once (the adapter's `counts.prefill_attn_ops_bytes`), over the scope's
device time (benchmark/window_trace.py::prefill_roofline_pct). The kernel
computes 2 x 128 keys a query where at most 128 are live, so half is its
ceiling on operations. None for a program without the scope. device_trace."""

from benchmark import window_trace


def read(run):
    return window_trace.prefill_roofline_pct(run, window=True)

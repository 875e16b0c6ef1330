"""Kernel (decode attention over the full layers' pages in a mixed stack,
`paged_decode` in ray_tpu/ops/paged_kv.py with keys wider than values, all
there is under the scope `full_attn` of `jit_decode`): the bytes of the live
rows a chunk's steps need (the dispatch spans' `live_kv_tokens` x the
chunk's steps; 4 kv heads of 192 + 128 numbers a row, the adapter's
`counts.decode_attn_bytes`) times the full layers, over peak HBM bytes/s,
over the scope's device self-time an execution
(benchmark/window_trace.py::decode_roofline_pct). The arena holds a 192-wide
key in 256 lanes, so five sixths is the ceiling. `decode_attn_roofline_pct`
multiplies by the configuration's layer count and one head width, and is not
this stack's. None for a program without the scope. device_trace."""

from benchmark import window_trace


def read(run):
    return window_trace.decode_roofline_pct(run, window=False)

"""Kernel (decode attention over the window layers' rings,
ray_tpu/ops/slot_state.py::window_decode_attention, all there is under the
scope `window_attn` of `jit_decode`): the bytes of the ring rows a chunk's
steps need (the dispatch spans' `window_kv_tokens`: min(position + 1, 128) a
live slot a step; 8 kv heads of 192 + 128 numbers a row, the adapter's
`counts.decode_attn_bytes`) times the window layers, over peak HBM bytes/s,
over the scope's device self-time an execution
(benchmark/window_trace.py::decode_roofline_pct). The program reads every
slot's whole ring as it is tiled (256 + 128 lanes), idle slots' too, so the
share can only under-read. None for a program without the scope or the
counter. device_trace."""

from benchmark import window_trace


def read(run):
    return window_trace.decode_roofline_pct(run, window=True)

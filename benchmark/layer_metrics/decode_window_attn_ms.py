"""Model step (decode): device self-time a decode step of the window layers'
cache (scopes `window_write`, the step's row into each active slot's ring,
and `window_attn`, the read of the ring with the sink;
ray_tpu/ops/slot_state.py, all the window layers) in `jit_decode`, the median
over the whole executions of the trace. None for a program without those
scopes. device_trace."""

from benchmark import window_trace


def read(run):
    found = window_trace.decode_scope(
        run, ["window_attn", "window_write"], "active")
    if found is None:
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return found[1] * 1e3 / chunk

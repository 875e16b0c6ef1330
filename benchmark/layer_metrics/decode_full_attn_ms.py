"""Model step (decode): device self-time a decode step of the full layers'
cache (scopes `kv_write`, the step's row into each active slot's page, and
`full_attn`, the `paged_decode` kernel over the slot's live pages;
ray_tpu/ops/paged_kv.py, all the full layers) in `jit_decode` of a mixed
stack, the median over the whole executions of the trace. None for a program
without a mixed stack's scopes. device_trace."""

from benchmark import window_trace


def read(run):
    found = window_trace.decode_scope(run, ["full_attn", "kv_write"],
                                      "active")
    if found is None:
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return found[1] * 1e3 / chunk

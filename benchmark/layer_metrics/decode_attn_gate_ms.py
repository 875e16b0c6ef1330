"""Model step (decode): device self-time a decode step of the gate on
attention's output (scope `attn_gate`: every layer's `[slots, heads, 128]`
result times the sigmoid of its head's logit, before `wo`;
ray_tpu/models/block.py::gated) in `jit_decode`, the median over the whole
executions of the trace. None for a trace without a mixed stack's scopes;
near 0 where the compiler makes the product part of another scope's
operation (0.005 ms on the chip, PR 62). device_trace."""

from benchmark import gate_trace


def read(run):
    found = gate_trace.decode(run)
    if found is None:
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return found[1] * 1e3 / chunk

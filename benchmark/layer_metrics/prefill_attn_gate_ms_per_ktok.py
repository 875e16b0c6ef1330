"""Model step (prefill): device self-time of the gate on attention's output
(scope `attn_gate` inside `attn`: every layer's `[rows, heads, 128]` result
times the sigmoid of its head's logit, before `wo`;
ray_tpu/models/block.py::gated) in the `jit_prefill` executions of the trace,
over the thousands of prompt tokens of the admits paired with them, as
`prefill_ms_per_ktok` counts the whole program. The gate's logits are columns
of the fused projection and cost no pass of their own: this is the product's
pass over the attention's result. None for a trace without a mixed stack's
scopes, 0 for a mixed stack's program with nothing under the gate's.
device_trace."""

from benchmark import gate_trace


def read(run):
    found = gate_trace.prefill(run)
    if found is None or not sum(found[0]):
        return None
    tokens, took_s = found
    return took_s * 1e3 / (sum(tokens) / 1e3)

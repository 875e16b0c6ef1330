"""Model step (prefill): device self-time under the scope `linear_attn` (the
decayed linear-attention operator over a prompt, every linear layer: the
kernel `linear_chunk`, a chunk's own pairs, its past and the state moved on;
ray_tpu/ops/linear_attention.py::linear_prompt) in the `jit_prefill`
executions of the trace, over the thousands of prompt tokens of the admits
paired with them, as `prefill_ms_per_ktok` counts the whole program. None for
a program without the scope. device_trace."""

from benchmark import sala_trace


def read(run):
    return sala_trace.prefill_ms_per_ktok(run, sala_trace.LINEAR)

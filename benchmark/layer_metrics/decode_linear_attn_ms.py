"""Model step (decode): device self-time a decode step under the scope
`linear_attn` (every linear layer's `linear_step`: the active slots' state
read, decayed, added to, read against the query and written back;
ray_tpu/ops/linear_attention.py::linear_state_step) in `jit_decode`, the
median over the whole executions of the trace. None for a program without the
scope. device_trace."""

from benchmark import sala_trace


def read(run):
    return sala_trace.decode_step_ms(run, sala_trace.LINEAR)

"""Model step (decode): device self-time a decode step of `jit_decode`
instructions under `qkv`, `attn_out` and `mlp`: the weight matmuls of the
layers. device_trace."""

from benchmark import program_trace


def read(run):
    ms = program_trace.scoped_ms(run, "jit_decode", ("qkv", "attn_out", "mlp"))
    if ms is None:
        return None
    return ms / run["config"]["deployment"]["engine"]["decode_chunk"]

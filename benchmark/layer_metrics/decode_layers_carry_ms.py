"""Model step (decode): device self-time a decode step of `jit_decode`
instructions whose deepest `jax.named_scope` is `layers` itself: the scan
over the stack and what the compiler copies to carry the KV arena through it,
outside every scope of a layer's own arithmetic. device_trace."""

from benchmark import program_trace


def read(run):
    ms = program_trace.scoped_ms(run, "jit_decode", ("layers",))
    if ms is None:
        return None
    return ms / run["config"]["deployment"]["engine"]["decode_chunk"]

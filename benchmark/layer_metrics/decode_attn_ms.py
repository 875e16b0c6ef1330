"""Model step (decode): device self-time a decode step of `jit_decode`
instructions under `attn`: scores, softmax and the weighted sum over the
gathered history. device_trace."""

from benchmark import program_trace


def read(run):
    ms = program_trace.scoped_ms(run, "jit_decode", ("attn",))
    if ms is None:
        return None
    return ms / run["config"]["deployment"]["engine"]["decode_chunk"]

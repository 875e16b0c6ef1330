"""Model step (decode): device self-time a decode step of `jit_decode`
instructions under `kv_write` (the scatter of the new position) and
`kv_gather` (each slot's pages into its logical history). device_trace."""

from benchmark import program_trace


def read(run):
    ms = program_trace.scoped_ms(run, "jit_decode", ("kv_write", "kv_gather"))
    if ms is None:
        return None
    return ms / run["config"]["deployment"]["engine"]["decode_chunk"]

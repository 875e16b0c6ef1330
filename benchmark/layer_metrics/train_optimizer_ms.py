"""Device (train): device self-time a step of `jit_step_fn` instructions under
`optimizer` (train/spmd.py: clipping, AdamW, applying the updates).
device_trace."""

from benchmark import program_trace


def read(run):
    return program_trace.scoped_ms(run, "jit_step_fn", ("optimizer",))

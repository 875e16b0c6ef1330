"""Device (train): device self-time a step of `jit_step_fn` instructions under
`head` and `loss` (models/llama.py: final norm, the vocabulary matmul and the
cross-entropy), forward and backward. device_trace."""

from benchmark import program_trace


def read(run):
    return program_trace.scoped_ms(run, "jit_step_fn", ("head", "loss"))

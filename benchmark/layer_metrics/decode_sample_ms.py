"""Model step (decode): device self-time a decode step of `jit_decode`
instructions under `sample` (`models/serving.py::sample_tokens`: the argmax
of every slot's logits and, where a slot of the step asks for a sample, the
top-k, the draws and the gather behind a conditional, whose own event is
charged what its branch's instructions leave: once). device_trace."""

from benchmark import program_trace


def read(run):
    ms = program_trace.scoped_ms(run, "jit_decode", ("sample",))
    if ms is None:
        return None
    return ms / run["config"]["deployment"]["engine"]["decode_chunk"]

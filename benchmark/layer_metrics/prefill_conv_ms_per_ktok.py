"""Model step (prefill): device self-time of the conv operator's three scopes
(`conv_in`, `conv`, `conv_out`; ray_tpu/models/block.py::conv_mixer, all the
conv layers) in the `jit_prefill` executions of the trace, over the thousands
of prompt tokens of the admits paired with them, as `prefill_ms_per_ktok`
counts the whole program. None for a program without those scopes.
device_trace."""

from benchmark import conv_trace


def read(run):
    read = conv_trace.prefills(run)
    if read is None:
        return None
    tokens = sum(admit.args["prompt_tokens"] for admit, _ in read[1])
    return sum(conv_trace.ns(d) for _, d in read[1]) / 1e6 / (tokens / 1e3)

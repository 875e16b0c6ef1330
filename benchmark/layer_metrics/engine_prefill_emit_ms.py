"""Scheduler (serve/engine.py): median, over the requests prefilled inside the
traced window, of the end of the request's `serve.engine.emit` span (`kind` =
first: the engine's first-token instant) less the start of its `jit_prefill`
program on the device: the prefill itself plus the emitter's lag.
program_span + device_trace."""

from benchmark import program_trace
from benchmark.stats import median


def read(run):
    t = program_trace.load(run)
    took = [(emit.end - prefill[1]) / 1e6
            for _, prefill, emit in (t.prefills() if t else []) if emit]
    return median(took) if took else None

"""Scheduler (serve/engine.py): median, over the requests prefilled inside the
traced window, of the start of the request's `jit_prefill` program on the
device less the start of its `serve.engine.admit` span: how long the prefill
queued behind decode chunks already dispatched (the engine's pipeline depth).
Pairing: program_trace.ProgramTrace.prefills. program_span + device_trace."""

from benchmark import program_trace
from benchmark.stats import median


def read(run):
    t = program_trace.load(run)
    waits = [(prefill[1] - admit.start) / 1e6
             for admit, prefill, _ in (t.prefills() if t else [])]
    return median(waits) if waits else None

"""Scheduler (serve/engine.py): median over the `serve.engine.admit` spans of
the traced window of `queue_wait_us`, the engine's own stamp of submit ->
admission: waiting for a slot or for pages, not for the device.
program_span."""

from benchmark import program_trace
from benchmark.stats import median


def read(run):
    t = program_trace.load(run)
    waits = [s.args["queue_wait_us"] for s in
             (t.named("serve.engine.admit") if t else [])]
    return median(waits) / 1e3 if waits else None

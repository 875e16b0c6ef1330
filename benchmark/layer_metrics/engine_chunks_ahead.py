"""Scheduler (serve/engine.py): mean over the `serve.engine.admit` spans of
the traced window of `chunks_ahead`, the decode chunks in flight (dispatched,
output not yet fetched) at the instant of admission: what the request's
prefill queued behind on the device, 0 when the engine was idle. A program
whose admit spans carry no such count leaves the metric out.
program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    ahead = [s.args["chunks_ahead"] for s in
             (t.named("serve.engine.admit") if t else [])
             if "chunks_ahead" in s.args]
    return sum(ahead) / len(ahead) if ahead else None

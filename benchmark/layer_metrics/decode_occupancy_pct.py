"""Scheduler (serve/engine.py): share of the decode program's slot-steps that
produced a token a request wanted, sum(`useful`) over sum(`capacity`) from the
`serve.engine.decode_dispatch` spans of the traced window.
program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    chunks = t.named("serve.engine.decode_dispatch") if t else []
    capacity = sum(s.args["capacity"] for s in chunks)
    if not capacity:
        return None
    return 100.0 * sum(s.args["useful"] for s in chunks) / capacity

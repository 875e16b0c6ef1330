"""Device: the share of the traced window in which chip 0 ran nothing while
the engine had something to do: the chip's idle time (the window less its
instructions, as `busy_s` counts them) that does NOT fall under the self time
of a `serve.engine.idle` span, the loop's stand with no slot live. What is
left is idle under `serve.engine.admit` (a prefill on its way to an empty
chip), `serve.engine.emit_block` (the loop waits for the emitter),
`serve.engine.decode_dispatch` and the lines between them
(benchmark/engine_trace.py splits it). The other share, idle because no
request has arrived, is the traffic's and no metric. None for a program
without the span. program_span + device_trace."""

from benchmark import engine_trace, program_trace


def read(run):
    per = engine_trace.shares(program_trace.load(run), run.get("trace_data"))
    return None if per is None else engine_trace.with_work_pct(per)

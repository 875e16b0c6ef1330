"""Model step (prefill): device time of the `jit_prefill` executions of the
trace over the thousands of prompt tokens they prefilled, both sides from the
trace's own pairing of each `serve.engine.admit` span with its execution
(program_trace.ProgramTrace.prefills), so both cover the same requests. (Up
to PR 25 the tokens were counted between the driver's marks around the
profiler calls; a saturated replica answers `bench_trace_stop` seconds late,
the trace runs on, and the time was summed over twice the window the tokens
came from: 101.7 read where this reads about half.) device_trace."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    pairs = t.prefills() if t else []
    tokens = sum(admit.args["prompt_tokens"] for admit, _, _ in pairs)
    if not tokens:
        return None
    return sum(e - s for _, (_, s, e), _ in pairs) / 1e6 / (tokens / 1e3)

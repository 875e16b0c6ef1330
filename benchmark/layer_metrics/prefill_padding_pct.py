"""Scheduler (serve/engine.py): share of the prefilled positions that were
padding, sum(`bucket` - `prompt_tokens`) over sum(`bucket`), from the
`serve.engine.admit` spans (`kind` = prefill) of the traced window.
program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    admits = t.named("serve.engine.admit", kind="prefill") if t else []
    width = sum(s.args["bucket"] for s in admits)
    if not width:
        return None
    return 100.0 * (width - sum(s.args["prompt_tokens"] for s in admits)) \
        / width

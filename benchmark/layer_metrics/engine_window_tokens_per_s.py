"""Scheduler (serve/engine.py): tokens a second over the window by the
program's own count, CONTINUOUS (`timeline_record.window_tokens`): a
prefill's `prompt_tokens`, first token and `riders` spread over admit start
-> first emit end, a chunk's `useful` over dispatch start -> its emit's end,
each counted by the part inside the window. `batch_tokens_per_s` counts the
same tokens at the client and puts a whole prompt on the instant of its first
byte, so two runs of one code differ by whole prompts; this count has no such
lattice. program_counter."""

from benchmark import timeline_record


def read(run):
    tokens = timeline_record.window_tokens(run)
    return None if tokens is None else tokens / run["seconds"]

"""Scheduler (serve/engine.py): p95 of `queue_wait_us` / 1000 over EVERY
`serve.engine.admit` span of the window (about 110 in `serve-chat`; the
median over the 8 of the traced 4 s is `engine_queue_wait_ms`): submit to
admission, the wait for a slot or for pages. program_span."""

from benchmark import timeline_record
from benchmark.stats import percentile


def read(run):
    spans = timeline_record.spans(run, timeline_record.ADMIT)
    return percentile([s.args["queue_wait_us"] / 1e3 for s in spans], 95.0) \
        if spans else None

"""Scheduler (serve/engine.py): p95, over every prefill admitted in the
window, of the end of the request's `serve.engine.emit` span (`kind` first)
less the start of its `serve.engine.admit` span, paired by `rid`: the wait
behind the chunks in flight, the prefill and the fetch of its token, a
request. What `engine_pipeline_wait_ms` and `engine_prefill_emit_ms` split,
as medians, for the 8 prefills of the traced 4 s. program_span."""

from benchmark import timeline_record
from benchmark.stats import percentile


def read(run):
    ms = timeline_record.admit_to_first_ms(run)
    return percentile(ms, 95.0) if ms else None

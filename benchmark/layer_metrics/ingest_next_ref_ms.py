"""Train scheduler (data/iterator.py): median a step of the host time inside
the `data.iter.next_ref` spans of the traced window: the iterator asking the
dataset's executor for its next block reference, which for a streaming split
is a call to its coordinator (ROADMAP S12). With `ingest_get_ms` and
`ingest_device_put_ms` it is what `ingest_wait_ms` times from outside.
program_span."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    return t.per_step_ms(("data.iter.next_ref",)) if t else None

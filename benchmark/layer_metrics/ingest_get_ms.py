"""Train scheduler (data/iterator.py): median a step of the host time inside
`data.iter.get_block` (the store `get`) plus `data.iter.format` (slice and
numpy batch) spans of the traced window. program_span."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    return t.per_step_ms(("data.iter.get_block", "data.iter.format")) \
        if t else None

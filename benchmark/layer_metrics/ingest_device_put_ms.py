"""Train scheduler (data/iterator.py): median a step of the host time inside
`data.iter.device_put` spans of the traced window. program_span."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    return t.per_step_ms(("data.iter.device_put",)) if t else None

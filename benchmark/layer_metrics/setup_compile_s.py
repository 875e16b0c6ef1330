"""Scheduler (the warm-up): the sum of `compile_s` over the
`serve.engine.warm` / `train.compile` spans of the set-up, the seconds JAX
spent in backend compiles or cache reads inside them (`tracing.compile_span`;
the timeline's copy of the span carries it). Inside `setup_warm_s`: what is
left of that is the programs' first runs and the host work around them. The
spans' `cache_misses` beside it (`timeline_record.setup_parts`, and the
script's print) say whether the set-up was cold. program_span."""

from benchmark import timeline_record


def read(run):
    return timeline_record.setup_part(run, "compile")

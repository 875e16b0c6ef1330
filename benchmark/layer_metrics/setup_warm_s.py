"""Scheduler (serve/engine.py's constructor and `_warm_buckets`; for a train
job train/spmd.py's first step): the union of the `serve.engine.warm` /
`train.compile` spans between process start and `t0`, in seconds: every
program's first call, compiled or read from the persistent cache, and run
once. program_span."""

from benchmark import timeline_record


def read(run):
    return timeline_record.setup_part(run, "warm")

"""Service layer (the harness's side of the set-up): from the start of the
first `serve.engine.warm` / `train.compile` span to `t0`, the seconds outside
every such span: the readiness poll and the benchmark's reference check,
which has no span of its own (its requests have: they are the
`serve.proxy.request` spans before the window); in a train job the check
between the two compiles, the first batches and the warm steps. With
`setup_boot_s` and `setup_warm_s` it adds up to `setup_s`. program_span."""

from benchmark import timeline_record


def read(run):
    return timeline_record.setup_part(run, "check")

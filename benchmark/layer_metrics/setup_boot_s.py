"""Service layer and below (`ray_tpu.init`, serve's proxy and controller, the
replica's or the train worker's process, TPU runtime start, weights): process
start (`t0` less `setup_s`) to the start of the first `serve.engine.warm` /
`train.compile` span of the session's timeline, in seconds. What a set-up
costs before a single program is warmed: the 17.5-25.8 s that stood
"whatever the tree" (ROADMAP R0h). None on a program that leaves no timeline
(before PR 51). program_span."""

from benchmark import timeline_record


def read(run):
    return timeline_record.setup_part(run, "boot")

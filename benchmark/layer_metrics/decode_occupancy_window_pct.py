"""Scheduler (serve/engine.py): `decode_occupancy_pct` over the whole window:
sum(`useful`) over sum(`capacity`) of every `serve.engine.decode_dispatch`
span that started in it, from the session's timeline. program_counter."""

from benchmark import timeline_record


def read(run):
    return timeline_record.occupancy_pct(
        timeline_record.spans(run, timeline_record.DISPATCH))

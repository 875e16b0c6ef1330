"""Scheduler (serve/engine.py): `engine_slot_refill_ms` over the whole
window: the mean of `slot_idle_us` / 1000 over every `serve.engine.admit`
span (`kind` prefill) that started in it and whose slot was freed in it:
first tenants (0) are left out as in the 4 s reader, and so is a slot that
was freed before `t0` (the check's requests left it: its `slot_idle_us` is
the time since the check). From the session's timeline. program_span."""

from benchmark import timeline_record


def read(run):
    return timeline_record.slot_refill_ms(
        timeline_record.spans(run, timeline_record.ADMIT, kind="prefill"),
        since=run["t0"])

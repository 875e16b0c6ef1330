"""Scheduler (serve/engine.py): mean, over the `serve.engine.admit` spans
(`kind` = prefill) of the traced window, of `slot_idle_us` / 1000: how long
the slot a request was given had stood empty since its last tenant finished
(`Engine._finish_state` stamps it, `_admit` reads it). A slot's first tenant
reads 0 and is left out; a program whose admit spans carry no such argument
leaves the metric out. On a saturated replica it is what a freed slot waits
for its next request (ROADMAP S14). The MEAN, as `slot_idle_s_sum` over
`admitted` of `Engine.counters()` is: times the admits of the window, over
the window x `n_slots`, it is the share of slot-time left unfilled, which the
dispatch spans' `active` tell too (1 - mean(`active`) / `n_slots`). The
distribution is skewed (on `serve-batch` the median reads 47 ms where the
mean reads 211: PERF.md, PR 37), so a median times the admits is no sum.
program_span."""

from benchmark import engine_trace, program_trace


def read(run):
    t = program_trace.load(run)
    refills = engine_trace.slot_refill(t) if t else []
    return sum(refills) / len(refills) if refills else None

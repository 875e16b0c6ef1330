"""Scheduler (serve/engine.py): the share of the traced window's slot-time
that decoding slots spent waiting through another request's prefill. Over
the prefills paired with their admit spans (program_trace.prefills): device
time of the `jit_prefill` execution x `decoding`, the slots live when the
request was admitted, whose next chunk queued behind that prefill on the
device; summed, over the traced window (`window_s`) x the configuration's
`deployment.engine.n_slots`. What a prefill that rides a decode chunk would
give back (ROADMAP S4, the stall). None for a program whose admit spans carry
no `decoding`. program_span + device_trace."""

from benchmark import engine_trace, program_trace


def read(run):
    t = program_trace.load(run)
    stalled = engine_trace.stalled_slot_ns(t) if t else None
    window_s = run["device"].get("window_s")
    if stalled is None or not window_s:
        return None
    slots = run["config"]["deployment"]["engine"]["n_slots"]
    return 100.0 * stalled / 1e9 / (window_s * slots)

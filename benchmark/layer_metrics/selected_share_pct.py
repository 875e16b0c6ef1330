"""Scheduler (serve), sparse attention: of the positions the active slots
held, the share whose K and V rows their decode steps read: the engine's
`selected_keys` over its `live_keys` (min(positions, index_topk) against
positions, a slot a step), summed over the trace's
`serve.engine.decode_dispatch` spans. 100 is dense attention. None for a
program whose spans carry no `selected_keys`. program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    spans = [s for s in (t.named("serve.engine.decode_dispatch") if t else [])
             if "selected_keys" in s.args]
    live = sum(s.args["live_keys"] for s in spans)
    if not live:
        return None
    return 100.0 * sum(s.args["selected_keys"] for s in spans) / live

"""Scheduler (serve), routing: the busiest expert's tokens over the mean
expert's, all layers, prefills and decode steps together, over the traced
window: the engine's running `expert_tokens` (`:`-joined on each
`serve.engine.decode_dispatch` span), last less first. 1.0 is a perfectly
even load; a grouped matmul's time follows its busiest group only where
groups run in parallel, so this says how skewed the router is, not a time.
None for a dense model. program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    seen = [s.args["expert_tokens"] for s in
            (t.named("serve.engine.decode_dispatch") if t else [])
            if s.args.get("expert_tokens")]
    if len(seen) < 2:
        return None
    first, last = ([int(n) for n in str(x).split(":")]
                   for x in (seen[0], seen[-1]))
    window = [b - a for a, b in zip(first, last)]
    if not sum(window):
        return None
    return max(window) / (sum(window) / len(window))

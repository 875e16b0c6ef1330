"""Scheduler (serve), routing of a share: of the assignments the routers
made in the traced window (live rows x experts a token x sparse layers), the
share that fell to experts held on this chip: `local` over `routed` of the
`serve.engine.prefill_experts` spans plus `local_assignments` over
`routed_assignments` of the `serve.engine.decode_dispatch` spans (each chunk's
sums, which the next dispatch reports; `Engine.counters()` has the running
totals under the same two names). Under even routing it is held / total
experts (16 of 256: 6.25); it is what this chip's grouped matmuls and its
`expert_tokens` see of the model's routed work. None for a model that holds
every expert, whose spans carry neither. program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    if t is None:
        return None
    local = routed = 0
    for s in t.named("serve.engine.prefill_experts"):
        local += s.args.get("local", 0)
        routed += s.args.get("routed", 0)
    for s in t.named("serve.engine.decode_dispatch"):
        local += s.args.get("local_assignments", 0)
        routed += s.args.get("routed_assignments", 0)
    return 100.0 * local / routed if routed else None

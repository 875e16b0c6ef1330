"""Scheduler (serve), what a decode step moves: of the least bytes a step of
`jit_decode` has to move across HBM (the adapter's
`counts.decode_step_ops_bytes`: every weight outside the routed experts
once, the held experts TOUCHED, the live K and V, the live slots' recurrent
state in and out), the share that is recurrent state
(`counts.decode_state_bytes` of the live slots, one step). Counted at the
medians of the trace's `serve.engine.decode_dispatch` spans: `active` slots,
each at the mean context `live_kv_tokens / active`, `experts_touched` over
the chunk's steps and the sparse layers. It says when the state and not the
weights sets the step: a third on 64 slots of Granite-4.0-H's ten layers,
2% on Jamba's. None for a model whose counts have no recurrent state, or a
program whose spans lack the counters. program_counter."""

from benchmark import conv_trace, models, program_trace


def read(run):
    m = run["config"]
    counts = models.adapter(m["arch"]).counts
    t = program_trace.load(run)
    if t is None or not hasattr(counts, "decode_state_bytes") \
            or not hasattr(counts, "layers"):
        return None
    spans = t.named("serve.engine.decode_dispatch")
    active, live, touched = (conv_trace.span_median(spans, arg) for arg in (
        "active", "live_kv_tokens", "experts_touched"))
    if not active or live is None or not touched:
        return None
    chunk = m["deployment"]["engine"]["decode_chunk"]
    wb, ab = (conv_trace.BYTES[m["dtypes"][k]]
              for k in ("params", "activations"))
    _, byts = counts.decode_step_ops_bytes(
        m, [live / active] * int(round(active)), wb, ab,
        experts_touched=touched / (chunk * counts.layers(m)[1]))
    state = counts.decode_state_bytes(m, int(round(active)), ab)
    return 100.0 * state / byts if byts else None

"""Scheduler (serve), block-sparse attention: of the blocks (pages) the
active slots held, the share their decode steps read: the engine's
`blocks_selected` over its `blocks_visible` (a kv head a slot a step: every
block up to the slot's own under `dense_len`, `topk` of them from there on),
summed over the trace's `serve.engine.decode_dispatch` spans. 100 is dense
attention. None for a program whose spans carry no `blocks_selected`.
program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    spans = [s for s in (t.named("serve.engine.decode_dispatch") if t else [])
             if "blocks_selected" in s.args]
    visible = sum(s.args["blocks_visible"] for s in spans)
    if not visible:
        return None
    return 100.0 * sum(s.args["blocks_selected"] for s in spans) / visible

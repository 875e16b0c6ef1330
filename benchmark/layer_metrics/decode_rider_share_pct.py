"""Scheduler (serve/engine.py): share of the tokens decoded in the traced
window that were made INSIDE a prefill, by a live slot's one decode step in
the padding rows of another request's prompt (`riders` of the
`serve.engine.admit` spans, ROADMAP S4): sum(`riders`) over that plus
sum(`useful`) of the `serve.engine.decode_dispatch` spans. A rider's step
reads no weights of its own (the prefill of the same layers reads them
anyway), so the share is decode steps the chip did not pay for; it is bounded
by how often a prompt is admitted while slots are live (one step a riding
prefill against the chunk's eight). A program whose admit spans carry no
`riders` (every rung of an indexed, a hybrid and a latent stack; a dense or a
sparse stack's narrow rungs; any program before PR 41) leaves the metric out.
program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    admits = [s for s in (t.named("serve.engine.admit") if t else [])
              if "riders" in s.args]
    if not admits:
        return None
    riders = sum(s.args["riders"] for s in admits)
    decoded = riders + sum(
        s.args["useful"] for s in t.named("serve.engine.decode_dispatch"))
    return 100.0 * riders / decoded if decoded else None

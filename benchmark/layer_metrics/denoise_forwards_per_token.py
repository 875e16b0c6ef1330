"""Scheduler (serve), generation by blocks: the forwards a token cost its
slot over the traced window: over the trace's `serve.engine.decode_dispatch`
spans, `forwards` x `active` (each forward of a chunk serves every live slot)
summed, over `useful`, the tokens the chunks' plans emit, summed. A block of
B positions in T denoising forwards and a commit is (T + 1) / B, 0.75 at 4
and 2; what lies above it is the prompts' tails (positions of a first block
that are the prompt's) and the blocks a request ends inside. None for a
program whose spans carry no `forwards`. program_counter."""

from benchmark import block_trace


def read(run):
    found = block_trace.chunks(run)
    if found is None:
        return None
    _, _, spans = found
    useful = sum(s.args.get("useful", 0) for s in spans)
    if not useful:
        return None
    return sum(s.args["forwards"] * s.args.get("active", 0)
               for s in spans) / useful

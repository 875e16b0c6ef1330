"""Scheduler (serve), the two caches: of the cached positions a FULL layer's
decode steps read in the traced window, the share a WINDOW layer's read:
the sum of the `serve.engine.decode_dispatch` spans' `window_kv_tokens`
(min(position + 1, window) a live slot a step, summed over the chunk's steps)
over the sum of their `live_kv_tokens` (positions the live slots held at the
chunk's start) times the chunk's steps. About window / mean context: 2 at 128
of 6-8k positions. `Engine.counters()` has the running totals under the same
names. None for a program whose spans carry no `window_kv_tokens`.
program_counter."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    spans = [s for s in (t.named("serve.engine.decode_dispatch") if t else [])
             if "window_kv_tokens" in s.args]
    live = sum(s.args.get("live_kv_tokens", 0) for s in spans)
    if not live:
        return None
    chunk = run["config"]["deployment"]["engine"]["decode_chunk"]
    return 100.0 * sum(s.args["window_kv_tokens"] for s in spans) \
        / (live * chunk)

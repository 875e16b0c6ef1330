"""What the engine tests of every family hold the scheduler's books to.

The keys of `Engine.counters()`, a family: a table written down from the
parent commit of PR 57 (760b70f), before that PR moved what a model counts
out of the scheduler and behind `Programs`. The set of keys is static a
model (no request is needed), so each family's case reads it off an engine
its file builds anyway (tests/test_parents_programs.py for the six older
stacks, the family's own engine file for the four newer). And `Spans`, which
records the spans an engine opens, with their arguments."""

from ray_tpu.utils import tracing

# What every model counts: the scheduler's own.
GENERIC = frozenset({
    "admitted", "queue_wait_s_sum", "admit_chunks_ahead",
    "admit_decoding_slots", "admit_pending", "slot_idle_s_sum",
    "prefill_tokens", "prefill_padded_tokens", "decode_chunks",
    "decode_chunks_sampling", "decode_useful_tokens", "rider_tokens",
    "rider_steps", "live_kv_tokens", "peak_pages_used", "n_slots", "chunk"})

_ROUTING = {"expert_tokens", "decode_experts_touched"}
_SHARE = _ROUTING | {"routed_assignments", "local_assignments"}

# What each family counts beyond that, at its adapter's rehearsal widths (the
# tiny configurations of the engine files).
MODEL = {
    "dense": set(),
    "sparse": _ROUTING,
    "indexed": _ROUTING | {"decode_selected_keys", "decode_live_keys"},
    "hybrid": {"state_bytes", "state_writes"},
    "latent": _SHARE | {"latent_cache_bytes"},
    "mixed": _SHARE | {"full_cache_bytes", "window_cache_bytes",
                       "window_kv_tokens"},
    "lfm2": _ROUTING | {"conv_state_bytes"},
    "granite": _SHARE | {"state_bytes", "state_writes"},
    "sdar": _ROUTING | {"block", "denoise_forwards", "commits_rode",
                        "block_tokens", "tail_tokens"},
    "nemotron_h": _SHARE | {"state_bytes", "state_writes"},
    "brumby": {"state_bytes", "state_writes", "state_bytes_moved"},
}


def pinned(engine, family):
    """Whether the engine's counters are the family's row of the table."""
    got = set(engine.counters())
    assert GENERIC <= got, GENERIC - got
    assert got - GENERIC == MODEL[family], (family, sorted(got - GENERIC))
    return True


class Spans:
    """`with Spans() as spans:` records (name, arguments) of every span the
    program opens meanwhile, beside what `tracing.span` does with it, and
    says a recorder is there (the loop puts a chunk's routing together only
    where a span is recorded)."""

    def __enter__(self):
        self.seen, self._span = [], tracing.span
        self._recording = tracing.recording

        def recording(name, **args):
            self.seen.append((name, args))
            return self._span(name, **args)

        tracing.span, tracing.recording = recording, lambda: True
        return self

    def __exit__(self, *exc):
        tracing.span, tracing.recording = self._span, self._recording

    def named(self, name):
        return [args for n, args in self.seen if n == name]

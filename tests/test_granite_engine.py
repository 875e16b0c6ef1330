"""Granite-4.0-H-Small's stack through the engine, at small float32 widths on
the CPU: section (e) of tests/test_granite.py (which holds the scan, the step
kernel, the mixer, the attention block, the multipliers, the shares, the
adapter and the counts, and says what the tolerances are), in a file of its
own so that neither is the suite's longest (`--dist loadfile` keeps a file on
one worker). The served tokens against `benchmark/reference_granite.py`,
the paths and the spans, the step kernel under the engine, a slot's state
its tenant's alone, and what the stack refuses.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_granite as ref
from ray_tpu.models import llama, serving
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import attention, slot_state
from ray_tpu.serve.engine import Engine
from engine_pins import Spans as _Spans, pinned
from test_granite import LOGIT_TOL, MODEL, _tokens, tiny


def _serve(engine, prompts, n):
    outs = [engine.submit(p, n) for p in prompts]
    served = []
    for q in outs:
        toks = []
        while (chunk := q.get(timeout=300)) is not None:
            toks += chunk
        served.append(toks)
    return served


# -- (e) the engine ----------------------------------------------------------

@pytest.fixture(scope="module")
def engine(tiny):
    """ONE engine of four slots for every test that serves through it and
    leaves its slots free behind it: a test reads the counters as what it
    added to them. (A test that builds the engine under a patch, or with
    one slot, builds its own.)"""
    cfg, params = tiny
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=4,
                 decode_chunk=4, page_size=16)
    yield eng
    eng.stop()


@pytest.mark.timeout(240)
def test_engine_prefill_then_decode_match_the_reference(tiny, engine):
    """Three slots at once: a prompt that fills its bucket (64), one that
    leaves padding behind it (70 in 128) and one whose decode crosses two
    page boundaries (21 -> 45, pages of 16). At every served position the
    token the engine chose is the reference's largest logit to float32
    rounding, and the logits the prefill program itself returns are the
    reference's, with the K and V of ONE layer, the state of three and the
    share's routing counts."""
    cfg, params = tiny
    before = engine.counters()["state_writes"]
    prompts = [_tokens(64, 5), _tokens(70, 6), _tokens(21, 7)]
    served = _serve(engine, prompts, 24)
    assert [len(s) for s in served] == [24, 24, 24]
    for prompt, toks in zip(prompts, served):
        gaps = ref.served_token_gaps(params, MODEL, prompt, toks)
        assert max(gaps) < LOGIT_TOL, gaps
    core = jax.jit(serving.prefill_core(cfg))
    for prompt, width in zip(prompts, (64, 128, 32)):
        padded = jnp.asarray([prompt + [9] * (width - len(prompt))], jnp.int32)
        _, ks, _, logits, experts, (ssm_rows, conv_rows) = core(
            fuse_qkv(params, cfg), padded, len(prompt))
        want = np.asarray(ref.logits_last(params, MODEL, prompt, 1))[0]
        assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
        assert ks.shape == (1, width, 2, 32)
        assert ssm_rows.shape == (3, 16, 256) and conv_rows.shape == (3, 3, 288)
        # 4 layers x the prompt's rows x 3 experts a token, about half local
        assert experts.shape == (6,) and int(experts[-1]) == 12 * len(prompt)
        assert 0 < int(experts[:4].sum()) < int(experts[-1])
    counts = engine.counters()
    assert counts["state_writes"] - before == 3
    assert counts["state_bytes"] == 3 * 4 * (16 * 256 * 4 + 3 * 288 * 4)
    assert 0 < counts["local_assignments"] < counts["routed_assignments"]
    assert len(counts["expert_tokens"]) == 4
    assert engine._caches.kc.shape[0] == 1 and engine._caches.ic is None


def test_the_hybrid_engine_took_its_paths_and_its_spans_carry_the_share(
        tiny, engine):
    """What the share's readers need, as the latent and the mixed engines'
    spans carry it (tests/test_dots.py, tests/test_mimo.py):
    `serve.engine.prefill_experts` has `local` and `routed` beside `touched`,
    `serve.engine.decode_dispatch` `local_assignments`, `experts_touched`,
    `active` and `live_kv_tokens`; and the paths the programs took."""
    import time
    cfg, _ = tiny
    prompt = _tokens(40, 31)
    with _Spans() as spans:
        assert len(_serve(engine, [prompt], 16)[0]) == 16
        # a chunk's routing is reported by the NEXT dispatch: one more request
        assert len(_serve(engine, [_tokens(9, 32)], 12)[0]) == 12
        deadline = time.monotonic() + 30
        while len(spans.named("serve.engine.prefill_experts")) < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.01)    # the emitter counts AFTER the tokens
    pre = spans.named("serve.engine.prefill_experts")
    layers, k = cfg.n_layers, cfg.top_k_experts
    assert [a["routed"] for a in pre] == [40 * k * layers, 9 * k * layers]
    assert all(0 < a["local"] < a["routed"] for a in pre)
    assert all(0 < a["touched"] <= 4 * layers for a in pre)
    chunks = [a for a in spans.named("serve.engine.decode_dispatch")
              if a.get("local_assignments")]
    assert chunks
    for a in chunks:
        assert 0 < a["local_assignments"] < a["routed_assignments"]
        assert 0 < a["experts_touched"] <= 4 * layers * engine.chunk
        assert a["active"] == 1 and a["live_kv_tokens"] > 0
        assert len(str(a["expert_tokens"]).split(":")) == 4
    counts = attention.attention_path_counts()
    assert counts["ssd_chunked"] >= 1 and counts["fwd_reference"] >= 1
    assert counts["decode_reference"] >= 1      # the CPU's decode path
    assert counts["ssd_step_reference"] >= 1    # ... and its state's update
    assert counts["share_combine_gather"] >= 1  # off the chip, the gather
    assert counts["experts_ragged_dot"] >= 1


def test_an_engine_decodes_through_the_step_kernel(tiny, monkeypatch):
    """An engine built with `slot_state.step_layer` interpreted (what the
    mixer calls is the function as it stands on the module) updates its
    slots' state through the kernel's own code, in place in the decode
    program's carry, one slot of four live: the served tokens are the
    reference's to the engine's tolerance."""
    import functools
    cfg, params = tiny
    monkeypatch.setattr(slot_state, "step_layer", functools.partial(
        slot_state.step_layer, interpret=True))
    before = attention.attention_path_counts().get("ssd_step_pallas", 0)
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=4,
                 decode_chunk=4, page_size=16)
    try:
        assert attention.attention_path_counts()["ssd_step_pallas"] > before
        prompt = _tokens(40, 41)
        toks = _serve(eng, [prompt], 12)[0]
    finally:
        eng.stop()
    assert max(ref.served_token_gaps(params, MODEL, prompt, toks)) < LOGIT_TOL


def test_a_bfloat16_state_is_outside_the_tolerance(tiny, engine):
    """The tolerance tells a narrower recurrence from the real one: the
    reference with its state rounded to bfloat16 after every token is not
    within LOGIT_TOL of what the engine serves."""
    cfg, params = tiny
    prompt = _tokens(70, 6)
    toks = _serve(engine, [prompt], 8)[0]
    seq = prompt + toks[:-1]
    exact = np.asarray(ref.logits_last(params, MODEL, seq, 8))
    coarse = np.asarray(ref.logits_last(params, MODEL, seq, 8,
                                        state_dtype=jnp.bfloat16))
    assert max(ref.served_token_gaps(params, MODEL, prompt, toks)) < LOGIT_TOL
    assert np.abs(coarse - exact).max() > 10 * LOGIT_TOL


def test_a_request_is_served_alike_alone_after_another_and_beside_idle_slots(
        tiny, engine):
    """One slot: the same prompt first, then after a longer tenant of the
    same slot (whose state and window the admission must overwrite whole),
    gives the same tokens. Four slots: beside three idle ones, and while a
    neighbour decodes and finishes (an idle slot's state must not move, an
    active one's must not leak), the same again; all the reference's."""
    cfg, params = tiny
    a, b = _tokens(60, 21), _tokens(140, 22)
    one = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=1,
                 decode_chunk=4, page_size=16)
    try:
        first = _serve(one, [a], 12)[0]
        other = _serve(one, [b], 12)[0]
        again = _serve(one, [a], 12)[0]
        assert one.counters()["state_writes"] == 3
    finally:
        one.stop()
    alone = _serve(engine, [a], 12)[0]
    beside = _serve(engine, [a, b], 12)
    later = _serve(engine, [b[:30], a], 12)[1]
    assert first == again == alone == beside[0] == later
    assert other == beside[1]
    for prompt, toks in ((a, first), (b, other)):
        assert max(ref.served_token_gaps(params, MODEL, prompt, toks)) \
            < LOGIT_TOL


def test_a_pd_handoff_and_the_training_forward_are_refused_by_name(tiny,
                                                                   engine):
    cfg, params = tiny
    assert not serving.adopts(cfg)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        engine.submit_prefilled(None, None, 8, 1, 4)
    with pytest.raises(NotImplementedError, match="state-space layers"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    dense = llama.LlamaConfig.tiny(embed_scale=12.0)
    with pytest.raises(NotImplementedError, match="embed_scale"):
        llama.forward(llama.init_params(dense, jax.random.PRNGKey(0)),
                      jnp.zeros((1, 8), jnp.int32), dense)


def test_what_the_engine_counts_is_what_the_parent_counted(engine):
    """The keys of `Engine.counters()`: tests/engine_pins.py's row."""
    assert pinned(engine, "granite")

"""NVIDIA-Nemotron-3-Nano's stack through the engine, at tiny float32 widths
on the CPU, against `benchmark/reference_nemotron_h.py`: section (c) of
tests/test_nemotron_h.py (which holds the recurrence with groups, the parts
against the reference, the pattern, the stacks and the refusals, and says
what the tolerances are), in a file of its own so that neither is the suite's
longest (`--dist loadfile` keeps a file on one worker).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_nemotron_h as ref
from ray_tpu.models import serving
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import attention, slot_state
from ray_tpu.serve.engine import Engine
from engine_pins import Spans, pinned
from test_nemotron_h import ADAPTER, F32, LOGIT_TOL, MODEL, _params, tiny


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _serve(engine, prompts, n):
    outs = [engine.submit(p, n) for p in prompts]
    served = []
    for q in outs:
        toks = []
        while (chunk := q.get(timeout=300)) is not None:
            toks += chunk
        served.append(toks)
    return served


# -- (c) the engine -----------------------------------------------------------

@pytest.fixture(scope="module")
def engine(tiny):
    """ONE engine for the tests that serve through it and leave its slots
    free behind them, or read what is static of it."""
    cfg, params = tiny
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=4,
                 decode_chunk=4, page_size=16)
    yield eng
    eng.stop()


@pytest.mark.timeout(240)
def test_engine_prefill_then_decode_match_the_reference(tiny, engine):
    """Three slots at once: a prompt that fills its bucket and ends on a
    chunk's edge (256: one chunk of the dual form), one that ends inside the
    second chunk with dead rows behind it (300 in 512) and one whose decode
    crosses two page boundaries (21 -> 45, pages of 16). At every served
    position the token the engine chose is the reference's largest logit to
    float32 rounding, and the logits the prefill program itself returns are
    the reference's, with the K and V of ONE layer, the state of three and
    the share's routing counts over the TWO sparse layers."""
    cfg, params = tiny
    prompts = [_tokens(256, 5), _tokens(300, 6), _tokens(21, 7)]
    before = engine.counters()
    with Spans() as spans:
        served = _serve(engine, prompts, 24)
    assert [len(s) for s in served] == [24, 24, 24]
    for prompt, toks in zip(prompts, served):
        gaps = ref.served_token_gaps(params, MODEL, prompt, toks)
        assert max(gaps) < LOGIT_TOL, gaps
    core = jax.jit(serving.prefill_core(cfg))
    for prompt, width in zip(prompts, (256, 512, 32)):
        padded = jnp.asarray([prompt + [9] * (width - len(prompt))], jnp.int32)
        _, ks, _, logits, experts, (ssm_rows, conv_rows) = core(
            fuse_qkv(params, cfg), padded, len(prompt))
        want = np.asarray(ref.logits_last(params, MODEL, prompt, 1))[0]
        assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
        assert ks.shape == (1, width, 2, 32)
        assert ssm_rows.shape == (3, 16, 128) and conv_rows.shape == (3, 3, 192)
        # 2 sparse layers x the prompt's rows x 3 experts a token
        assert experts.shape == (6,) and int(experts[-1]) == 6 * len(prompt)
        assert 0 < int(experts[:4].sum()) < int(experts[-1])
    counts = engine.counters()
    assert counts["state_writes"] == 3
    assert counts["state_bytes"] == 3 * 4 * (16 * 128 * 4 + 3 * 192 * 4)
    # The rows the routers saw: the prompts', a row a live slot a step of a
    # chunk (whole chunks of 4 steps: a request's last may overshoot), and a
    # row a rider of a riding rung's prefill (256 and 512 here; who rides
    # what follows the order the requests are admitted in).
    steps = sum(4 * c["active"] for c in
                spans.named("serve.engine.decode_dispatch"))
    rode = sum(a.get("riders", 0) for a in spans.named("serve.engine.admit"))
    assert rode == counts["rider_tokens"] - before["rider_tokens"]
    assert 3 * 23 <= steps + rode <= 3 * 27
    routed = (256 + 300 + 21 + steps + rode) * 3 * cfg.sparse_layers
    assert counts["routed_assignments"] == routed
    assert 0 < counts["local_assignments"] < routed
    assert len(counts["expert_tokens"]) == 4
    assert engine._caches.kc.shape[0] == 1 and engine._caches.ic is None
    paths = attention.attention_path_counts()
    assert paths["ssd_chunked"] >= 1 and paths["ssd_step_reference"] >= 1


def test_an_engine_decodes_through_the_step_kernel(monkeypatch):
    """An engine at 128 states (the kernel's lanes a group) built with
    `slot_state.step_layer` interpreted updates its slots' state through the
    grouped kernel's own code, in place in the decode program's carry: the
    served tokens are the reference's to the engine's tolerance."""
    import functools
    model = dict(MODEL, ssm_state_size=128, mamba_head_dim=32,
                 hybrid_override_pattern="ME*M", num_hidden_layers=4)
    cfg = ADAPTER.build_config(model, F32, 128)
    params = _params(cfg)
    monkeypatch.setattr(slot_state, "step_layer", functools.partial(
        slot_state.step_layer, interpret=True))
    before = attention.attention_path_counts().get("ssd_step_pallas", 0)
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                 decode_chunk=2, page_size=16)
    try:
        assert attention.attention_path_counts()["ssd_step_pallas"] > before
        prompt = _tokens(40, 41)
        toks = _serve(eng, [prompt], 8)[0]
    finally:
        eng.stop()
    assert max(ref.served_token_gaps(params, model, prompt, toks)) < LOGIT_TOL


def test_bfloat16_is_outside_the_tolerance(tiny):
    """The tolerance tells a lower precision from the stated one: the same
    program with parameters and activations in bfloat16 is not within
    LOGIT_TOL of the reference on the very weights it holds, and neither is
    the reference with its state rounded to bfloat16 after every token."""
    cfg, params = tiny
    prompt = _tokens(70, 6)
    padded = jnp.asarray([prompt + [9] * 58], jnp.int32)
    exact = np.asarray(ref.logits_last(params, MODEL, prompt, 1))[0]
    got = jax.jit(serving.prefill_core(cfg))(
        fuse_qkv(params, cfg), padded, 70)[3]
    assert np.abs(np.asarray(got) - exact).max() < LOGIT_TOL
    cfg16 = ADAPTER.build_config(
        MODEL, {"params": "bfloat16", "activations": "bfloat16"}, 512)
    params16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    low = jax.jit(serving.prefill_core(cfg16))(
        fuse_qkv(params16, cfg16), padded, 70)[3]
    held = np.asarray(ref.logits_last(params16, MODEL, prompt, 1))[0]
    assert np.abs(np.asarray(low) - held).max() > 100 * LOGIT_TOL
    coarse = np.asarray(ref.logits_last(params, MODEL, prompt, 1,
                                        state_dtype=jnp.bfloat16))[0]
    assert np.abs(coarse - exact).max() > 10 * LOGIT_TOL


def test_a_model_with_rope_is_another_model(tiny):
    """The attention layer takes NO position signal: the reference given a
    rotary theta is not within the tolerance of what the program computes."""
    cfg, params = tiny
    prompt = _tokens(40, 8)
    turned = np.asarray(ref.logits_last(params, MODEL, prompt, 1,
                                        rope_theta=10000.0))[0]
    exact = np.asarray(ref.logits_last(params, MODEL, prompt, 1))[0]
    assert np.abs(turned - exact).max() > 100 * LOGIT_TOL


def test_what_the_engine_counts_is_what_the_parent_counted(engine):
    """The keys of `Engine.counters()`: tests/engine_pins.py's row."""
    assert pinned(engine, "nemotron_h")

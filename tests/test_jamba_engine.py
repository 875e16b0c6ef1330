"""Jamba's hybrid stack through the engine, at tiny float32 widths on the
CPU, against `benchmark/reference_jamba.py`: sections (b) the engine against
the reference and (c) a slot's state is its tenant's alone of
tests/test_jamba.py (which holds the scan, the steps, the split prompt, the
tied head, the adapter and the counts, and says what the tolerances are), in
a file of its own so that neither is the suite's longest (`--dist loadfile`
keeps a file on one worker). Every engine here prefills through the Pallas
scan kernel, interpreted.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_jamba as ref
from ray_tpu.models import block
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import attention, ssm
from ray_tpu.models.serving import prefill_core
from ray_tpu.serve.engine import Engine
from test_jamba import LOGIT_TOL, MODEL, _tokens, tiny


def _serve(engine, prompts, n):
    outs = [engine.submit(p, n) for p in prompts]
    served = []
    for q in outs:
        toks = []
        while (chunk := q.get(timeout=300)) is not None:
            toks += chunk
        served.append(toks)
    return served


# -- (b) the engine against the reference -----------------------------------

@pytest.fixture(scope="module", autouse=True)
def scan_in_interpret_mode():
    """tests/test_jamba.py's fixture of this name, for the whole of this
    file: every program traced here, by an engine's warm-up thread as well,
    takes the Pallas scan kernel, interpreted."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(block, "selective_scan", functools.partial(
            ssm.selective_scan, interpret=True))
        yield


@pytest.fixture(scope="module")
def engine(tiny, scan_in_interpret_mode):
    """ONE engine of four slots for every test that serves through it and
    leaves its slots free behind it: a test reads the counters as what it
    added to them. (The test of one slot builds its own.)"""
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
    reference's, with the K and V of ONE layer and the state of three."""
    cfg, params = tiny
    before = attention.attention_path_counts().get("scan_pallas", 0)
    writes = engine.counters()["state_writes"]
    prompts = [_tokens(64, 5), _tokens(70, 6), _tokens(21, 7)]
    served = _serve(engine, prompts, 24)
    assert [len(s) for s in served] == [24, 24, 24]
    for prompt, toks in zip(prompts, served):
        gaps = ref.served_token_gaps(params, MODEL, prompt, toks)
        assert max(gaps) < LOGIT_TOL, gaps
    core = jax.jit(prefill_core(cfg))
    for prompt, width in zip(prompts, (64, 128, 32)):
        padded = jnp.asarray([prompt + [9] * (width - len(prompt))], jnp.int32)
        _, ks, _, logits, experts, (ssm_rows, conv_rows) = core(
            fuse_qkv(params), padded, len(prompt))
        want = np.asarray(ref.logits_last(params, MODEL, prompt, 1))[0]
        assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
        assert ks.shape == (1, width, 1, 16) and experts is None
        assert ssm_rows.shape == (3, 16, 128) and conv_rows.shape == (3, 3, 128)
    assert attention.attention_path_counts()["scan_pallas"] > before
    counts = engine.counters()
    assert counts["state_writes"] - writes == 3
    assert counts["state_bytes"] == 3 * 4 * (16 * 128 * 4 + 3 * 128 * 4)
    assert engine._caches.kc.shape[0] == 1 and engine._caches.ic is None


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


# -- (c) a slot's state is its tenant's alone -------------------------------

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


def test_a_pd_handoff_is_refused_not_served_without_its_state(tiny, engine):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        engine.submit_prefilled(None, None, 8, 1, 4)

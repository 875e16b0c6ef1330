"""MiMo-V2's mixed stack through ONE engine (two slots, the full layers'
decode kernel interpreted), at small float32 widths on the CPU, against
`benchmark/reference_mimo.py`: the section "Through the engine" of
tests/test_mimo.py (which holds the configuration, the share, the kernels and
the ring, and says what the tolerances are), in a file of its own so that
neither is the suite's longest (`--dist loadfile` keeps a file on one worker).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_mimo
from ray_tpu.models import llama
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import attention, paged_kv
from ray_tpu.models.serving import prefill_core
from ray_tpu.serve.engine import Engine
from test_mimo import LOGIT_TOL, WINDOW, tiny


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _drain(q):
    out = []
    while (item := q.get(timeout=300)) is not None:
        out.extend(item)
    return out


# ---------------------------------------------------------------------------
# Through the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(tiny):
    """The engine with the full layers' decode kernel interpreted."""
    _, _, cfg, params = tiny
    mp = pytest.MonkeyPatch()
    mp.setattr(paged_kv, "paged_decode_attention", functools.partial(
        paged_kv.paged_decode_attention, interpret=True))
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                 decode_chunk=4, page_size=16)
    mp.undo()
    yield eng
    eng.stop()


@pytest.mark.timeout(300)
@pytest.mark.parametrize("n,bucket", [(10, 32), (16, 32), (100, 128),
                                      (7, 32)],
                         ids=["under-the-window", "the-window",
                              "six-windows", "a-shorter-one-after"])
def test_prefill_then_decode_through_both_caches_is_the_reference(
        tiny, engine, n, bucket):
    """Prompts shorter than, equal to and several times the window (16), and
    a shorter one into the slot the longer one left; then 40 tokens decoded,
    the full layers through their pages (pages of 16, the kernel
    interpreted), the window layers through a ring of 16 rows that wraps
    twice: the prefill's logits are the reference's at the prompt's last
    position, and every served token is the reference's largest logit to
    float32 rounding."""
    adapter, model, cfg, params = tiny
    prompt = _tokens(n, n)
    ref = adapter.reference()
    _, ks, vs, logits, experts, (kws, vws) = jax.jit(prefill_core(cfg))(
        fuse_qkv(params, cfg),
        jnp.asarray([prompt + [0] * (bucket - n)], jnp.int32), n)
    want = np.asarray(ref.logits_last(params, model, prompt, 1))[0]
    assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
    assert ks.shape == (2, bucket, 1, 48) and vs.shape == (2, bucket, 1, 32)
    assert kws.shape == (2, bucket, 2, 48) and vws.shape == (2, bucket, 2, 32)
    held = cfg.n_held
    assert experts.shape == (held + 2,)
    assert int(experts[-1]) == n * cfg.top_k_experts * 3    # 3 sparse layers
    before = engine.counters()
    served = _drain(engine.submit(prompt, 40))
    assert len(served) == 40
    gaps = ref.served_token_gaps(params, model, prompt, served)
    assert max(gaps) < LOGIT_TOL, gaps
    # teeth: against the prompt less its last token the same tokens are
    # another row's
    short = ref.served_token_gaps(params, model, prompt[:-1], served)
    assert max(short) > 100 * LOGIT_TOL
    after = engine.counters()
    # sequential requests: each took slot 0, the last tenant's ring and all
    assert engine._slot_req == [None, None]
    assert after["window_kv_tokens"] > before["window_kv_tokens"]
    assert after["window_kv_tokens"] - before["window_kv_tokens"] \
        <= WINDOW * 4 * (after["decode_chunks"] - before["decode_chunks"])


def test_the_two_caches_are_two_shapes_and_the_pool_is_the_full_layers(
        tiny, engine):
    import time
    _, _, cfg, _ = tiny
    assert len(_drain(engine.submit(_tokens(20, 1), 8))) == 8
    seen, deadline = None, time.monotonic() + 30
    while time.monotonic() < deadline:  # the emitter counts AFTER the tokens
        c = engine.counters()
        now = (c["routed_assignments"], c["local_assignments"],
               sum(c["expert_tokens"]))
        if now == seen and now[0] >= 27 * cfg.top_k_experts * 3:
            break
        seen = now
        time.sleep(0.05)
    counts = attention.attention_path_counts()
    assert counts["decode_pallas"] >= 1             # interpreted, in decode
    assert counts["window_decode_reference"] >= 1
    assert counts["window_fwd_reference"] >= 1      # the CPU's prefill path
    assert counts["full_fwd_reference"] >= 1
    assert counts["share_combine_gather"] >= 1      # off the chip, the gather
    # pages: the 2 full layers alone, 1 kv head, keys and values in lanes
    kc, vc, _, rings = engine._caches
    assert kc.shape == vc.shape == (2, engine.n_pages, 1, 16, 128)
    # rings: the 2 window layers, 2 slots, 2 kv heads, 16 rows, never more
    assert [s.shape for s in rings] == [(2, 2, 2, 16, 128)] * 2
    assert c["window_cache_bytes"] == sum(s.nbytes for s in rings) \
        == 2 * 2 * 2 * 2 * 16 * 128 * 4
    assert c["full_cache_bytes"] == kc.nbytes + vc.nbytes
    assert engine.pool.pages_for(100, 40) == 9      # positions, not layers
    assert c["routed_assignments"] > c["local_assignments"] > 0
    assert sum(c["expert_tokens"]) == c["local_assignments"]


def test_a_pd_handoff_and_the_training_forward_refuse_mixed_attention_by_name(
        tiny, engine):
    _, _, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="mixed attention"):
        engine.submit_prefilled(None, None, 4, 1, 4)
    with pytest.raises(NotImplementedError, match="mixed attention"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="serves only"):
        reference_mimo.loss_and_check_grads(params, {}, None)

"""Brumby-14B-Base's stack at tiny widths on the CPU, seeded float32 weights,
against its plain reference (benchmark/reference_brumby.py: the ATTENTION
form in float32 at precision "highest", no state, no `phi`): power retention
of degree 2 in every layer, through `Engine`: prefill by the attention form
and one build of the state, decode through the slots' recurrent state, and a
model with NO paged layer admitted, served and freed. The operator alone is
tests/test_retention.py's.

(a) the engine against the reference; (b) what the check must tell apart (a
bfloat16 state, a dropped gate, a lost state); (c) no pages; (d) the kernels
under the engine; (e) refusals, the adapter, the counts and the file.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from benchmark import reference_brumby as ref
from ray_tpu.models import block, llama, serving
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import attention, norms, retention, slot_state
from ray_tpu.serve.engine import Engine
from engine_pins import Spans, pinned

# Float32 everywhere on the CPU: what is left between the program and the
# reference is the order of float32 sums (the recurrent state against the
# attention form's pairs) on logits of a few units: 2e-5 measured at most,
# 2e-4 allowed. A state kept in bfloat16 reads 1e-2 (test (b)).
LOGIT_TOL = 2e-4

ADAPTER = models.adapter("brumby")
MODEL = dict(ADAPTER.REHEARSE, rms_norm_eps=1e-6, rope_theta=1000000)
F32 = {"params": "float32", "activations": "float32"}
FILE = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                    "brumby-14b-base-serve.json")


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _params(cfg, seed=3):
    """Seeded weights with every norm off one, and matrices large enough
    that every branch (and the gate's projection) moves the logits."""
    params = ADAPTER.init_params(cfg, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    lay = dict(params["layers"])
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        lay[name] = 1.0 + 0.2 * jax.random.normal(next(keys), lay[name].shape)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        lay[name] = lay[name] * 8.0
    lay["wg"] = lay["wg"] * 8.0    # x W_g of order 1, as at the cell's widths
    return dict(params, layers=lay, embed=params["embed"] * 30.0,
                lm_head=params["lm_head"] * 8.0,
                final_norm=1.0 + 0.2 * jax.random.normal(
                    next(keys), params["final_norm"].shape))


@pytest.fixture(scope="module")
def tiny():
    cfg = ADAPTER.build_config(MODEL, F32, 256)
    assert (cfg.mixer, cfg.retention_degree, cfg.kv_layers, cfg.state_layers,
            cfg.qk_norm, cfg.head_dim, cfg.segments()) \
        == ("retention", 2, 0, 2, "head", 16, (("layers", 0, 2),))
    return cfg, _params(cfg)


def _serve(engine, prompts, n):
    outs = [engine.submit(p, n) for p in prompts]
    served = []
    for q in outs:
        toks = []
        while (chunk := q.get(timeout=300)) is not None:
            toks += chunk
        served.append(toks)
    return served


def _engine(tiny, n_slots=4):
    cfg, params = tiny
    return Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=n_slots,
                  decode_chunk=4, page_size=16)


@pytest.fixture(scope="module")
def engine(tiny):
    """ONE engine of four slots for every test that serves through it and
    leaves its slots free behind it. (A test that builds the engine under a
    patch builds its own.)"""
    eng = _engine(tiny)
    yield eng
    eng.stop()


# -- (a) the engine against the reference -----------------------------------

@pytest.mark.timeout(240)
def test_engine_prefill_then_decode_match_the_reference(tiny, engine):
    """Three slots at once at different positions: a prompt that fills its
    bucket (64), one that leaves padding behind it (70 in 128) and a short
    one (21 in 32). At every served position the token the engine chose is
    the reference's largest logit to float32 rounding: the prefill's state,
    carried 24 steps through the in-place update, is the attention form's
    whole sequence."""
    cfg, params = tiny
    before = engine.counters()["state_writes"]
    prompts = [_tokens(64, 5), _tokens(70, 6), _tokens(21, 7)]
    served = _serve(engine, prompts, 24)
    assert [len(s) for s in served] == [24, 24, 24]
    worst = 0.0
    for prompt, toks in zip(prompts, served):
        gaps = ref.served_token_gaps(params, MODEL, prompt, toks)
        assert max(gaps) < LOGIT_TOL, gaps
        worst = max(worst, max(gaps))
    print("worst gap", worst)
    counts = engine.counters()
    assert counts["state_writes"] - before == 3
    S, z = engine._caches.state
    assert S.shape == (2, 4, 2, 9, 16, 16) and z.shape == (2, 4, 2, 16, 16)
    assert counts["state_bytes"] == 4 * (S.size + z.size)
    paths = attention.attention_path_counts()
    assert paths["retention_state_reference"] >= 1      # the CPU's paths
    assert paths["retention_step_reference"] >= 1


def test_a_request_is_served_alike_alone_after_another_and_beside_others(
        tiny, engine):
    """One slot: the same prompt first, then after a longer tenant of the
    same slot (whose state the admission must overwrite whole), gives the
    same tokens. Four slots: beside idle ones and while a neighbour decodes
    (an idle slot's state must not move, an active one's must not leak), the
    same again; all the reference's."""
    cfg, params = tiny
    a, b = _tokens(60, 21), _tokens(140, 22)
    one = _engine(tiny, n_slots=1)
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


# -- (b) what the check must tell apart --------------------------------------

def _logits_through_the_state(params, cfg, seq, last,
                              state_dtype=jnp.float32, forget_after=None):
    """The logits of the last `last` positions of `seq` through the program's
    OWN decode path a token at a time from an empty state (the mixer's step
    on a one-slot state, the feed-forward, the head), the state rounded to
    `state_dtype` after every token and emptied after token `forget_after`."""
    fused = fuse_qkv(params, cfg)
    tables = norms.rope_frequencies(cfg.head_dim, len(seq), cfg.rope_theta)
    live = jnp.ones(1, bool)

    @jax.jit
    def token(state, tok, t):
        x = serving._embed(fused, tok[None], cfg)
        c, s = (table[t][None, None] for table in tables)
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda w: w[l], fused["layers"])
            x, state = block.retention_mixer(
                lp, x, cfg, lambda u: serving._rope_one(u, c, s), state,
                step=True, layer=l, active=live)
            x, _ = block.feed_forward(lp, x, cfg)
        x = norms.rms_norm(x, fused["final_norm"], cfg.norm_eps)
        return tuple(a.astype(state_dtype).astype(a.dtype) for a in state), \
            serving._head_logits(fused, x, cfg)[0]

    state = slot_state.empty_retention(cfg.n_layers, 1, cfg.n_kv_heads,
                                       cfg.head_dim)
    rows = []
    for t, tok in enumerate(seq):
        state, logits = token(state, jnp.int32(tok), jnp.int32(t))
        if t == forget_after:
            state = jax.tree.map(jnp.zeros_like, state)
        rows.append(logits)
    return np.asarray(jnp.stack(rows[-last:]))


def test_a_bfloat16_state_is_outside_the_tolerance(tiny):
    """The tolerance tells a narrower state from the real one: the program's
    decode path with its state rounded to bfloat16 after every token is not
    within LOGIT_TOL of the reference's logits; with the float32 state it
    is (the recurrent form from the first token on against the attention
    form)."""
    cfg, params = tiny
    seq = _tokens(78, 6)
    exact = np.asarray(ref.logits_last(params, MODEL, seq, 8))
    sound = _logits_through_the_state(params, cfg, seq, 8, jnp.float32)
    coarse = _logits_through_the_state(params, cfg, seq, 8, jnp.bfloat16)
    assert np.abs(sound - exact).max() < LOGIT_TOL
    assert np.abs(coarse - exact).max() > 10 * LOGIT_TOL


def test_a_dropped_gate_is_outside_the_tolerance(tiny):
    """The reference of the SAME weights without the gate (a gate of 1 at
    every position: nothing is ever forgotten) is not within the tolerance
    of the reference with it: the gate matters to what the check compares."""
    cfg, params = tiny
    seq = _tokens(78, 6)
    lay = dict(params["layers"])
    lay["wg"] = jnp.zeros_like(lay["wg"])
    lay["bg"] = jnp.full_like(lay["bg"], 40.0)
    gated = np.asarray(ref.logits_last(params, MODEL, seq, 8))
    ungated = np.asarray(ref.logits_last(dict(params, layers=lay), MODEL,
                                         seq, 8))
    assert np.abs(ungated - gated).max() > 10 * LOGIT_TOL


@pytest.mark.timeout(300)
def test_the_128th_token_still_hears_the_prompt(tiny):
    """With the half-lives the initialisation draws (16..4,096 positions) a
    prompt's state still moves the logits 128 steps on: with the state
    EMPTIED after a prompt of 60 (the prompt forgotten, every later step
    sound) the logits of the 128th token after it leave the tolerance. With
    a gate of a half (b_g = 0) they would not, and the cell's check would be
    deaf to the carried state."""
    cfg, params = tiny
    seq = _tokens(60 + 128, 9)
    exact = np.asarray(ref.logits_last(params, MODEL, seq, 1))
    deaf = _logits_through_the_state(params, cfg, seq, 1, forget_after=59)
    assert np.abs(deaf - exact).max() > 10 * LOGIT_TOL
    half = dict(params, layers=dict(
        params["layers"], wg=jnp.zeros_like(params["layers"]["wg"]),
        bg=jnp.zeros_like(params["layers"]["bg"])))
    exact = np.asarray(ref.logits_last(half, MODEL, seq, 1))
    deaf = _logits_through_the_state(half, cfg, seq, 1, forget_after=59)
    assert np.abs(deaf - exact).max() < LOGIT_TOL


# -- (c) no pages ------------------------------------------------------------

def test_the_engine_admits_serves_and_frees_without_a_page(tiny, engine):
    """A model with no paged layer: there is no arena, a request reserves no
    page and waits for a free slot alone (six requests on four slots, each
    longer than the pool's pages could hold at once if they were reserved),
    the spans carry the state's bytes and no live K and V."""
    cfg, params = tiny
    c = engine._caches
    assert c.kc is None and c.vc is None and c.ic is None
    assert not engine._programs.paged and engine._programs.by_slot
    free = engine.pool.free
    prompts = [_tokens(40 + 7 * i, 50 + i) for i in range(6)]
    with Spans() as spans:
        outs = [engine.submit(p, 100) for p in prompts]
        assert engine.pages_in_use() == 0
        served = []
        for q in outs:
            toks = []
            while (chunk := q.get(timeout=300)) is not None:
                toks += chunk
            served.append(toks)
    assert [len(s) for s in served] == [100] * 6
    assert engine.pages_in_use() == 0 and engine.pool.free == free
    counts = engine.counters()
    assert counts["peak_pages_used"] == 0 and counts["live_kv_tokens"] == 0
    chunks = spans.named("serve.engine.decode_dispatch")
    slot_bytes = counts["state_bytes"] // engine.n_slots
    assert chunks and all(
        a["live_kv_tokens"] == 0
        and a["state_bytes"] == 2 * slot_bytes * a["active"] * engine.chunk
        for a in chunks)
    assert counts["state_bytes_moved"] >= sum(a["state_bytes"]
                                              for a in chunks)
    assert max(ref.served_token_gaps(params, MODEL, prompts[5], served[5])) \
        < LOGIT_TOL
    assert pinned(engine, "brumby")


# -- (d) the kernels under the engine ----------------------------------------

@pytest.mark.timeout(300)
def test_an_engine_serves_through_both_kernels(tiny, monkeypatch):
    """An engine built with the step's op and the prompt's operator
    interpreted (what the mixer calls is the function as it stands on its
    module) builds a prompt's state through `retention_state` and updates
    its slots' state through `retention_state_step`, in place in the decode
    program's carry, two slots of four live: the served tokens are the
    reference's to the engine's tolerance."""
    cfg, params = tiny
    monkeypatch.setattr(slot_state, "retention_step_layer", functools.partial(
        slot_state.retention_step_layer, interpret=True))
    monkeypatch.setattr(retention, "retention_prompt", functools.partial(
        retention.retention_prompt, interpret=True))
    before = dict(attention.attention_path_counts())
    eng = _engine(tiny)
    try:
        paths = attention.attention_path_counts()
        for path in ("retention_step_pallas", "retention_state_pallas"):
            assert paths[path] > before.get(path, 0)
        prompts = [_tokens(40, 41), _tokens(17, 42)]
        served = _serve(eng, prompts, 12)
    finally:
        eng.stop()
    for prompt, toks in zip(prompts, served):
        assert max(ref.served_token_gaps(params, MODEL, prompt, toks)) \
            < LOGIT_TOL


# -- (e) refusals, the adapter, the counts, the file -------------------------

def test_a_pd_handoff_training_and_riders_are_refused_by_name(tiny, engine):
    cfg, params = tiny
    assert not serving.adopts(cfg) and not engine._programs.takes_riders
    with pytest.raises(NotImplementedError, match="power-retention"):
        engine.submit_prefilled(None, None, 8, 1, 4)
    with pytest.raises(NotImplementedError, match="power retention"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


@pytest.mark.parametrize("kw,said", [
    (dict(n_experts=4), "power retention"),
    (dict(retention_degree=3), "retention_degree"),
    (dict(mixer="linear"), "mixer"),
    (dict(head_dim=15), "even head_dim"),
    (dict(block_length=4, denoise_steps=2), "power retention")])
def test_the_config_refuses_what_no_stack_serves(kw, said):
    with pytest.raises(ValueError, match=said):
        llama.LlamaConfig.tiny(**{"mixer": "retention", "qk_norm": "head",
                                  "head_dim": 16, **kw})


@pytest.mark.parametrize("change,said", [
    ({"tie_word_embeddings": True}, "tied embeddings"),
    ({"use_sliding_window": True}, "sliding window"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"retention_degree": 4}, "degree"),
    ({"attention_bias": True}, "attention_bias")])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    with pytest.raises(ValueError, match=said):
        ADAPTER.check_supported(dict(MODEL, **change))


def test_param_count_is_the_published_size_and_the_counts_the_issues():
    with open(FILE) as f:
        m = json.load(f)
    whole = dict(m, num_hidden_layers=40)
    cfg = ADAPTER.build_config(whole, m["dtypes"], 2048)
    assert llama.param_count(cfg) == 14_769_945_600
    counts = ADAPTER.counts
    assert counts.layer_params(m) == 330_352_896
    assert counts.total_params(whole) == 14_769_945_600
    assert counts.total_params(m) == 2_877_241_344
    assert counts.state_rows(m) == 8_256
    assert counts.slot_state_bytes(m) == 34_080_768
    # a step of 48 slots over 4 layers: 13.1 GB of state beside 4.20 GB of
    # weights, 76% of its bytes; 21.1 ms at 819 GB/s
    ops, byts = counts.decode_step_ops_bytes(m, [0] * 48, 2)
    state = counts.decode_state_bytes(m, 48)
    assert state == 2 * 48 * 4 * 34_080_768
    assert round(100 * state / byts) == 76
    assert abs(byts / 819e9 - 0.0211) < 1e-4
    assert ops / 197e12 < byts / 819e9          # bound by bytes
    # the program's layout: 65 blocks of 128 lanes and 72 rows of z
    cell = ADAPTER.build_config(m, m["dtypes"], 2048)
    S, z = retention.state_shapes(cell.n_layers, 1, cell.n_kv_heads,
                                  cell.head_dim)
    assert S == (4, 1, 8, 65, 128, 128) and z == (4, 1, 8, 72, 128)


def test_the_configuration_file_holds_the_catalogs_row():
    with open(FILE) as f:
        m = json.load(f)
    ADAPTER.check_supported(m)
    assert set(m["reduced"]) == {"num_hidden_layers"}
    for key, entry in m["reduced"].items():
        assert set(entry) == {"published", "run", "decided_by"}
        assert entry["run"] == m[key]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for key, value in published.items():
        assert m[key] == (value if key not in m["reduced"]
                          else m["reduced"][key]["run"]), key
    assert m["reduced"]["num_hidden_layers"]["published"] == 40
    assert len(m["source"]) <= 200 and len(m["assumed"]) >= 7
    assert "first of ten pipeline stages" in m["deployment"]["stands_for"]

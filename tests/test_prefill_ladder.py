"""The engine's ladder of prefill widths (`serve/engine.py::prefill_widths`):
the doubling widths and, in the octave under `max_seq`, rungs a quarter of it
apart and 512 rows at least; the hand-off path stays on the doubling widths
(`doubling_widths`: `PrefillServer`'s ladder and the engine's `adopt`
programs). On the CPU at tiny float32 widths: what the rule gives, that under
a `max_seq` of 2,048 a prompt of 1,100 tokens prefills 1,536 rows wide and is
served the tokens of the benchmark's plain reference on a dense, a sparse, an
indexed and a hybrid model (the adapters' REHEARSE widths), which widths are
warm when the constructor returns, and that a hand-off padded to 2,048 is
adopted.

Tolerance: program and reference compute the same mathematics in float32 and
differ in the order of their sums; LOGIT_TOL 2e-4 is the one
tests/test_olmoe.py, test_keye.py and test_jamba.py hold the same pairs to.
"""

import functools
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from ray_tpu.models import block
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import ssm
from ray_tpu.ops.attention import attention_path_counts
from ray_tpu.models.serving import prefill_core
from ray_tpu.serve.engine import Engine, doubling_widths, prefill_widths
from ray_tpu.utils import tracing

LOGIT_TOL = 2e-4
F32 = {"params": "float32", "activations": "float32"}
PROMPT = 1100       # over 1024, under 1536: the one rung max_seq 2048 adds

LADDERS = {
    16: [16],
    128: [32, 64, 128],
    1024: [32, 64, 128, 256, 512, 1024],
    2048: [32, 64, 128, 256, 512, 1024, 1536, 2048],
    3000: [32, 64, 128, 256, 512, 1024, 2048, 2560, 3000],
    4096: [32, 64, 128, 256, 512, 1024, 2048, 2560, 3072, 3584, 4096],
    8192: [32, 64, 128, 256, 512, 1024, 2048, 4096, 5120, 6144, 7168, 8192],
}


@pytest.mark.parametrize("max_seq", sorted(LADDERS))
def test_the_ladder_doubles_and_steps_a_quarter_octave_under_max_seq(max_seq):
    """Sorted, no rung twice, `max_seq` last; the doubling widths are all
    rungs; under the last doubling width below `max_seq` nothing else is; from
    there up a rung is a multiple of 512 (but `max_seq` itself), 512 rows at
    least and a quarter of that width at most from the one before, so that
    from 2,048 rows up the padding a prompt can meet is a fifth of its bucket
    at most, where doubling allowed a half."""
    ladder = prefill_widths(max_seq)
    doubling = doubling_widths(max_seq)
    assert ladder == LADDERS[max_seq]
    assert ladder == sorted(set(ladder)) and ladder[-1] == max_seq
    assert doubling == [b for b in ladder
                        if b == max_seq or b & (b - 1) == 0]
    top = doubling[-2] if len(doubling) > 1 else max_seq
    assert [b for b in ladder if b <= top] == [b for b in doubling if b <= top]
    fine = [b for b in ladder if b >= top]
    assert all(b % 512 == 0 for b in fine[1:] if b != max_seq)
    for below, rung in zip(fine, fine[1:]):
        assert rung - below >= 512 or rung == max_seq
        assert rung - below <= max(512, top // 4)
        if top >= 2048:
            assert (rung - below) / rung <= 0.2


KINDS = {   # kind -> (adapter, what REHEARSE leaves to the configuration)
    "dense": ("llama", dict(rope_theta=10000, rms_norm_eps=1e-5)),
    "sparse": ("olmoe", dict(rope_theta=10000, rms_norm_eps=1e-5,
                             norm_topk_prob=False)),
    "indexed": ("keye", dict(rope_theta=10000000, rms_norm_eps=1e-6,
                             norm_topk_prob=True)),
    "hybrid": ("jamba", dict(rms_norm_eps=1e-6, num_experts=1,
                             tie_word_embeddings=True)),
}
MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "wiq", "wik",
           "wiw", "in_proj", "x_proj", "dt_proj", "out_proj")


def _tiny(kind, max_seq=2048):
    """(adapter, model, cfg, params): the adapter's rehearsal widths with
    weights that decide (at the init's 0.02 every logit is a near-tie and any
    token passes): matmuls x 8, the router x 40, the embedding spread."""
    adapter = models.adapter(KINDS[kind][0])
    model = dict(adapter.REHEARSE, **KINDS[kind][1])
    cfg = adapter.build_config(model, F32, max_seq)
    params = dict(adapter.init_params(cfg, 3))
    for stack in ("layers", "mamba"):
        if stack in params:
            params[stack] = {
                k: w * (8.0 if k in MATMULS else 40.0 if k == "router"
                        else 1.0) for k, w in params[stack].items()}
    params["embed"] = params["embed"] * (12.0 if cfg.tie_embeddings else 50.0)
    if "lm_head" in params:
        params["lm_head"] = params["lm_head"] * 8.0
    return adapter, model, cfg, params


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _drain(q):
    out = []
    while (item := q.get(timeout=300)) is not None:
        out.extend(item)
    return out


def _until_all_warm(eng, seconds=240):
    deadline = time.monotonic() + seconds
    while sorted(eng._warm) != eng.buckets:
        assert not eng.warm_error, eng.warm_error
        assert time.monotonic() < deadline, "the warm-up thread timed out"
        time.sleep(0.05)


def _padded_since(eng, before):
    after = eng.counters()
    return (after["prefill_tokens"] - before["prefill_tokens"],
            after["prefill_padded_tokens"] - before["prefill_padded_tokens"])


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_prompt_of_1100_prefills_1536_wide_and_serves_the_reference_tokens(
        kind, monkeypatch):
    """`max_seq` 2048, whose top octave has the one rung 1536: the prompt
    meets it (436 rows of padding, where doubling gave 948), warm since the
    constructor. Its first token is
    prefill's and the rest are decoded from what prefill left in the caches:
    each is the reference's largest logit to float32 rounding, so the padding
    behind the prompt reached nothing. The hybrid's slot holds the recurrent
    state after the last REAL token: the state of a prefill exactly as wide
    as the prompt; its prefills run the scan KERNEL, interpreted (three
    blocks of 512 rows, the last of them 76 real rows and then padding)."""
    if kind == "hybrid":
        monkeypatch.setattr(block, "selective_scan", functools.partial(
            ssm.selective_scan, interpret=True))
        scans = attention_path_counts().get("scan_pallas", 0)
    adapter, model, cfg, params = _tiny(kind)
    prompt = _tokens(PROMPT, 11)
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                 decode_chunk=4, page_size=64)
    try:
        assert eng.buckets == LADDERS[2048] and 1536 in eng._warm
        before = eng.counters()
        first = _drain(eng.submit(prompt, 1))
        assert _padded_since(eng, before) == (PROMPT, 1536 - PROMPT)
        if kind == "hybrid":
            state, window = (np.asarray(a) for a in eng._caches.state)
            *_, (want_ssm, want_window) = jax.jit(prefill_core(cfg))(
                fuse_qkv(params), jnp.asarray([prompt], jnp.int32), PROMPT)
            # values of size ~1, summed in another order at another width
            assert np.abs(state[:, 0] - np.asarray(want_ssm)).max() < 2e-5
            assert np.abs(window[:, :, 0] - np.asarray(want_window)).max() \
                < 2e-5
            assert np.abs(state[:, 0]).max() > 1e-2 and not state[:, 1].any()
            assert attention_path_counts()["scan_pallas"] > scans
        served = _drain(eng.submit(prompt, 10))
    finally:
        eng.stop()
    assert len(served) == 10 and served[:1] == first
    gaps = adapter.reference().served_token_gaps(params, model, prompt, served)
    assert max(gaps) < LOGIT_TOL, gaps
    # The tolerance has teeth here: the token after the prompt less its last
    # real token is another one's logit row.
    short = adapter.reference().served_token_gaps(params, model, prompt[:-1],
                                                  served)
    assert max(short) > 100 * LOGIT_TOL


@pytest.fixture
def warm_spans(monkeypatch):
    """Every `serve.engine.warm` span opened while the fixture stands:
    (program, width, the thread's name)."""
    spans = []
    compile_span = tracing.compile_span

    def recording(name, **args):
        if name == "serve.engine.warm":
            spans.append((args["program"], args["width"],
                          threading.current_thread().name))
        return compile_span(name, **args)

    monkeypatch.setattr(tracing, "compile_span", recording)
    return spans


@pytest.mark.parametrize("adopts", [True, False],
                         ids=["as-DecodeServer", "as-LLMServer"])
def test_wide_rungs_warm_in_the_constructor_and_adopt_at_doubling_widths_only(
        warm_spans, adopts):
    """Every rung wider than half of `max_seq` is warm when the constructor
    returns, warmed by the constructing thread (against the live arena: a
    wide prefill's temporaries do not fit beside the warm-up thread's scratch
    arena on the chip); the thread warms the rest. An engine built as
    `DecodeServer` builds it (`adopts=True`) compiles an `adopt` program at
    the doubling widths and at no other; one built as `LLMServer` builds it
    is never sent a hand-off, warms none (2-3 s of every start at the
    benchmark's widths: PERF.md section 6, PR 41) and refuses
    `submit_prefilled` with the reason, instead of compiling inside its
    loop."""
    _, _, cfg, params = _tiny("dense", 4096)
    here = threading.current_thread().name
    eng = Engine(params, cfg, n_slots=2, decode_chunk=4, page_size=64,
                 adopts=adopts)
    adopted = doubling_widths(4096) if adopts else []
    try:
        wide = [2560, 3072, 3584, 4096]
        assert set(wide) <= eng._warm
        built = [(p, w) for p, w, name in warm_spans if name == here]
        assert [w for p, w in built if p == "prefill"] == [32] + wide
        assert [w for p, w in built if p == "adopt"] == adopted[:1] + adopted[-1:]
        _until_all_warm(eng)
        if not adopts:
            kv = jnp.zeros((cfg.n_layers, 32, cfg.n_kv_heads, cfg.head_dim))
            with pytest.raises(RuntimeError, match="warmed no `adopt`"):
                eng.submit_prefilled(kv, kv, 20, 7, 4)
    finally:
        eng.stop()
    assert sorted(w for p, w, _ in warm_spans if p == "prefill") \
        == LADDERS[4096]
    assert sorted(w for p, w, _ in warm_spans if p == "adopt") == adopted
    assert {w for p, w, name in warm_spans if name != here} \
        == set(LADDERS[4096]) - {32} - set(wide)
    assert eng._programs.adopt._cache_size() == len(adopted)


def test_a_prefill_pool_pads_to_doubling_widths_and_its_hand_off_is_adopted():
    """`PrefillServer`'s widths are the doubling ones (a prompt of 2,100
    tokens leaves it 4,096 wide where the engine prefills 2,560), so a prompt
    of 1,100 tokens leaves it 2,048 wide; the decode pool's engine adopts it
    at 2,048 with the `adopt` program it warmed (none compiles), counts no
    prefill rows for it, and goes on to the tokens it serves when it prefills
    the same prompt itself. A hand-off of a width no `PrefillServer` sends
    (2,560: a prefill rung with no `adopt` program) is padded on the host to
    the next doubling one."""
    import cloudpickle

    from ray_tpu.serve.llm import DecodeServer, LLMConfig, PrefillServer

    blob = cloudpickle.dumps(LLMConfig(
        vocab_size=256, d_model=128, n_layers=2, max_seq=4096, num_tpus=0,
        decode_chunk=4, max_ongoing_requests=2))
    prompt = _tokens(PROMPT, 12)
    pool = PrefillServer(blob)
    assert pool.buckets == doubling_widths(4096) != prefill_widths(4096)
    assert [next(b for b in ladder if b >= 2100)
            for ladder in (pool.buckets, prefill_widths(4096))] == [4096, 2560]
    width = next(b for b in pool.buckets if b >= PROMPT)
    assert width == 2048
    first, ks, vs, _, _ = pool._core(
        pool.params, jnp.asarray([prompt + [0] * (width - PROMPT)], jnp.int32),
        PROMPT)
    pad = jnp.zeros((ks.shape[0], 512) + ks.shape[2:], ks.dtype)
    decode = DecodeServer(blob)
    eng = decode.engine
    try:
        _until_all_warm(eng)
        programs = eng._programs.adopt._cache_size()
        before = eng.counters()
        want = _drain(eng.submit(prompt, 8))
        assert _padded_since(eng, before) == (PROMPT, 2048 - PROMPT)
        rest = _drain(eng.submit_prefilled(ks, vs, PROMPT, int(first), 8))
        odd = _drain(eng.submit_prefilled(
            jnp.concatenate([ks, pad], axis=1),
            jnp.concatenate([vs, pad], axis=1), PROMPT, int(first), 8))
        assert _padded_since(eng, before) == (PROMPT, 2048 - PROMPT)
        assert eng._programs.adopt._cache_size() == programs == 8
    finally:
        eng.stop()
    assert len(want) == 8 and [int(first)] + rest == want
    assert odd == rest

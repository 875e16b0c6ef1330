"""OLMoE's block through the train path and through the serving engine,
against the benchmark's plain reference (benchmark/reference_olmoe.py: the
published equations in float32, every expert computed densely), on seeded
random weights at a small size on the CPU: 2 layers, hidden 64, 4 heads of 16,
8 experts of width 32, 2 a token, vocabulary 256, float32 throughout.

Tolerances. Program and reference compute the same mathematics in float32
and differ in the order of their sums (sorted grouped matmuls against a
dense loop over the experts, flash blocks against whole rows), so logits of
size ~1 agree to a few 1e-6; LOGIT_TOL 2e-4 leaves two orders of room for
that and none for a different model: `test_the_tolerance_has_teeth` shows
that the reference with the weights renormalised, with the q/k norm a head
at a time, or computed in bfloat16 misses it by a factor of 30 or more.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from benchmark import reference_olmoe as ref
from ray_tpu.models import llama
from ray_tpu.models.block import feed_forward, fuse_qkv
from ray_tpu.models.serving import prefill_core
from ray_tpu.serve.engine import Engine

LOGIT_TOL = 2e-4
# Gradients are sums over 47 positions of products of such numbers: relative
# error of a leaf's gradient, as the benchmark's train check measures it.
GRAD_REL_TOL = 1e-4

ADAPTER = models.adapter("olmoe")
MODEL = dict(ADAPTER.REHEARSE, rope_theta=10000, rms_norm_eps=1e-5,
             norm_topk_prob=False)
F32 = {"params": "float32", "activations": "float32"}


def _params(cfg, seed=3, skew=False):
    """Seeded weights with every norm off one and a router that decides
    (logits of size ~1; at the init's 0.02 they are near-ties all over), or,
    with `skew`, one that sends every token to experts 0 and 1."""
    params = ADAPTER.init_params(cfg, seed)
    lay = dict(params["layers"])
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        lay[name] = 1.0 + 0.2 * jax.random.normal(next(keys), lay[name].shape)
    lay["router"] = jnp.zeros_like(lay["router"]) if skew \
        else lay["router"] * 40.0
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        lay[name] = lay[name] * 8.0
    return dict(params, layers=lay,
                final_norm=1.0 + 0.2 * jax.random.normal(
                    next(keys), params["final_norm"].shape),
                embed=params["embed"] * 50.0, lm_head=params["lm_head"] * 8.0)


@pytest.fixture(scope="module")
def tiny():
    cfg = ADAPTER.build_config(MODEL, F32, 256)
    assert (cfg.n_experts, cfg.top_k_experts, cfg.d_ff, cfg.n_kv_heads,
            cfg.norm_topk_prob, cfg.qk_norm) == (8, 2, 32, 4, False, True)
    return cfg, _params(cfg)


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _ref_logits(params, seq, last, model=MODEL):
    return np.asarray(ref.logits_last(params, model, seq, last))


# -- (a) the train path ------------------------------------------------------

def test_train_logits_loss_and_gradients_match_the_reference(tiny):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(48, 1), _tokens(48, 2)], jnp.int32)
    got = np.asarray(llama.forward(params, toks, cfg))
    for row in range(2):
        want = _ref_logits(params, [int(t) for t in toks[row]], 48)
        assert np.abs(want).max() > 1.0          # logits of a size that shows
        assert np.abs(got[row] - want).max() < LOGIT_TOL
    checked = ref.CHECKED + ("w_gate",)
    want_loss, want_g = ref.loss_and_check_grads(params, MODEL, toks, checked)
    loss, g = jax.value_and_grad(
        lambda p: llama.loss_fn(p, toks, cfg)[0])(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    # The router, the q/k norms, and one expert (the last layer's busiest).
    for name in ("router", "q_norm", "k_norm", "attn_norm", "mlp_norm"):
        assert rel(g["layers"][name], want_g[name]) < GRAD_REL_TOL, name
    assert rel(g["final_norm"], want_g["final_norm"]) < GRAD_REL_TOL
    busiest = int(jnp.argmax(jnp.sum(jnp.abs(want_g["w_gate"][-1]), (1, 2))))
    assert float(jnp.abs(want_g["w_gate"][-1, busiest]).max()) > 0
    assert rel(g["layers"]["w_gate"][-1, busiest],
               want_g["w_gate"][-1, busiest]) < GRAD_REL_TOL


# -- (b) the engine: prefill, then decode through the paged cache ------------

def _serve(engine, prompts, n):
    outs = [engine.submit(p, n) for p in prompts]
    served = []
    for q in outs:
        toks = []
        while (chunk := q.get(timeout=120)) is not None:
            toks += chunk
        served.append(toks)
    return served


@pytest.fixture(scope="module")
def engine(tiny):
    cfg, params = tiny
    # A copy: the engine takes its tree's q/k/v stacks over, and `tiny` is
    # every test's.
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=16,
                 decode_chunk=4, page_size=16)
    yield eng
    eng.stop()


def test_engine_prefill_then_paged_decode_match_the_reference(tiny, engine):
    """Two slots of different lengths, each over four pages of 16: at every
    served position the token the engine chose is the reference's largest
    logit (its gap there is float32 rounding), and the logits the prefill
    program itself returns are the reference's."""
    cfg, params = tiny
    prompts = [_tokens(21, 5), _tokens(38, 6)]
    served = _serve(engine, prompts, 24)
    assert [len(s) for s in served] == [24, 24]
    for prompt, toks in zip(prompts, served):
        gaps = ref.served_token_gaps(params, MODEL, prompt, toks)
        assert max(gaps) < LOGIT_TOL, gaps
    core = jax.jit(prefill_core(cfg))
    for prompt in prompts:
        padded = jnp.asarray([prompt + [0] * (64 - len(prompt))], jnp.int32)
        first, _, _, logits, experts = core(fuse_qkv(params), padded,
                                            len(prompt))
        want = _ref_logits(params, prompt, 1)[0]
        assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
        # every live token counted twice (2 experts) in each of 2 layers,
        # the bucket's padding not at all
        assert int(experts[:-1].sum()) == 2 * 2 * len(prompt)
        assert 2 <= int(experts[-1]) <= 2 * 8
    routed = engine.counters()
    assert sum(routed["expert_tokens"]) >= 2 * 2 * (21 + 38 + 2 * 23)
    assert routed["decode_experts_touched"] > 0


def test_engine_tokens_with_the_decode_kernel_equal_the_reference_paths(
        tiny, request):
    """The sparse model through the engine with decode attention on the
    Pallas kernel (interpret mode, on this CPU) and on the XLA reference
    path: the same greedy tokens through a prefill, an adopt and chunks in
    which slots join and leave; and the kernel's tokens are the plain
    reference's largest logits too."""
    from ray_tpu.ops import attention

    cfg, params = tiny
    core = jax.jit(prefill_core(cfg))
    prompts = [_tokens(14, 11), _tokens(20, 12), _tokens(3, 13)]

    def served():
        eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                     decode_chunk=4, page_size=16, adopts=True)
        try:
            a = eng.submit(prompts[0], 11)             # positions 14..24
            first, ks, vs, _, _ = core(fuse_qkv(params), jnp.asarray(
                [prompts[1] + [0] * 12], jnp.int32), len(prompts[1]))
            b = eng.submit_prefilled(ks, vs, len(prompts[1]), int(first), 6)
            c = eng.submit(prompts[2], 9)              # waits for a slot
            outs = []
            for q in (a, b, c):
                toks = []
                while (chunk := q.get(timeout=120)) is not None:
                    toks += chunk
                outs.append(toks)
            return outs, int(first)
        finally:
            eng.stop()

    want, _ = served()
    before = attention.attention_path_counts().get("decode_pallas", 0)
    request.getfixturevalue("kernel_in_interpret_mode")
    got, first = served()
    assert attention.attention_path_counts().get("decode_pallas", 0) > before
    assert got == want and [len(t) for t in got] == [11, 5, 9]
    got[1] = [first] + got[1]          # the adopt's first token was out
    for prompt, toks in zip(prompts, got):
        gaps = ref.served_token_gaps(params, MODEL, prompt, toks)
        assert max(gaps) < LOGIT_TOL, gaps


# -- (c) nobody's answer depends on the batch --------------------------------

def test_a_request_alone_beside_fifteen_others_and_in_two_buckets(tiny, engine):
    cfg, params = tiny
    prompt = _tokens(30, 7)
    alone = _serve(engine, [prompt], 16)[0]
    crowd = _serve(engine, [prompt] + [_tokens(20 + 3 * i, 20 + i)
                                       for i in range(15)], 16)[0]
    assert alone == crowd
    # The prefill program in two bucket widths: the padding is computed, takes
    # nobody's place, and changes nothing (float32 sums in another order).
    core = jax.jit(prefill_core(cfg))
    rows = [np.asarray(core(fuse_qkv(params), jnp.asarray(
        [prompt + [9] * (width - len(prompt))], jnp.int32), len(prompt))[3])
        for width in (32, 128)]
    assert np.abs(rows[0] - rows[1]).max() < 1e-5
    # One block's feed-forward on a row alone and among 16: with a capacity
    # the row's place, and so its output, was the batch's to decide.
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(0), (16, cfg.d_model))
    among, _ = feed_forward(lp, x, cfg)
    single, _ = feed_forward(lp, x[3:4], cfg)
    assert np.abs(np.asarray(among[3] - single[0])).max() < 1e-6


def test_experts_stored_in_another_dtype_are_cast_once_and_loudly(
        tiny, monkeypatch):
    """Serving reads the experts' stacks whole, in the compute dtype
    (`models.block.expert_stacks`): there is one layout, and an engine handed
    float32 experts to compute in bfloat16 casts its copy when it is built,
    says so, and serves what one handed the cast copy serves."""
    import dataclasses

    from ray_tpu.models import serving
    cfg, params = tiny
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    said = []
    monkeypatch.setattr(serving.logger, "warning",
                        lambda msg, *a: said.append(msg % a))
    assert serving._experts_in_compute_dtype(params, cfg) is params \
        and not said
    held = serving._experts_in_compute_dtype(params, half)
    assert len(said) == 1 and "casts its own copy once" in said[0]
    assert {k: str(v.dtype) for k, v in held["layers"].items()
            if v.dtype != jnp.float32} == {
        "w_gate": "bfloat16", "w_up": "bfloat16", "w_down": "bfloat16"}
    core = jax.jit(prefill_core(half))
    prompt = jnp.asarray([_tokens(64, 4)], jnp.int32)
    for a, b in zip(core(fuse_qkv(params), prompt, 50),
                    core(fuse_qkv(held), prompt, 50)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- (d) no token dropped under skew -----------------------------------------

def test_every_token_to_the_same_experts_still_equals_the_reference(tiny):
    """A router of zeros: every token's top 2 are experts 0 and 1, a load
    four times the mean (capacity_factor 1.25 kept 31% of them)."""
    cfg, _ = tiny
    params = _params(cfg, skew=True)
    seq = _tokens(64, 8)
    got = np.asarray(llama.forward(params, jnp.asarray([seq], jnp.int32), cfg))[0]
    assert np.abs(got - _ref_logits(params, seq, 64)).max() < LOGIT_TOL
    core = jax.jit(prefill_core(cfg))
    experts = np.asarray(
        core(fuse_qkv(params), jnp.asarray([seq], jnp.int32), 64)[4])
    assert list(experts) == [128, 128, 0, 0, 0, 0, 0, 0, 4]


# -- (e) the tolerance has teeth ----------------------------------------------

def test_the_tolerance_has_teeth(tiny, monkeypatch):
    cfg, params = tiny
    seq = _tokens(48, 1)
    got = np.asarray(llama.forward(params, jnp.asarray([seq], jnp.int32), cfg))[0]

    def miss(logits):
        return np.abs(got - np.asarray(logits, np.float32)).max()

    assert miss(_ref_logits(params, seq, 48)) < LOGIT_TOL
    renormalised = _ref_logits(params, seq, 48,
                               dict(MODEL, norm_topk_prob=True))
    assert miss(renormalised) > 30 * LOGIT_TOL

    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
                        params)
    assert miss(_ref_logits(bf16, seq, 48)) > 30 * LOGIT_TOL

    # The q/k norm a head at a time (Qwen3's, OLMo-2's is over the whole
    # projection): `_layer` norms attn's input, k, q, then the feed-forward's.
    rms, calls = ref._rms_norm, []

    def qk_per_head(x, w, eps):
        calls.append(None)
        if len(calls) % 4 not in (2, 3):
            return rms(x, w, eps)
        heads = x.reshape(x.shape[0], 4, 16)
        var = jnp.mean(jnp.square(heads), axis=-1, keepdims=True)
        return (heads * jax.lax.rsqrt(var + eps)).reshape(x.shape) * w

    monkeypatch.setattr(ref, "_rms_norm", qk_per_head)
    x = jnp.asarray(params["embed"])[jnp.asarray(seq)]
    for i in range(2):
        x = ref._layer(x, ref._layer_f32(params, i), MODEL, 0)
    monkeypatch.setattr(ref, "_rms_norm", rms)
    logits = rms(x, params["final_norm"], 1e-5) @ params["lm_head"]
    assert len(calls) == 8
    assert miss(logits) > 30 * LOGIT_TOL

"""Keye-VL-2.0's language-model block (a Qwen3-MoE decoder under a learned
sparse-attention indexer) through the train path and through the serving
engine, against the benchmark's plain reference (benchmark/reference_keye.py:
the published equations in float32, `lax.top_k` on each query's full score
row, every expert computed densely), on seeded random weights at a small size
on the CPU: the adapter's REHEARSE widths (2 layers, hidden 64, 4 heads of 32
and 2 kv heads, so head_dim is NOT hidden / heads; 8 experts of width 32, 2 a
token; 4 indexer heads of 16, top-k 32; mrope sections 4 + 6 + 6; vocabulary
256), float32 throughout. Every sequence is longer than top-k, so the
selection really cuts.

Tolerances. Program and reference compute the same mathematics in float32
and differ in the order of their sums, so logits of size ~1 agree to a few
1e-6 as long as both select the same keys; LOGIT_TOL 2e-4 leaves room for
that and none for a different model (dense attention misses it by orders:
`test_selection_cuts_and_topk_at_least_t_is_dense_attention`).

Here: (a) the train path, (d) what the check's tolerance means, (e) the
adapter, and the helpers; (b) the engine and (c) the kernels of the TPU path
are tests/test_keye_serving.py.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from benchmark import reference_keye as ref
from ray_tpu.models import llama
from ray_tpu.models.block import attention_inputs
from ray_tpu.ops import norms, sparse_attention

LOGIT_TOL = 2e-4
GRAD_REL_TOL = 1e-4

ADAPTER = models.adapter("keye")
MODEL = dict(ADAPTER.REHEARSE, rope_theta=10000000, rms_norm_eps=1e-6,
             norm_topk_prob=True)
TOPK = MODEL["sa_config"]["topk"]
F32 = {"params": "float32", "activations": "float32"}
INDEX_LEAVES = ("wiq", "wik", "wiw", "ik_norm", "ik_bias")


def _params(cfg, seed=3):
    """Seeded weights with every norm off one, a router that decides and an
    indexer whose scores spread (at the init's 0.02 they are near-ties)."""
    params = ADAPTER.init_params(cfg, seed)
    lay = dict(params["layers"])
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "ik_norm"):
        lay[name] = 1.0 + 0.2 * jax.random.normal(next(keys), lay[name].shape)
    lay["ik_bias"] = 0.1 * jax.random.normal(next(keys), lay["ik_bias"].shape)
    lay["router"] = lay["router"] * 40.0
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "wiq",
                 "wik", "wiw"):
        lay[name] = lay[name] * 8.0
    return dict(params, layers=lay,
                final_norm=1.0 + 0.2 * jax.random.normal(
                    next(keys), params["final_norm"].shape),
                embed=params["embed"] * 50.0, lm_head=params["lm_head"] * 8.0)


@pytest.fixture(scope="module")
def tiny():
    cfg = ADAPTER.build_config(MODEL, F32, 256)
    assert (cfg.head_dim, cfg.d_model // cfg.n_heads, cfg.qk_norm,
            cfg.index_topk, cfg.index_heads, cfg.index_head_dim, cfg.d_ff,
            cfg.mrope_section) == (32, 16, "head", 32, 4, 16, 32, (4, 6, 6))
    return cfg, _params(cfg)


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _ref_logits(params, seq, last, model=MODEL):
    return np.asarray(ref.logits_last(params, model, seq, last))


# -- (a) the train path ------------------------------------------------------

@pytest.mark.timeout(540)
def test_train_logits_loss_and_gradients_match_the_reference(tiny):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(80, 1), _tokens(80, 2)], jnp.int32)
    got = np.asarray(llama.forward(params, toks, cfg))
    for row in range(2):
        want = _ref_logits(params, [int(t) for t in toks[row]], 80)
        assert np.abs(want).max() > 1.0          # logits of a size that shows
        assert np.abs(got[row] - want).max() < LOGIT_TOL
    want_loss, want_g = ref.loss_and_check_grads(params, MODEL, toks)
    loss, g = jax.value_and_grad(
        lambda p: llama.loss_fn(p, toks, cfg)[0])(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    for name in ref.CHECKED:
        assert rel(g["layers"][name], want_g[name]) < GRAD_REL_TOL, name
    assert rel(g["final_norm"], want_g["final_norm"]) < GRAD_REL_TOL
    # The indexer only chooses: this loss gives it no gradient.
    for name in INDEX_LEAVES:
        assert not np.asarray(g["layers"][name]).any(), name


def test_selection_cuts_and_topk_at_least_t_is_dense_attention(tiny):
    """With top-k >= T the model IS the same weights under dense attention
    (the program's own dense block, which has no indexer at all); with the
    published-shaped top-k < T it is another function by orders of the
    tolerance, so the tests above hold the selection and not a model that
    ignores it."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(80, 4)], jnp.int32)
    dense_cfg = dataclasses.replace(cfg, index_topk=0)
    dense_params = dict(params, layers={
        k: v for k, v in params["layers"].items() if k not in INDEX_LEAVES})
    dense = np.asarray(llama.forward(dense_params, toks, dense_cfg))
    wide = np.asarray(llama.forward(
        params, toks, dataclasses.replace(cfg, index_topk=80)))
    assert np.abs(wide - dense).max() < 1e-5
    cut = np.asarray(llama.forward(params, toks, cfg))
    assert np.abs(cut[0, :TOPK] - dense[0, :TOPK]).max() < 1e-5  # t < top-k
    assert np.abs(cut[0, TOPK:] - dense[0, TOPK:]).max() > 100 * LOGIT_TOL
    wide_ref = _ref_logits(params, [int(t) for t in toks[0]], 80, dict(
        MODEL, sa_config=dict(MODEL["sa_config"], topk=80)))
    assert np.abs(wide_ref - dense[0]).max() < LOGIT_TOL


def test_mrope_with_equal_streams_is_the_rope_the_repo_has():
    """`mrope_section` is carried: three position streams pick their
    sections' frequencies. Text sets them equal, and the tables are then the
    plain RoPE's at those positions, bit for bit; streams that differ turn
    each section by its own."""
    cos, sin = norms.rope_frequencies(32, 64, 1e7)
    pos = jnp.asarray([5, 0, 63, 17])
    c, s = norms.mrope_tables(cos, sin, jnp.broadcast_to(pos, (3, 4)),
                              (4, 6, 6))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cos[pos]))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sin[pos]))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 4, 32))
    np.testing.assert_array_equal(
        np.asarray(norms.apply_rope(x, c, s)),
        np.asarray(norms.apply_rope(x, cos, sin, pos)))
    streams = jnp.stack([pos, pos + 1, pos + 2])
    c3, _ = norms.mrope_tables(cos, sin, streams, (4, 6, 6))
    np.testing.assert_array_equal(np.asarray(c3[:, :4]), np.asarray(cos[pos, :4]))
    np.testing.assert_array_equal(np.asarray(c3[:, 4:10]),
                                  np.asarray(cos[pos + 1, 4:10]))
    np.testing.assert_array_equal(np.asarray(c3[:, 10:]),
                                  np.asarray(cos[pos + 2, 10:]))
    # and the reference's own rotation, written from the description
    y = ref._rope(x[0].transpose(1, 0, 2), jnp.broadcast_to(pos, (3, 4)),
                  1e7, [4, 6, 6])
    assert np.abs(np.asarray(y) - np.asarray(
        norms.apply_rope(x, cos, sin, pos)[0].transpose(1, 0, 2))).max() < 1e-5


# -- (d) what the check's tolerance means ------------------------------------

def test_bf16_and_float32_select_the_same_keys_but_for_near_ties(tiny):
    """The served path computes the indexer in bfloat16 (float32
    accumulation) and the reference in float32, so the two may select
    different keys, and a limit on logits has to allow for it. What it has to
    allow for is small: the two sets differ only where the reference's score
    lies within bfloat16 rounding of its own top-k-th (a bound from the
    operands' sizes, 2^-6 of the sum over heads of |w| |qI| |kI|), and that
    is a few keys in a hundred."""
    cfg, params = tiny
    seq = jnp.asarray(_tokens(160, 9))
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    x = params["embed"][seq]
    # the reference's scores, from its own functions
    hn = ref._rms_norm(x, lp["attn_norm"], 1e-6)
    streams = jnp.broadcast_to(jnp.arange(160), (3, 160))
    qi = ref._rope((hn @ lp["wiq"]).reshape(160, 4, 16), streams, 1e7)
    ki = ref._rope(ref._layer_norm(hn @ lp["wik"], lp["ik_norm"],
                                   lp["ik_bias"], 1e-6)[:, None], streams,
                   1e7)[:, 0]
    w = (hn @ lp["wiw"]) * (4 ** -0.5 * 16 ** -0.5)
    scores = jnp.einsum("tj,tjs->ts", w, jax.nn.relu(
        jnp.einsum("tjd,sd->tjs", qi, ki)))
    causal = jnp.arange(160)[None, :] <= jnp.arange(160)[:, None]
    want = sparse_attention.select_mask(scores, causal, TOPK)
    # the system's, in bfloat16
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    icos, isin = norms.rope_frequencies(16, 160, 1e7)
    cos, sin = norms.rope_frequencies(32, 160, 1e7)
    _, _, _, (sqi, ski, sw) = attention_inputs(
        lp, x[None].astype(jnp.bfloat16), half,
        lambda t: norms.apply_rope(t, cos, sin),
        lambda t: norms.apply_rope(t, icos, isin))
    assert sqi.dtype == jnp.bfloat16 and sw.dtype == jnp.float32
    got = sparse_attention.select_mask(
        sparse_attention.index_scores(sqi[0].transpose(1, 0, 2), ski[0, 0],
                                      sw[0]), causal, TOPK)
    differ = np.asarray(got != want)
    rows = np.arange(160) >= TOPK
    assert not differ[~rows].any()           # under top-k everything is kept
    kth = jnp.sort(jnp.where(causal, scores, -jnp.inf), axis=1)[:, -TOPK]
    bound = 2.0 ** -6 * jnp.einsum(
        "tj,tj,s->ts", jnp.abs(w), jnp.linalg.norm(qi, axis=-1),
        jnp.linalg.norm(ki, axis=-1))
    near = np.asarray(jnp.abs(scores - kth[:, None]) <= bound)
    assert not (differ & ~near).any()
    assert 0 < differ.sum() < 0.05 * np.asarray(want)[rows].sum()


# -- (e) the adapter ----------------------------------------------------------

@pytest.mark.parametrize("change, said", [
    ({"n_shared_experts": 1}, "shared expert"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0,
                       "mrope_section": [4, 6, 6]}}, "rope_scaling"),
    ({"rope_scaling": None}, "rope_scaling"),
    ({"sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4}},
     "sa_config without topk"),
    ({"sliding_window": 4096}, "sliding window"),
    ({"mlp_only_layers": [0]}, "dense layers"),
])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    with pytest.raises(ValueError, match=said):
        ADAPTER.check_supported(dict(MODEL, **change))


def test_counts_follow_the_selection():
    """flops_keye: past top-k a query attends to top-k keys and the indexer
    still scores every one; a decode step reads K and V of the selected
    positions and the indexer key of all."""
    import json
    import os

    c = ADAPTER.counts
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/keye-vl-2.0-30b-a3b-serve.json")) as f:
        m = json.load(f)
    assert c.selected_pairs(m, 100) == c.causal_pairs(100) == 5050
    assert c.selected_pairs(m, 8192) == c.causal_pairs(2048) \
        + (8192 - 2048) * 2048
    # the issue's count: 18.9M attention + 2.3M indexer + 0.26M router
    # + 128 x 4.72M experts a layer
    assert round(c.attention_params(m) / 1e6, 1) == 18.9
    assert round(c.indexer_params(m) / 1e6, 1) == 2.3
    assert round(c.expert_params(m) / 1e6, 2) == 4.72
    assert 620e6 < c.layer_params(m) < 630e6
    ops, byts = c.sparse_decode_counts(m, 2048, 7000, 2)
    assert byts == 2048 * 2 * 4 * 128 * 2 + 7000 * 64 * 2
    assert ops == c.attention_flops(m, 2048) + c.index_flops(m, 7000)
    dense_like = dict(m, sa_config=dict(m["sa_config"], topk=10 ** 9))
    assert c.prefill_flops(m, 8000) < c.prefill_flops(dense_like, 8000)
    assert c.prefill_flops(m, 2000) == c.prefill_flops(dense_like, 2000)

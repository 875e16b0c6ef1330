"""LFM2-24B-A2B (arch `lfm2`: gated short-convolution layers beside attention
on heads of 64, sparse experts behind a sigmoid router after a leading dense
layer, a tied head) at small widths on the CPU: the program, through its
pages and its slots' windows, against `benchmark/reference_lfm2.py`; the conv
operator's two forms against each other; the window a slot keeps; the routing
counts; the router's 1e-6; the kernels at a head of half a tile in interpret
mode against their reference paths; the refusals; the configuration file
against the catalog's row. (The window a slot keeps and the program through
ONE float32 engine: tests/test_lfm2_engine.py.)

Tolerances. LOGIT_TOL 2e-4: program and reference compute the same
mathematics in float32 and differ in the order of their sums; the one
test_olmoe.py, test_keye.py, test_jamba.py, test_dots.py and test_mimo.py
hold the same pairs to. It is tight enough that bfloat16 where float32 is
stated fails: the same engine computing in bfloat16 reads gaps of thousands
of tolerances (`test_bfloat16_...` holds it under BF16_TOL and OVER
LOGIT_TOL). BF16_TOL 2.0: with these weights (matmuls x 8) a logit is tens
wide and bfloat16 keeps 8 bits of it, through six layers; the largest gap of
the prompts below is 0.53 (means 0.004-0.022), so 2.0 is for a wrong row (a
prompt less its last token reads tens), not for a rounding.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models, reference_lfm2
from ray_tpu.models import block, llama, serving
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import attention, moe, paged_kv, slot_state
from ray_tpu.serve.engine import Engine
import mixer_riders

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-4
BF16_TOL = 2.0
F32 = {"params": "float32", "activations": "float32"}
BF16 = {"params": "bfloat16", "activations": "bfloat16"}
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
    routed_scaling_factor=1, use_expert_bias=True,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"})
# Two segments of each sparse kind: dense, attention, conv x 2, attention,
# conv.
LAYERS = ["conv", "full_attention", "conv", "conv", "full_attention", "conv"]
MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "in_proj",
           "out_proj")
STACKS = ("dense", "conv", "layers")


def _tiny(dtypes=F32, max_seq=256, **more):
    """(adapter, model, cfg, params) at the adapter's rehearsal widths over
    six layers, with weights that decide (at the init's 0.02 every logit is a
    near-tie): matmuls x 8, the router x 40, the embedding spread."""
    adapter = models.adapter("lfm2")
    model = dict(adapter.REHEARSE, **PUBLISHED, num_hidden_layers=6,
                 layer_types=LAYERS, **more)
    cfg = adapter.build_config(model, dtypes, max_seq)
    params = dict(adapter.init_params(cfg, 3))
    for stack in STACKS:
        params[stack] = {
            k: (w * (8.0 if k in MATMULS else 40.0 if k == "router" else 1.0)
                ).astype(w.dtype) for k, w in params[stack].items()}
    params["embed"] = (params["embed"] * 50.0).astype(params["embed"].dtype)
    return adapter, model, cfg, params


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _drain(q):
    out = []
    while (item := q.get(timeout=300)) is not None:
        out.extend(item)
    return out


def _engine(cfg, params, **sizes):
    """An engine with the attention layers' decode kernel interpreted."""
    mp = pytest.MonkeyPatch()
    mp.setattr(paged_kv, "paged_decode_attention", functools.partial(
        paged_kv.paged_decode_attention, interpret=True))
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                 decode_chunk=4, page_size=16, **sizes)
    mp.undo()
    return eng


# ---------------------------------------------------------------------------
# The model's description
# ---------------------------------------------------------------------------

def test_the_stack_is_segments_by_kind_and_no_stack_holds_anothers_weight(
        tiny):
    _, _, cfg, params = tiny
    assert cfg.segments() == (("dense", 0, 1), ("layers", 0, 1),
                              ("conv", 0, 2), ("layers", 1, 2),
                              ("conv", 2, 3))
    assert cfg.kv_layers == 2 and cfg.conv and cfg.head_dim == 64
    assert set(params) == {"embed", "final_norm", *STACKS}    # a tied head
    operator = {"norm", "in_proj", "conv_w", "out_proj"}
    ffn = {"mlp_norm", "w_gate", "w_up", "w_down"}
    routed = {"router", "router_bias"}
    assert set(params["dense"]) == operator | ffn
    assert set(params["conv"]) == operator | ffn | routed
    assert set(params["layers"]) == {"attn_norm", "wq", "wk", "wv", "wo",
                                     "q_norm", "k_norm"} | ffn | routed
    assert params["conv"]["conv_w"].shape == (3, 3, 256)
    assert params["conv"]["w_gate"].shape == (3, 8, 256, 128)
    assert params["dense"]["w_gate"].shape == (1, 256, 192)
    axes = llama.logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)


@pytest.mark.parametrize("change,said", [
    (dict(kv_lora_rank=8), "plain attention"),
    (dict(n_experts=0), "sparse experts"),
    (dict(experts_held=(0, 4)), "every one held"),
    (dict(conv_layers=(1, 2)), "leading layers are conv layers"),
    (dict(conv_layers=(0, 1, 2, 3)), "some attention"),
    (dict(conv_layers=(0, 9)), "indices under n_layers"),
    (dict(ssm_state=16, attn_layers=(1,)), "plain attention"),
    (dict(index_topk=8), "plain attention"),
    (dict(d_ff_dense=0), "d_ff_dense"),
    (dict(n_shared_experts=1), "shared experts"),
])
def test_what_the_stack_cannot_mix_is_refused_by_name(change, said):
    base = dict(vocab_size=64, d_model=128, n_layers=4, n_heads=2,
                n_kv_heads=2, d_ff=32, d_ff_dense=64, n_experts=4,
                top_k_experts=2, first_dense=1, conv_layers=(0, 2, 3),
                router_score="sigmoid")
    llama.LlamaConfig(**base)
    with pytest.raises(ValueError, match=said):
        llama.LlamaConfig(**dict(base, **change))


def test_a_mamba_hybrid_and_a_uniform_stack_still_refuse_what_they_did():
    """What PR 46 did NOT close: the sigmoid router or leading dense layers
    under a uniform stack, and either beside state-space layers (sparse
    experts under state-space layers came with PR 49: the `mamba` stack has
    its router and experts)."""
    cfg = llama.LlamaConfig.tiny(n_experts=4, ssm_state=4, ssm_dt_rank=2,
                                 attn_layers=(1,))
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["mamba"]["w_gate"].shape[:2] == (1, 4) \
        and shapes["mamba"]["router"].shape == (1, 64, 4)
    with pytest.raises(ValueError, match="run as segments"):
        llama.LlamaConfig.tiny(n_experts=4, router_score="sigmoid",
                               ssm_state=4, ssm_dt_rank=2, attn_layers=(1,))
    with pytest.raises(ValueError, match="run as segments"):
        llama.LlamaConfig.tiny(n_experts=4, router_score="sigmoid")
    with pytest.raises(ValueError, match="first_dense"):
        llama.LlamaConfig.tiny(n_experts=4, first_dense=1)


# ---------------------------------------------------------------------------
# The conv operator, the slot's window, the router
# ---------------------------------------------------------------------------

def test_the_conv_operators_prompt_form_is_its_step_form_token_by_token(tiny):
    """`conv_mixer` over a sequence, and one token a slot at a time from the
    window it hands back: the same rows, and the window after row t the two
    rows of z before row t + 1; a prompt cut at `length` hands back the
    window at `length`, whatever the padding holds."""
    _, _, cfg, params = tiny
    lp = jax.tree.map(lambda w: w[1], params["conv"])
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.d_model))
    whole, window = block.conv_mixer(lp, x, cfg)
    assert window.shape == (2, cfg.d_model)
    scale = float(jnp.abs(whole).max())     # rows of ~100: float32 sums in another order
    win = jnp.zeros((2, 1, cfg.d_model))
    for t in range(24):
        row, win = block.conv_mixer(lp, x[t:t + 1], cfg, win, step=True)
        assert np.abs(np.asarray(row[0] - whole[t])).max() < 1e-5 * scale, t
    assert np.abs(np.asarray(win[:, 0] - window)).max() < 1e-5 * scale
    # the window at `length`, behind padding that is anything
    padded = x.at[17:].set(1e3)
    _, cut = block.conv_mixer(lp, padded, cfg, length=17)
    _, want = block.conv_mixer(lp, x[:17], cfg)
    np.testing.assert_array_equal(np.asarray(cut), np.asarray(want))
    # and the reference's three shifted products
    u = reference_lfm2._rms_norm(x, lp["norm"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        ref = x + reference_lfm2.conv_operator(u, lp, {"conv_L_cache": 3})
    assert np.abs(np.asarray(whole - ref)).max() < 1e-5 * scale


def test_riders_in_a_prompts_tail_rows_take_a_step_and_leave_the_prompt_alone(
        tiny):
    """`conv_mixer(riders=)`: tests/mixer_riders.py says what is held, of a
    state with no recurrent part. The step alone is `step=True` on the layer's
    windows and their write back, as `models/serving.py::_conv_kind`'s decode
    body has it."""
    _, _, cfg, params = tiny
    lp = jax.tree.map(lambda w: w[1], params["conv"])

    def step(x, slots, layer, active):
        out, window = block.conv_mixer(
            lp, x, cfg, slot_state.layer_state(slots, layer)[1], step=True)
        return out, slot_state.update_layer(slots, layer, active, None,
                                            window)

    empty = slot_state.empty_state(
        mixer_riders.LAYERS, mixer_riders.SLOTS, 0, cfg.d_model,
        cfg.conv_taps, jnp.float32)
    tol = 1e-3      # rows of ~100 at 1e-5, as the test above
    mixer_riders.check(block.conv_mixer, lp, cfg, step, tol, empty)

    def deaf(lp, x, cfg, riders=None, layer=None, active=None, **kw):
        """An operator that ignores its riders: the prompt's own rows and
        the slots' windows handed back as they came."""
        return block.conv_mixer(lp, x, cfg, **kw) + (
            () if riders is None else (riders,))

    with pytest.raises(AssertionError):     # teeth: the check says so
        mixer_riders.check(deaf, lp, cfg, step, tol, empty)


def test_top_k_routing_with_the_published_eps_is_a_plain_transcription():
    """s = sigmoid(logits); the 4 largest of s + bias chosen; the weights s
    at the chosen over (their sum + 1e-6), times the factor: against numpy,
    and 1e-6 is seen where the chosen scores are tiny (1e-20 is not)."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(12, 16)).astype(np.float32)
    logits[0] = -20.0 + rng.normal(size=16)     # scores of 1e-9: the eps
    bias = rng.normal(size=16).astype(np.float32) * 0.3
    w, idx = moe.top_k_routing(jnp.asarray(logits), 4, True, score="sigmoid",
                               bias=jnp.asarray(bias), scale=1.5,
                               norm_eps=1e-6)
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    order = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :4]
    chosen = np.take_along_axis(s, order, axis=-1)
    want = chosen / (chosen.sum(-1, keepdims=True) + 1e-6) * 1.5
    np.testing.assert_array_equal(np.asarray(idx), order)
    np.testing.assert_allclose(np.asarray(w), want, rtol=2e-6, atol=1e-12)
    assert want[0].sum() < 1.4                  # the eps weighed in
    w20, _ = moe.top_k_routing(jnp.asarray(logits), 4, True, score="sigmoid",
                               bias=jnp.asarray(bias), scale=1.5)
    assert float(jnp.sum(w20[0])) == pytest.approx(1.5, rel=1e-5)
    # the model's description carries it to the layer
    assert _tiny()[2].routing()["norm_eps"] == 1e-6
    assert llama.LlamaConfig.tiny().router_norm_eps == 1e-20


# ---------------------------------------------------------------------------
# The kernels at a head of half a tile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [128, 384])
def test_flash_forward_at_a_head_of_64_is_its_reference_path(S):
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 4, S, 64))
               for i in range(3))
    got, lse = attention._flash_fwd_pallas(q, k, v, causal=True,
                                           sm_scale=0.125, block_q=128,
                                           block_k=128, interpret=True)
    want, lse_ref = attention._fwd_with_lse_reference(q, k, v, causal=True,
                                                      sm_scale=0.125)
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    assert np.abs(np.asarray(lse - lse_ref)).max() < 2e-5


def test_the_forward_gate_takes_a_head_of_64_and_the_backward_does_not(
        monkeypatch):
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q64, q128, q32 = (jnp.zeros((1, 2, 256, d)) for d in (64, 128, 32))
    assert attention.pallas_eligible(q64, q64)
    assert not attention.pallas_eligible(q64, q64, backward=True)
    assert attention.pallas_eligible(q128, q128, backward=True)
    assert not attention.pallas_eligible(q32, q32)
    assert not attention.pallas_eligible(q64[:, :, :100], q64[:, :, :100])


def test_heads_of_64_lie_two_to_a_row_and_no_lane_is_padded():
    """`empty` packs two kv heads of 64 to a 128-lane row; `write_prompt` and
    `write_token` put head 2r + h in lanes 64 h .. 64 h + 63 of row r; an odd
    number of heads, or another width, lies as it did."""
    kc, vc = paged_kv.empty(2, 5, 4, 16, 64, jnp.float32)
    assert kc.shape == vc.shape == (2, 5, 2, 16, 128)
    assert paged_kv.empty(1, 5, 3, 16, 64, jnp.float32)[0].shape \
        == (1, 5, 3, 16, 64)
    assert paged_kv.empty(1, 5, 4, 16, 32, jnp.float32)[0].shape \
        == (1, 5, 4, 16, 32)
    assert paged_kv.empty(1, 5, 4, 16, 128, jnp.float32)[0].shape \
        == (1, 5, 4, 16, 128)
    rng = np.random.default_rng(0)
    ks, vs = (jnp.asarray(rng.normal(size=(2, 20, 4, 64)), jnp.float32)
              for _ in range(2))
    kc, vc = paged_kv.write_prompt(kc, vc, jnp.asarray([3, 1]), ks, vs)
    for head in range(4):
        lanes = slice(64 * (head % 2), 64 * (head % 2) + 64)
        np.testing.assert_array_equal(
            np.asarray(kc[1, 3, head // 2, :, lanes]),
            np.asarray(ks[1, :16, head]))
        np.testing.assert_array_equal(
            np.asarray(vc[0, 1, head // 2, :4, lanes]),
            np.asarray(vs[0, 16:, head]))
    k, v = (jnp.asarray(rng.normal(size=(2, 4, 64)), jnp.float32)
            for _ in range(2))
    bt = jnp.asarray([[3, 1], [2, 4]])
    kc, vc = paged_kv.write_token(kc, vc, 1, bt, jnp.asarray([20, 7]),
                                  jnp.asarray([True, False]), k, v)
    np.testing.assert_array_equal(np.asarray(kc[1, 1, 1, 4, 64:]),
                                  np.asarray(k[0, 3]))
    assert not np.asarray(kc[1, 2]).any()       # the idle slot wrote nothing


@pytest.mark.parametrize("lengths", [(70, 0, 128), (1, 33, 64)])
def test_paged_decode_kernel_at_a_head_of_64_is_its_reference_path(lengths):
    """`paged_decode` over a packed arena in interpret mode, ragged lengths
    and an idle slot, against the XLA gather and against naive attention
    over the heads taken apart."""
    ns, H, KVH, page = 3, 8, 4, 16
    kc, vc = paged_kv.empty(1, 9 * ns + 1, KVH, page, 64, jnp.float32)
    rng = np.random.default_rng(sum(lengths))
    kc = jnp.asarray(rng.normal(size=kc.shape), jnp.float32)
    vc = jnp.asarray(rng.normal(size=vc.shape), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(9 * ns).reshape(ns, 9), jnp.int32)
    q = jnp.asarray(rng.normal(size=(ns, H, 64)), jnp.float32)
    args = (q, kc, vc, 0, bt, jnp.asarray(lengths, jnp.int32))
    got = paged_kv.paged_decode_attention(*args, interpret=True,
                                          pages_per_block=2)
    want = paged_kv.paged_decode_attention(*args)
    assert got.shape == want.shape == (ns, H, 64)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    for s, n in enumerate(lengths):
        rows = np.asarray(kc[0, bt[s]]), np.asarray(vc[0, bt[s]])
        # [pages, rows, page, 128] -> [positions, kv heads, 64]
        ks, vs = (r.reshape(9, 2, page, 2, 64).transpose(0, 2, 1, 3, 4)
                  .reshape(9 * page, KVH, 64)[:n] for r in rows)
        for h in range(H):
            if n == 0:
                assert not np.asarray(got[s, h]).any()
                continue
            sc = ks[:, h // 2] @ np.asarray(q[s, h]) / 8.0
            p = np.exp(sc - sc.max())
            naive = (p / p.sum()) @ vs[:, h // 2]
            assert np.abs(np.asarray(got[s, h]) - naive).max() < 1e-5


# ---------------------------------------------------------------------------
# Through the engine: in bfloat16 (in float32: tests/test_lfm2_engine.py)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(360)
def test_bfloat16_is_held_to_its_own_limit_and_fails_float32s():
    """The same engine computing in bfloat16 (weights, activations, caches):
    its tokens stay within BF16_TOL of the float32 reference on the same
    rounded weights, and NOT within LOGIT_TOL: the float32 tolerance tells
    the two precisions apart."""
    adapter, model, cfg, params = _tiny(BF16)
    assert cfg.dtype == jnp.bfloat16 and params["conv"]["in_proj"].dtype \
        == jnp.bfloat16
    eng = _engine(cfg, params)
    try:
        worst = 0.0
        for n in (10, 50):
            prompt = _tokens(n, n)
            served = _drain(eng.submit(prompt, 24))
            gaps = adapter.reference().served_token_gaps(params, model,
                                                         prompt, served)
            worst = max(worst, max(gaps))
            assert max(gaps) < BF16_TOL, gaps
        kc, _, _, (_, window) = eng._caches
        assert kc.dtype == window.dtype == jnp.bfloat16
    finally:
        eng.stop()
    # (a gap is 0 wherever rounding left the argmax alone: the logits, not
    # the gaps, are what differs in every row)
    prompt = _tokens(50, 50)
    _, _, _, logits, *_ = jax.jit(serving.prefill_core(cfg))(
        fuse_qkv(params, cfg), jnp.asarray([prompt + [0] * 14], jnp.int32),
        50)
    want = adapter.reference().logits_last(params, model, prompt, 1)[0]
    assert np.abs(np.asarray(logits) - np.asarray(want)).max() \
        > 100 * LOGIT_TOL


# ---------------------------------------------------------------------------
# The adapter and the configuration file
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("change,said", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(tie_word_embeddings=False), "untied head"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(layer_types=["conv"] * 6), "both kinds"),
    (dict(layer_types=["full_attention"] + LAYERS[1:]),
     "dense layer with attention"),
    (dict(layer_types=LAYERS[:5]), "each of num_hidden_layers"),
    (dict(num_dense_layers=6), "num_dense_layers"),
    (dict(use_expert_bias=False), "expert_bias"),
    (dict(num_experts_per_tok=9), "num_experts_per_tok"),
    (dict(head_dim=32), "head_dim"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope scaling"),
])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    adapter = models.adapter("lfm2")
    model = dict(adapter.REHEARSE, **PUBLISHED, num_hidden_layers=6,
                 layer_types=LAYERS)
    adapter.check_supported(model)
    with pytest.raises(ValueError, match=said):
        adapter.check_supported(dict(model, **change))


def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone():
    """benchmark/configs/lfm2-24b-a2b-serve.json: every published key and
    width unchanged, all 64 experts and the whole vocabulary; the three
    reduced keys with what was published; the counts follow it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b-serve.json")) as f:
        m = json.load(f)
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=11776, max_position_embeddings=128000,
        model_type="lfm2_moe", moe_intermediate_size=1536, norm_eps=1e-5,
        norm_topk_prob=True, num_attention_heads=32, num_experts=64,
        num_experts_per_tok=4, num_key_value_heads=8,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)
    assert {k: m[k] for k in published} == published
    assert list(m["reduced"]) == ["num_hidden_layers", "num_dense_layers",
                                  "layer_types"]
    assert {k: (v["published"], v["run"]) for k, v in m["reduced"].items()
            if isinstance(v["published"], int)} == dict(
        num_hidden_layers=(40, 9), num_dense_layers=(2, 1))
    assert all(m[k] == v["run"] and v["decided_by"]
               for k, v in m["reduced"].items())
    assert m["layer_types"] == ["conv"] + ["full_attention", "conv", "conv",
                                           "conv"] * 2
    assert len(m["source"]) <= 200 and len(m["assumed"]) >= 4
    for word in ("tied", "rotate-half", "B, C, X", "intermediate_size"):
        assert any(word in line for line in m["assumed"]), word
    counts = models.adapter("lfm2").counts
    assert counts.total_params(m) == 5_177_950_976
    assert counts.layers(m) == (1, 8) and counts.attention_layers(m) == 2
    assert counts.conv_params(m) == 16_783_360
    assert counts.attention_params(m) == 10_485_760
    assert counts.decode_attn_bytes(m, 1000, 2) == 1000 * 2048
    assert counts.conv_state_bytes(m, 2) * 7 * 64 == 3_670_016     # 3.7 MB
    eng = m["deployment"]["engine"]
    assert (eng["n_slots"], eng["max_seq"], eng["decode_chunk"],
            eng["page_size"]) == (64, 2048, 8, 64)
    assert eng["kv_pages"] == 1 + 64 * 2048 // 64
    cfg = models.adapter("lfm2").build_config(m, m["dtypes"], 2048)
    assert cfg.head_dim == 64 and cfg.kv_layers == 2 and cfg.tie_embeddings
    assert cfg.segments() == (("dense", 0, 1), ("layers", 0, 1),
                              ("conv", 0, 3), ("layers", 1, 2),
                              ("conv", 3, 6))
    assert cfg.router_norm_eps == 1e-6 and cfg.qk_norm == "head"
    assert llama.param_count(cfg) == counts.total_params(m)
    # the cell's traffic, letter for letter
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "generate-long-lfm2.json")) as f:
        mix = json.load(f)
    assert mix["arrivals"] == {"process": "closed", "clients": 96,
                               "pool_per_client_second": 0.1}
    assert (mix["prompt_tokens"], mix["output_tokens"], mix["shape_seed"]) \
        == ({"dist": "uniform", "min": 256, "max": 1024},
            {"dist": "uniform", "min": 512, "max": 1024}, 4601)
    assert mix["check"]["prompt_lengths"] == [300, 500, 1000] + [100] * 5
    assert mix["check"]["tokens"] == 128

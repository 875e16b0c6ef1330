"""MiMo-V2-Flash (arch `mimo`: window attention with a learned sink beside
full attention on other kv heads, keys wider than values, a partial
rotation, a leading dense layer, a sigmoid router over experts of which a
SHARE is held) at small float32 widths on the CPU: the program, through both
its caches, against `benchmark/reference_mimo.py`
(tests/test_mimo_engine.py); its kernels in interpret
mode against their reference paths, sink and window edge included; the ring
against the positions it no longer holds; the share against the uncut
layer; the refusals; the configuration file against the catalog's row.

Tolerance: program and reference compute the same mathematics in float32 and
differ in the order of their sums; LOGIT_TOL 2e-4 is the one test_olmoe.py,
test_keye.py, test_jamba.py and test_dots.py hold the same pairs to.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models, reference_mimo
from ray_tpu.models import llama
from ray_tpu.models.block import fuse_qkv, split_qkv
from ray_tpu.ops import attention, moe, paged_kv, slot_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-4
F32 = {"params": "float32", "activations": "float32"}
PUBLISHED = dict(
    rope_theta=5000000, swa_rope_theta=10000, layernorm_epsilon=1e-5,
    partial_rotary_factor=0.334, attention_value_scale=0.707,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc",
    n_group=1, topk_group=1, routed_scaling_factor=None,
    n_shared_experts=None, attention_bias=False, tie_word_embeddings=False,
    hidden_act="silu")
MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
STACKS = ("dense", "window", "layers")
WINDOW = 16     # the rehearsal's


def _tiny(max_seq=256, **more):
    """(adapter, model, cfg, params) at the adapter's rehearsal widths, with
    weights that decide (at the init's 0.02 every logit is a near-tie):
    matmuls x 8, the router x 40, the embedding spread."""
    adapter = models.adapter("mimo")
    model = dict(adapter.REHEARSE, **PUBLISHED, **more)
    cfg = adapter.build_config(model, F32, max_seq)
    params = dict(adapter.init_params(cfg, 3))
    for stack in STACKS:
        params[stack] = {
            k: w * (8.0 if k in MATMULS else 40.0 if k == "router" else 1.0)
            for k, w in params[stack].items()}
    params["embed"] = params["embed"] * 50.0
    params["lm_head"] = params["lm_head"] * 8.0
    return adapter, model, cfg, params


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


# ---------------------------------------------------------------------------
# The configuration of the program
# ---------------------------------------------------------------------------

def test_the_stack_is_segments_by_kind_and_stacks_hold_their_own_kind(tiny):
    _, _, cfg, params = tiny
    assert cfg.mixed and cfg.attn_pattern == (0, 1, 1, 0)
    assert cfg.segments() == (("dense", 0, 1), ("window", 0, 2),
                              ("layers", 0, 1))
    assert cfg.kv_layers == 2 and cfg.rotary_dim == 16
    assert cfg.attention_kind("window") == (2, 10000.0, WINDOW, True, 4, 16)
    assert cfg.attention_kind("layers") == (1, 5000000.0, 0, False, 4, 16)
    # the published order: layer 0 full, 1-4 window, 5 full, then five
    # window and one full, seven times
    long = llama.LlamaConfig.tiny(
        n_layers=12, head_dim=48, v_head_dim=32, rotary_dim=16, window=16,
        window_kv_heads=2, first_dense=1, d_ff_dense=128, n_experts=4,
        attn_pattern=[0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0])
    assert long.segments() == (
        ("dense", 0, 1), ("window", 0, 4), ("layers", 0, 1),
        ("window", 4, 9), ("layers", 1, 2))
    shapes = {s: {k: v.shape for k, v in params[s].items()} for s in STACKS}
    assert shapes["window"]["wk"] == (2, 64, 2 * 48)        # its own kv heads
    assert shapes["layers"]["wk"] == (1, 64, 1 * 48)
    assert shapes["window"]["wv"] == (2, 64, 2 * 32)
    assert shapes["window"]["sink"] == (2, 4)
    assert "sink" not in shapes["layers"] and "sink" not in shapes["dense"]
    assert "router" not in shapes["dense"] \
        and shapes["dense"]["w_gate"] == (1, 64, 128)
    assert shapes["window"]["w_gate"] == (2, 4, 64, 32)     # the experts HELD
    assert shapes["window"]["router"] == (2, 64, 16)        # scores them all
    assert set(llama.logical_axes(cfg)["window"]) == set(params["window"])
    # serving's layout and back, bit for bit
    fused = fuse_qkv(params, cfg)
    assert fused["window"]["wqkv"].shape == (2, 64, 4 * 48 + 2 * (48 + 32))
    back = split_qkv(fused, cfg)
    for stack in STACKS:
        for k in ("wq", "wk", "wv"):
            assert (np.asarray(back[stack][k])
                    == np.asarray(params[stack][k])).all()


@pytest.mark.parametrize("change,said", [
    (dict(kv_lora_rank=8), "latent attention"),
    (dict(window=0), "window and window_kv_heads"),
    (dict(rotary_dim=50), "rotary_dim"),
    (dict(attn_pattern=(1, 1, 1, 0)), "leading layers"),
    (dict(attn_pattern=(0, 1, 1)), "one of 0"),
    (dict(attn_pattern=None), "first_dense"),
    (dict(attn_pattern=None, first_dense=0), "a share of the experts"),
], ids=["latent", "no-window", "more-than-a-head-turned", "dense-window",
        "short-pattern", "dense-without-segments", "share-without-segments"])
def test_the_config_refuses_by_name(tiny, change, said):
    import dataclasses
    _, _, cfg, _ = tiny
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(cfg, **change)


def test_a_program_without_the_fields_is_refused_in_the_adapter_by_name(
        monkeypatch):
    """A parent-style `LlamaConfig`: `build_config` names what it lacks, in
    the caller's process, before any program is built."""
    adapter = models.adapter("mimo")
    model = dict(adapter.REHEARSE, **PUBLISHED)
    monkeypatch.setattr(adapter, "NEEDS", adapter.NEEDS + ("ring_of_saturn",))
    with pytest.raises(ValueError, match=r"LlamaConfig fields "
                                         r"\['ring_of_saturn'\]"):
        adapter.build_config(model, F32, 128)


@pytest.mark.parametrize("change,said", [
    (dict(scoring_func="softmax"), "sigmoid"),
    (dict(tie_word_embeddings=True), "tied"),
    (dict(n_group=8, topk_group=4), "group-limited"),
    (dict(n_shared_experts=1), "shared experts"),
    (dict(swa_head_dim=64), "swa_head_dim"),
    (dict(add_full_attention_sink_bias=True), "full-attention layers"),
    (dict(hybrid_layer_pattern=[0, 1, 1]), "hybrid_layer_pattern"),
    (dict(moe_layer_freq=[0, 1, 0, 1]), "leading dense"),
    (dict(hybrid_layer_pattern=[1, 1, 1, 0]), "window attention"),
    (dict(partial_rotary_factor=1.0), "partial_rotary_factor"),
    (dict(expert_parallel={"chips": 3, "rank": 0,
                           "routed_experts_total": 16}), "expert_parallel"),
], ids=["softmax-router", "tied", "groups", "shared", "swa-width",
        "full-sink", "pattern", "dense-inside", "dense-window",
        "whole-rotation", "share"])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    adapter = models.adapter("mimo")
    model = {**adapter.REHEARSE, **PUBLISHED, **change}
    with pytest.raises(ValueError, match=said):
        adapter.build_config(model, F32, 128)


# ---------------------------------------------------------------------------
# The share adds up
# ---------------------------------------------------------------------------

def test_the_shares_parts_are_the_uncut_layer(tiny):
    """16 experts in 4 shares of 4: every share in turn holds its 4 experts'
    weights (drawn here for all 16), routes over all 16 and computes its
    part; the four parts are what the uncut reference gives for the whole
    layer, nothing counted twice, and no part is nothing."""
    _, model, cfg, params = tiny
    lp = {k: v[1] for k, v in params["window"].items()}
    g = jax.random.normal(jax.random.PRNGKey(1), (48, cfg.d_model))
    total, n = cfg.n_experts, cfg.n_held
    assert (total, n) == (16, 4)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    full = {name: 0.16 * jax.random.normal(
        k, (total,) + lp[name].shape[1:]) for name, k in
        zip(("w_gate", "w_up", "w_down"), ks)}
    routing = dict(cfg.routing(), bias=lp["router_bias"])
    parts, met = [], 0
    for share in range(total // n):
        mine = {k: w[share * n:(share + 1) * n] for k, w in full.items()}
        out, _, counts = moe.moe_ffn(
            g, lp["router"], mine["w_up"], mine["w_gate"], mine["w_down"],
            top_k=cfg.top_k_experts, norm_topk_prob=True, routing=routing,
            held=(share * n, n))
        assert counts.shape == (n,)
        met += int(counts.sum())
        parts.append(np.asarray(out))
        want = reference_mimo.routed_part(g, dict(lp, **mine), model,
                                          (share * n, n), total)
        assert np.abs(parts[-1] - np.asarray(want)).max() < 1e-4
        assert np.abs(parts[-1]).max() > 1e-2
    assert met == 48 * cfg.top_k_experts        # every assignment, once
    whole = reference_mimo.routed_part(g, dict(lp, **full), model,
                                       (0, total), total)
    assert np.abs(sum(parts) - np.asarray(whole)).max() < 2e-4


# ---------------------------------------------------------------------------
# The kernels, the ring
# ---------------------------------------------------------------------------

def _qkv(S, kvh, H=4, dn=128, dr=64, dv=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(1, H, S, dn), (1, H, S, dr), (1, kvh, S, dn), (1, kvh, S, dr),
              (1, kvh, S, dv)]
    return [jax.random.normal(k, s) for k, s in zip(ks, shapes)], ks[5]


@pytest.mark.parametrize("window,S,kvh", [
    (128, 384, 2), (100, 256, 2), (128, 128, 4), (0, 256, 2), (0, 128, 1)],
    ids=["window-3-blocks", "window-100", "window-1-block", "full-gqa",
         "full-mqa"])
def test_mixed_flash_kernels_are_their_reference_path(window, S, kvh):
    """`window_flash_fwd` (a sink a head; queries at the window's edge in the
    block before the diagonal; block 0, which has no block before it) and
    `full_flash_fwd` (keys in two parts a kv head, values narrower, K and V
    read by kv head) in interpret mode against every score under a mask."""
    (q_n, q_r, k_n, k_r, v), key = _qkv(S, kvh)
    sink = 2.0 * jax.random.normal(key, (4,)) if window else None
    got = attention.mixed_flash_attention(
        q_n, q_r, k_n, k_r, v, 192 ** -0.5, window=window, sink=sink,
        interpret=True)
    want = attention.mixed_attention_reference(
        q_n, q_r, k_n, k_r, v, 192 ** -0.5, window, sink)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    if window:  # the sink takes its share: without it the rows differ
        bare = attention.mixed_attention_reference(
            q_n, q_r, k_n, k_r, v, 192 ** -0.5, window, None)
        assert np.abs(np.asarray(bare) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d,S", [(64, 200), (16, 33)])
def test_the_narrow_rope_is_apply_rope_to_the_bit(dtype, d, S):
    """`apply_rope_narrow` (the halves swapped by a signed permutation
    matmul, the one RoPE a prompt of this stack takes) against `apply_rope`
    at positions 0..S-1, at MiMo-V2's rotary width and at the tiny stack's:
    the same products and the same sums, so bit for bit op by op in either
    dtype and under jit in bfloat16, the dtype served. Under jit in float32
    the CPU compiler contracts a product and a sum into one rounding where
    it likes, in `apply_rope` too (jitted, it differs from itself op by op
    by as much): there the two agree to that last place."""
    from ray_tpu.ops.norms import (apply_rope, apply_rope_narrow,
                                   rope_frequencies)
    x = (3.0 * jax.random.normal(jax.random.PRNGKey(d + S), (2, 3, S, d))
         ).astype(dtype)
    cos, sin = rope_frequencies(d, S, 1e4)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    want = f32(apply_rope(x, cos, sin))
    assert np.abs(want - f32(x)).max() > 0.1
    got = apply_rope_narrow(x, cos, sin)
    assert got.dtype == x.dtype and np.array_equal(f32(got), want)
    jitted = f32(jax.jit(apply_rope_narrow)(x, cos, sin))
    if dtype == jnp.bfloat16:
        assert np.array_equal(jitted, want)
    else:
        last_place = np.finfo(np.float32).eps * np.abs(want).max()
        assert np.abs(jitted - want).max() <= last_place
        assert np.abs(f32(jax.jit(apply_rope)(x, cos, sin)) - want).max() \
            <= last_place


def test_a_window_layer_never_reads_past_its_window():
    """Keys and values older than the window overwritten with garbage: the
    window kernel's rows, and its reference's, do not move. The cache can be
    bounded because the mathematics is."""
    (q_n, q_r, k_n, k_r, v), key = _qkv(384, 2)
    sink = jax.random.normal(key, (4,))
    old = 384 - 128     # the last query sees 256..383; rows >= 256 + 127
    trash = [t.at[:, :, :old].set(1e4) for t in (k_n, k_r, v)]
    for fn in (functools.partial(attention.mixed_flash_attention,
                                 interpret=True, window=128, sink=sink),
               lambda *a: attention.mixed_attention_reference(
                   *a, 128, sink)):
        clean = np.asarray(fn(q_n, q_r, k_n, k_r, v, 192 ** -0.5))
        dirty = np.asarray(fn(q_n, q_r, *trash, 192 ** -0.5))
        assert (clean[:, :, old + 127:] == dirty[:, :, old + 127:]).all()
        assert np.abs(clean[:, :, :old] - dirty[:, :, :old]).max() > 1.0


@pytest.mark.parametrize("window", [16, 12])
def test_the_ring_is_the_last_window_positions_and_no_more(window):
    """A slot's ring written by a prefill's tail and then a decode step at a
    time, read by `window_decode_attention`: the naive attention of the
    step's query over the last `window` positions with the sink's column, at
    prompts shorter than, equal to and several times the window, through
    several wraps; a slot re-used by a shorter prompt holds nothing of its
    last tenant; the ring's size is the window's (rounded to a tile's rows),
    whatever the prompt's length."""
    H, KVH, dk, dv, L = 4, 2, 48, 32, 2
    rng = np.random.default_rng(window)
    state = slot_state.empty_window(L, 3, KVH, window, dk, dv, jnp.float32)
    assert [s.shape for s in state] == [(L, 3, KVH, 16, 128)] * 2
    sink = jnp.asarray(rng.normal(size=H), jnp.float32)
    scale = dk ** -0.5
    act = jnp.asarray([False, True, False])
    admit = jax.jit(lambda state, n, ks, vs: slot_state.write_window_prompt(
        state, 1, n, ks, vs))

    @jax.jit
    def step(state, w, q, k, v):
        pos = jnp.zeros(3, jnp.int32).at[1].set(w)
        for layer in range(L):
            state = slot_state.write_window_token(state, layer, pos, act, k, v)
        return state, slot_state.window_decode_attention(
            jnp.pad(q, ((0, 0), (0, 0), (0, 128 - dk))), state, 1, pos, act,
            window=window, sm_scale=scale, sink=sink)

    def softmax(x):
        e = np.exp(x - x.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    for n in (100, 5, window, 40):      # slot 1's tenants, one after another
        ks = rng.normal(size=(L, 128, KVH, dk)).astype(np.float32)
        vs = rng.normal(size=(L, 128, KVH, dv)).astype(np.float32)
        state = admit(state, n, jnp.asarray(ks), jnp.asarray(vs))
        hist_k, hist_v = list(ks[1, :n]), list(vs[1, :n])   # layer 1's
        for w in range(n, n + 36):
            q, k, v = (rng.normal(size=(3, heads, d)).astype(np.float32)
                       for heads, d in ((H, dk), (KVH, dk), (KVH, dv)))
            state, got = step(state, w, *map(jnp.asarray, (q, k, v)))
            hist_k.append(k[1])
            hist_v.append(v[1])
            allk = np.stack(hist_k[-window:])                # [<=W, KVH, dk]
            allv = np.stack(hist_v[-window:])
            qg = q[1].reshape(KVH, H // KVH, dk)
            s = np.einsum("kgd,skd->kgs", qg, allk) * scale
            s = np.concatenate([s, np.asarray(sink).reshape(KVH, -1, 1)], -1)
            want = np.einsum("kgs,skd->kgd", softmax(s)[..., :-1],
                             allv).reshape(H, dv)
            got = np.asarray(got)
            assert np.abs(got[1, :, :dv] - want).max() < 1e-5, (n, w)
            assert not got[0].any()                 # an idle slot: zeros
    # nobody wrote the other slots' rings
    assert not np.asarray(state[0][:, 0]).any()
    assert not np.asarray(state[0][:, 2]).any()


@pytest.mark.parametrize("lengths", [(70, 0, 128), (1, 33, 64)])
def test_paged_decode_kernel_with_keys_wider_than_values(lengths):
    """`paged_decode` at a mixed stack's widths (keys of 256 lanes, values of
    128) in interpret mode against the XLA gather, idle slot included."""
    ns, H, KVH, page = 3, 8, 2, 16
    kc, vc = paged_kv.empty(1, 9 * ns + 1, KVH, page, 192, jnp.float32,
                            v_head_dim=128)
    assert kc.shape[-1] == 256 and vc.shape[-1] == 128
    rng = np.random.default_rng(sum(lengths))
    kc = jnp.asarray(rng.normal(size=kc.shape), jnp.float32)
    vc = jnp.asarray(rng.normal(size=vc.shape), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(9 * ns).reshape(ns, 9), jnp.int32)
    q = jnp.asarray(rng.normal(size=(ns, H, 256)), jnp.float32)
    args = (q, kc, vc, 0, bt, jnp.asarray(lengths, jnp.int32))
    got = paged_kv.paged_decode_attention(*args, sm_scale=192 ** -0.5,
                                          interpret=True, pages_per_block=2)
    want = paged_kv._paged_decode_reference(*args, sm_scale=192 ** -0.5)
    assert got.shape == (ns, H, 128)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# ---------------------------------------------------------------------------
# The configuration file
# ---------------------------------------------------------------------------

def test_the_configuration_is_the_catalogs_row_cut_to_a_share():
    """benchmark/configs/mimo-v2-flash-serve.json: every published width
    unchanged, the five reduced keys with what was published, the share in
    words and numbers; the counts follow it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2-flash-serve.json")) as f:
        m = json.load(f)
    assert {k: m[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "v_head_dim", "swa_num_attention_heads",
        "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
        "sliding_window", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "partial_rotary_factor",
        "attention_value_scale", "rope_theta", "swa_rope_theta")} == dict(
        hidden_size=4096, num_attention_heads=64, num_key_value_heads=4,
        head_dim=192, v_head_dim=128, swa_num_attention_heads=64,
        swa_num_key_value_heads=8, swa_head_dim=192, swa_v_head_dim=128,
        sliding_window=128, intermediate_size=16384,
        moe_intermediate_size=2048, num_experts_per_tok=8,
        partial_rotary_factor=0.334, attention_value_scale=0.707,
        rope_theta=5000000, swa_rope_theta=10000)
    assert list(m["reduced"]) == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    assert {k: (v["published"], v["run"]) for k, v in m["reduced"].items()
            if isinstance(v["published"], int)} == dict(
        num_hidden_layers=(48, 7), n_routed_experts=(256, 16),
        vocab_size=(152576, 19072))
    assert all(m[k] == v["run"] for k, v in m["reduced"].items())
    assert m["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert m["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert m["expert_parallel"]["chips"] * m["n_routed_experts"] \
        == m["expert_parallel"]["routed_experts_total"] == 256
    assert len(m["source"]) <= 200
    counts = models.adapter("mimo").counts
    assert counts.attention_params(m, True) == 94_371_840 + 64    # the sinks
    assert counts.attention_params(m, False) == 89_128_960
    assert counts.total_params(m) == pytest.approx(3_430e6, rel=1e-3)
    assert counts.expected_local(m) == 0.5
    assert counts.layers(m) == (1, 6) and counts.attention_layers(m) == (2, 5)
    eng = m["deployment"]["engine"]
    assert (eng["n_slots"], eng["max_seq"], eng["decode_chunk"],
            eng["page_size"]) == (32, 8192, 8, 64)
    assert eng["kv_pages"] == 1 + 32 * 8192 // 64
    cfg = models.adapter("mimo").build_config(m, m["dtypes"], 8192)
    assert cfg.experts_held == (0, 16) and cfg.n_experts == 256
    assert (cfg.rotary_dim, cfg.head_dim, cfg.v_head_dim) == (64, 192, 128)
    assert cfg.segments() == (("dense", 0, 1), ("window", 0, 5),
                              ("layers", 0, 1))
    # LIVE pairs: a window layer's grow with the prompt, a full layer's with
    # its square
    w2, _ = counts.prefill_attn_ops_bytes(m, 2048, True, 2)
    w8, _ = counts.prefill_attn_ops_bytes(m, 8192, True, 2)
    f2, _ = counts.prefill_attn_ops_bytes(m, 2048, False, 2)
    f8, _ = counts.prefill_attn_ops_bytes(m, 8192, False, 2)
    assert w8 / w2 == pytest.approx(4.0, rel=0.03)
    assert f8 / f2 == pytest.approx(16.0, rel=0.01)
    assert counts.decode_attn_bytes(m, 1000, False, 2) == 1000 * 4 * 320 * 2
    assert counts.decode_attn_bytes(m, 1000, True, 2) == 1000 * 8 * 320 * 2

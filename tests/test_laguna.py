"""Laguna-S-2.1 (arch `laguna`: window layers whose query heads outnumber the
full layers', each kind with its own rotation, the full kind's under YaRN
with a magnitude; a sigmoid gate a head; a scaled softmax router over experts
of which a SHARE is held, a shared expert beside them) at small float32
widths on the CPU: the program, through both its caches, against
`benchmark/reference_laguna.py`; what the comparison sees of a model computed
wrongly; the share against the uncut layer; the refusals; the configuration
file against the catalog's row and the issue's count. The kernels are
tests/test_laguna_kernels.py's.

Tolerance: program and reference compute the same mathematics in float32 and
differ in the order of their sums; LOGIT_TOL 2e-4 is the one test_mimo.py and
test_dots.py hold the same pairs to.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models, reference_laguna
from ray_tpu.models import llama, serving
from ray_tpu.models.block import fuse_qkv, split_qkv
from ray_tpu.models.serving import prefill_core
from ray_tpu.ops import attention, moe, paged_kv
from ray_tpu.serve.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-4
F32 = {"params": "float32", "activations": "float32"}
MATMULS = ("wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down",
           "ws_gate", "ws_up", "ws_down")
STACKS = ("dense", "window", "layers")
WINDOW = 16     # the rehearsal's


def published():
    """The catalog's keys as the configuration file has them."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-s-2.1-serve.json")) as f:
        return json.load(f)


def _tiny(max_seq=256, **more):
    """(adapter, model, cfg, params) at the adapter's rehearsal widths, with
    weights that decide (at the init's 0.02 every logit is a near-tie):
    matmuls x 8, the router x 40, the embedding spread."""
    adapter = models.adapter("laguna")
    model = dict(published(), **adapter.REHEARSE, **more)
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


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _drain(q):
    out = []
    while (item := q.get(timeout=300)) is not None:
        out.extend(item)
    return out


# ---------------------------------------------------------------------------
# The configuration of the program
# ---------------------------------------------------------------------------

def test_the_kinds_differ_in_heads_and_rotation_and_a_kind_runs_in_two_runs(
        tiny):
    _, _, cfg, params = tiny
    assert cfg.mixed and cfg.attn_pattern == (0, 1, 1, 0, 1, 1)
    # the window layers are ONE stack in two runs, a full layer between them
    assert cfg.segments() == (("dense", 0, 1), ("window", 0, 2),
                              ("layers", 0, 1), ("window", 2, 4))
    assert cfg.kv_layers == 2
    assert cfg.attention_kind("window") == (2, 10000.0, WINDOW, False, 6, 16)
    assert cfg.attention_kind("layers") == (2, 500000.0, 0, False, 4, 8)
    assert cfg.attention_kind("dense") == cfg.attention_kind("layers")
    assert cfg.rope_yarn == (128.0, 8192.0, 32.0, 1.0)
    assert cfg.rope_magnitude == pytest.approx(0.1 * np.log(128) + 1)
    assert (cfg.attn_gate, cfg.routed_scale, cfg.n_shared_experts,
            cfg.router_score, cfg.experts_held) == (
        True, 2.5, 1, "softmax", (4, 4))
    assert cfg.routing() == {"scale": 2.5}
    shapes = jax.tree.map(lambda x: x.shape, params)
    assert shapes["window"]["wq"] == (4, 64, 6 * 16)    # 6 heads of 16
    assert shapes["window"]["wo"] == (4, 6 * 16, 64)
    assert shapes["window"]["wg"] == (4, 64, 6)
    assert shapes["layers"]["wq"] == (1, 64, 4 * 16)
    assert shapes["layers"]["wg"] == shapes["dense"]["wg"] == (1, 64, 4)
    assert all(shapes[s]["wk"][-1] == shapes[s]["wv"][-1] == 2 * 16
               for s in STACKS)
    assert "router" not in shapes["dense"] \
        and shapes["dense"]["w_gate"] == (1, 64, 128)
    assert shapes["window"]["w_gate"] == (4, 4, 64, 32)     # the experts HELD
    assert shapes["window"]["router"] == (4, 64, 16)        # scores them all
    assert shapes["window"]["ws_up"] == (4, 64, 32)         # the shared one
    assert "router_bias" not in shapes["window"]
    assert set(llama.logical_axes(cfg)["window"]) == set(params["window"])
    # the tables: the window kind's plain, the full kind's YaRN's times the
    # magnitude (at position 0 cos is the magnitude itself)
    cos_w, _ = cfg.rope_tables("window", 32)
    cos_f, _ = cfg.rope_tables("layers", 32)
    assert cos_w.shape == (32, 8) and cos_f.shape == (32, 4)
    assert float(cos_w[0, 0]) == 1.0
    assert float(cos_f[0, 0]) == pytest.approx(cfg.rope_magnitude)
    # serving's layout (the gate's columns in a tile of their own) and back
    fused = fuse_qkv(params, cfg)
    assert fused["window"]["wqkv"].shape == (4, 64, 6 * 16 + 2 * 32 + 128)
    assert fused["layers"]["wqkv"].shape == (1, 64, 4 * 16 + 2 * 32 + 128)
    back = split_qkv(fused, cfg)
    for stack in STACKS:
        for k in ("wq", "wk", "wv", "wg"):
            assert (np.asarray(back[stack][k])
                    == np.asarray(params[stack][k])).all()


@pytest.mark.parametrize("change,said", [
    (dict(window_heads=5), "whole groups"),
    (dict(window_rotary_dim=18), "rotary_dim"),
    (dict(rope_yarn=(4.0, 32.0)), "rope_yarn"),
    (dict(attn_pattern=None, first_dense=0, experts_held=None,
          n_shared_experts=0, rope_yarn=None), "attn_gate"),
    (dict(window_sink=True), "attn_gate with window_sink"),
], ids=["ragged-groups", "more-than-a-head-turned", "short-yarn",
        "a-gate-without-the-stack", "gate-and-sink"])
def test_the_config_refuses_by_name(tiny, change, said):
    import dataclasses
    _, _, cfg, _ = tiny
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(cfg, **change)


@pytest.mark.parametrize("change,said", [
    (dict(gating="per-element"), "gate other than"),
    (dict(gating_types=["per_head", "none"] + ["per_head"] * 4),
     "gate other than"),
    (dict(moe_router_logit_softcapping=30.0), "cap on the router"),
    (dict(moe_apply_router_weight_on_input=True), "on an expert's input"),
    (dict(tie_word_embeddings=True), "tied"),
    (dict(mlp_layer_types=["dense", "sparse", "dense", "sparse", "sparse",
                           "sparse"]), "leading dense"),
    (dict(mlp_layer_types=["sparse"] * 6), "leading dense"),
    (dict(layer_types=["sliding_attention"] * 6), "window attention"),
    (dict(num_attention_heads_per_layer=[4, 6, 8, 4, 6, 6]),
     "one count a kind"),
    (dict(shared_expert_intermediate_size=48), "whole multiples"),
    (dict(expert_parallel={"chips": 3, "rank": 0,
                           "routed_experts_total": 16}), "expert_parallel"),
], ids=["another-gate", "a-layer-without-gate", "softcap", "weight-on-input",
        "tied", "dense-inside", "no-dense", "dense-window", "ragged-heads",
        "shared-width", "share"])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    adapter = models.adapter("laguna")
    model = {**published(), **adapter.REHEARSE, **change}
    with pytest.raises(ValueError, match=said):
        adapter.build_config(model, F32, 128)


def test_a_program_without_the_fields_is_refused_in_the_adapter_by_name(
        monkeypatch):
    """A parent-style `LlamaConfig`: `build_config` names what it lacks, in
    the caller's process, before any program is built."""
    import dataclasses
    adapter = models.adapter("laguna")
    real = dataclasses.fields
    monkeypatch.setattr(dataclasses, "fields", lambda c: [
        f for f in real(c) if f.name not in ("window_heads", "attn_gate")])
    with pytest.raises(ValueError, match="window_heads.*attn_gate"):
        adapter.build_config({**published(), **adapter.REHEARSE}, F32, 128)


def test_the_file_is_the_catalogs_row_and_the_count_is_the_issues():
    """Every number of the catalog's `config` under the same key but the
    three `reduced` ones; the per-layer lists whole; and
    `flops_laguna.total_params` at the cell's configuration is what the
    program's own tree holds and ISSUE 62's 2,843.1 M."""
    m = published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-S-2.1")
        differ = {k for k, v in row["config"].items() if m.get(k) != v}
        assert differ == set(m["reduced"]) == {
            "num_hidden_layers", "num_experts", "vocab_size"}
        assert m["source_url"] == row["source_url"]
    adapter = models.adapter("laguna")
    counts = adapter.counts
    assert counts.attention_params(m, False) == 44_187_648
    assert counts.attention_params(m, True) == 63_135_744
    assert counts.total_params(m) == 2_843_053_056
    assert round(counts.total_params(m) / 1e6, 1) == 2843.1
    assert counts.layers(m) == (1, 7) and counts.attention_layers(m) == (2, 6)
    assert counts.expected_local(m) == 1.25
    cfg = adapter.build_config(m, m["dtypes"], 8192)
    assert llama.param_count(cfg) == counts.total_params(m)
    assert cfg.segments() == (("dense", 0, 1), ("window", 0, 3),
                              ("layers", 0, 1), ("window", 3, 6))
    # every rung of the cell is a kernel's, and a shape that is not is named
    assert all(serving.rung_refusal(cfg, w) is None
               for w in (128, 1024, 7168, 8192))
    import dataclasses
    odd = dataclasses.replace(cfg, head_dim=96, rotary_dim=32,
                              window_rotary_dim=96)
    assert "whole tiles of 128" in serving.rung_refusal(odd, 1024)


# ---------------------------------------------------------------------------
# A shape no kernel takes is refused where the server is built
# ---------------------------------------------------------------------------

def test_on_a_tpu_a_server_whose_prompts_would_fall_to_xla_is_refused(
        tiny, monkeypatch):
    """The rehearsal's heads of 16 are no kernel's: off the chip the
    reference path is the path; on one (the test says so, there is none
    here) `Engine` refuses the model by the shape, before it builds a
    program. MiMo-V2's shapes pass (tests/test_tpu_compile_shares.py builds
    them)."""
    _, _, cfg, params = tiny
    assert serving.rung_refusal(cfg, 64) is None        # under one block
    why = serving.rung_refusal(cfg, 128)
    assert "stack `layers`" in why and "128 rows" in why \
        and "8 passed + 8 turned" in why
    serving.check_rungs(cfg, [32, 128])                 # off the chip: silent
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="attention in XLA.*128 rows"):
        Engine(params, cfg, n_slots=2, decode_chunk=4, page_size=16)
    for dn, dr, window, ok in ((128, 64, 128, True), (64, 64, 512, True),
                               (0, 128, 512, True), (0, 128, 0, True),
                               (32, 64, 0, False), (0, 64, 512, False)):
        assert (attention.mixed_kernel_refusal(
            1024, dn, dr, 128, window) is None) == ok
    assert "blocks of 128" in attention.mixed_kernel_refusal(
        1000, 128, 64, 128, 128)


# ---------------------------------------------------------------------------
# The share adds up
# ---------------------------------------------------------------------------

def test_the_shares_parts_and_the_shared_expert_once_are_the_uncut_layer(
        tiny):
    """16 experts in 4 shares of 4: every share in turn holds its 4 experts'
    weights (drawn here for all 16), routes over all 16 by the scaled,
    renormalised softmax and computes its part; the four parts and the
    shared expert, counted ONCE, are what the uncut reference gives for the
    whole layer, and no part is nothing."""
    _, model, cfg, params = tiny
    lp = {k: v[1] for k, v in params["window"].items()}
    g = jax.random.normal(jax.random.PRNGKey(1), (48, cfg.d_model))
    total, n = cfg.n_experts, cfg.n_held
    assert (total, n) == (16, 4)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    full = {name: 0.16 * jax.random.normal(
        k, (total,) + lp[name].shape[1:]) for name, k in
        zip(("w_gate", "w_up", "w_down"), ks)}
    shared = tuple(lp["ws_" + k] for k in ("gate", "up", "down"))
    parts, met = [], 0
    for share in range(total // n):
        mine = {k: w[share * n:(share + 1) * n] for k, w in full.items()}
        out, _, counts = moe.moe_ffn(
            g, lp["router"], mine["w_up"], mine["w_gate"], mine["w_down"],
            top_k=cfg.top_k_experts, norm_topk_prob=True,
            routing=cfg.routing(), held=(share * n, n),
            shared=shared if share == 0 else None)
        assert counts.shape == (n,)
        met += int(counts.sum())
        parts.append(np.asarray(out))
        want = reference_laguna.routed_part(g, dict(lp, **mine), model,
                                            (share * n, n), total)
        if share == 0:
            want = want + reference_laguna.shared_part(g, lp)
        assert np.abs(parts[-1] - np.asarray(want)).max() < 1e-4
        assert np.abs(parts[-1]).max() > 1e-2
    assert met == 48 * cfg.top_k_experts        # every assignment, once
    whole = reference_laguna.routed_part(
        g, dict(lp, **full), model, (0, total), total) \
        + reference_laguna.shared_part(g, lp)
    assert np.abs(sum(parts) - np.asarray(whole)).max() < 2e-4
    # the weights of a token's 4 sum to the factor, 2.5
    combine = reference_laguna.route(g, lp["router"], model, total)
    assert np.allclose(np.asarray(combine).sum(-1), 2.5, atol=1e-5)


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


@pytest.fixture(scope="module")
def served(tiny, engine):
    """A prompt of six windows and the 40 tokens the engine serves after
    it."""
    prompt = _tokens(100, 100)
    return prompt, _drain(engine.submit(prompt, 40))


@pytest.mark.timeout(300)
@pytest.mark.parametrize("n,bucket", [(10, 32), (16, 32), (7, 32)],
                         ids=["under-the-window", "the-window",
                              "a-shorter-one-after"])
def test_prefill_then_decode_through_both_caches_is_the_reference(
        tiny, engine, n, bucket):
    """Prompts shorter than and equal to the window (16), and a shorter one
    into the slot a longer one left; then 40 tokens decoded, the full layers
    (4 heads on 2) through their pages (pages of 16, the kernel
    interpreted), the window layers (6 heads on 2) through a ring of 16 rows
    that wraps twice: the prefill's logits are the reference's at the
    prompt's last position, and every served token is the reference's
    largest logit to float32 rounding."""
    adapter, model, cfg, params = tiny
    prompt = _tokens(n, n)
    ref = adapter.reference()
    _, ks, vs, logits, experts, (kws, vws) = jax.jit(prefill_core(cfg))(
        fuse_qkv(params, cfg),
        jnp.asarray([prompt + [0] * (bucket - n)], jnp.int32), n)
    want = np.asarray(ref.logits_last(params, model, prompt, 1))[0]
    assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
    assert ks.shape == vs.shape == (2, bucket, 2, 16)
    assert kws.shape == vws.shape == (4, bucket, 2, 16)
    held = cfg.n_held
    assert experts.shape == (held + 2,)
    assert int(experts[-1]) == n * cfg.top_k_experts * 5    # 5 sparse layers
    got = _drain(engine.submit(prompt, 40))
    assert len(got) == 40
    gaps = ref.served_token_gaps(params, model, prompt, got)
    assert max(gaps) < LOGIT_TOL, gaps


@pytest.mark.timeout(300)
def test_six_windows_then_decode_is_the_reference_and_counts_its_rings(
        tiny, engine, served):
    adapter, model, cfg, params = tiny
    prompt, got = served
    ref = adapter.reference()
    assert len(got) == 40
    gaps = ref.served_token_gaps(params, model, prompt, got)
    assert max(gaps) < LOGIT_TOL, gaps
    # teeth: against the prompt less its last token the same tokens are
    # another row's
    short = ref.served_token_gaps(params, model, prompt[:-1], got)
    assert max(short) > 100 * LOGIT_TOL
    c = engine.counters()
    assert 0 < c["window_kv_tokens"] <= WINDOW * 4 * c["decode_chunks"]
    counts = attention.attention_path_counts()
    assert counts["decode_pallas"] >= 1             # interpreted, in decode
    assert counts["window_decode_reference"] >= 1
    # pages: the 2 full layers alone; rings: the 4 window layers, 16 rows
    kc, vc, _, rings = engine._caches
    assert kc.shape == vc.shape == (2, engine.n_pages, 2, 16, 128)
    assert [s.shape for s in rings] == [(4, 2, 2, 16, 128)] * 2
    assert c["window_cache_bytes"] == sum(s.nbytes for s in rings)
    assert c["full_cache_bytes"] == kc.nbytes + vc.nbytes


def _cut_heads(params, cfg):
    """The window layers with the full layers' query heads: the first 4 of
    their 6, `wq`, `wo` and `wg` cut alike."""
    hd, keep = cfg.head_dim, cfg.n_heads
    w = dict(params["window"])
    w["wq"] = w["wq"][..., :keep * hd]
    w["wo"] = w["wo"][:, :keep * hd]
    w["wg"] = w["wg"][..., :keep]
    return dict(params, window=w)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("wrong", [
    (("gate", False),), (("magnitude", 1.0),), "heads", (("norm", False),),
    (("scale", 1.0),), (("shared", False),)],
    ids=["no-gate", "no-yarn-magnitude", "48-heads-where-72-belong",
         "unrenormalised-router", "unscaled-router", "no-shared-expert"])
def test_a_model_computed_wrongly_reads_gaps_far_over_the_tolerance(
        tiny, served, wrong):
    """What the program served, held against the reference computing each of
    the model's mechanisms WRONGLY in turn: every one reads gaps a hundred
    tolerances and more, so the comparison that passes above sees each."""
    adapter, model, cfg, params = tiny
    prompt, got = served
    ref = adapter.reference()
    if wrong == "heads":
        gaps = ref.served_token_gaps(_cut_heads(params, cfg), model, prompt,
                                     got)
    else:
        gaps = ref.served_token_gaps(params, model, prompt, got, wrong)
    assert max(gaps) > 100 * LOGIT_TOL, (wrong, max(gaps))


def test_a_pd_handoff_and_the_training_forward_refuse_mixed_attention_by_name(
        tiny, engine):
    _, _, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="mixed attention"):
        engine.submit_prefilled(None, None, 4, 1, 4)
    with pytest.raises(NotImplementedError, match="mixed attention"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="serves only"):
        reference_laguna.loss_and_check_grads(params, {}, None)

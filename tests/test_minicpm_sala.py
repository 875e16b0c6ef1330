"""MiniCPM-SALA (arch `minicpm_sala`: decayed linear attention in three layers
of four beside a grouped-query layer with no rotation that selects BLOCKS of
keys against mean-pooled ones, gates and a norm on their outputs, the
family's three scalar multipliers) at small float32 widths on the CPU: the
program, through its pages, its pooled keys and its state, against
`benchmark/reference_minicpm_sala.py`; what the comparison sees of a model
computed wrongly; the refusals; the configuration file against the catalog's
row and the issue's count. The kernels are tests/test_minicpm_sala_kernels.py's.

Tolerance: program and reference compute the same mathematics in float32 and
differ in the order of their sums; LOGIT_TOL 2e-4 is the one test_laguna.py,
test_mimo.py and test_dots.py hold the same pairs to.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models, reference_minicpm_sala
from ray_tpu.models import llama, serving
from ray_tpu.models.block import fuse_qkv, split_qkv
from ray_tpu.models.serving import prefill_core
from ray_tpu.ops import attention, paged_kv
from ray_tpu.serve.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-4
F32 = {"params": "float32", "activations": "float32"}
MATMULS = ("wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down")
NORMS = ("q_norm", "k_norm", "o_norm", "attn_norm", "mlp_norm")
STACKS = ("sparse", "linear")
# Pooled keys of 8 positions every 4, blocks (and pages) of 16, 4 of them a
# query (the first and the 2 of the window of 32 among them), whole contexts
# under 64. A sparse layer, three linear ones, a sparse one.
SIZES = {"kernel_size": 8, "kernel_stride": 4, "block_size": 16, "topk": 4,
         "init_blocks": 1, "window_size": 32, "dense_len": 64}
PAGE = 16
KINDS = {"S": "minicpm4", "L": "lightning-attn"}


def published():
    """The catalog's keys as the configuration file has them."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala-serve.json")) as f:
        return json.load(f)


def _tiny(max_seq=256, **more):
    """(adapter, model, cfg, params) at the adapter's rehearsal widths and
    the sizes above, with weights that decide (at the init's 0.02 every logit
    is a near-tie): matmuls x 8, the embedding spread, and norms that are not
    all ones."""
    adapter = models.adapter("minicpm_sala")
    model = dict(published(), **adapter.REHEARSE)
    # (a stack PUBLISHED with 8 layers, of which the first 5 are run: a
    # second sparse layer at place 4 makes the sparse stack two runs, and at
    # a depth of 8 a layer's factor on its decay, 1 - place / 7, shows)
    kinds = [KINDS[k] for k in "SLLLSLLS"]
    model.update(sparse_config=SIZES, num_hidden_layers=5, mixer_types=kinds,
                 **more)
    cfg = adapter.build_config(model, F32, max_seq)
    params = dict(adapter.init_params(cfg, 3))
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
    for stack in STACKS:
        params[stack] = {
            k: w * 8.0 if k in MATMULS
            else w * (1.0 + 0.3 * jax.random.normal(next(keys), w.shape))
            if k in NORMS else w
            for k, w in params[stack].items()}
    params["embed"] = params["embed"] * 50.0
    params["lm_head"] = params["lm_head"] * 32.0
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


def _engine(cfg, params, *patches):
    """An engine with the sparse layers' decode kernel interpreted;
    `patches`: (module, name, what stands there while it is built)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(paged_kv, "paged_decode_attention", functools.partial(
        paged_kv.paged_decode_attention, interpret=True))
    for module, name, fn in patches:
        mp.setattr(module, name, fn)
    try:
        return Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                      decode_chunk=4, page_size=PAGE)
    finally:
        mp.undo()


# ---------------------------------------------------------------------------
# The configuration of the program
# ---------------------------------------------------------------------------

def test_the_kinds_run_in_no_period_each_a_stack_of_its_own(tiny):
    _, _, cfg, params = tiny
    assert cfg.sala and not cfg.rope
    assert cfg.mixer_types == ("minicpm4",) + ("lightning-attn",) * 3 \
        + ("minicpm4",)
    # the sparse layers are ONE stack in two runs, the linear ones between
    assert cfg.segments() == (("sparse", 0, 1), ("linear", 0, 3),
                              ("sparse", 1, 2))
    assert (cfg.kv_layers, cfg.state_layers, cfg.published_layers) == (2, 3, 8)
    assert cfg.block_sparse == (8, 4, 16, 4, 1, 32, 64)
    assert cfg.block_sparse.window_blocks == 2
    assert cfg.embed_scale == 12.0 and cfg.logit_scale == 16 / 64
    assert cfg.residual_scale == pytest.approx(1.4 / 8 ** 0.5)
    # a layer's decay is its place's in the PUBLISHED stack: layers 1, 2, 3
    rates = cfg.linear_rates()
    assert rates.shape == (3, 4)
    for row, place in zip(rates, (1, 2, 3)):
        assert np.allclose(row, 2.0 ** (-8 * np.arange(1, 5) / 4)
                           * (1 - place / 7 + 1e-5))
    shapes = jax.tree.map(lambda x: x.shape, params)
    assert shapes["sparse"]["wq"] == shapes["sparse"]["wg"] == (2, 64, 64)
    assert shapes["sparse"]["wk"] == shapes["sparse"]["wv"] == (2, 64, 32)
    assert shapes["linear"]["wk"] == shapes["linear"]["wg"] == (3, 64, 64)
    assert shapes["linear"]["q_norm"] == shapes["linear"]["k_norm"] == (3, 16)
    assert shapes["linear"]["o_norm"] == (3, 64)
    assert not {"q_norm", "k_norm", "o_norm"} & set(shapes["sparse"])
    assert all(shapes[s]["w_gate"] == (n, 64, 128)
               for s, n in (("sparse", 2), ("linear", 3)))
    for stack in STACKS:
        assert set(llama.logical_axes(cfg)[stack]) == set(params[stack])
    # serving's layout (the gate's logits further columns) and back
    fused = fuse_qkv(params, cfg)
    assert fused["sparse"]["wqkv"].shape == (2, 64, 64 + 2 * 32 + 64)
    assert fused["linear"]["wqkv"].shape == (3, 64, 4 * 64)
    back = split_qkv(fused, cfg)
    for stack in STACKS:
        for k in ("wq", "wk", "wv", "wg"):
            assert (np.asarray(back[stack][k])
                    == np.asarray(params[stack][k])).all()


@pytest.mark.parametrize("change,said", [
    (dict(mixer_types=("minicpm4", "mamba")), "mixer_types"),
    (dict(mixer_types=("minicpm4",)), "mixer_types"),
    (dict(rope=True), "no rotation"),
    (dict(sparse_kernel=12), "two sparse_stride"),
    (dict(sparse_window=40), "divides sparse_window"),
    (dict(sparse_topk=2), "sparse_topk"),
    (dict(published_layers=1), "published_layers"),
    (dict(n_experts=4), "dense feed-forward"),
    (dict(qk_norm="head"), "q/k norm in the sparse layers"),
    (dict(tie_embeddings=True), "untied head"),
    (dict(index_topk=8, index_heads=2, index_head_dim=8), "indexer"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_what_does_not_combine_is_refused_by_name(change, said):
    base = dict(n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, rope=False,
                mixer_types=("minicpm4", "lightning-attn"), sparse_kernel=8,
                sparse_stride=4, sparse_block=16, sparse_topk=4,
                sparse_window=32, dense_len=64, max_seq=128)
    llama.LlamaConfig.tiny(**base)
    with pytest.raises(ValueError, match=said):
        llama.LlamaConfig.tiny(**dict(base, **change))


def test_the_adapter_refuses_what_the_block_does_not_compute():
    adapter = models.adapter("minicpm_sala")
    model = dict(published(), **adapter.REHEARSE)
    adapter.check_supported(model)
    for change, said in ((dict(attn_use_rope=True), "no rotation"),
                         (dict(lightning_nkv=2), "lightning_nkv"),
                         (dict(tie_word_embeddings=True), "tied"),
                         (dict(mixer_types=["minicpm4"]), "mixer_types"),
                         (dict(lightning_scale="1"), "lightning_scale"),
                         (dict(scale_depth=None), "scale_depth")):
        with pytest.raises(ValueError, match=said):
            adapter.check_supported(dict(model, **change))


def test_the_page_is_the_block_and_the_rungs_take_whole_tiles(tiny,
                                                             monkeypatch):
    """A selected block IS a page of the slot's table: another page size is
    refused when the caches are made. The rehearsal's heads of 16 are no
    kernel's: off the chip the `jnp` path is the path; on one (the test says
    so) `Engine` refuses the model by the shape. The published widths pass at
    every rung of the cell's ladder."""
    adapter, _, cfg, params = tiny
    with pytest.raises(ValueError, match="IS a page"):
        serving.build_programs(cfg, 2, 4, 32, 20).empty()
    assert serving.rung_refusal(cfg, 64) is None        # under one block
    assert "stack `linear`" in serving.rung_refusal(cfg, 128)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="attention in XLA.*128 rows"):
        Engine(params, cfg, n_slots=2, decode_chunk=4, page_size=PAGE)
    model = published()
    real = adapter.build_config(model, model["dtypes"], 12288)
    from ray_tpu.serve.engine import prefill_widths
    assert prefill_widths(12288)[-3:] == [8192, 10240, 12288]
    assert all(serving.rung_refusal(real, w) is None
               for w in prefill_widths(12288))


# ---------------------------------------------------------------------------
# The configuration file
# ---------------------------------------------------------------------------

def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone():
    model = published()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert model["source_url"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if model.get(k) != v}
    assert differs == set(model["reduced"]) == {"num_hidden_layers"}
    assert model["reduced"]["num_hidden_layers"]["published"] \
        == row["config"]["num_hidden_layers"] == len(model["mixer_types"])
    assert model["num_hidden_layers"] == 4
    held = model["mixer_types"][:4]
    assert held == ["minicpm4"] + ["lightning-attn"] * 3
    # the published stack is in no period: sparse at these places
    assert [i for i, k in enumerate(model["mixer_types"])
            if k == "minicpm4"] == [0, 9, 16, 17, 22, 29, 30, 31]
    eng = model["deployment"]["engine"]
    assert eng["page_size"] == model["sparse_config"]["block_size"]
    assert eng["kv_pages"] == eng["n_slots"] * eng["max_seq"] // 64 + 1
    assert len(model["assumed"]) >= 12


def test_the_tree_at_published_widths_counts_what_the_issue_reckoned():
    """Abstract shapes only: the program's tree at the configuration's
    widths, the adapter's count and the issue's arithmetic agree to the
    parameter: 253.8 M a sparse layer, 285.2 M a linear one, 601.7 M of
    embedding and head; the catalog's "about 273 M a layer" is 9.48 B less
    the embeddings over 32 layers of both kinds, (8 x 253.8 + 24 x 285.2) /
    32 = 277 M, a quarter of them the cheaper kind."""
    adapter = models.adapter("minicpm_sala")
    model = published()
    cfg = adapter.build_config(model, model["dtypes"], 12288)
    counts = adapter.counts
    assert llama.param_count(cfg) == counts.total_params(model) \
        == 1_711_129_344
    assert counts.sparse_layer_matmul_params(model) == 253_755_392
    assert counts.linear_layer_matmul_params(model) == 285_212_672
    assert 2 * counts.head_params(model) == 601_686_016
    whole = dict(model, num_hidden_layers=32)
    assert counts.mixer_layers(whole) == (8, 24)
    assert round(counts.total_params(whole) / 1e9, 2) == 9.48
    # a slot at 12,288 positions: K and V of one layer, pooled keys, state
    caches = jax.eval_shape(serving.build_programs(cfg, 32, 8, 64, 6145).empty)
    kc, vc, (pooled, sums), (state,) = caches
    per_slot = 2 * 2 * 192 * 64 * 128 * 2 + pooled.size * 2 // 32 \
        + state.size * 4 // 32
    assert state.size * 4 // 32 == 3 * counts.linear_state_bytes(model) \
        == 6_291_456
    assert round(per_slot / 1e6, 1) == 19.3


# ---------------------------------------------------------------------------
# Through the programs and the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bucket", [(40, 64), (64, 64), (100, 128),
                                      (200, 256), (256, 256)],
                         ids=["dense", "the-row-that-reaches-dense-len",
                              "past-it", "thirteen-blocks", "a-full-bucket"])
def test_a_prompts_logits_are_the_references(tiny, n, bucket):
    """Prompts under `dense_len` (64: the flash path in a 64-row bucket whose
    LAST row alone would select), at it and past it (the pooled keys, the
    selection of 4 of up to 16 blocks, the mask by blocks), the linear layers
    in one chunk: the last position's logits are the reference's, and what
    the caches keep has the shapes the slot's stores take."""
    adapter, model, cfg, params = tiny
    prompt = _tokens(n, n)
    ref = adapter.reference()
    _, ks, vs, logits, _, (pooled, sums), (state, _) = jax.jit(
        prefill_core(cfg))(
        fuse_qkv(params, cfg),
        jnp.asarray([prompt + [0] * (bucket - n)], jnp.int32), n)
    want = np.asarray(ref.logits_last(params, model, prompt, 1))[0]
    assert np.abs(want).max() > 1.0
    assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
    assert ks.shape == vs.shape == (2 * 2, bucket, 1, 16)   # (layer, kv head)
    assert pooled.shape == (2, bucket // 4, 32) and sums.shape == (2, 2, 32)
    assert state.shape == (3, 4, 16, 16) and state.dtype == jnp.float32


@pytest.fixture(scope="module")
def engine(tiny):
    _, _, cfg, params = tiny
    eng = _engine(cfg, params)
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def served(tiny, engine):
    """A prompt of ten blocks and the 40 tokens the engine serves after it."""
    prompt = _tokens(150, 150)
    return prompt, _drain(engine.submit(prompt, 40))


@pytest.mark.timeout(300)
@pytest.mark.parametrize("n", [50, 20, 120],
                         ids=["crosses-dense-len", "a-shorter-one-after",
                              "selects-from-the-first-step"])
def test_prefill_then_decode_through_pages_pooled_keys_and_state(
        tiny, engine, n):
    """A stream that starts under `dense_len` and crosses it at its 14th
    token (50 + 14 = 64: every live page until then, the selected ones from
    there, switched by `pos` on the device), a shorter one into the slot the
    longer one left (its pooled keys and state overwritten or finished anew
    before anything reads them), and one that selects from its first step:
    40 tokens each through the pages (pages of 16, a kv head a layer of the
    arena, the kernel interpreted), the pooled keys finished every 4th step
    from the running sums, and the state updated in place: every served
    token is the reference's largest logit to float32 rounding."""
    adapter, model, cfg, params = tiny
    prompt = _tokens(n, n)
    got = _drain(engine.submit(prompt, 40))
    assert len(got) == 40
    gaps = adapter.reference().served_token_gaps(params, model, prompt, got)
    assert max(gaps) < LOGIT_TOL, gaps


@pytest.mark.timeout(300)
def test_ten_blocks_then_decode_is_the_reference_and_counts_its_blocks(
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
    _drain(engine.submit(_tokens(20, 1), 8))    # steps under dense_len too
    c = engine.counters()
    # 4 of 10-12 blocks a kv head a step past dense_len, all of them under it
    assert 0 < c["decode_blocks_selected"] < c["decode_blocks_visible"]
    assert c["decode_dense_rows"] > 0
    assert c["state_bytes_moved"] > 0 and c["state_writes"] >= 1
    counts = attention.attention_path_counts()
    assert counts["decode_pallas"] >= 1             # interpreted, in decode
    assert counts["linear_reference"] >= 1 \
        and counts["block_sparse_reference"] >= 1
    # pages: the 2 sparse layers' 2 kv heads, each a layer of the arena; the
    # pooled keys and their running sums; the 3 linear layers' state
    kc, vc, (pooled, sums), (state,) = engine._caches
    assert kc.shape == vc.shape == (4, engine.n_pages, 1, PAGE, 16)
    assert pooled.shape == (2, 2, 256 // 4, 32) and sums.shape == (2, 2, 2, 32)
    assert state.shape == (3, 2, 4, 16, 16) and state.dtype == jnp.float32
    assert c["linear_state_bytes"] == state.nbytes
    assert c["pooled_key_bytes"] == pooled.nbytes + sums.nbytes
    assert not engine._programs.takes_riders and not engine._programs.adopts


@pytest.mark.timeout(300)
@pytest.mark.parametrize("wrong", [
    ("linear_decay", False), ("linear_factor", False), ("sparse_rope", True),
    ("sparse_per_head", True), ("sparse_window", False),
    ("sparse_init", False), ("sparse_gate", False), ("linear_gate", False),
    ("linear_norm", False)],
    ids=["no-decay", "no-layer-factor", "rope-on-the-sparse-layer",
         "a-selection-a-head", "window-not-forced", "init-block-not-forced",
         "no-sparse-gate", "no-linear-gate", "no-output-norm"])
def test_a_model_computed_wrongly_reads_gaps_far_over_the_tolerance(
        tiny, served, wrong):
    """What the program served, held against the reference computing each of
    the model's mechanisms WRONGLY in turn: every one reads gaps a hundred
    tolerances and more, so the comparison that passes above sees each."""
    adapter, model, cfg, params = tiny
    prompt, got = served
    gaps = adapter.reference().served_token_gaps(params, model, prompt, got,
                                                 (wrong,))
    assert max(gaps) > 100 * LOGIT_TOL, (wrong, max(gaps))


def test_a_bfloat16_state_is_another_model(tiny):
    """A linear layer's 40 decode steps from a prompt's state, the state kept
    in float32 and kept in bfloat16 (every step's sum rounded to 8 bits as it
    is written back): the first is the recurrence's to the tolerance, the
    second leaves it by eight tolerances and more (2^-9 of every sum, under
    the output's norm: the served tokens' argmax may well survive that, so
    it is the layer's output that is compared, and the wrong MODELS above
    that read a hundred)."""
    from ray_tpu.models import block
    from ray_tpu.ops import linear_attention
    _, _, cfg, params = tiny
    lp = {k: v[0] for k, v in fuse_qkv(params, cfg)["linear"].items()}
    rates = jnp.asarray(cfg.linear_rates()[0])
    xs = jax.random.normal(jax.random.PRNGKey(9), (40, 2, cfg.d_model))
    active = jnp.array([True, True])
    start = jax.random.normal(jax.random.PRNGKey(10), (1, 2, 4, 16, 16))

    def run(dtype):
        state, outs = (start.astype(dtype),), []
        for x in xs:
            y, state = block.linear_mixer(
                lp, x, cfg, lambda t: t, rates, state, step=True, layer=0,
                active=active)
            outs.append(y - x)
        return jnp.stack(outs)

    exact, rounded = run(jnp.float32), run(jnp.bfloat16)
    # the recurrence itself, from the same start
    h = jax.vmap(lambda x: block.rms_norm(x, lp["attn_norm"], cfg.norm_eps))(xs)
    parts = block._sala_parts(lp, h, cfg, "linear")
    q, k, v = (parts[n].reshape(40, 2, 4, 16) for n in ("wq", "wk", "wv"))
    q = block.rms_norm(q, lp["q_norm"], cfg.norm_eps)
    k = block.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    o = jnp.stack([linear_attention.linear_recurrence(
        q[:, s].transpose(1, 0, 2), k[:, s].transpose(1, 0, 2),
        v[:, s].transpose(1, 0, 2), rates, 0.25, start[0, s])[0]
        for s in range(2)], axis=2)                       # [H, 40, ns, d]
    o = o.transpose(1, 2, 0, 3).reshape(40, 2, 64)
    want = cfg.residual_scale * (block.output_gated(
        o, parts["wg"], lp["o_norm"], cfg.norm_eps) @ lp["wo"])
    assert np.abs(np.asarray(want)).max() > 1.0
    assert np.abs(np.asarray(exact - want)).max() < LOGIT_TOL
    assert np.abs(np.asarray(rounded - want)).max() > 8 * LOGIT_TOL


def test_a_pd_handoff_and_the_training_forward_refuse_the_stack_by_name(
        tiny, engine):
    _, _, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="mixer_types"):
        engine.submit_prefilled(None, None, 4, 1, 4)
    with pytest.raises(NotImplementedError, match="mixer_types"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="serves only"):
        reference_minicpm_sala.loss_and_check_grads(params, {}, None)

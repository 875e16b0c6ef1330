"""Granite-4.0-H-Small's stack at tiny widths on the CPU, seeded weights,
against its plain reference (benchmark/reference_granite.py: float32,
precision "highest", the recurrence row by row): Mamba-2 by the chunked dual
form (`ops/ssm.py::ssd_scan`, `ssd_step`), the mixer and the attention block
with the family's four multipliers, a SHARE of the experts beside a shared
expert in a hybrid stack, and the whole of it through `Engine`.

(a) the scan alone; (b) steps and split prompts; (c) the mixer, the attention
block and the multipliers; (d) the share; (e) the engine
(tests/test_granite_engine.py); (f) the adapter and the counts; (g) the path
counters.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from benchmark import reference_granite as ref
from ray_tpu.models import block, llama, serving
from ray_tpu.models.block import fuse_qkv, mamba2_mixer
from ray_tpu.ops import attention, slot_state, ssm
import mixer_riders

# Float32 everywhere on the CPU: what is left between the program and the
# reference is the order of float32 sums (a chunk's matrix products against
# the row-by-row recurrence; a token's experts summed in routing order
# against expert order), on logits that the family divides by 16 (0.05 at the
# largest here): 1e-7 measured, 2e-6 allowed. A state kept in bfloat16 reads
# 1e-4 to 3e-4 (test (e)).
LOGIT_TOL = 2e-6
SCAN_TOL = 2e-5

ADAPTER = models.adapter("granitemoehybrid")
MODEL = dict(ADAPTER.REHEARSE, rms_norm_eps=1e-5, embedding_multiplier=12,
             residual_multiplier=0.22, logits_scaling=16,
             tie_word_embeddings=True)
F32 = {"params": "float32", "activations": "float32"}
FILE = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                    "granite-4.0-h-small-serve.json")


def _params(cfg, seed=3):
    """Seeded weights with every norm off one, a convolution bias and a D
    that matter, matrices large enough that every branch moves the logits,
    and an embedding whose logits spread."""
    params = ADAPTER.init_params(cfg, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def jitter(stack, names):
        out = dict(stack)
        for name in names:
            out[name] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                      stack[name].shape)
        return out

    lay = jitter(params["layers"], ("attn_norm", "mlp_norm"))
    mam = jitter(params["mamba"], ("norm", "mlp_norm", "w_norm", "D"))
    experts = ("router", "w_gate", "w_up", "w_down", "ws_gate", "ws_up",
               "ws_down")
    for name in ("wq", "wk", "wv", "wo") + experts:
        lay[name] = lay[name] * 8.0
    for name in ("in_proj", "out_proj") + experts:
        mam[name] = mam[name] * 8.0
    mam["conv_b"] = 0.3 * jax.random.normal(next(keys), mam["conv_b"].shape)
    return dict(params, layers=lay, mamba=mam, embed=params["embed"] * 12.0,
                final_norm=1.0 + 0.2 * jax.random.normal(
                    next(keys), params["final_norm"].shape))


@pytest.fixture(scope="module")
def tiny():
    cfg = ADAPTER.build_config(MODEL, F32, 256)
    assert (cfg.attn_layers, cfg.kv_layers, cfg.ssm_state, cfg.ssm_heads,
            cfg.ssm_inner, cfg.ssm_conv_channels, cfg.n_experts,
            cfg.experts_held, cfg.n_shared_experts, cfg.rope,
            cfg.tie_embeddings) \
        == ((1,), 1, 16, 8, 256, 288, 8, (0, 4), 2, False, True)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logit_scale,
            cfg.softmax_scale) == (12.0, 0.22, 1 / 16, 0.03125)
    assert cfg.segments() == (("mamba", 0, 1), ("attn", 0, 1),
                              ("mamba", 1, 3))
    return cfg, _params(cfg)


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


def _err(got, want):
    """The largest difference, relative to the values' own scale where that
    is over one (a state sums hundreds of rows' inputs)."""
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / max(1.0, np.abs(want).max())


def _layer_f32(params, name, i):
    return {k: v if k in ref._EXPERTS else v[i].astype(jnp.float32)
            for k, v in params[name].items()}


# -- (a) the scan alone ------------------------------------------------------

def _scan_inputs(S=80, Di=256, N=16, H=8, seed=0):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    x = jax.random.normal(next(k), (S, Di))
    dt = jax.nn.softplus(jax.random.normal(next(k), (S, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(next(k), (H,)) * 0.5)
    B = jax.random.normal(next(k), (S, N))
    C = jax.random.normal(next(k), (S, N))
    D = jax.random.normal(next(k), (H,))
    s0 = jax.random.normal(next(k), (N, Di))
    return x, dt, A, B, C, D, s0


def _row_by_row(x, dt, A, B, C, D, s0, length):
    """The published recurrence a head, a row at a time, in numpy float64."""
    x, dt, A, B, C, D = (np.asarray(a, np.float64)
                         for a in (x, dt, A, B, C, D))
    S, Di = x.shape
    H = dt.shape[1]
    P = Di // H
    s = np.zeros((H, P, B.shape[1])) if s0 is None \
        else np.asarray(s0, np.float64).T.reshape(H, P, -1)
    ys = []
    for t in range(S if length is None else length):
        xt = x[t].reshape(H, P)
        s = np.exp(dt[t] * A)[:, None, None] * s \
            + (dt[t][:, None] * xt)[:, :, None] * B[t][None, None]
        ys.append((s @ C[t] + D[:, None] * xt).reshape(Di))
    return np.stack(ys), s.reshape(Di, -1).T


@pytest.mark.parametrize("start", [False, True], ids=["fresh", "carried"])
@pytest.mark.parametrize("length", [None, 64, 50, 7],
                         ids=["inside-a-chunk", "at-an-edge", "past-length",
                              "first-chunk"])
def test_ssd_scan_is_the_row_by_row_recurrence_and_the_broadcast_scan(
        length, start):
    """80 rows in chunks of 32: the prompt ends inside the third chunk, at
    the second's edge (64), and `length` rows in with a bucket's padding
    behind it (50; 7: inside the first chunk), which must not enter the
    state. Equal to the recurrence a row at a time, and to Mamba-1's
    `selective_scan` fed A and dt broadcast over a head's channels."""
    x, dt, A, B, C, D, s0 = _scan_inputs()
    s0 = s0 if start else None
    y, state = jax.jit(lambda: ssm.ssd_scan(x, dt, A, B, C, D, s0, length,
                                            chunk=32))()
    want_y, want_s = _row_by_row(x, dt, A, B, C, D, s0, length)
    n = len(want_y)
    assert np.abs(np.asarray(y)[:n] - want_y).max() < SCAN_TOL
    assert np.abs(np.asarray(state) - want_s).max() < SCAN_TOL
    assert np.isfinite(np.asarray(y)).all()
    rep = lambda v: jnp.repeat(v, 256 // 8, axis=-1)
    y1, s1 = ssm.selective_scan(
        x, rep(dt), jnp.broadcast_to(rep(A)[None], (16, 256)), B, C, rep(D),
        s0, length)
    assert np.abs(np.asarray(y)[:n] - np.asarray(y1)[:n]).max() < SCAN_TOL
    assert np.abs(np.asarray(state) - np.asarray(s1)).max() < SCAN_TOL


def test_the_chunk_is_an_implementations_size():
    x, dt, A, B, C, D, s0 = _scan_inputs()
    outs = [ssm.ssd_scan(x, dt, A, B, C, D, s0, 70, chunk=q)
            for q in (16, 80, 256)]
    for y, s in outs[1:]:
        assert np.abs(np.asarray(y)[:70]
                      - np.asarray(outs[0][0])[:70]).max() < SCAN_TOL
        assert np.abs(np.asarray(s) - np.asarray(outs[0][1])).max() < SCAN_TOL


# -- (b) steps and split prompts ---------------------------------------------

def _kernel_step(x, dt, A, B, C, D, state):
    """`ssd_step`'s signature over `slot_state.step_layer`'s kernel path,
    interpreted: the slots' rows as the one layer of a whole state."""
    before = attention.attention_path_counts().get("ssd_step_pallas", 0)
    y, (ssm_all, _) = slot_state.step_layer(
        (state[None], None), jnp.int32(0), jnp.ones(len(x), bool), x, dt, A,
        B, C, D, interpret=True)
    assert attention.attention_path_counts()["ssd_step_pallas"] == before + 1
    return y, ssm_all[0]


@pytest.mark.parametrize("step", [ssm.ssd_step, _kernel_step],
                         ids=["reference", "kernel"])
def test_one_token_steps_are_the_scan(step):
    """`ssd_step` a row at a time, two slots at once, is `ssd_scan` over the
    two sequences; and so is the step kernel, on the state where it lies."""
    a, b = _scan_inputs(S=40, seed=1), _scan_inputs(S=40, seed=2)
    A, D = a[2], a[5]
    state = jnp.stack([a[6], b[6]])
    ys = []
    for t in range(40):
        y, state = step(
            *(jnp.stack([a[i][t], b[i][t]]) for i in (0, 1)), A,
            *(jnp.stack([a[i][t], b[i][t]]) for i in (3, 4)), D, state)
        ys.append(y)
    ys = np.asarray(jnp.stack(ys, axis=1))
    for slot, seq in enumerate((a, b)):
        y, s = ssm.ssd_scan(seq[0], seq[1], A, seq[3], seq[4], D, seq[6],
                            chunk=16)
        assert np.abs(ys[slot] - np.asarray(y)).max() < SCAN_TOL
        assert np.abs(np.asarray(state[slot]) - np.asarray(s)).max() \
            < SCAN_TOL


@pytest.mark.parametrize("blocks", [1, 2], ids=["one_block", "two_blocks"])
@pytest.mark.parametrize("layer", [0, 2], ids=["first_layer", "last_layer"])
@pytest.mark.parametrize("active", [(1, 1, 1, 1), (0, 1, 1, 0), (0, 0, 0, 0)],
                         ids=["all_active", "some_idle", "none_active"])
def test_the_step_kernel_is_ssd_step_and_update_layer_in_one_visit(
        active, layer, blocks):
    """`ssd_state_step` (interpreted) on a state of three layers and four
    slots against `ssd_step` on the layer's rows and `update_layer`: y and
    the active slots' new state to float32 rounding (the kernel sums the N
    states in another order); every idle slot's state and every OTHER
    layer's bit for bit; an idle slot's row of y zeros."""
    L, ns, N, Di, H = 3, 4, 16, 256, 8
    x, dt, A, B, C, D, _ = _scan_inputs(S=ns, Di=Di, N=N, H=H, seed=5)
    ssm0 = jax.random.normal(jax.random.PRNGKey(6), (L, ns, N, Di))
    act = jnp.array(active, bool)
    want_y, rows = ssm.ssd_step(x, dt, A, B, C, D, ssm0[layer])
    want, _ = slot_state.update_layer((ssm0, None), layer, act, rows, None)
    y, got = ssm.ssd_state_step(ssm0, jnp.int32(layer), act, x, dt, A, B, C,
                                D, interpret=True,
                                block_channels=Di // blocks)
    y, got, want, idle = (np.asarray(a) for a in (y, got, want, ~act))
    assert got.shape == ssm0.shape and got.dtype == np.float32
    if not idle.all():
        assert _err(y[~idle], np.asarray(want_y)[~idle]) < 1e-6
    assert _err(got, want) < 1e-6
    assert (y[idle] == 0).all()
    assert (got[:, idle] == np.asarray(ssm0)[:, idle]).all()
    others = [l for l in range(L) if l != layer]
    assert (got[others] == np.asarray(ssm0)[others]).all()


@pytest.mark.parametrize("cut", [1, 17, 32, 47])
def test_a_split_prompt_is_the_unsplit_one(tiny, cut):
    """The mixer over 48 rows, and over the first `cut` then the rest from
    the carried state and window (a bucket's padding behind each part), and
    the last rows one token a slot: the same outputs, state and window."""
    cfg, params = tiny
    lp = jax.tree.map(lambda w: w[1], params["mamba"])
    x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model))
    whole, state, window = mamba2_mixer(lp, x, cfg, length=48)
    assert window.shape == (3, 288) and state.shape == (16, 256)
    head = jnp.concatenate([x[:cut], jnp.full((9, cfg.d_model), 7.0)])
    first, s1, w1 = mamba2_mixer(lp, head, cfg, length=cut)
    rest, s2, w2 = mamba2_mixer(lp, x[cut:], cfg, s1, w1, length=48 - cut)
    got = np.concatenate([np.asarray(first)[:cut],
                          np.asarray(rest)[:48 - cut]])
    assert _err(got, np.asarray(whole)[:48]) < SCAN_TOL
    assert _err(s2, state) < SCAN_TOL
    assert _err(w2, window) < SCAN_TOL
    # ... and the rows after the cut a token at a time, as a decode step has
    # them: the step is handed the slots' whole state (`ops/slot_state.py`'s
    # pair, here one layer of one slot) and the layer's window of that slot
    s, w = (s1[None, None], None), w1[:, None]
    for t in range(cut, 48):
        y, s, w = mamba2_mixer(lp, x[t][None], cfg, s, w, step=True,
                               layer=0, active=jnp.ones(1, bool))
        assert _err(y[0], whole[t]) < SCAN_TOL
    assert _err(s[0][0, 0], state) < SCAN_TOL


def test_riders_in_a_prompts_tail_rows_take_a_step_and_leave_the_prompt_alone(
        tiny):
    """`mamba2_mixer(riders=)`: tests/mixer_riders.py says what is held.
    The step alone is `step=True` on the slots' whole state and the window's
    write back, as `models/serving.py::_mamba_kind`'s decode body has it."""
    cfg, params = tiny
    lp = jax.tree.map(lambda w: w[1], params["mamba"])

    def step(x, slots, layer, active):
        out, slots, window = mamba2_mixer(
            lp, x, cfg, slots, slot_state.layer_state(slots, layer)[1],
            step=True, layer=layer, active=active)
        return out, slot_state.update_layer(slots, layer, active, None,
                                            window)

    mixer_riders.check(mamba2_mixer, lp, cfg, step, SCAN_TOL)


# -- (c) the mixer, the attention block, the multipliers ---------------------

def test_the_mixer_is_the_references(tiny):
    cfg, params = tiny
    x = jax.random.normal(jax.random.PRNGKey(5), (40, cfg.d_model))
    for i in range(3):
        lp = jax.tree.map(lambda w: w[i], params["mamba"])
        got, _, _ = mamba2_mixer(lp, x, cfg)
        with jax.default_matmul_precision("highest"):
            want = ref.mixer_half(x, _layer_f32(params, "mamba", i), MODEL,
                                  True)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < SCAN_TOL


def _attention_block(cfg, params, x):
    """The program's attention layer over x [S, D], as the prefill walk runs
    it: serving's layout, the experts' stacks whole."""
    stack = serving._stack(cfg)
    sliced, whole = block.expert_stacks(fuse_qkv(params, cfg)["layers"], cfg)
    lp = dict(jax.tree.map(lambda w: w[0], sliced), **whole)
    S = x.shape[0]
    ctx = dict(stack.tables(S, True), live=jnp.ones((1, S), bool), length=S,
               riders=None)
    y, _, _, counts = stack.kinds["attn"].prefill(lp, x[None], None, 0, ctx)
    return y[0], counts


def test_the_attention_block_is_the_references_and_takes_no_position(tiny):
    """Scale 1/32 here (the published model's 1/128: `attention_multiplier`,
    not head_dim^-1/2 = 0.177), the residual's 0.22, the share's feed-forward
    beside the shared expert; and no position signal: the last row's output
    is the same whatever the order of the rows before it, so in the model a
    permutation of earlier tokens reaches it only through the Mamba layers."""
    cfg, params = tiny
    x = jax.random.normal(jax.random.PRNGKey(6), (24, cfg.d_model))
    got, counts = _attention_block(cfg, params, x)
    with jax.default_matmul_precision("highest"):
        want = ref._layer(x, _layer_f32(params, "layers", 0), MODEL, False,
                          layer=0)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < SCAN_TOL
    # a share's counts: per held expert, the distinct ones, the routed
    assert counts.shape == (4 + 2,) and int(counts[-1]) == 24 * 3
    assert int(counts[:4].sum()) <= 24 * 3
    order = np.random.default_rng(0).permutation(23)
    moved, _ = _attention_block(cfg, params,
                                jnp.concatenate([x[order], x[23:]]))
    assert np.abs(np.asarray(moved[-1]) - np.asarray(got[-1])).max() < 1e-5
    wrong = dataclasses.replace(cfg, attn_scale=0.0)    # head_dim^-1/2
    other, _ = _attention_block(wrong, params, x)
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 100 * SCAN_TOL
    # ... and through the whole model the order of earlier tokens matters
    core = jax.jit(serving.prefill_core(cfg))
    toks = _tokens(24, 8)
    a = core(fuse_qkv(params, cfg), jnp.asarray([toks], jnp.int32), 24)[3]
    b = core(fuse_qkv(params, cfg), jnp.asarray(
        [[toks[i] for i in order] + toks[23:]], jnp.int32), 24)[3]
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 100 * LOGIT_TOL


@pytest.mark.parametrize("field,neutral", [
    (None, None), ("embed_scale", 1.0), ("residual_scale", 1.0),
    ("logit_scale", 1.0), ("attn_scale", 0.0)])
def test_the_four_multipliers_are_the_references_and_each_is_needed(
        tiny, field, neutral):
    """The prefill program's logits against the reference's full forward;
    with any one multiplier left at its neutral value they are another
    model's."""
    cfg, params = tiny
    prompt = _tokens(50, 9)
    run = cfg if field is None else dataclasses.replace(cfg,
                                                        **{field: neutral})
    logits = jax.jit(serving.prefill_core(run))(
        fuse_qkv(params, run), jnp.asarray([prompt + [9] * 14], jnp.int32),
        50)[3]
    want = np.asarray(ref.logits_last(params, MODEL, prompt, 1))[0]
    err = np.abs(np.asarray(logits) - want).max()
    assert err < LOGIT_TOL if field is None else err > 100 * LOGIT_TOL, err


# -- (d) the share -----------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The routed parts of shares (0, 4) and (4, 4) of 8 experts, with the
    shared expert counted once, are the uncut reference's whole layer: in the
    program (`block.feed_forward` under `experts_held`) and in the reference
    handed the same shares."""
    cfg, _ = tiny
    whole_cfg = dataclasses.replace(cfg, experts_held=None)
    params = _params(whole_cfg)
    assert params["mamba"]["w_gate"].shape[1] == 8
    lp = jax.tree.map(lambda w: w[0], params["mamba"])
    x = jax.random.normal(jax.random.PRNGKey(7), (40, cfg.d_model))
    uncut = dict(MODEL, num_local_experts=8)
    del uncut["expert_parallel"]
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        whole = ref.feed_forward_half(x, f32, uncut) - x
        u = ref._rms_norm(x, f32["mlp_norm"], 1e-5)
        shared = 0.22 * ref.shared_part(u, f32)
    total = -shared
    for rank, held in enumerate(((0, 4), (4, 4))):
        part = {k: v[held[0]:held[0] + 4] if k in ref._EXPERTS else v
                for k, v in lp.items()}
        got, (_, counts) = block.feed_forward(
            part, x, dataclasses.replace(cfg, experts_held=held))
        assert counts.shape == (4,)
        m = dict(MODEL, expert_parallel=dict(MODEL["expert_parallel"],
                                             rank=rank))
        with jax.default_matmul_precision("highest"):
            want = ref.feed_forward_half(
                x, jax.tree.map(lambda w: w.astype(jnp.float32), part), m)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < SCAN_TOL
        total = total + (got - x)
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < SCAN_TOL
    assert np.abs(np.asarray(whole)).max() > 0.1


# -- (f) the adapter and the counts ------------------------------------------

@pytest.mark.parametrize("change,said", [
    (dict(mamba_n_groups=8), "mamba_n_groups"),
    (dict(mamba_proj_bias=True), "projection bias"),
    (dict(attention_bias=True), "projection bias"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
    (dict(tie_word_embeddings=False), "untied head"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(layer_types=["mamba"] * 4), "both kinds"),
    (dict(mamba_d_head=16), "mamba_expand"),
    (dict(shared_intermediate_size=100), "shared_intermediate_size"),
    (dict(expert_parallel={"chips": 3, "rank": 0,
                           "routed_experts_total": 8}), "expert_parallel"),
])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    ADAPTER.check_supported(MODEL)
    with pytest.raises(ValueError, match=said):
        ADAPTER.check_supported(dict(MODEL, **change))


@pytest.mark.parametrize("kw,said", [
    (dict(ssm_heads=8), "ssm_heads"),
    (dict(ssm_state=16, attn_layers=(1,), ssm_heads=7), "ssm_heads"),
    (dict(n_experts=8, experts_held=(0, 4)), "share of the experts"),
    (dict(n_shared_experts=1), "shared experts"),
    (dict(ssm_state=16, attn_layers=(1,), n_experts=8, experts_held=(6, 4)),
     "experts_held"),
    (dict(ssm_state=16, attn_layers=(1,), index_topk=4, index_heads=2,
          index_head_dim=16), "no indexer"),
    (dict(conv_layers=(0,), n_experts=4, residual_scale=0.22),
     "residual_scale"),
])
def test_the_config_refuses_what_no_stack_serves(kw, said):
    with pytest.raises(ValueError, match=said):
        llama.LlamaConfig.tiny(**kw)


def test_counts_follow_the_layer_pattern_and_the_issues_arithmetic():
    counts = ADAPTER.counts
    with open(FILE) as f:
        m = json.load(f)
    assert (counts.attention_layers(m), counts.mamba_layers(m),
            counts.layers(m)) == (1, 9, (0, 10))
    assert counts.total_params(m) == 4_757_211_776      # ISSUE 49's 4,757 M
    # ... which is what the program's init makes of the configuration
    cfg = ADAPTER.build_config(m, m["dtypes"], 2048)
    assert llama.param_count(cfg) == counts.total_params(m)
    assert counts.slot_state_bytes(m, 2) == 128 * 8192 * 4 + 3 * 8448 * 2
    assert counts.decode_state_bytes(m, 64, 2) \
        == 2 * 64 * 9 * counts.slot_state_bytes(m, 2)
    # the state is a third of a step's least bytes at 64 slots
    _, byts = counts.decode_step_ops_bytes(m, [1000] * 64, 2, 2)
    assert 0.30 < counts.decode_state_bytes(m, 64, 2) / byts < 0.36
    # a layer more of either kind moves its own terms and no other's
    more = dict(m, num_hidden_layers=11, layer_types=m["layer_types"]
                + ["attention"])
    assert counts.total_params(more) - counts.total_params(m) \
        == counts.attention_params(m) + counts.router_params(m) \
        + counts.shared_params(m) + 36 * counts.expert_params(m) + 2 * 4096
    assert counts.decode_state_bytes(more, 64, 2) \
        == counts.decode_state_bytes(m, 64, 2)
    ops, byts = counts.selective_scan_ops_bytes(m, 1024, 2)
    assert 8.4e6 < ops / 1024 < 8.7e6                   # the issue's 8.4 M
    assert byts == 1024 * (2 * 8192 * 2 + 128 * 4 + 2 * 128 * 2) \
        + 2 * 8192 * 128 * 4 + 2 * 128 * 4


def test_the_configuration_file_holds_the_catalogs_row():
    with open(FILE) as f:
        m = json.load(f)
    ADAPTER.check_supported(m)
    assert set(m["reduced"]) == {"num_hidden_layers", "layer_types",
                                 "num_local_experts", "vocab_size"}
    for key, entry in m["reduced"].items():
        assert set(entry) == {"published", "run", "decided_by"}
        assert entry["run"] == m[key]
    assert (m["hidden_size"], m["intermediate_size"],
            m["shared_intermediate_size"], m["mamba_n_heads"],
            m["mamba_d_head"], m["mamba_d_state"], m["num_experts_per_tok"],
            m["expert_parallel"]["routed_experts_total"]) \
        == (4096, 768, 1536, 128, 64, 128, 10, 72)
    assert m["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert "chip 0 of 2 that share each layer" in m["deployment"]["stands_for"]


# -- (g) the path counters ---------------------------------------------------

def test_a_prompt_of_this_stack_counts_ssd_chunked_and_jambas_does_not(tiny):
    cfg, params = tiny
    toks = jnp.zeros((1, 32), jnp.int32)
    before = attention.attention_path_counts()
    jax.jit(serving.prefill_core(cfg)).lower(fuse_qkv(params, cfg), toks, 5)
    after = attention.attention_path_counts()
    assert after["ssd_chunked"] == before.get("ssd_chunked", 0) + 1
    assert after.get("scan_reference", 0) == before.get("scan_reference", 0)
    jamba = llama.LlamaConfig.tiny(n_layers=2, ssm_state=16, ssm_dt_rank=8,
                                   attn_layers=(1,), rope=False,
                                   tie_embeddings=True)
    shapes = jax.eval_shape(
        lambda: fuse_qkv(llama.init_params(jamba, jax.random.PRNGKey(0))))
    jax.jit(serving.prefill_core(jamba)).lower(shapes, toks, 5)
    last = attention.attention_path_counts()
    assert last["ssd_chunked"] == after["ssd_chunked"]
    assert last["scan_reference"] == after.get("scan_reference", 0) + 1

"""AI21-Jamba2-3B's stack (Mamba-1 layers among attention layers without
positions, a tied head) through the serving engine, and `ops/ssm.py` alone,
against the benchmark's plain reference (benchmark/reference_jamba.py: the
published equations in float32, the recurrence a token at a time), on seeded
random weights at a small size on the CPU: the adapter's REHEARSE widths (4
layers, attention at layer 1; hidden 64, inner 128, 16 states, time-step rank
8, a convolution of 4; 4 query heads on ONE kv head; vocabulary 256), float32
throughout. The engine's prefill runs the `selective_scan` kernel itself, in
Pallas interpret mode.

Tolerances. Program and reference compute the same mathematics in float32
and differ in the order of their sums (and the kernel's exponential is the
interpreter's), so logits of size ~2 agree to a few 1e-6; LOGIT_TOL 2e-4
leaves room for that and none for a lower precision: the same reference with
its recurrent state kept in bfloat16 misses it by two orders
(`test_a_bfloat16_state_is_outside_the_tolerance`). SCAN_TOL 2e-5 is for the
scan alone on inputs of size ~1 (state and y of size ~1, sums of 16 terms).

Here: (a) the scan alone, (d) a prompt split at any point, (e) the tied head,
(f) the adapter, and the helpers; (b) the engine against the reference and
(c) a slot's state its tenant's alone are tests/test_jamba_engine.py.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from benchmark import reference_jamba as ref
from ray_tpu.models import block, llama
from ray_tpu.models.block import fuse_qkv, mamba_mixer
from ray_tpu.ops import attention, slot_state, ssm
from ray_tpu.models.serving import prefill_core
import mixer_riders

LOGIT_TOL = 2e-4
SCAN_TOL = 2e-5

ADAPTER = models.adapter("jamba")
MODEL = dict(ADAPTER.REHEARSE, rms_norm_eps=1e-6, num_experts=1,
             tie_word_embeddings=True)
F32 = {"params": "float32", "activations": "float32"}


def _params(cfg, seed=3):
    """Seeded weights with every norm off one, a convolution bias and a D
    that matter, and an embedding whose logits spread."""
    params = ADAPTER.init_params(cfg, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def jitter(stack, names):
        out = dict(stack)
        for name in names:
            out[name] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                      stack[name].shape)
        return out

    lay = jitter(params["layers"], ("attn_norm", "mlp_norm"))
    mam = jitter(params["mamba"], ("norm", "mlp_norm", "dt_norm", "b_norm",
                                   "c_norm", "D"))
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        lay[name] = lay[name] * 8.0
    for name in ("in_proj", "x_proj", "dt_proj", "out_proj", "w_gate", "w_up",
                 "w_down"):
        mam[name] = mam[name] * 8.0
    mam["conv_b"] = 0.3 * jax.random.normal(next(keys), mam["conv_b"].shape)
    return dict(params, layers=lay, mamba=mam, embed=params["embed"] * 12.0,
                final_norm=1.0 + 0.2 * jax.random.normal(
                    next(keys), params["final_norm"].shape))


@pytest.fixture(scope="module")
def tiny():
    cfg = ADAPTER.build_config(MODEL, F32, 256)
    assert (cfg.attn_layers, cfg.kv_layers, cfg.ssm_state, cfg.ssm_inner,
            cfg.ssm_dt_rank, cfg.n_kv_heads, cfg.rope, cfg.tie_embeddings) \
        == ((1,), 1, 16, 128, 8, 1, False, True)
    assert cfg.segments() == (("mamba", 0, 1), ("attn", 0, 1),
                              ("mamba", 1, 3))
    return cfg, _params(cfg)


@pytest.fixture
def scan_in_interpret_mode(monkeypatch):
    """`mamba_mixer` under this fixture takes the Pallas scan kernel,
    interpreted, on this CPU, as `kernel_in_interpret_mode` does for decode
    attention: the program needs no switch for the tests' sake."""
    monkeypatch.setattr(block, "selective_scan", functools.partial(
        ssm.selective_scan, interpret=True))


def _tokens(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)]


# -- (a) the scan alone ------------------------------------------------------

def _scan_inputs(S=64, Di=256, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        x=jax.random.normal(ks[0], (S, Di)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (S, Di)) - 3.0),
        A=-jnp.exp(0.5 * jax.random.normal(ks[2], (N, Di))),
        B=jax.random.normal(ks[3], (S, N)), C=jax.random.normal(ks[4], (S, N)),
        D=1.0 + 0.2 * jax.random.normal(ks[5], (Di,)),
        z=jax.random.normal(ks[6], (S, Di)),
        s0=jax.random.normal(ks[7], (N, Di)))


def _recurrence(x, dt, A, B, C, D, z, s0, length):
    """A token at a time, in numpy float64."""
    x, dt, A, B, C, D, z, s = (np.asarray(a, np.float64)
                               for a in (x, dt, A, B, C, D, z, s0))
    ys = []
    for t in range(length):
        s = np.exp(dt[t][None, :] * A) * s + (dt[t] * x[t])[None, :] \
            * B[t][:, None]
        y = (s * C[t][:, None]).sum(0) + D * x[t]
        ys.append(y * z[t] / (1.0 + np.exp(-z[t])))
    return np.stack(ys), s


@pytest.mark.parametrize("path", ["kernel", "reference"])
@pytest.mark.parametrize("length", [None, 37, 16])
@pytest.mark.parametrize("start", ["zeros", "state0"])
def test_scan_paths_equal_the_token_by_token_recurrence(path, length, start):
    """With and without a state to start from, and with `length` short of
    the width (inside a chunk of 16 rows, and on its edge): y up to `length`
    and the state after row `length - 1` are the recurrence's, whatever
    follows it in the bucket."""
    a = _scan_inputs()
    s0 = a["s0"] if start == "state0" else None
    before = dict(attention.attention_path_counts())
    y, s = ssm.selective_scan(
        a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"], s0, length,
        z=a["z"], interpret=path == "kernel", block_channels=128,
        block_rows=32)
    name = "scan_pallas" if path == "kernel" else "scan_reference"
    assert attention.attention_path_counts()[name] == before.get(name, 0) + 1
    n = 64 if length is None else length
    want_y, want_s = _recurrence(
        a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"], a["z"],
        np.zeros((16, 256)) if s0 is None else s0, n)
    assert np.abs(np.asarray(y)[:n] - want_y).max() < SCAN_TOL
    assert np.abs(np.asarray(s) - want_s).max() < SCAN_TOL
    assert np.isfinite(np.asarray(y)).all()      # the padding's rows too


def test_the_reference_path_is_differentiable():
    a = _scan_inputs(S=8, Di=128)

    def loss(x, dt):
        y, s = ssm.selective_scan(x, dt, a["A"], a["B"], a["C"], a["D"])
        return jnp.sum(y) + jnp.sum(s)

    gx, gdt = jax.grad(loss, argnums=(0, 1))(a["x"], a["dt"])
    assert np.isfinite(np.asarray(gx)).all() and float(jnp.abs(gdt).max()) > 0


def test_one_token_steps_are_the_scan(tiny):
    """`ssm_step` a row at a time from the scan's state at t gives the scan's
    rows after t."""
    a = _scan_inputs(S=32, Di=128)
    y, s = ssm.selective_scan(a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"])
    _, at20 = ssm.selective_scan(a["x"], a["dt"], a["A"], a["B"], a["C"],
                                 a["D"], length=20)
    state = at20[None]
    for t in range(20, 32):
        row, state = ssm.ssm_step(a["x"][t][None], a["dt"][t][None], a["A"],
                                  a["B"][t][None], a["C"][t][None], a["D"],
                                  state)
        assert np.abs(np.asarray(row[0]) - np.asarray(y[t])).max() < SCAN_TOL
    assert np.abs(np.asarray(state[0]) - np.asarray(s)).max() < SCAN_TOL


# -- (d) a prompt split at any point ----------------------------------------

@pytest.mark.parametrize("cut", [1, 2, 3, 16, 29, 47])
def test_a_split_prompt_is_the_unsplit_one(tiny, cut, scan_in_interpret_mode):
    """The mixer over rows 0..cut, then over the rest from the carried state
    and window, is the mixer over all 48: the window is the last K - 1 real
    inputs, the state the one after the last real row (`length` short of the
    first part's width of 48, so its padding must not count)."""
    cfg, params = tiny
    lp = jax.tree.map(lambda w: w[1], params["mamba"])
    x = jax.random.normal(jax.random.PRNGKey(cut), (48, cfg.d_model))
    whole, state, window = mamba_mixer(lp, x, cfg)
    head_rows = jnp.where(jnp.arange(48)[:, None] < cut, x, 7.0)  # padding
    first, s1, w1 = mamba_mixer(lp, head_rows, cfg, length=cut)
    tail = jnp.concatenate([x[cut:], jnp.zeros((cut, cfg.d_model))])
    rest, s2, w2 = mamba_mixer(lp, tail, cfg, s1, w1, length=48 - cut)
    got = np.concatenate([np.asarray(first)[:cut],
                          np.asarray(rest)[:48 - cut]])
    assert np.abs(got - np.asarray(whole)).max() < SCAN_TOL
    assert np.abs(np.asarray(s2) - np.asarray(state)).max() < SCAN_TOL
    np.testing.assert_allclose(np.asarray(w2), np.asarray(window), atol=1e-6)


def test_riders_in_a_prompts_tail_rows_take_a_step_and_leave_the_prompt_alone(
        tiny, scan_in_interpret_mode):
    """`mamba_mixer(riders=)`: tests/mixer_riders.py says what is held; the
    prompt's scan is the kernel's own code, told `length`. The step alone is `step=True` on the layer's rows and their write back, as
    `models/serving.py::_mamba_kind`'s decode body has it."""
    cfg, params = tiny
    lp = jax.tree.map(lambda w: w[1], params["mamba"])

    def step(x, slots, layer, active):
        out, ssm, window = mamba_mixer(
            lp, x, cfg, *slot_state.layer_state(slots, layer), step=True)
        return out, slot_state.update_layer(slots, layer, active, ssm,
                                            window)

    mixer_riders.check(mamba_mixer, lp, cfg, step, SCAN_TOL)


def test_the_training_forward_refuses_state_space_layers_by_name(tiny):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="state-space"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


# -- (e) the tied head, and attention without positions ----------------------

def test_the_head_is_the_embedding_and_attention_takes_no_position(tiny):
    """`lm_head` is no leaf of the tree (the program's logits are the
    reference's, whose head is `embed` transposed), and the attention layer
    agrees with a reference that applies no RoPE and disagrees with one that
    does."""
    cfg, params = tiny
    assert "lm_head" not in params and set(params) == {
        "embed", "layers", "mamba", "final_norm"}
    assert "lm_head" not in llama.logical_axes(cfg)
    assert params["layers"]["wq"].shape[0] == 1       # one attention layer
    assert params["mamba"]["in_proj"].shape[0] == 3   # three Mamba layers
    prompt = _tokens(64, 9)
    core = jax.jit(prefill_core(cfg))
    got = np.asarray(core(fuse_qkv(params), jnp.asarray([prompt]), 64)[3])
    plain = np.asarray(ref.logits_last(params, MODEL, prompt, 1))[0]
    turned = np.asarray(ref.logits_last(params, MODEL, prompt, 1,
                                        rope_theta=10000.0))[0]
    assert np.abs(got - plain).max() < LOGIT_TOL
    assert np.abs(got - turned).max() > 100 * LOGIT_TOL


# -- (f) the adapter's refusals ----------------------------------------------

@pytest.mark.parametrize("change, said", [
    ({"num_experts": 16}, "sparse experts"),
    ({"sliding_window": 4096}, "sliding window"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"tie_word_embeddings": False}, "untied head"),
    ({"attn_layer_offset": 9}, "no attention layer"),
])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    with pytest.raises(ValueError, match=said):
        ADAPTER.check_supported(dict(MODEL, **change))


def test_counts_follow_the_layer_pattern():
    counts = ADAPTER.counts
    m = dict(MODEL)
    assert (counts.attention_layers(m), counts.mamba_layers(m)) == (1, 3)
    cfg = ADAPTER.build_config(m, F32, 128)
    assert counts.total_params(m) == llama.param_count(cfg)
    ops, byts = counts.selective_scan_ops_bytes(m, 100, 2)
    assert ops == 9 * 100 * 128 * 16
    assert byts == 100 * 128 * (3 * 2 + 4) + 100 * 2 * 16 * 4 \
        + 3 * 128 * 16 * 4 + 128 * 4
    # a decode step moves each live slot's state in and out, once a layer
    assert counts.decode_state_bytes(m, 5, 2) == \
        2 * 5 * 3 * 128 * (16 * 4 + 3 * 2)

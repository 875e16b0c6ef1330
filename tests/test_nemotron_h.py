"""NVIDIA-Nemotron-3-Nano's stack at tiny widths on the CPU, seeded weights,
against its plain reference (benchmark/reference_nemotron_h.py: float32,
precision "highest", the recurrence row by row): layers of ONE part each,
Mamba-2 with GROUPS of B and C (`ops/ssm.py::ssd_scan`, `ssd_step`,
`ssd_state_step`) at an inner width that is heads x a head's channels,
ungated relu^2 experts under the sigmoid router with a SHARE held beside a
shared expert, attention alone with no position signal, and the whole of it
through `Engine`.

(a) the recurrence with groups; (b) the parts against the reference: mixer,
router, experts, shares; (c) the engine (tests/test_nemotron_h_engine.py);
(d) the pattern, the stacks and the refusals.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from benchmark import reference_nemotron_h as ref
from ray_tpu.models import llama, serving
from ray_tpu.models.block import feed_forward, mamba2_mixer
from ray_tpu.ops import attention, moe, slot_state, ssm
import mixer_riders

# Float32 everywhere on the CPU: what is left between the program and the
# reference is the order of float32 sums (a chunk's matrix products against
# the row-by-row recurrence; a token's experts summed in routing order
# against expert order) on logits of a few units (the jittered weights below
# spread them): 2e-6 to 6e-6 measured over the cases here, 4e-5 allowed.
# Everything in bfloat16 reads 0.02 to 0.2, a state kept in bfloat16 1e-3
# (test (c)).
LOGIT_TOL = 4e-5
SCAN_TOL = 2e-5
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

ADAPTER = models.adapter("nemotron_h")
MODEL = dict(ADAPTER.REHEARSE, layer_norm_epsilon=1e-5, norm_eps=1e-5,
             mlp_hidden_act="relu2", mamba_hidden_act="silu",
             routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1,
             topk_group=1, n_shared_experts=1, tie_word_embeddings=False)
F32 = {"params": "float32", "activations": "float32"}


def _params(cfg, seed=3):
    """Seeded weights with every norm off one, a convolution bias, a D and a
    selection bias that matter, and matrices large enough that every branch
    moves the logits."""
    params = ADAPTER.init_params(cfg, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def jitter(stack, names):
        out = dict(stack)
        for name in names:
            out[name] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                      stack[name].shape)
        return out

    lay = jitter(params["layers"], ("attn_norm",))
    mam = jitter(params["mamba"], ("norm", "w_norm", "D"))
    exp = jitter(params["experts"], ("mlp_norm",))
    for name in ("wq", "wk", "wv", "wo"):
        lay[name] = lay[name] * 8.0
    for name in ("in_proj", "out_proj"):
        mam[name] = mam[name] * 8.0
    for name in ("router", "w_up", "w_down", "ws_up", "ws_down"):
        exp[name] = exp[name] * 6.0
    mam["conv_b"] = 0.3 * jax.random.normal(next(keys), mam["conv_b"].shape)
    exp["router_bias"] = 0.1 * jax.random.normal(next(keys),
                                                 exp["router_bias"].shape)
    return dict(params, layers=lay, mamba=mam, experts=exp,
                embed=params["embed"] * 12.0, lm_head=params["lm_head"] * 8.0,
                final_norm=1.0 + 0.2 * jax.random.normal(
                    next(keys), params["final_norm"].shape))


@pytest.fixture(scope="module")
def tiny():
    cfg = ADAPTER.build_config(MODEL, F32, 512)
    assert (cfg.layer_parts, cfg.attn_layers, cfg.kv_layers,
            cfg.state_layers, cfg.sparse_layers) == ("MEM*EM", (3,), 1, 3, 2)
    assert (cfg.ssm_state, cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_inner,
            cfg.ssm_conv_channels, cfg.d_model) == (16, 8, 2, 128, 192, 96)
    assert (cfg.n_experts, cfg.experts_held, cfg.n_shared_experts,
            cfg.router_score, cfg.routed_scale, cfg.ffn, cfg.up_out_in,
            cfg.rope, cfg.tie_embeddings) \
        == (8, (0, 4), 2, "sigmoid", 2.5, "relu2", True, False, False)
    assert cfg.segments() == (("mamba", 0, 1), ("experts", 0, 1),
                              ("mamba", 1, 2), ("attn", 0, 1),
                              ("experts", 1, 2), ("mamba", 2, 3))
    return cfg, _params(cfg)


def _layer_f32(params, name, i):
    return {k: v if k in ref._EXPERTS else v[i].astype(jnp.float32)
            for k, v in params[name].items()}


def _sha(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()[:16]


# -- (a) the recurrence with groups -------------------------------------------

def _scan_inputs(S=80, Di=256, N=16, H=8, G=1, seed=0):
    """tests/test_granite.py's inputs (the same keys in the same order), B
    and C with a group axis where G > 1."""
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    x = jax.random.normal(next(k), (S, Di))
    dt = jax.nn.softplus(jax.random.normal(next(k), (S, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(next(k), (H,)) * 0.5)
    shape = (S, N) if G == 1 else (S, G, N)
    B = jax.random.normal(next(k), shape)
    C = jax.random.normal(next(k), shape)
    D = jax.random.normal(next(k), (H,))
    s0 = jax.random.normal(next(k), (N, Di))
    return x, dt, A, B, C, D, s0


def _row_by_row(x, dt, A, B, C, D, s0, length):
    """The published recurrence a head, a row at a time, in numpy float64:
    head h reads group h // (H / G)."""
    x, dt, A, B, C, D = (np.asarray(a, np.float64)
                         for a in (x, dt, A, B, C, D))
    S, Di = x.shape
    H = dt.shape[1]
    P = Di // H
    B, C = (m.reshape(S, -1, m.shape[-1]) for m in (B, C))      # [S, G, N]
    B, C = (np.repeat(m, H // m.shape[1], axis=1) for m in (B, C))
    s = np.asarray(s0, np.float64).T.reshape(H, P, -1)
    ys = []
    for t in range(S if length is None else length):
        xt = x[t].reshape(H, P)
        s = np.exp(dt[t] * A)[:, None, None] * s \
            + (dt[t][:, None] * xt)[:, :, None] * B[t][:, None, :]
        ys.append((np.einsum("hpn,hn->hp", s, C[t])
                   + D[:, None] * xt).reshape(Di))
    return np.stack(ys), s.reshape(Di, -1).T


# sha256 (first 16 hex digits) of what ONE group gave on PR 55's parent
# (751e3d2; jax 0.9.0 on the CPU) at the inputs below: groups may not move
# Granite's numbers by a bit.
PARENT = {"scan": "d63c53b7c62e2006", "step": "4fdd99a69be71fbe",
          "kernel": "8ada22c26e4dbe1c"}


@pytest.mark.parametrize("length", [None, 64, 50],
                         ids=["inside-a-chunk", "at-an-edge", "dead-rows"])
@pytest.mark.parametrize("G", [1, 2, 8])
def test_ssd_scan_with_groups_is_the_row_by_row_recurrence(G, length):
    """80 rows in chunks of 32, the prompt ending inside the third chunk, at
    the second's edge (64) and 50 rows in with a bucket's padding behind it:
    G groups of B and C give the recurrence a row at a time in which head h
    reads group h // (H / G)."""
    x, dt, A, B, C, D, s0 = _scan_inputs(G=G)
    y, state = jax.jit(lambda: ssm.ssd_scan(x, dt, A, B, C, D, s0, length,
                                            chunk=32))()
    want_y, want_s = _row_by_row(x, dt, A, B, C, D, s0, length)
    n = len(want_y)
    assert np.abs(np.asarray(y)[:n] - want_y).max() < SCAN_TOL
    assert np.abs(np.asarray(state) - want_s).max() < SCAN_TOL
    assert np.isfinite(np.asarray(y)).all()


def test_one_group_is_the_parents_outputs_to_the_bit():
    """One group, given without its axis or with it, computes by the text it
    always was: the scan's, the step's and the step kernel's (interpreted)
    results are the parent commit's, bit for bit."""
    x, dt, A, B, C, D, s0 = _scan_inputs()
    for b, c in ((B, C), (B[:, None], C[:, None])):
        assert _sha(*jax.jit(lambda: ssm.ssd_scan(
            x, dt, A, b, c, D, s0, 50, chunk=32))()) == PARENT["scan"]
    x, dt, A, B, C, D, _ = _scan_inputs(S=4, seed=5)
    state = jax.random.normal(jax.random.PRNGKey(6), (3, 4, 16, 256))
    act = jnp.array([0, 1, 1, 0], bool)
    for b, c in ((B, C), (B[:, None], C[:, None])):
        assert _sha(*ssm.ssd_step(x, dt, A, b, c, D, state[1])) \
            == PARENT["step"]
        assert _sha(*ssm.ssd_state_step(
            state, jnp.int32(1), act, x, dt, A, b, c, D, interpret=True,
            block_channels=128)) == PARENT["kernel"]


@pytest.mark.parametrize("G", [1, 2, 8])
def test_one_token_steps_with_groups_are_the_recurrence(G):
    """`ssd_step`, two slots at once, a row at a time from a carried state,
    is the row-by-row recurrence of each sequence."""
    a, b = _scan_inputs(S=24, G=G, seed=1), _scan_inputs(S=24, G=G, seed=2)
    A, D = a[2], a[5]
    state = jnp.stack([a[6], b[6]])
    ys = []
    for t in range(24):
        y, state = ssm.ssd_step(
            *(jnp.stack([a[i][t], b[i][t]]) for i in (0, 1)), A,
            *(jnp.stack([a[i][t], b[i][t]]) for i in (3, 4)), D, state)
        ys.append(y)
    ys = np.asarray(jnp.stack(ys, axis=1))
    for slot, seq in enumerate((a, b)):
        want_y, want_s = _row_by_row(*seq[:2], A, *seq[3:5], D, seq[6], None)
        assert np.abs(ys[slot] - want_y).max() < SCAN_TOL
        assert np.abs(np.asarray(state[slot]) - want_s).max() < SCAN_TOL


@pytest.mark.parametrize("block", [1024, 256, 128],
                         ids=["a_group_at_most", "256", "128"])
@pytest.mark.parametrize("G", [1, 2, 8])
def test_the_step_kernel_with_groups_is_ssd_step_in_one_visit(G, block):
    """`ssd_state_step` (interpreted) at 128 states and 1,024 channels in G
    groups, its block of channels the widest under `block` that divides a
    group's (all 1,024 of one group, a group of 512 of two, half a group,
    one of eight groups of 128: never across a group's edge), against
    `ssd_step` on the layer's rows: y and the active slots' new state to
    float32 rounding, an idle slot's state and the other layer's bit for
    bit, an idle slot's row of y zeros."""
    L, ns, N, Di, H, layer = 2, 4, 128, 1024, 16, 1
    x, dt, A, B, C, D, _ = _scan_inputs(S=ns, Di=Di, N=N, H=H, G=G, seed=5)
    ssm0 = jax.random.normal(jax.random.PRNGKey(6), (L, ns, N, Di))
    act = jnp.array([1, 0, 1, 1], bool)
    assert ssm.state_step_tiles(ssm0.shape, G)
    want_y, rows = ssm.ssd_step(x, dt, A, B, C, D, ssm0[layer])
    want, _ = slot_state.update_layer((ssm0, None), layer, act, rows, None)
    y, got = ssm.ssd_state_step(ssm0, jnp.int32(layer), act, x, dt, A, B, C,
                                D, interpret=True, block_channels=block)
    y, got, want, idle = (np.asarray(a) for a in (y, got, want, ~act))
    scale = max(1.0, np.abs(want).max())
    assert np.abs(y[~idle] - np.asarray(want_y)[~idle]).max() / scale < 1e-6
    assert np.abs(got - want).max() / scale < 1e-6
    assert (y[idle] == 0).all()
    assert (got[:, idle] == np.asarray(ssm0)[:, idle]).all()
    assert (got[0] == np.asarray(ssm0)[0]).all()


def test_the_step_kernel_asks_for_whole_lanes_a_group():
    assert ssm.state_step_tiles((7, 32, 128, 4096), 8)      # the cell's
    assert ssm.state_step_tiles((9, 64, 128, 8192), 1)      # Granite's
    assert not ssm.state_step_tiles((3, 4, 16, 128), 2)     # N not in lanes
    assert not ssm.state_step_tiles((3, 4, 128, 512), 8)    # 64 channels


# -- (b) the parts against the reference --------------------------------------

@pytest.mark.parametrize("length", [48, 33], ids=["whole", "dead-rows"])
def test_the_mixer_is_the_references_and_its_steps_go_on_from_its_state(
        tiny, length):
    """The mixer over a prompt of `length` rows in a bucket of 48 (dead rows
    behind it at 33, which may enter neither the state nor the window), then
    six tokens one at a time from the state and window it handed back, is the
    reference's mixer over the `length + 6` rows as one sequence. Inner width
    8 x 16 = 128 under a hidden size of 96, two groups, the gated norm a
    group."""
    cfg, params = tiny
    lp = _layer_f32(params, "mamba", 1)
    x = jax.random.normal(jax.random.PRNGKey(9), (48 + 6, cfg.d_model))
    seq = jnp.concatenate([x[:length], x[48:]])
    want = np.asarray(seq) + np.asarray(ref.mamba2_mixer(
        ref._rms_norm(seq, lp["norm"], 1e-5), lp, MODEL))
    out, state, window = jax.jit(
        lambda v: mamba2_mixer(lp, v, cfg, length=length))(x[:48])
    assert np.abs(np.asarray(out)[:length] - want[:length]).max() < SCAN_TOL
    assert state.shape == (16, 128) and window.shape == (3, 192)
    slots, window = (state[None, None], None), window[:, None]
    for t in range(6):
        out, slots, window = mamba2_mixer(
            lp, x[48 + t][None], cfg, slots, window, step=True, layer=0,
            active=jnp.ones(1, bool))
        assert np.abs(np.asarray(out)[0] - want[length + t]).max() < SCAN_TOL


def test_riders_in_a_prompts_tail_rows_take_a_step_and_leave_the_prompt_alone(
        tiny):
    """`mamba2_mixer(riders=)`: tests/mixer_riders.py says what is held; the one-part stack's mixer has two groups of B and C.
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


def test_the_adapter_draws_the_routed_down_matrices_smaller():
    """Every matrix at 0.02 but the routed experts' W_down, at 0.02 x
    ROUTED_DOWN (the adapter says why), whatever the seed."""
    cfg = ADAPTER.build_config(MODEL, F32, 512)
    ex = ADAPTER.init_params(cfg, 5)["experts"]
    std = {k: float(jnp.std(ex[k])) for k in ("w_up", "w_down", "ws_down")}
    assert 0 < ADAPTER.ROUTED_DOWN < 1
    assert abs(std["w_down"] / (0.02 * ADAPTER.ROUTED_DOWN) - 1) < 0.03
    assert all(abs(std[k] / 0.02 - 1) < 0.03 for k in ("w_up", "ws_down"))


def test_the_router_bias_chooses_and_does_not_weigh():
    """Sigmoid scores; the 3 largest of score + bias; the weights are the
    SCORES at the chosen over their sum + 1e-20, times 2.5. A bias that lifts
    the weakest expert into the choice gives it its own small score's
    weight."""
    logits = jnp.array([[2.0, 1.0, 0.5, -3.0, 0.0, -1.0]])
    bias = jnp.array([0.0, 0.0, 0.0, 5.0, 0.0, 0.0])
    w, idx = moe.top_k_routing(logits, 3, True, score="sigmoid", bias=bias,
                               scale=2.5, norm_eps=1e-20)
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 3]
    chosen = s[np.asarray(idx[0])]
    assert np.allclose(np.asarray(w[0]), 2.5 * chosen / (chosen.sum() + 1e-20),
                       rtol=1e-6)
    combine = ref.route(jnp.eye(6)[:1] * 0 + logits, jnp.eye(6), bias,
                        dict(MODEL, num_experts_per_tok=3))
    assert np.allclose(np.asarray(combine[0])[np.asarray(idx[0])],
                       np.asarray(w[0]), rtol=1e-6)
    assert np.asarray(combine[0])[[2, 4, 5]].tolist() == [0, 0, 0]


def test_relu2_ungated_experts_are_a_dense_loop_over_experts(tiny):
    """`moe_ffn` with no gate and relu^2, every expert held, against the
    reference's loop over every expert on every row."""
    cfg, params = tiny
    lp = _layer_f32(params, "experts", 0)
    key = jax.random.PRNGKey(4)
    full = {k: 0.12 * jax.random.normal(kk, (8,) + lp[k].shape[2:])
            for k, kk in zip(ref._EXPERTS, jax.random.split(key))}
    u = jax.random.normal(jax.random.PRNGKey(5), (40, cfg.d_model))
    out, _, counts = moe.moe_ffn(
        u, lp["router"], full["w_up"], None, full["w_down"],
        top_k=3, routing=dict(cfg.routing(), bias=lp["router_bias"]),
        act="relu2", out_in=True)
    want = ref.routed_part(u, dict(lp, **full), MODEL, held=((0, 8), 8))
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < 2e-5
    assert int(counts.sum()) == 40 * 3


@pytest.mark.parametrize("ffn", ["relu2", "swiglu"])
@pytest.mark.parametrize("width", [64, 128], ids=["off_lanes", "whole_lanes"])
def test_the_width_lays_the_up_matrices_out_and_not_the_gate(ffn, width):
    """`ops.moe.up_out_in` from the expert's width alone: `[F, D]` off the
    lanes, `[D, F]` on them, the gate and the shared expert as the up matrix,
    gated or not; and the layer the program computes from either layout is
    the dense loop over the experts held plus the shared expert."""
    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=96, n_layers=2, layer_parts="ME", n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=width, ssm_state=16, ssm_heads=8,
        ssm_head_dim=16, ssm_groups=2, rope=False, n_experts=8,
        top_k_experts=3, experts_held=(0, 4), n_shared_experts=2,
        router_score="sigmoid", routed_scale=2.5, ffn=ffn,
        param_dtype=jnp.float32, dtype=jnp.float32)
    assert cfg.up_out_in == (width == 64) == moe.up_out_in(width)
    stack = jax.tree.map(lambda w: 6.0 * w if w.ndim > 2 else w,
                         llama.init_params(cfg, jax.random.PRNGKey(2))
                         ["experts"])
    up = (width, 96) if cfg.up_out_in else (96, width)
    gates = ("w_gate", "ws_gate") if ffn == "swiglu" else ()
    assert set(stack) == {"mlp_norm", "router", "router_bias", "w_up",
                          "w_down", "ws_up", "ws_down", *gates}
    assert all(stack[k].shape[-2:] == up for k in ("w_up", *gates[:1]))
    assert all(stack[k].shape[-2:] == (2 * up[0], up[1]) if cfg.up_out_in
               else stack[k].shape[-2:] == (up[0], 2 * up[1])
               for k in ("ws_up", *gates[1:]))
    assert llama.logical_axes(cfg)["experts"]["w_up"][-2:] == (
        ("mlp", "embed") if cfg.up_out_in else ("embed", "mlp"))
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 96))
    lp = jax.tree.map(lambda w: w[0], stack)
    got, _ = feed_forward(
        {k: stack[k] if k in ("w_up", "w_gate", "w_down") else v
         for k, v in lp.items()}, h, cfg, None, jnp.int32(0))
    u = np.asarray(h[0] * jax.lax.rsqrt(
        jnp.mean(jnp.square(h[0]), -1, keepdims=True) + cfg.norm_eps))
    weights, idx = moe.top_k_routing(
        u @ lp["router"], 3, **dict(cfg.routing(), bias=lp["router_bias"]))

    def expert(w_up, w_down, w_gate=None):
        into = (lambda w: u @ (w.T if cfg.up_out_in else w))
        if w_gate is None:
            return np.square(np.maximum(into(w_up), 0)) @ w_down
        return np.asarray(jax.nn.silu(into(w_gate))) * into(w_up) @ w_down

    want = expert(lp["ws_up"], lp["ws_down"], lp.get("ws_gate"))
    for e in range(4):
        w = np.sum(np.where(np.asarray(idx) == e, np.asarray(weights), 0), -1)
        want = want + w[:, None] * expert(
            lp["w_up"][e], lp["w_down"][e],
            lp["w_gate"][e] if gates else None)
    assert np.abs(np.asarray(got[0] - h[0]) - want).max() < 2e-4
    assert np.abs(want).max() > 0.1


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """8 experts in 2 shares of 4: each share in turn holds its 4 experts'
    weights and computes its part; the two parts and the shared expert ONCE
    are what the uncut reference gives for the whole E layer, and the
    program's own layer (`feed_forward`, the share it holds) adds the shared
    expert once."""
    cfg, params = tiny
    lp = _layer_f32(params, "experts", 1)
    full = {k: 0.12 * jax.random.normal(kk, (8,) + lp[k].shape[2:])
            for k, kk in zip(ref._EXPERTS,
                             jax.random.split(jax.random.PRNGKey(7)))}
    u = jax.random.normal(jax.random.PRNGKey(8), (40, cfg.d_model))
    routing = dict(cfg.routing(), bias=lp["router_bias"])
    parts, local = [], 0
    for offset in (0, 4):
        mine = {k: w[offset:offset + 4] for k, w in full.items()}
        part, _, counts = moe.moe_ffn(
            u, lp["router"], mine["w_up"], None, mine["w_down"], top_k=3,
            routing=routing, held=(offset, 4), act="relu2", out_in=True)
        assert np.abs(np.asarray(part) - np.asarray(ref.routed_part(
            u, dict(lp, **mine), MODEL, held=((offset, 4), 8)))).max() < 2e-5
        parts.append(np.asarray(part))
        local += int(counts.sum())
    assert local == 40 * 3      # every assignment is some share's
    shared = np.asarray(ref.shared_part(u, lp))
    whole = np.asarray(ref.routed_part(u, dict(lp, **full), MODEL,
                                       held=((0, 8), 8)))
    assert np.abs(sum(parts) + shared - (whole + shared)).max() < 4e-5
    # the program's layer, experts 0..3 held (the stack's own weights)
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 40, cfg.d_model))
    stack = {k: v if k in ref._EXPERTS else v[1]
             for k, v in params["experts"].items()}
    got, (_, n) = feed_forward(stack, h, cfg, None, jnp.int32(1))
    un = ref._rms_norm(h[0], lp["mlp_norm"], 1e-5)
    want = h[0] + ref.experts_part(un, lp, MODEL, layer=1)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 4e-5
    assert n.shape == (4,)


# -- (d) the pattern, the stacks and the refusals -----------------------------

@pytest.mark.parametrize("letters,kinds", [
    (PUBLISHED, (23, 23, 6)), (PUBLISHED[:16], (7, 7, 2)),
    (PUBLISHED[:13], (6, 5, 2))], ids=["published", "held", "fallback"])
def test_the_pattern_gives_the_segments_and_the_stacks(letters, kinds):
    """The published 52 letters, the 16 held and the fallback's 13: the
    layers by kind, the segments in the order they run (a run of one kind a
    segment, an attention layer always its own), and a stack a kind that
    holds that kind's leaves and no other's."""
    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=96, n_layers=len(letters),
        layer_parts=letters, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=64,
        ssm_state=16, ssm_heads=8, ssm_head_dim=16, ssm_groups=2, rope=False,
        n_experts=8, top_k_experts=3, experts_held=(0, 4),
        n_shared_experts=2, router_score="sigmoid", ffn="relu2")
    assert (cfg.state_layers, cfg.sparse_layers, cfg.kv_layers) == kinds
    assert cfg.attn_layers == tuple(i for i, c in enumerate(letters)
                                    if c == "*")
    names = {"M": "mamba", "E": "experts", "*": "attn"}
    order = [(names[c]) for c in letters]
    walked = [name for name, lo, hi in cfg.segments() for _ in range(lo, hi)]
    assert walked == order
    for name in names.values():     # ordinals run 0.. in order, a kind
        spans = [(lo, hi) for n, lo, hi in cfg.segments() if n == name]
        assert spans[0][0] == 0 and all(a[1] == b[0]
                                        for a, b in zip(spans, spans[1:]))
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    assert set(shapes["layers"]) == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert set(shapes["mamba"]) == {
        "norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
        "w_norm", "out_proj"}
    assert set(shapes["experts"]) == {
        "mlp_norm", "router", "router_bias", "w_up", "w_down", "ws_up",
        "ws_down"}
    assert [shapes[k]["wq" if k == "layers" else "norm" if k == "mamba"
                      else "router"].shape[0]
            for k in ("mamba", "experts", "layers")] == list(kinds)
    assert shapes["mamba"]["in_proj"].shape[1:] == (96, 128 + 192 + 8)
    assert shapes["experts"]["w_up"].shape[1:] == (4, 64, 96) \
        == shapes["experts"]["w_down"].shape[1:]        # both [F, D]
    assert shapes["experts"]["ws_up"].shape[1:] == (128, 96)
    assert shapes["experts"]["router"].shape[1:] == (96, 8)
    axes = llama.logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(shapes)


@pytest.mark.parametrize("kw,said", [
    (dict(layer_parts="MEX*"), "one of 'M'"),
    (dict(layer_parts="ME*"), "one of 'M'"),
    (dict(attn_layers=(2,)), "no attn_layers beside it"),
    (dict(ssm_heads=0, ssm_head_dim=0, ssm_groups=1), "Mamba-2 mixers"),
    (dict(n_experts=0, experts_held=None, n_shared_experts=0,
          router_score="softmax"), "sparse experts"),
    (dict(ssm_groups=3), "ssm_groups"),
    (dict(ffn="gelu"), "ffn 'swiglu' or 'relu2'"),
    (dict(residual_scale=0.5), "not served by the stack of one-part"),
])
def test_the_config_refuses_what_the_one_part_stack_does_not_serve(kw, said):
    base = dict(
        vocab_size=256, d_model=96, n_layers=4, layer_parts="ME*M", n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=64, ssm_state=16, ssm_heads=8,
        ssm_head_dim=16, ssm_groups=2, rope=False, n_experts=8,
        top_k_experts=3, experts_held=(0, 4), n_shared_experts=2,
        router_score="sigmoid", ffn="relu2")
    llama.LlamaConfig(**base)
    with pytest.raises(ValueError, match=said):
        llama.LlamaConfig(**dict(base, **kw))


@pytest.mark.parametrize("kw,said", [
    (dict(ffn="relu2"), "one-part"),
    (dict(ffn="relu2", d_ff=256), "one-part"),
    (dict(ssm_groups=2), "ssm_groups"),
    (dict(ssm_head_dim=16), "ssm_head_dim"),
    (dict(n_experts=4, router_score="sigmoid"), "sigmoid router"),
])
def test_the_other_stacks_refuse_the_one_part_stacks_fields(kw, said):
    """What this stack brought is served where it was built: an ungated or
    relu^2 feed-forward and the sigmoid router of a hybrid in the stack of
    one-part layers, groups and a head's width under Mamba-2's heads."""
    with pytest.raises(ValueError, match=said):
        llama.LlamaConfig.tiny(**kw)


def test_the_training_forward_and_a_handoff_are_refused_by_name(tiny):
    cfg, _ = tiny
    with pytest.raises(NotImplementedError, match="state-space layers"):
        llama.forward_with_aux({}, jnp.zeros((1, 8), jnp.int32), cfg)
    assert not serving.adopts(cfg)


@pytest.mark.parametrize("transposed", [False, True], ids=["in_out", "out_in"])
@pytest.mark.parametrize("n", [192, 256], ids=["ragged_columns", "whole"])
def test_the_grouped_kernel_takes_a_width_that_is_not_whole_lanes(
        n, transposed):
    """The grouped matmul's kernel (interpreted) at a width of one and a half
    lane tiles, 192, as an expert's 1,856 is fourteen and a half: its column
    tiles cover the width rounded up and the columns past it are never
    written; and a group's matrix as an `nn.Linear` weight, `[N, K]`, the
    product with its transpose, which is how an ungated expert's up matrix is
    held. Against `ragged_dot`, rows in no group left out."""
    m, k, g = 64, 128, 3
    keys = jax.random.split(jax.random.PRNGKey(n), 2)
    xs = jax.random.normal(keys[0], (m, k))
    w = jax.random.normal(keys[1], (g, n, k) if transposed else (g, k, n))
    groups = jnp.array([20, 0, 30], jnp.int32)
    assert moe._tiling(m, k, n) is not None
    before = attention.attention_path_counts().get("experts_grouped_pallas", 0)
    got = moe.grouped_matmul(xs, w, groups, transposed=transposed,
                             interpret=True)
    assert attention.attention_path_counts()["experts_grouped_pallas"] \
        == before + 1
    want = jax.lax.ragged_dot(xs, jnp.swapaxes(w, 1, 2) if transposed else w,
                              groups)
    assert got.shape == (m, n)
    assert np.abs(np.asarray(got)[:50] - np.asarray(want)[:50]).max() < 1e-4

"""dots.vlm1.inst's language model (arch `dots`: latent attention, YaRN, a
leading dense layer, a sigmoid group-limited router over experts of which a
SHARE is held, a shared expert) at small float32 widths on the CPU: the
program against `benchmark/reference_dots.py`, its two kernels in interpret
mode against their reference paths, the share against the uncut layer, and
the refusals. (The other models' programs against the parent's, which
this model's PR pinned first: tests/test_parents_programs.py.)

Tolerance: program and reference compute the same mathematics in float32 and
differ in the order of their sums; LOGIT_TOL 2e-4 is the one test_olmoe.py,
test_keye.py and test_jamba.py hold the same pairs to.
"""

import functools
import math
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models, reference_dots
from ray_tpu.models import llama, serving
from ray_tpu.models.block import (fuse_qkv, latent_attention_inputs,
                                  latent_attention_output, split_qkv)
from ray_tpu.models.serving import prefill_core
from ray_tpu.ops import attention, moe, paged_kv
from ray_tpu.ops.norms import apply_rope, yarn_inv_frequencies
from ray_tpu.serve.engine import Engine

LOGIT_TOL = 2e-4
F32 = {"params": "float32", "activations": "float32"}
PUBLISHED = dict(rope_theta=10000, rms_norm_eps=1e-6, n_shared_experts=1,
                 norm_topk_prob=True, routed_scaling_factor=2.5,
                 scoring_func="sigmoid", topk_method="noaux_tc")
MATMULS = ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo", "w_gate", "w_up", "w_down",
           "ws_gate", "ws_up", "ws_down")


def _tiny(max_seq=256, **more):
    """(adapter, model, cfg, params) at the adapter's rehearsal widths, with
    weights that decide (at the init's 0.02 every logit is a near-tie):
    matmuls x 8, the router x 40, the embedding spread."""
    adapter = models.adapter("dots")
    model = dict(adapter.REHEARSE, **PUBLISHED, **more)
    cfg = adapter.build_config(model, F32, max_seq)
    params = dict(adapter.init_params(cfg, 3))
    for stack in ("layers", "dense"):
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
# YaRN, the router
# ---------------------------------------------------------------------------

def test_yarn_frequencies_are_the_closed_form():
    """64 rotary dims, theta 1e4, factor 40 over 4,096 trained positions,
    beta 32 and 1: pairs under 10 turn as trained, pairs from 23 on a
    fortieth as fast, a linear ramp between; the program's, the reference's
    and the numbers worked by hand agree."""
    got = np.asarray(yarn_inv_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0))
    lo = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                    / (2 * math.log(10000)))
    hi = math.ceil(64 * math.log(4096 / (2 * math.pi))
                   / (2 * math.log(10000)))
    assert (lo, hi) == (10, 23)
    f = 10000.0 ** (-np.arange(32) / 32.0)
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    want = f / 40 * ramp + f * (1 - ramp)
    assert got.shape == (32,) and np.allclose(got, want, rtol=1e-6)
    assert np.allclose(got[:11], f[:11], rtol=1e-6)
    assert np.allclose(got[23:], f[23:] / 40, rtol=1e-6)
    m = {"qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40,
        "original_max_position_embeddings": 4096}}
    assert np.allclose(np.asarray(reference_dots.yarn_inv_freq(m)), want,
                       rtol=1e-6)
    cfg = llama.LlamaConfig(kv_lora_rank=512, q_lora_rank=1536,
                            qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
                            rope_yarn=(40.0, 4096, 32.0, 1.0, 1.0))
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert (0.1 * math.log(40) + 1) == pytest.approx(1.3689, abs=1e-4)


def _scores(rows):
    """[tokens, 8] sigmoid scores -> logits whose sigmoid they are."""
    s = np.asarray(rows, np.float64)
    return jnp.asarray(np.log(s / (1 - s)), jnp.float32)


ROUTER_CASES = {
    # 8 experts, 4 groups of 2, 2 groups kept, 2 experts a token
    "a bias chooses and does not weigh": dict(
        s=[[.9, .1, .8, .1, .7, .1, .1, .1]],
        bias=[0, 0, 0, 0, .3, 0, 0, 0], chosen=[[0, 4]],
        weights=[[.9 / 1.6, .7 / 1.6]]),
    "a group loses though it holds the single best expert": dict(
        s=[[.95, .01, .6, .6, .5, .55, .1, .1]],
        bias=[0] * 8, chosen=[[2, 3]], weights=[[.5, .5]]),
    "a tie goes to the smaller index": dict(
        s=[[.5, .5, .5, .5, .5, .5, .5, .5]],
        bias=[0] * 8, chosen=[[0, 1]], weights=[[.5, .5]]),
}


@pytest.mark.parametrize("case", sorted(ROUTER_CASES))
@pytest.mark.parametrize("factor", [1.0, 2.5])
def test_router_on_hand_built_scores(case, factor):
    """The program's `top_k_routing` and the reference's
    `combine_from_scores` against choices and weights worked by hand; the
    weights sum to `routed_scaling_factor`."""
    c = ROUTER_CASES[case]
    bias = jnp.asarray(c["bias"], jnp.float32)
    w, idx = moe.top_k_routing(_scores(c["s"]), 2, True, score="sigmoid",
                               bias=bias, n_group=4, topk_group=2,
                               scale=factor)
    order = np.argsort(np.asarray(idx), axis=-1)
    got_idx = np.take_along_axis(np.asarray(idx), order, -1)
    got_w = np.take_along_axis(np.asarray(w), order, -1)
    assert got_idx.tolist() == c["chosen"]
    assert np.allclose(got_w, factor * np.asarray(c["weights"]), atol=1e-6)
    assert np.allclose(got_w.sum(-1), factor, atol=1e-6)
    combine = np.asarray(reference_dots.combine_from_scores(
        jnp.asarray(c["s"], jnp.float32), bias, 2, 4, 2, True, factor))
    want = np.zeros((1, 8))
    want[0, c["chosen"][0]] = factor * np.asarray(c["weights"][0])
    assert np.allclose(combine, want, atol=1e-6)


def test_softmax_routing_is_what_it_was():
    logits = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    w, idx = moe.top_k_routing(logits, 2, False)
    p = jax.nn.softmax(logits, -1)
    want_w, want_idx = jax.lax.top_k(p, 2)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    assert (np.asarray(w) == np.asarray(want_w)).all()
    with pytest.raises(ValueError, match="router score"):
        moe.top_k_routing(logits, 2, score="tanh")


# ---------------------------------------------------------------------------
# The share adds up
# ---------------------------------------------------------------------------

def _sparse_layer(tiny, tokens=48, seed=1):
    _, model, cfg, params = tiny
    lp = {k: v[1] for k, v in params["layers"].items()}
    g = jax.random.normal(jax.random.PRNGKey(seed), (tokens, cfg.d_model))
    return model, cfg, lp, g


@pytest.mark.timeout(240)
def test_the_shares_parts_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    """16 experts in 4 shares of 4: every share in turn holds its 4 experts'
    weights (drawn here for all 16), routes over all 16 and computes its
    part; the four parts and the shared expert ONCE are what the uncut
    reference gives for the whole layer, and no part is nothing."""
    model, cfg, lp, g = _sparse_layer(tiny)
    total, n = cfg.n_experts, cfg.n_held
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    full = {name: 0.16 * jax.random.normal(
        k, (total,) + lp[name].shape[1:]) for name, k in
        zip(("w_gate", "w_up", "w_down"), ks)}
    routing = dict(cfg.routing(), bias=lp["router_bias"])
    parts = []
    for share in range(total // n):
        mine = {k: w[share * n:(share + 1) * n] for k, w in full.items()}
        out, _, counts = moe.moe_ffn(
            g, lp["router"], mine["w_up"], mine["w_gate"], mine["w_down"],
            top_k=cfg.top_k_experts, norm_topk_prob=True, routing=routing,
            held=(share * n, n))
        assert counts.shape == (n,)
        parts.append(np.asarray(out))
        # the reference's part of the same share
        want = reference_dots.routed_part(g, dict(lp, **mine), model,
                                          (share * n, n), total)
        assert np.abs(parts[-1] - np.asarray(want)).max() < 1e-4
        assert np.abs(parts[-1]).max() > 1e-2
    shared = np.asarray(reference_dots.shared_part(g, lp))
    whole = reference_dots.routed_part(g, dict(lp, **full), model,
                                       (0, total), total)
    assert np.abs(sum(parts) + shared - (np.asarray(whole) + shared)).max() \
        < 2e-4
    # and the program's own layer with a shared expert adds it once
    mine = {k: w[:n] for k, w in full.items()}
    with_shared, _, _ = moe.moe_ffn(
        g, lp["router"], mine["w_up"], mine["w_gate"], mine["w_down"],
        top_k=cfg.top_k_experts, routing=routing, held=(0, n),
        shared=(lp["ws_gate"], lp["ws_up"], lp["ws_down"]))
    assert np.abs(np.asarray(with_shared) - parts[0] - shared).max() < 1e-4


@pytest.mark.parametrize("block_rows", [1, 2])
def test_a_share_walked_in_several_blocks_is_the_share_in_one(
        tiny, block_rows, monkeypatch):
    """`_share_experts` walks the sorted assignments in blocks of
    `_SHARE_BLOCK` times the even load and goes on while a block still holds
    a local row: with 2 of 16 experts held and blocks of 1x and 2x the even
    load (24 and 48 of 192 rows) a skewed router needs several; the result
    and the counts are those of one block that holds every row."""
    model, cfg, lp, g = _sparse_layer(tiny, tokens=48)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    mine = [0.16 * jax.random.normal(k, (2,) + lp[name].shape[1:])
            for name, k in zip(("w_up", "w_gate", "w_down"), ks)]
    # a bias that sends almost every token to experts 4 and 5
    bias = jnp.zeros(16).at[4:6].set(0.6)
    routing = dict(cfg.routing(), bias=bias)
    args = dict(top_k=4, routing=routing, held=(4, 2))
    monkeypatch.setattr(moe, "_SHARE_BLOCK", 100)
    want, _, want_counts = moe.moe_ffn(g, lp["router"], *mine, **args)
    assert int(want_counts.sum()) > 48      # more than one block of either
    monkeypatch.setattr(moe, "_SHARE_BLOCK", block_rows)
    got, _, counts = moe.moe_ffn(g, lp["router"], *mine, **args)
    assert (np.asarray(counts) == np.asarray(want_counts)).all()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("case", ["no-local-assignment", "every-one-local",
                                  "rows-in-no-group-are-NaN"])
def test_a_shares_combine_at_the_edges_off_the_chip(tiny, case, monkeypatch):
    """The combine that stands off the chip (a gather back to token-major;
    tests/test_grouped_matmul.py holds the chip's kernel to the same cases):
    a share nobody chose gives exact zeros, one every token chose with all
    its k (four blocks of the even load) gives what one block gives, and the
    rows a grouped matmul leaves in no group may hold NaN."""
    model, cfg, lp, g = _sparse_layer(tiny, tokens=48)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    mine = [0.16 * jax.random.normal(k, (4,) + lp[name].shape[1:])
            for name, k in zip(("w_up", "w_gate", "w_down"), ks)]
    lean = {"no-local-assignment": -5.0, "every-one-local": 5.0,
            "rows-in-no-group-are-NaN": 0.3}[case]
    routing = dict(cfg.routing(), bias=jnp.zeros(16).at[4:8].set(lean))
    args = dict(top_k=4, routing=routing, held=(4, 4))
    before = attention.attention_path_counts().get("share_combine_gather", 0)
    got, _, counts = moe.moe_ffn(g, lp["router"], *mine, **args)
    assert attention.attention_path_counts()["share_combine_gather"] \
        == before + 1
    if case == "no-local-assignment":
        assert int(counts.sum()) == 0 and not np.asarray(got).any()
        monkeypatch.setattr(moe, "_SHARE_BLOCK", 100)   # a block, walked
        got, _, _ = moe.moe_ffn(g, lp["router"], *mine, **args)
        assert not np.asarray(got).any()
    elif case == "every-one-local":
        assert int(counts.sum()) == 48 * 4
        monkeypatch.setattr(moe, "_SHARE_BLOCK", 1)     # 48 rows a block
        cut, _, cut_counts = moe.moe_ffn(g, lp["router"], *mine, **args)
        assert (np.asarray(cut_counts) == np.asarray(counts)).all()
        assert np.abs(np.asarray(cut) - np.asarray(got)).max() < 1e-5
        assert np.abs(np.asarray(got)).max() > 1e-2
    else:
        assert 0 < int(counts.sum()) < 96       # rows in no group at the end
        real = moe.grouped_matmul
        monkeypatch.setattr(moe, "grouped_matmul", lambda xs, w, groups, **kw: (
            jnp.where((jnp.arange(xs.shape[0]) < jnp.sum(groups))[:, None],
                      real(xs, w, groups, **kw), jnp.nan)))
        poisoned, _, _ = moe.moe_ffn(g, lp["router"], *mine, **args)
        assert np.array_equal(np.asarray(poisoned), np.asarray(got))
        assert np.abs(np.asarray(got)).max() > 1e-2


# ---------------------------------------------------------------------------
# The absorbed form, the kernels
# ---------------------------------------------------------------------------

def _rope_tables(cfg, n):
    from ray_tpu.models.serving import latent_rope_tables
    return latent_rope_tables(cfg, n)


@pytest.mark.timeout(240)
def test_the_absorbed_decode_form_is_the_naive_form(tiny):
    """The last token of a sequence through the prompt's form (keys and
    values up-projected a head, `latent_flash_attention`) and through the
    decode step's (the key up-projection absorbed into q, attention over the
    cached latent rows, the value up-projection after): one output."""
    _, _, cfg, params = tiny
    lp = {k: v[0] for k, v in params["layers"].items()}
    S = 40
    x = jax.random.normal(jax.random.PRNGKey(2), (1, S, cfg.d_model))
    cos, sin = _rope_tables(cfg, S)
    q_n, q_r, k_n, v, c, kr = latent_attention_inputs(
        lp, x, cfg, lambda t: apply_rope(t, cos, sin))
    naive = attention.latent_flash_attention(q_n, q_r, k_n, kr, v,
                                             cfg.softmax_scale)
    naive = naive[0, :, -1].reshape(1, -1)                  # [1, H * dv]

    def rope_last(t):       # [1, heads, dr] at position S - 1
        return apply_rope(t[:, :, None], cos, sin,
                          jnp.asarray([S - 1]))[:, :, 0]

    served = fuse_qkv({"layers": {k: w[None] for k, w in lp.items()}}, cfg)
    slp = {k: w[0] for k, w in served["layers"].items()}
    ql, q_r1, c1, kr1 = latent_attention_inputs(slp, x[0, -1:], cfg,
                                                rope_last, absorb=True)
    assert np.abs(np.asarray(c1) - np.asarray(c[0, -1:])).max() < 1e-5
    assert np.abs(np.asarray(kr1) - np.asarray(kr[0, -1:])).max() < 1e-5
    page = 16
    arena = paged_kv.empty_latent(1, 4, page, cfg.latent_width, jnp.float32)
    assert arena.shape[-1] == 128       # 40 numbers in rows of 128 lanes
    arena = paged_kv.write_prompt_rows(
        arena, jnp.asarray([1, 2, 3]),
        paged_kv.latent_rows(c, kr, arena))
    ol = paged_kv.paged_latent_decode(
        ql, q_r1, arena, 0, jnp.asarray([[1, 2, 3]]), jnp.asarray([S]),
        sm_scale=cfg.softmax_scale)
    absorbed = latent_attention_output(slp, ol, cfg)
    assert np.abs(np.asarray(absorbed) - np.asarray(naive)).max() < 1e-5
    assert np.abs(np.asarray(naive)).max() > 1e-2
    # the serving layout cuts the published matrices and joins them back
    back = split_qkv(served, cfg)["layers"]
    assert all((np.asarray(back[k][0]) == np.asarray(lp[k])).all()
               for k in ("w_uq", "w_ukv"))


@pytest.mark.parametrize("lengths", [(70, 0, 128), (1, 33, 64)])
def test_paged_latent_decode_kernel_is_its_reference_path(lengths):
    """The Pallas kernel, interpreted, against the XLA gather: 3 slots (a
    short one, an idle one, a full table), 128 heads... here 8, rows of 256 +
    64 in 384 lanes, blocks of 2 pages so that a slot walks several."""
    ns, H, rank, dr, page, maxp = 3, 8, 256, 64, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    arena = paged_kv.empty_latent(2, 1 + ns * maxp, page, rank + dr,
                                  jnp.float32)
    rows = jax.random.normal(ks[0], arena.shape[1:3] + (rank + dr,))
    arena = arena.at[1, :, :, :rank + dr].set(rows)
    bt = 1 + jnp.arange(ns * maxp, dtype=jnp.int32).reshape(ns, maxp)
    ql = jax.random.normal(ks[1], (ns, H, rank))
    q_r = jax.random.normal(ks[2], (ns, H, dr))
    args = (ql, q_r, arena, 1, bt, jnp.asarray(lengths, jnp.int32))
    before = attention.attention_path_counts()
    want = paged_kv.paged_latent_decode(*args, sm_scale=0.05)
    got = paged_kv.paged_latent_decode(*args, sm_scale=0.05,
                                       pages_per_block=2, interpret=True)
    after = attention.attention_path_counts()
    assert after["latent_decode_reference"] \
        == before.get("latent_decode_reference", 0) + 1
    assert after["latent_decode_pallas"] \
        == before.get("latent_decode_pallas", 0) + 1
    assert got.shape == (ns, H, rank)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    idle = [i for i, n in enumerate(lengths) if n == 0]
    assert all(not np.asarray(got[i]).any() for i in idle)
    assert np.abs(np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("S", [128, 384])
def test_latent_flash_kernel_is_its_reference_path(S):
    """Queries and keys of 128 + 64 (the 64 ONE key for all heads), values of
    128: the Pallas kernel, interpreted, against the XLA path."""
    H, dn, dr, dv = 2, 128, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q_n, k_n = (jax.random.normal(k, (1, H, S, dn)) for k in ks[:2])
    q_r = jax.random.normal(ks[2], (1, H, S, dr))
    k_r = jax.random.normal(ks[3], (1, S, dr))
    v = jax.random.normal(ks[4], (1, H, S, dv))
    before = attention.attention_path_counts()
    want = attention.latent_flash_attention(q_n, q_r, k_n, k_r, v, 0.07)
    got = attention.latent_flash_attention(q_n, q_r, k_n, k_r, v, 0.07,
                                           interpret=True)
    after = attention.attention_path_counts()
    assert after["latent_fwd_pallas"] == before.get("latent_fwd_pallas", 0) + 1
    assert got.shape == (1, H, S, dv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    # against plain attention over the joined widths
    q = jnp.concatenate([q_n, q_r], -1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r[:, None], q_r.shape)], -1)
    plain = attention.attention_reference(q, k, v, sm_scale=0.07)
    assert np.abs(np.asarray(want) - np.asarray(plain)).max() < 2e-5


# ---------------------------------------------------------------------------
# Through the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(tiny):
    """The engine with both latent-attention kernels interpreted: what
    `build_programs` and the prefill core import is the function with its
    `interpret` argument set."""
    _, _, cfg, params = tiny
    mp = pytest.MonkeyPatch()
    mp.setattr(paged_kv, "paged_latent_decode", functools.partial(
        paged_kv.paged_latent_decode, interpret=True))
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                 decode_chunk=4, page_size=16)
    mp.undo()
    yield eng
    eng.stop()


@pytest.mark.parametrize("n,bucket", [(30, 32), (100, 128)])
def test_prefill_then_decode_through_the_latent_cache_is_the_reference(
        tiny, engine, n, bucket):
    """A prompt of 30 (bucket 32) and of 100 tokens (bucket 128, 28 rows of
    padding), then 24 tokens decoded through the paged latent cache across
    page boundaries (pages of 16) in the absorbed form, the decode kernel
    interpreted: the prefill's logits are the reference's at the prompt's
    last position, and every served token is the reference's largest logit
    to float32 rounding."""
    adapter, model, cfg, params = tiny
    prompt = _tokens(n, n)
    ref = adapter.reference()
    _, rows, vs, logits, experts = jax.jit(prefill_core(cfg))(
        fuse_qkv(params, cfg),
        jnp.asarray([prompt + [0] * (bucket - n)], jnp.int32), n)
    want = np.asarray(ref.logits_last(params, model, prompt, 1))[0]
    assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
    assert vs is None and rows.shape == (cfg.n_layers, bucket,
                                         cfg.latent_width)
    held = cfg.n_held
    assert experts.shape == (held + 2,)
    assert int(experts[-1]) == n * cfg.top_k_experts * 2    # 2 sparse layers
    assert int(experts[:held].sum()) <= int(experts[-1])
    before = engine.counters()
    served = _drain(engine.submit(prompt, 24))
    want = (n + 23) * cfg.top_k_experts * 2     # 2 sparse layers
    deadline = time.monotonic() + 30
    while True:     # the emitter counts a chunk's routing AFTER its tokens
        after = engine.counters()
        if after["routed_assignments"] - before["routed_assignments"] >= want \
                or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    assert len(served) == 24
    gaps = ref.served_token_gaps(params, model, prompt, served)
    assert max(gaps) < LOGIT_TOL, gaps
    # teeth: against the prompt less its last token the same tokens are
    # another row's
    short = ref.served_token_gaps(params, model, prompt[:-1], served)
    assert max(short) > 100 * LOGIT_TOL
    routed = after["routed_assignments"] - before["routed_assignments"]
    local = after["local_assignments"] - before["local_assignments"]
    assert routed >= want and 0 < local < routed
    assert sum(after["expert_tokens"]) == after["local_assignments"]
    assert len(after["expert_tokens"]) == held
    assert after["latent_cache_bytes"] == engine._caches.kc.nbytes \
        and engine._caches.vc is None


@pytest.mark.parametrize("riding", [(True, True), (False, True)],
                         ids=["both-ride", "one-rides"])
def test_a_riding_prefills_tail_rows_are_one_decode_step_and_the_references(
        tiny, monkeypatch, riding):
    """Two slots hold prompts of 40 and 23 tokens; a prompt of 100 is then
    prefilled in the riding rung 128 (`max_seq` 256) with the slots' next
    tokens in its last two rows, the decode kernel interpreted. The logits of
    a riding slot's row are those of ONE decode step from the same caches and
    the float32 reference's at that position, the prompt's own row is the
    reference's too, the slots' `last` and `pos` move as the step moves them,
    and every layer of the arena holds in the slots' pages what the step
    leaves there (the dense layer is the arena's layer 0, the sparse ones 1
    and 2). A slot that does not ride stands still: its pages, `last` and
    `pos` are what they were."""
    adapter, model, cfg, params = tiny
    ns, page, V = 2, 16, cfg.vocab_size
    maxp = cfg.max_seq // page
    monkeypatch.setattr(paged_kv, "paged_latent_decode", functools.partial(
        paged_kv.paged_latent_decode, interpret=True))
    seen = []
    sample = serving.sample_tokens

    def spy(logits, *how):      # every program samples through the module's
        jax.debug.callback(lambda l: seen.append(np.asarray(l)), logits)
        return sample(logits, *how)

    monkeypatch.setattr(serving, "sample_tokens", spy)
    programs = serving.build_programs(cfg, ns, 1, page, 3 * maxp + 1)
    assert programs.takes_riders
    fused = fuse_qkv(params, cfg)
    bt = jnp.arange(1, 1 + ns * maxp, dtype=jnp.int32).reshape(ns, maxp)
    key = jnp.zeros(2, jnp.uint32)

    def prefill(caches, pages, prompt, bucket, *slots):
        return programs.prefill(
            fused, caches, pages,
            jnp.asarray([prompt + [0] * (bucket - len(prompt))], jnp.int32),
            len(prompt), 0.0, 0, key, None, *slots)

    prompts = [_tokens(40, 1), _tokens(23, 2)]
    caches, firsts = programs.empty(), []
    for slot, (prompt, bucket) in enumerate(zip(prompts, (64, 32))):
        caches, first, _ = prefill(caches, bt[slot], prompt, bucket)
        firsts.append(int(first))
    # (host arrays: both programs are handed, and donate, their own copies)
    last, pos = np.asarray(firsts, np.int32), np.asarray([40, 23], np.int32)
    sampling = (jnp.zeros(ns), jnp.zeros(ns, jnp.int32),
                jnp.zeros((ns, 2), jnp.uint32))
    marks = jnp.asarray(riding)
    arena = np.asarray(caches.kc)
    jax.effects_barrier()
    seen.clear()
    third = _tokens(100, 3)
    rode, _, _, last_r, pos_r, toks = prefill(
        jax.tree.map(jnp.copy, caches),
        jnp.arange(1 + ns * maxp, 1 + 3 * maxp, dtype=jnp.int32), third, 128,
        jnp.asarray(last), jnp.asarray(pos), (bt, marks, *sampling))
    stepped, last_d, pos_d, out, _ = programs.decode(
        fused, jax.tree.map(jnp.copy, caches), bt, jnp.asarray(last),
        jnp.asarray(pos), marks, *sampling)
    jax.effects_barrier()
    riders, step = seen
    assert riders.shape == (1 + ns, V) and step.shape == (ns, V)
    ref = adapter.reference()
    own = np.asarray(ref.logits_last(params, model, third, 1))[0]
    assert np.abs(riders[0] - own).max() < LOGIT_TOL
    for slot, rides in enumerate(riding):
        mine = np.asarray(bt[slot])
        if not rides:
            assert np.array_equal(np.asarray(rode.kc)[:, mine],
                                  arena[:, mine])
            continue
        assert np.abs(riders[1 + slot] - step[slot]).max() < LOGIT_TOL
        want = np.asarray(ref.logits_last(
            params, model, prompts[slot] + [firsts[slot]], 1))[0]
        assert np.abs(riders[1 + slot] - want).max() < LOGIT_TOL
        assert int(toks[slot]) == int(out[slot, 0]) == int(want.argmax())
        # the step's row, in every layer, and nothing else of the slot's
        assert np.abs(np.asarray(rode.kc)[:, mine]
                      - np.asarray(stepped.kc)[:, mine]).max() < LOGIT_TOL
        assert np.abs(np.asarray(rode.kc)[:, mine] - arena[:, mine]).max() > 0
    assert np.array_equal(np.asarray(last_r), np.asarray(last_d))
    assert np.array_equal(np.asarray(pos_r), np.asarray(pos_d))
    assert np.array_equal(np.asarray(pos_r), pos + np.asarray(riding))


def test_the_engine_took_the_latent_paths(engine):
    counts = attention.attention_path_counts()
    assert counts["latent_decode_pallas"] >= 1      # interpreted, in decode
    assert counts["latent_fwd_reference"] >= 1      # the CPU's prefill path
    # a share's combine: off the chip the gather (the chip's kernel, counted
    # as `share_combine_local`: tests/test_tpu_compile.py)
    assert counts["share_combine_gather"] >= 1
    assert engine._caches.kc.shape[-1] == 128 and engine._caches.kc.ndim == 4


def test_a_pd_handoff_and_the_training_forward_refuse_latent_attention_by_name(
        tiny, engine):
    _, _, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="latent attention"):
        engine.submit_prefilled(None, None, 4, 1, 4)
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="serves only"):
        reference_dots.loss_and_check_grads(params, {}, None)


@pytest.mark.parametrize("change,said", [
    (dict(num_nextn_predict_layers=1), "multi-token-prediction"),
    (dict(scoring_func="softmax"), "sigmoid"),
    (dict(tie_word_embeddings=True), "tied"),
    (dict(q_lora_rank=None), "q_lora_rank"),
    (dict(expert_parallel={"chips": 3, "rank": 0,
                           "routed_experts_total": 16}), "expert_parallel"),
    (dict(rope_scaling=None), "yarn"),
    (dict(first_k_dense_replace=0), "first_k_dense_replace"),
], ids=["mtp", "softmax-router", "tied", "no-q-latent", "share", "no-yarn",
        "no-dense"])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    adapter = models.adapter("dots")
    model = {**adapter.REHEARSE, **PUBLISHED, **change}
    with pytest.raises(ValueError, match=said):
        adapter.build_config(model, F32, 128)


def test_the_configuration_is_the_catalogs_row_cut_to_a_share():
    """benchmark/configs/dots.vlm1.inst-serve.json: every published width
    unchanged, the five reduced keys with what was published, the share in
    words and numbers; the counts follow it."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "dots.vlm1.inst-serve.json")) as f:
        m = json.load(f)
    assert {k: m[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "n_group", "topk_group", "n_shared_experts")} == dict(
        hidden_size=7168, num_attention_heads=128, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
        num_experts_per_tok=8, n_group=8, topk_group=4, n_shared_experts=1)
    assert {k: (v["published"], v["run"]) for k, v in m["reduced"].items()} \
        == dict(num_hidden_layers=(61, 5), first_k_dense_replace=(3, 1),
                n_routed_experts=(256, 16), vocab_size=(129280, 16160),
                num_nextn_predict_layers=(1, 0))
    assert all(m[k] == v["run"] for k, v in m["reduced"].items())
    assert m["expert_parallel"]["chips"] * m["n_routed_experts"] \
        == m["expert_parallel"]["routed_experts_total"] == 256
    assert len(m["source"]) <= 200
    counts = models.adapter("dots").counts
    assert counts.attention_params(m) == 187_105_280
    assert counts.total_params(m) == pytest.approx(4_565.6e6, rel=1e-3)
    assert counts.expected_local(m) == 0.5
    cfg = models.adapter("dots").build_config(m, m["dtypes"], 4096)
    assert cfg.experts_held == (0, 16) and cfg.n_experts == 256
    assert cfg.latent_width == 576
    assert cfg.segments() == (("dense", 0, 1), ("layers", 0, 4))
    ops, byts = counts.latent_decode_ops_bytes(m, [1000], 2)
    assert ops / (1000 * 1152) == pytest.approx(241.8, abs=0.1)

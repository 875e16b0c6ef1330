"""SDAR's generation by diffusion over blocks (a Qwen3-MoE decoder under the
block mask, a step of `block_length` rows a slot, tokens committed by
confidence) through the kernels, the paged cache and the serving engine,
against the benchmark's plain reference (benchmark/reference_sdar.py: the
published equations in float32, no cache, the whole sequence recomputed at
every forward), on seeded random weights at a small size on the CPU: the
adapter's REHEARSE widths (2 layers, hidden 64, 4 heads of 32 and 2 kv heads;
8 experts of width 32, 2 a token; vocabulary 256, the mask id its last row).

Tolerances. Program and reference compute the same mathematics in float32
and differ in the order of their sums, so logits of size ~1 agree to a few
1e-6; a served token's GAP (the reference's largest logit less its logit of
that token, in the state the token was committed from) is then 0 unless two
logits lie closer than that, and GAP_TOL 2e-4 leaves room for such a
near-tie and none for a wrong row, mask or commit (which read 0.1 to 3 here:
`test_served_token_gaps_finds_the_steps_and_a_wrong_commit`).

Here: (a) the block mask, (b) a block of rows a slot through the paged cache,
(f) the adapter and the counts, and the helpers; (c)-(e), the engine, are
tests/test_sdar_engine.py.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from ray_tpu.models import llama
from ray_tpu.ops import attention, paged_kv

GAP_TOL = 2e-4

ADAPTER = models.adapter("sdar")
F32 = {"params": "float32", "activations": "float32"}
BF16 = {"params": "bfloat16", "activations": "bfloat16"}


def _model(B=4, T=2):
    return dict(ADAPTER.REHEARSE, rope_theta=1000000, rms_norm_eps=1e-6,
                norm_topk_prob=True, block_length=B, denoise_steps=T)


def _params(cfg, seed=3):
    """Seeded weights with every norm off one, a router that decides and
    logits that spread (at the init's 0.02 they are near-ties)."""
    params = ADAPTER.init_params(cfg, seed)
    lay = dict(params["layers"])
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        lay[name] = (1.0 + 0.2 * jax.random.normal(
            next(keys), lay[name].shape)).astype(lay[name].dtype)
    lay["router"] = lay["router"] * 40.0
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        lay[name] = lay[name] * 8.0
    return dict(params, layers=lay, lm_head=params["lm_head"] * 8.0,
                final_norm=(1.0 + 0.2 * jax.random.normal(
                    next(keys), params["final_norm"].shape)
                ).astype(params["final_norm"].dtype))


# ---------------------------------------------------------------------------
# (a) the block mask
# ---------------------------------------------------------------------------

def _qkv(S, seed=0, heads=2, hd=32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (1, heads, S, hd), jnp.float32) for k in keys]


def _kernel(q, k, v, block):
    """The flash forward kernel, interpreted, in tiles of 64 x 64."""
    return attention._flash_fwd_pallas(
        q, k, v, causal=True, sm_scale=q.shape[-1] ** -0.5, block_q=64,
        block_k=64, interpret=True, block=block)[0]


@pytest.mark.parametrize("B", [2, 4, 8])
@pytest.mark.parametrize("length", [72, 100, 184])
def test_block_mask_kernel_is_the_reference(B, length):
    """The interpreted kernel over a bucket of 192 rows (3 x 3 tiles) agrees
    with `attention_reference` over the prompt's whole blocks alone, for
    prompts that end inside a tile: a row sees nothing after its block, so
    what lies past the prompt reaches no row of it."""
    q, k, v = _qkv(192)
    got = _kernel(q, k, v, B)
    whole = length // B * B
    want = attention.attention_reference(
        *(t[:, :, :whole] for t in (q, k, v)), block=B)
    np.testing.assert_allclose(got[:, :, :whole], want, atol=2e-6)
    # the XLA path of the public entry is the same function
    np.testing.assert_allclose(
        attention.block_flash_attention(q, k, v, B)[:, :, :whole], want,
        atol=2e-6)
    # and the mask is not the causal one
    assert np.abs(got - _kernel(q, k, v, 1)).max() > 1e-2


def test_block_one_is_the_causal_kernel_to_the_bit_and_to_the_text():
    q, k, v = _qkv(128, seed=1)
    scale = q.shape[-1] ** -0.5

    def kernel(**kw):
        def run(q, k, v):
            return attention._flash_fwd_pallas(
                q, k, v, causal=True, sm_scale=scale, interpret=True, **kw)
        return run

    causal, block_one = kernel(), kernel(block=1)

    for a, b in zip(causal(q, k, v), block_one(q, k, v)):
        assert np.array_equal(a, b)
    assert jax.jit(causal).lower(q, k, v).as_text() \
        == jax.jit(block_one).lower(q, k, v).as_text()
    assert np.array_equal(
        attention.attention_reference(q, k, v),
        attention.attention_reference(q, k, v, block=1))


@pytest.mark.parametrize("path", ["kernel", "reference"])
def test_a_changed_key_reaches_its_block_and_the_later_ones(path):
    """Changing position j's key and value changes every row of j's block
    (the rows BEFORE j in it too: a block sees itself both ways) and of every
    later block, and no row of an earlier block."""
    B, j = 4, 70
    q, k, v = _qkv(128, seed=2)
    run = (lambda *a: _kernel(*a, B)) if path == "kernel" else (
        lambda *a: attention.attention_reference(*a, block=B))
    base = run(q, k, v)
    moved = run(q, k.at[:, :, j].add(1.0), v.at[:, :, j].add(1.0))
    changed = np.abs(np.asarray(moved - base)).max(axis=(0, 1, 3)) > 1e-6
    first = j // B * B
    assert not changed[:first].any() and changed[first:].all()


# ---------------------------------------------------------------------------
# (b) a block of rows a slot through the paged cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_blocks_through_the_pages_are_plain_attention(interpret):
    """Blocks of 4 rows a slot written one after the other over a page
    boundary (pages of 16; slot 0 walks positions 8..23), beside an idle slot
    whose rows go to the null page: each block's rows attend to every
    position up to their block's end, and read what plain attention over the
    slot's rows so far reads."""
    B, KVH, H, hd, page = 4, 2, 4, 128, 16
    ns, n_pages = 3, 9
    kc, vc = paged_kv.empty(1, n_pages, KVH, page, hd, jnp.float32)
    bt = jnp.asarray([[3, 5, 0], [0, 0, 0], [7, 2, 0]], jnp.int32)
    act = jnp.asarray([True, False, True])
    rng = np.random.default_rng(0)
    ks = rng.standard_normal((ns, 24, KVH, hd)).astype(np.float32)
    vs = rng.standard_normal((ns, 24, KVH, hd)).astype(np.float32)
    for start in range(0, 24, B):
        w = jnp.asarray([start, 0, start], jnp.int32)
        # written twice: a denoising forward's rows, then the final ones
        for scale in (3.0, 1.0):
            kc, vc = paged_kv.write_token(
                kc, vc, 0, bt, w, act,
                jnp.asarray(scale * ks[:, start:start + B]),
                jnp.asarray(scale * vs[:, start:start + B]))
        if start < 8:
            continue
        q = jnp.asarray(rng.standard_normal((ns, B, H, hd)), jnp.float32)
        got = paged_kv.paged_decode_attention(
            q, kc, vc, 0, bt, jnp.where(act, w + B, 0), interpret=interpret)
        assert got.shape == (ns, B, H, hd)
        for s in (0, 2):
            kk = jnp.asarray(ks[s, :start + B]).transpose(1, 0, 2)[None]
            vv = jnp.asarray(vs[s, :start + B]).transpose(1, 0, 2)[None]
            want = attention.attention_reference(
                q[s].transpose(1, 0, 2)[None],
                attention.repeat_kv(kk, H // KVH),
                attention.repeat_kv(vv, H // KVH), causal=False)
            np.testing.assert_allclose(got[s].transpose(1, 0, 2)[None], want,
                                       atol=2e-5)
        assert not np.asarray(got[1]).any()          # the idle slot
    # the idle slot's rows went to the null page and nowhere else
    assert not np.asarray(kc[0, [1, 4, 6, 8]]).any()
    counts = attention.attention_path_counts()
    assert counts["block_decode_pallas" if interpret
                  else "block_decode_reference"] >= 4


@pytest.mark.parametrize("pos", [24, 32], ids=["inside", "page_start"])
@pytest.mark.parametrize("groups", [1, 8])
@pytest.mark.parametrize("B", [2, 4])
def test_two_blocks_in_one_call_are_two_calls(B, groups, pos):
    """The fused call: q [ns, 2B, H, hd], the block before the open one
    (positions pos - B..pos - 1, which sees 0..pos - 1) then the open one
    (which sees 0..pos + B - 1), `lag` = B. The interpreted kernel reads what
    two calls of the one-block kernel read (`lengths - B`, `lengths`), to the
    bit, in bfloat16 at 8 query heads a kv head (the cell's: the split of p
    doubles a kv head's rows) and in float32 at one, and what the XLA path
    reads to rounding; with `pos` at a page's first row the lagging block
    lies in the page before. An idle slot reads nothing."""
    KVH, hd, page, ns = 2, 128, 16, 3
    H = KVH * groups
    rng = np.random.default_rng(B * 100 + groups * 10 + pos)
    bt = jnp.asarray([[3, 5, 1], [0, 0, 0], [7, 2, 4]], jnp.int32)
    lengths = jnp.asarray([pos + B, 0, pos + B - page], jnp.int32)
    for dtype, tol in [(jnp.float32, 2e-5) if groups == 1
                       else (jnp.bfloat16, 2e-2)]:
        kc, vc = (jnp.asarray(rng.standard_normal((1, 9, KVH, page, hd)),
                              dtype) for _ in range(2))
        q = jnp.asarray(rng.standard_normal((ns, 2 * B, H, hd)), dtype)
        fused = paged_kv.paged_decode_attention(
            q, kc, vc, 0, bt, lengths, interpret=True, lag=B)
        two = jnp.concatenate([
            paged_kv.paged_decode_attention(
                q[:, :B], kc, vc, 0, bt, jnp.maximum(lengths - B, 0),
                interpret=True),
            paged_kv.paged_decode_attention(
                q[:, B:], kc, vc, 0, bt, lengths, interpret=True)], axis=1)
        assert fused.shape == (ns, 2 * B, H, hd)
        assert np.array_equal(np.asarray(fused, np.float32),
                              np.asarray(two, np.float32))
        xla = paged_kv.paged_decode_attention(q, kc, vc, 0, bt, lengths,
                                              lag=B)
        np.testing.assert_allclose(np.asarray(xla, np.float32),
                                   np.asarray(two, np.float32), atol=tol)
        assert not np.asarray(fused[1], np.float32).any()
        # the lag is a mask: without it the first block reads B keys more
        assert np.abs(np.asarray(paged_kv.paged_decode_attention(
            q, kc, vc, 0, bt, lengths, interpret=True)[0, :B] - two[0, :B],
            np.float32)).max() > 1e-3
    with pytest.raises(NotImplementedError, match="no row to lag"):
        paged_kv.paged_decode_attention(q[:, 0], kc, vc, 0, bt, lengths,
                                        lag=B)


def test_a_token_and_one_block_lower_to_the_parents_kernel_text():
    """The row lag is static: a call without it (a token a slot, every other
    stack's step; one block a slot, a block's forwards 2..T) lowers to the
    text it lowered to on PR 54's parent, the interpreted kernel's body
    inlined: the digests were taken there (and on this tree: equal, as the
    two trees' jaxprs of the TPU call at the cell's sizes are). Two blocks
    with the lag are another text."""
    import hashlib
    KVH, page, hd, ns = 2, 16, 128, 3
    kc, vc = paged_kv.empty(1, 9, KVH, page, hd, jnp.bfloat16)
    bt, lengths = jnp.zeros((ns, 3), jnp.int32), jnp.zeros((ns,), jnp.int32)

    def text(shape, **kw):
        return jax.jit(lambda q, kc, vc, bt, lengths:
                       paged_kv.paged_decode_attention(
                           q, kc, vc, 0, bt, lengths, interpret=True, **kw)
                       ).lower(jnp.zeros(shape, jnp.bfloat16), kc, vc, bt,
                               lengths).as_text()

    def digest(t):
        return hashlib.sha256(t.encode()).hexdigest()

    assert digest(text((ns, 4, hd))) == (
        "0abccb973a33bca65a2991d7dd695747dd0f63c62cec51cc3ad71f144aa04dee")
    one = text((ns, 4, 4, hd))
    assert digest(one) == (
        "f8bf795708cf39333acdd2307bcbf20eb4730f223eea8d29a6e2e466d64c9362")
    assert text((ns, 4, 4, hd), lag=0) == one
    assert text((ns, 4, 4, hd), lag=2) != one


# ---------------------------------------------------------------------------
# (f) the adapter and the counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("change,said", [
    ({"sliding_window": 4096}, "sliding window"),
    ({"tie_word_embeddings": True}, "tied embeddings"),
    ({"n_shared_experts": 1}, "shared expert"),
    ({"mlp_only_layers": [0]}, "dense layers"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"block_length": None}, "no block_length"),
    ({"attention_bias": True}, "attention_bias"),
])
def test_adapter_refuses_what_the_block_does_not_compute(change, said):
    with pytest.raises(ValueError, match=said):
        ADAPTER.build_config(dict(_model(), **change), F32, 128)


@pytest.mark.parametrize("kw,said", [
    (dict(index_topk=8, index_heads=2, index_head_dim=16), "indexer"),
    (dict(tie_embeddings=True), "tied head"),
    (dict(mrope_section=(4, 6, 6)), "mrope"),
    (dict(embed_scale=2.0), "multipliers"),
    (dict(denoise_steps=5), "denoise_steps"),
    (dict(block_length=3), "divides"),
    (dict(mask_id=256), "mask_id"),
    (dict(block_length=1, denoise_steps=2), "come with a block"),
])
def test_the_config_refuses_what_the_block_step_does_not_run(kw, said):
    cfg = ADAPTER.build_config(_model(), F32, 128)
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(cfg, **kw)


def test_training_and_a_chunk_of_part_blocks_are_refused_by_name():
    cfg = ADAPTER.build_config(_model(), F32, 128)
    with pytest.raises(NotImplementedError, match="block_length > 1"):
        llama.forward_with_aux({}, jnp.zeros((1, 8), jnp.int32), cfg)
    from ray_tpu.models.serving import build_programs
    with pytest.raises(ValueError, match="whole blocks"):
        build_programs(cfg, 2, 6, 16, 9)
    with pytest.raises(ValueError, match="never crosses"):
        build_programs(dataclasses.replace(cfg, block_length=8), 2, 8, 4, 9)


def test_counts_reproduce_the_issues_arithmetic():
    import json
    import os
    counts = ADAPTER.counts
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "sdar-30b-a3b-chat-serve.json")) as f:
        m = json.load(f)
    six = dict(m, num_hidden_layers=6)
    assert counts.layer_params(m) == 623_120_640            # 623.1 M
    assert round(counts.total_params(six) / 1e6) == 4361
    assert counts.forwards_per_position(m) == 0.75
    assert counts.block_pairs(m, 8) == 4 * 4 + 4 * 8
    # a forward of 64 slots x 4 rows at about 1,100 live positions a slot:
    # the issue's 0.33 TFLOP, and 9.0 GB with every expert touched
    ops, byts = counts.forward_ops_bytes(six, [1100] * 64, 2, 2,
                                         experts_touched=128)
    assert 0.32e12 < ops < 0.37e12 and 8.8e9 < byts < 9.2e9
    step = counts.decode_step_ops_bytes(six, [1100] * 64, 2, 2,
                                        experts_touched=0.75 * 128)
    assert 0.70 * byts < step[1] < 0.75 * byts


def test_the_configuration_file_holds_the_catalogs_row():
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "sdar-30b-a3b-chat-serve.json")) as f:
        m = json.load(f)
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: m[k] for k in published} == published
    assert list(m["reduced"]) == ["num_hidden_layers"]
    cut = m["reduced"]["num_hidden_layers"]
    assert cut["published"] == 48 and cut["run"] == m["num_hidden_layers"] \
        and 4 <= cut["run"] <= 7 and len(cut["decided_by"]) > 200
    assert (m["block_length"], m["denoise_steps"]) == (4, 2)
    assumed = " ".join(m["assumed"])
    for word in ("block_length 4", "denoise_steps 2", "low_confidence_static",
                 "OWN token", "mask_id", "UNVERIFIED", "q/k norm"):
        assert word in assumed, word
    eng = m["deployment"]["engine"]
    assert (eng["max_seq"], eng["n_slots"], eng["decode_chunk"],
            eng["page_size"], eng["kv_pages"]) == (2048, 64, 8, 64, 2049)
    ADAPTER.check_supported(m)

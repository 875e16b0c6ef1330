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
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models
from benchmark import reference_keye as ref
from ray_tpu.models import llama
from ray_tpu.models.block import attention_inputs, fuse_qkv
from ray_tpu.ops import norms, sparse_attention
from ray_tpu.models.serving import prefill_core
from ray_tpu.serve.engine import Engine

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


def _serve(engine, prompts, n):
    outs = [engine.submit(p, n) for p in prompts]
    served = []
    for q in outs:
        toks = []
        while (chunk := q.get(timeout=120)) is not None:
            toks += chunk
        served.append(toks)
    return served


# -- (a) the train path ------------------------------------------------------

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


# -- (b) the engine: prefill, then decode through the paged caches -----------

@pytest.fixture(scope="module")
def engine(tiny):
    cfg, params = tiny
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=4,
                 decode_chunk=4, page_size=16)
    yield eng
    eng.stop()


def test_engine_prefill_then_paged_decode_match_the_reference(tiny, engine):
    """Three slots: a prompt under top-k whose decode crosses position 32
    (where the selection starts to cut) and two page boundaries, one that
    starts past it, and one in the widest bucket. At every served position
    the token the engine chose is the reference's largest logit (its gap
    there is float32 rounding), and the logits the prefill program itself
    returns are the reference's."""
    cfg, params = tiny
    prompts = [_tokens(21, 5), _tokens(70, 6), _tokens(150, 7)]
    served = _serve(engine, prompts, 24)
    assert [len(s) for s in served] == [24, 24, 24]
    for prompt, toks in zip(prompts, served):
        gaps = ref.served_token_gaps(params, MODEL, prompt, toks)
        assert max(gaps) < LOGIT_TOL, gaps
    core = jax.jit(prefill_core(cfg))
    for prompt, width in zip(prompts, (32, 128, 256)):
        padded = jnp.asarray([prompt + [0] * (width - len(prompt))], jnp.int32)
        _, ks, _, logits, experts, iks = core(fuse_qkv(params), padded,
                                              len(prompt))
        want = _ref_logits(params, prompt, 1)[0]
        assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
        assert iks.shape == (2, width, 16) and ks.shape == (2, width, 2, 32)
        assert int(experts[:-1].sum()) == 2 * 2 * len(prompt)
    counts = engine.counters()
    # 21 -> 44 reads 22..32 keys a step and then 32; the others always 32.
    assert 0 < counts["decode_selected_keys"] < counts["decode_live_keys"]
    assert engine._caches.ic.shape == (2, engine.n_pages, 16, 16)


def test_a_wide_bucket_meets_the_experts_in_row_blocks_and_nothing_changes(
        tiny, monkeypatch):
    """An 8,192 bucket would sort 65,536 assignments at once; the prefill
    program hands the sparse feed-forward at most `_MOE_ROWS` rows at a
    time. Every row is computed from itself alone, so logits, caches and
    the experts' counts are what one pass gives."""
    from ray_tpu.models import serving
    cfg, params = tiny
    prompt = jnp.asarray([_tokens(100, 8) + [0] * 28], jnp.int32)
    whole = jax.jit(prefill_core(cfg))(fuse_qkv(params), prompt, 100)
    monkeypatch.setattr(serving, "_MOE_ROWS", 32)
    blocks = jax.jit(prefill_core(cfg))(fuse_qkv(params), prompt, 100)
    for a, b in zip(whole, blocks):
        assert np.abs(np.asarray(a, np.float32)
                      - np.asarray(b, np.float32)).max() < 1e-5
    np.testing.assert_array_equal(np.asarray(whole[4]), np.asarray(blocks[4]))


def test_indexer_keys_survive_a_slots_release_and_reuse(tiny):
    """One slot, so every request reuses the pages the last one returned,
    whose indexer keys (and K and V) past its own length are another
    request's: the same prompt served first, and again after a longer one
    has been through its pages, gives the same tokens, all the reference's."""
    cfg, params = tiny
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=1,
                 decode_chunk=4, page_size=16)
    try:
        a, b = _tokens(60, 21), _tokens(140, 22)
        first = _serve(eng, [a], 12)[0]
        other = _serve(eng, [b], 12)[0]
        again = _serve(eng, [a], 12)[0]
    finally:
        eng.stop()
    assert first == again
    for prompt, toks in ((a, first), (b, other)):
        assert max(ref.served_token_gaps(params, MODEL, prompt, toks)) \
            < LOGIT_TOL


def test_a_pd_handoff_is_refused_not_served_without_its_indexer_keys(
        tiny, engine):
    with pytest.raises(NotImplementedError, match="indexer"):
        engine.submit_prefilled(None, None, 8, 1, 4)


# -- (c) the kernels of the TPU path, interpreted ----------------------------

@pytest.mark.parametrize("ties", ["none", "some", "all"])
def test_select_kernel_picks_exactly_top_ks_set_ties_included(ties):
    """`index_select` (bisection on the scores' bits, in interpret mode
    here) against `lax.top_k` on the same scores: the same set for every
    row, where scores tie at the threshold too (integer-valued inputs make
    many equal scores; all-zero weights make every score equal, and the
    earliest positions win)."""
    S, topk, IH, Id = 256, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    qi = jax.random.normal(ks[0], (S, IH, Id))
    ki = jax.random.normal(ks[1], (S, Id))
    w = jax.random.normal(ks[2], (S, IH))
    if ties == "some":
        qi, ki, w = jnp.round(qi), jnp.round(ki), jnp.round(w)
    if ties == "all":
        w = w * 0
    scores = sparse_attention.index_scores(qi, ki, w)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    want = sparse_attention.select_mask(scores, causal, topk)
    got = sparse_attention._index_select_pallas(qi, ki, w, topk,
                                                interpret=True)
    np.testing.assert_array_equal(np.asarray(got != 0), np.asarray(want))
    assert int(want.sum(1).max()) == topk and int(want.sum(1).min()) == 1
    if ties == "all":
        assert bool(want[200, :topk].all())


def test_sparse_attention_kernels_equal_the_xla_path():
    B, KVH, G, S, hd, IH, Id, topk = 1, 2, 2, 256, 128, 4, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    q = jax.random.normal(ks[0], (B, KVH * G, S, hd))
    k = jax.random.normal(ks[1], (B, KVH, S, hd))
    v = jax.random.normal(ks[2], (B, KVH, S, hd))
    qi = jax.random.normal(ks[3], (B, S, IH, Id))
    ki = jax.random.normal(ks[4], (B, S, Id))
    w = jax.random.normal(ks[5], (B, S, IH))
    want = sparse_attention.sparse_attention(q, k, v, qi, ki, w, topk)
    got = sparse_attention.sparse_attention(q, k, v, qi, ki, w, topk,
                                            interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def _decode_case(lengths, dtype=jnp.float32, nulls=False, seed=0):
    """Four slots of a by-token arena (pages of 16, a table of 8: 128
    positions), 4 query heads over 2 kv heads of 128, 4 indexer heads of 16;
    the arena's every row random, so what a slot must not read would show."""
    ns, H, KVH, hd, IH, Id, page, maxp, L = len(lengths), 4, 2, 128, 4, 16, \
        16, 8, 2
    n_pages = 1 + ns * maxp
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    kc, vc = (jax.random.normal(k, (L, n_pages, page, KVH * hd)).astype(dtype)
              for k in ks[:2])
    ic = jax.random.normal(ks[2], (L, n_pages, page, Id)).astype(dtype)
    q = jax.random.normal(ks[3], (ns, H, hd)).astype(dtype)
    qi = jax.random.normal(ks[4], (ns, IH, Id)).astype(dtype)
    w = jax.random.normal(ks[5], (ns, IH))
    table = 1 + np.asarray(jax.random.permutation(ks[6], n_pages - 1)
                           ).reshape(ns, maxp)
    if nulls:       # as the pool leaves a table: null past the pages held
        for slot, n in enumerate(lengths):
            table[slot, -(-n // page):] = 0
    return (q, qi, w, kc, vc, ic, 1, jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("case,lengths,kw", [
    ("under top-k", [5, 20, 31, 32], {}),
    ("over top-k", [33, 64, 100, 128], {}),
    ("not a multiple of the page", [17, 45, 99, 127], {}),
    ("across a page boundary", [16, 17, 48, 49], {}),
    ("an idle slot", [0, 70, 0, 3], {}),
    ("a table with null pages", [1, 40, 0, 97], {"nulls": True}),
    ("bfloat16 caches", [5, 40, 100, 0], {"dtype": jnp.bfloat16}),
])
def test_streaming_decode_equals_the_gather(case, lengths, kw, monkeypatch):
    """`sparse_paged_decode` (interpreted here; blocks of two pages, so a
    slot walks up to four) against the XLA gather of the selected rows, on
    one indexer's scores: the same attention over the same top-32 set,
    whatever else lies in the slot's pages, the null page or its buffers."""
    monkeypatch.setattr(sparse_attention, "_STREAM_BLOCK_TOKENS", 32)
    args = _decode_case(lengths, **kw)
    before = sparse_attention.attention.attention_path_counts()
    want = sparse_attention.sparse_decode_attention(*args, 32)
    got = sparse_attention.sparse_decode_attention(*args, 32, interpret=True)
    after = sparse_attention.attention.attention_path_counts()
    assert [after.get(k, 0) - before.get(k, 0) for k in
            ("sparse_decode_gather", "sparse_decode_stream_pallas")] == [1, 1]
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = 1e-5 if got.dtype == jnp.float32 else 2e-2
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol
    idle = np.asarray(lengths) == 0
    assert not np.asarray(got, np.float32)[idle].any()


@pytest.mark.parametrize("ties", ["none", "some", "all"])
def test_decodes_mask_is_exactly_top_ks_set_ties_included(ties):
    """`decode_select_mask` against a scatter of `lax.top_k`'s indices on
    the same scores, dead positions at -inf: rows shorter than top-k, longer,
    and idle; integer-valued scores tie at the threshold, equal scores tie
    everywhere, and the earliest positions win."""
    ns, ctx, topk = 6, 256, 64
    scores = jax.random.normal(jax.random.PRNGKey(5), (ns, ctx)) * 3
    if ties == "some":
        scores = jnp.round(scores)
    if ties == "all":        # +0.0, as `index_scores` leaves an exact zero
        scores = jnp.zeros_like(scores)
    lengths = jnp.asarray([0, 1, 63, 64, 65, 256])
    scores = jnp.where(jnp.arange(ctx)[None] < lengths[:, None], scores,
                       -jnp.inf)
    vals, idx = jax.lax.top_k(scores, topk)
    want = np.zeros((ns, ctx), bool)
    for row in range(ns):
        want[row, np.asarray(idx[row])[np.asarray(vals[row]) > -np.inf]] = True
    got = np.asarray(sparse_attention.decode_select_mask(scores, topk))
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got != 0, want)
    assert list(want.sum(1)) == [0, 1, 63, 64, 64, 64]
    if ties == "all":
        assert want[5, :topk].all()
    if ties == "some":       # the threshold really is tied, and cut
        t = np.asarray(vals[5, -1])
        assert (np.asarray(scores[5]) == t).sum() > (want[5] & (
            np.asarray(scores[5]) == t)).sum() > 0
    np.testing.assert_array_equal(
        np.asarray(sparse_attention.decode_select_mask(scores, ctx)) != 0,
        np.asarray(scores) > -np.inf)


def test_an_engine_decodes_through_the_streaming_kernel(tiny, monkeypatch):
    """The engine's decode program with `sparse_paged_decode` in it
    (interpreted; blocks of two pages): a prompt under top-k whose decode
    crosses it and two page boundaries, and one that starts past it, serve
    the reference's tokens."""
    import functools
    cfg, params = tiny
    monkeypatch.setattr(sparse_attention, "_STREAM_BLOCK_TOKENS", 32)
    monkeypatch.setattr(
        sparse_attention, "sparse_decode_attention", functools.partial(
            sparse_attention.sparse_decode_attention, interpret=True))
    before = sparse_attention.attention.attention_path_counts()
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                 decode_chunk=4, page_size=16)
    try:
        prompts = [_tokens(21, 5), _tokens(70, 6)]
        served = _serve(eng, prompts, 16)
    finally:
        eng.stop()
    after = sparse_attention.attention.attention_path_counts()
    assert after.get("sparse_decode_stream_pallas", 0) \
        > before.get("sparse_decode_stream_pallas", 0)
    assert after.get("sparse_decode_gather", 0) \
        == before.get("sparse_decode_gather", 0)
    for prompt, toks in zip(prompts, served):
        assert len(toks) == 16
        assert max(ref.served_token_gaps(params, MODEL, prompt, toks)) \
            < LOGIT_TOL


def test_the_tables_width_against_top_k_chooses_the_decode_path(monkeypatch):
    """One rule in one place: a decode step streams while the block table is
    at most `_STREAM_UP_TO` x top-k positions wide, on a TPU; wider, or off
    the TPU, it gathers."""
    assert sparse_attention._streams(8192, 2048)
    assert sparse_attention._streams(16384, 2048)
    assert not sparse_attention._streams(16384 + 64, 2048)
    args = _decode_case([5, 40, 100, 0])
    monkeypatch.setattr(sparse_attention.attention, "_on_tpu", lambda: True)

    def path(topk):
        before = sparse_attention.attention.attention_path_counts()
        jax.eval_shape(lambda *a: sparse_attention.sparse_decode_attention(
            *a, topk), *args)
        after = sparse_attention.attention.attention_path_counts()
        return {k for k in after if after[k] != before.get(k, 0)}

    assert path(8) == {"sparse_decode_gather"}          # 128 > 8 x 8
    assert path(16) == {"sparse_decode_stream_pallas"}


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

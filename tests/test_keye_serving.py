"""Keye-VL-2.0's language-model block through the serving engine and through
the kernels of the TPU path, interpreted, at the adapter's REHEARSE widths in
float32 on the CPU: sections (b) and (c) of tests/test_keye.py (which holds
the train path, what the tolerance means and the adapter, and says what the
tolerances are), in a file of their own so that neither is the suite's
longest (`--dist loadfile` keeps a file on one worker).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_keye as ref
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import sparse_attention
from ray_tpu.models.serving import prefill_core
from ray_tpu.serve.engine import Engine
from engine_pins import Spans
from test_keye import LOGIT_TOL, MODEL, _ref_logits, _tokens, tiny


def _serve(engine, prompts, n):
    outs = [engine.submit(p, n) for p in prompts]
    served = []
    for q in outs:
        toks = []
        while (chunk := q.get(timeout=120)) is not None:
            toks += chunk
        served.append(toks)
    return served


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


def test_a_dispatch_span_carries_the_selected_and_the_live_keys(engine):
    """A prompt of 30: the prefill's token stands at position 30, so the
    first chunk's four steps read 31, 32, 33 and 34 positions a layer
    (`live_keys`), of which the indexer selects 32 at most
    (`selected_keys`); the next chunk's all read more than it selects."""
    with Spans() as spans:
        assert len(_serve(engine, [_tokens(30, 8)], 8)[0]) == 8
    first, second = [
        (a["selected_keys"], a["live_keys"])
        for a in spans.named("serve.engine.decode_dispatch")][:2]
    assert first == (31 + 3 * 32, 31 + 32 + 33 + 34)
    assert second == (4 * 32, 35 + 36 + 37 + 38)


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

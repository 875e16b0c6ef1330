"""The kernels of MiniCPM-SALA's two kinds of layer, interpreted on the CPU
(`interpret=True`: the kernels' own code, block specs and grids): the linear
layer's chunk kernel against the recurrence, its step kernel in place, the
selection by blocks against a sort, and flash attention under the mask by
blocks at the published group of 16 query heads a kv head. What Mosaic makes
of them is tests/test_tpu_compile_minicpm_sala.py's, the values on the chip
the benchmark cell's check."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import linear_attention as la
from ray_tpu.ops import paged_kv, sparse_attention as sa

SIZES = sa.BlockSparse(kernel=32, stride=16, block=64, topk=6, init_blocks=1,
                       window=128, dense_len=300)


def _qkv(shape, seed=0):
    return tuple(jax.random.normal(k, shape, jnp.float32)
                 for k in jax.random.split(jax.random.PRNGKey(seed), 3))


@pytest.mark.parametrize("chunk", [128, 256])
def test_the_chunk_kernel_is_the_recurrence_at_the_steepest_decay(chunk):
    """512 positions in chunks of 128 and of 256 at the published rates of
    layer 0's first four heads of 32 (head 0 forgets by e^-0.84 a position:
    `rate x 256` is past float32's range, and the kernel never forms it) and
    its last (e^-0.004): the rows and the final state are the recurrence's,
    as the `jnp` form's are; with a `length` the state is the one after row
    `length - 1` and a chunk wholly past it leaves it alone."""
    rates = jnp.asarray(la.decay_rates(32, 0, 32))[jnp.array([0, 1, 2, 31])]
    assert float(rates[0]) * 256 > np.log(np.finfo(np.float32).max)
    q, k, v = _qkv((4, 512, 128))
    want, state = la.linear_recurrence(q, k, v, rates, 128 ** -0.5)
    for interpret in (True, False):
        o, S = la.linear_prompt(q, k, v, rates, 128 ** -0.5, chunk=chunk,
                                interpret=interpret)
        assert np.abs(np.asarray(o - want)).max() < 2e-4
        assert np.abs(np.asarray(S - state)).max() < 5e-4
    _, upto = la.linear_recurrence(q[:, :200], k[:, :200], v[:, :200], rates,
                                   128 ** -0.5)
    o, S = la.linear_prompt(q, k, v, rates, 128 ** -0.5, 200, chunk=chunk,
                            interpret=True)
    assert np.abs(np.asarray(S - upto)).max() < 5e-4
    assert np.abs(np.asarray(o[:, :200] - want[:, :200])).max() < 2e-4
    assert np.isfinite(np.asarray(o)).all()


def test_the_step_kernel_moves_the_active_slots_tiles_in_place():
    """Six slots, four active, layer 1 of 2, sixteen heads in two blocks of
    eight: an active slot's tiles are decayed, added to and read against the
    query as the recurrence's step does; an idle slot's and the other
    layer's keep their BYTES, and an idle slot's output is 0."""
    H, d, ns = 16, 128, 6
    rates = jnp.asarray(la.decay_rates(H, 1, 32))
    state = jax.random.normal(jax.random.PRNGKey(3), (2, ns, H, d, d))
    q, k, v = _qkv((ns, H, d), 4)
    active = jnp.array([True, False, True, True, False, True])
    lam = jnp.exp(-rates)[None, :, None, None]
    moved = lam * state[1] + k[..., :, None] * v[..., None, :]
    want = 0.1 * jnp.einsum("nhd,nhde->nhe", q, moved)
    for interpret in (True, False):
        y, S = la.linear_state_step(state, 1, active, q, k, v, rates, 0.1,
                                    interpret=interpret)
        assert np.abs(np.asarray(y - jnp.where(active[:, None, None], want,
                                               0.0))).max() < 1e-4
        assert np.abs(np.asarray(S[1] - moved))[np.asarray(active)].max() \
            < 1e-5
        assert (np.asarray(S[1])[~np.asarray(active)]
                == np.asarray(state[1])[~np.asarray(active)]).all()
        assert (np.asarray(S[0]) == np.asarray(state[0])).all()


def _by_sort(scores, pos, sizes):
    """`block_select` by a stable sort, a row at a time."""
    out = np.zeros(scores.shape, bool)
    for idx in np.ndindex(scores.shape[:-1]):
        t = int(pos[idx[-1]])
        own = t // sizes.block
        if t + 1 < sizes.dense_len:
            out[idx][:own + 1] = True
            continue
        s = np.array(scores[idx], np.float64)
        s[:sizes.init_blocks] = np.inf
        s[max(own - sizes.window_blocks + 1, 0):own + 1] = np.inf
        s[own + 1:] = -np.inf
        order = np.argsort(-s, kind="stable")[:sizes.topk]
        out[idx][[b for b in order if b <= own]] = True
    return out


def test_the_selection_is_a_stable_sorts_ties_and_all():
    """Scores on a lattice of four values, so that most blocks tie: the
    blocks read are the first `topk` of a stable sort by falling score (ties
    to the smaller block), the first block and the window's always, every
    block up to a row's own under `dense_len` and where there are no more."""
    rng = np.random.default_rng(0)
    pos = np.array([5, 70, 298, 299, 300, 511, 640, 1000, 1023])
    scores = rng.integers(0, 4, (2, len(pos), 16)).astype(np.float32) / 4
    got = np.asarray(sa.block_select(jnp.asarray(scores), jnp.asarray(pos),
                                     SIZES))
    assert (got == _by_sort(scores, pos, SIZES)).all()
    assert got[0, 2].sum() == got[0, 3].sum() == 5 \
        and got[0, 5].sum() == SIZES.topk
    assert got[:, 3:, 0].all()                      # the first block, always


def test_block_scores_pool_a_groups_softmax_over_the_keys_a_row_may_see():
    q = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 3, 128))
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 512, 128))
    pooled, sums = sa.compress(k, SIZES, 400)
    for i in (0, 7, 23):
        assert np.allclose(np.asarray(pooled[:, i]),
                           np.asarray(k[:, 16 * i:16 * i + 32].mean(1)),
                           atol=1e-5)
    assert np.allclose(np.asarray(sums[:, 0]),
                       np.asarray(k[:, 384:400].sum(1)), atol=1e-4)
    assert np.abs(np.asarray(sums[:, 1])).max() == 0    # row 400 opens a group
    pos = jnp.array([40, 300, 399])
    got = np.asarray(sa.block_scores(q, pooled, pos, SIZES, 128 ** -0.5))
    assert got.shape == (2, 3, 8)
    for r, t in enumerate((40, 300, 399)):
        seen = [i for i in range(32) if 16 * i + 31 <= t]
        s = np.einsum("kgd,kid->kgi", np.asarray(q[:, :, r]),
                      np.asarray(pooled[:, seen])) * 128 ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p = (p / p.sum(-1, keepdims=True)).sum(1)           # [KVH, seen]
        for b in range(8):
            meets = [j for j, i in enumerate(seen) if 4 * b - 1 <= i <= 4 * b + 3]
            want = p[:, meets].max(-1) if meets else np.zeros(2)
            assert np.allclose(got[:, r, b], want, atol=1e-5)


@pytest.mark.timeout(300)
def test_block_flash_is_attention_over_the_selected_blocks_at_a_group_of_16():
    """1,024 rows of 32 query heads on 2 kv heads of 128 (the published
    group of 16), contexts under `dense_len` 300 read whole and the others 6
    blocks of 64: the kernel under the mask by blocks is attention over the
    selected keys, causal inside a row's own block, and a decode step's
    selected pages read by a table of each kv head's own give the same row."""
    H, KVH, T, hd = 32, 2, 1024, 128
    q, k, v = _qkv((H, T, hd), 5)
    q, k, v = 1.5 * q, k[:KVH], v[:KVH]
    pooled, _ = sa.compress(k, SIZES)
    want = sa.block_sparse_attention(q, k, v, pooled, SIZES)
    got = sa.block_sparse_attention(q, k, v, pooled, SIZES, interpret=True)
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    mask = sa.block_mask(q.reshape(KVH, H // KVH, T, hd), pooled, SIZES,
                         hd ** -0.5)
    assert mask.shape == (KVH, T, T // 64)
    picked = np.asarray(mask.sum(-1))
    assert (picked[:, 299:] == np.minimum(np.arange(299, T) // 64 + 1,
                                          6)).all()
    assert (picked[:, :299] == np.arange(299) // 64 + 1).all()
    assert not (np.asarray(mask[0]) == np.asarray(mask[1])).all()
    # a decode step at the same positions, through pages of one kv head
    kc, vc = paged_kv.empty(KVH, 40, 1, 64, hd, jnp.float32)
    pages = jnp.arange(1, 17)
    kc, vc = paged_kv.write_prompt(kc, vc, pages, k[:, :, None], v[:, :, None])
    bt = jnp.zeros((3, 20), jnp.int32).at[1, :16].set(pages)
    for w in (250, 299, 640, 1023):
        seen, _ = sa.compress(k, SIZES, w + 1)
        store = jnp.zeros((3, T // 16, KVH * hd)).at[1].set(
            seen.transpose(1, 0, 2).reshape(T // 16, -1))
        out = sa.block_sparse_decode(
            jnp.zeros((3, H, hd)).at[1].set(q[:, w]), store, kc, vc, 0, bt,
            jnp.array([0, w, 0]), jnp.array([False, True, False]), SIZES,
            paged_kv.paged_decode_attention)
        assert np.abs(np.asarray(out[1] - want[:, w])).max() < 2e-5
        assert np.abs(np.asarray(out[0])).max() == 0


def test_a_steps_key_finishes_the_pooled_key_that_ends_at_it():
    k = jax.random.normal(jax.random.PRNGKey(6), (2, 512, 128))
    pooled, sums = sa.compress(k, SIZES, 333)
    store = jnp.stack([pooled.transpose(1, 0, 2).reshape(32, -1)] * 2)
    moved = jnp.stack([sums.transpose(1, 0, 2).reshape(2, -1)] * 2)
    active = jnp.array([True, False])
    for w in range(333, 400):
        store, moved = sa.compress_step(
            store, moved, jnp.stack([k[:, w].reshape(-1)] * 2),
            jnp.array([w, w]), active, SIZES)
    whole, after = sa.compress(k, SIZES, 400)
    done = (400 - 32) // 16 + 1             # pooled keys that end under 400
    assert np.abs(np.asarray(
        store[0, :done] - whole.transpose(1, 0, 2).reshape(32, -1)[:done])
    ).max() < 1e-5
    assert np.abs(np.asarray(
        moved[0] - after.transpose(1, 0, 2).reshape(2, -1))).max() < 1e-4
    # the idle slot's rows are what the prompt left
    assert (np.asarray(store[1])
            == np.asarray(pooled.transpose(1, 0, 2).reshape(32, -1))).all()

"""Power retention of degree 2 (`ray_tpu/ops/retention.py`) alone, at tiny
widths on the CPU in float32: the expansion, the three forms against each
other, the two kernels in Pallas interpret mode against the `jnp` forms, and
the slots' state (`ops/slot_state.py`): an idle slot's unmoved, a reused
slot's overwritten whole. The model is tests/test_brumby.py's."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention, retention, slot_state

H, KVH, W, D = 4, 2, 48, 16
TOL = 2e-5      # float32 sums in another order, on outputs of a few units


def _inputs(seed=0, width=W, d=D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    # q and k of one sign: no score is near 0, so no row's sum of scores is,
    # and a row's error is not a rounding divided by almost nothing
    q = jnp.abs(jax.random.normal(ks[0], (H, width, d)))
    k = jnp.abs(jax.random.normal(ks[1], (KVH, width, d)))
    v = jax.random.normal(ks[2], (KVH, width, d))
    # half-lives of a few to a few dozen positions: the state matters
    gamma = jax.nn.log_sigmoid(jax.random.normal(ks[3], (width, KVH)) + 3.0)
    return q, k, v, gamma


def _recurrent(q, k, v, gamma):
    """The recurrent form, a position at a time from an empty state."""
    S, z = (jnp.zeros(s[1:]) for s in retention.state_shapes(
        1, 1, k.shape[0], k.shape[-1]))
    ys = []
    for t in range(q.shape[1]):
        y, S, z = retention.retention_step(S, z, q[:, t][None], k[:, t][None],
                                           v[:, t][None], gamma[t][None])
        ys.append(y[0])
    return jnp.stack(ys, axis=1), S[0], z[0]


@pytest.mark.parametrize("d", [2, 16, 128])
def test_the_expansions_inner_product_is_the_square(d):
    u, w = jax.random.normal(jax.random.PRNGKey(d), (2, 3, d))
    pu, pw = retention.phi(u), retention.phi(w)
    assert pu.shape == (3, d // 2 + 1, d)
    want = np.asarray(jnp.sum(u * w, axis=-1) ** 2)
    got = np.asarray(jnp.sum(pu * pw, axis=(-2, -1)))
    np.testing.assert_allclose(got, want, rtol=2e-5)
    # 64 of 8,320 lanes hold nothing at d = 128: d (d + 1) / 2 are used
    assert int(jnp.sum(retention.phi(jnp.ones(d)) != 0)) == d * (d + 1) // 2


def test_the_three_forms_agree():
    q, k, v, gamma = _inputs()
    attn = retention.retention_attention(q, k, v, gamma)
    rec, S, z = _recurrent(q, k, v, gamma)
    assert float(jnp.abs(attn - rec).max()) < TOL
    assert float(jnp.abs(attn).max()) > 0.5
    for chunk in (8, 16, 48):       # 16: a chunk's boundary inside the prompt
        y, Sc, zc = retention.retention_chunked(q, k, v, gamma, chunk)
        assert float(jnp.abs(y - attn).max()) < TOL, chunk
        assert float(jnp.abs(Sc - S).max()) < TOL
        assert float(jnp.abs(zc - z).max()) < TOL


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("length", [None, 48, 31, 1])
def test_a_prompts_rows_and_its_state_after_row_length(length, interpret):
    """A prompt shorter than its bucket: the rows before `length` are the
    attention form's, the state the recurrent form's after row `length - 1`,
    whatever the bucket's other rows hold; by `phi` as an array and by the
    kernel `retention_state` interpreted."""
    q, k, v, gamma = _inputs(1)
    n = W if length is None else length
    before = dict(attention.attention_path_counts())
    y, S, z = retention.retention_prompt(
        q, k, v, gamma, None if length is None else jnp.int32(length),
        interpret=interpret)
    want_y, want_S, want_z = _recurrent(q[:, :n], k[:, :n], v[:, :n],
                                        gamma[:n])
    assert float(jnp.abs(y[:, :n] - want_y).max()) < TOL
    assert float(jnp.abs(S - want_S).max()) < TOL
    assert float(jnp.abs(z - want_z).max()) < TOL
    assert bool(jnp.isfinite(y).all())
    path = "retention_state_pallas" if interpret \
        else "retention_state_reference"
    assert attention.attention_path_counts()[path] > before.get(path, 0)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("active", [(1, 1, 1, 1), (0, 1, 1, 0), (0, 0, 0, 0)],
                         ids=["all", "two", "none"])
def test_the_step_kernel_is_retention_step_in_one_visit(active, layer):
    """`retention_state_step` interpreted, on the slots' whole state: an
    active slot's tiles and output are `retention_step`'s, an idle slot's
    tiles and every other layer's are what they were TO THE BIT, and an idle
    slot's output is zeros; and the same through `slot_state`'s one op, on
    either path."""
    ns, L = 4, 3
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    shapes = retention.state_shapes(L, ns, KVH, D)
    S = jax.random.normal(ks[0], shapes[0])
    z = jnp.abs(jax.random.normal(ks[1], shapes[1])) + 1.0
    z = z.at[..., retention.n_blocks(D):, :].set(0.0)   # as they always are
    q = jax.random.normal(ks[2], (ns, H, D))
    k = jax.random.normal(ks[3], (ns, KVH, D))
    v = jax.random.normal(ks[4], (ns, KVH, D))
    gamma = -0.1 * jnp.abs(jax.random.normal(ks[5], (ns, KVH)))
    act = jnp.asarray(active, bool)
    want_y, want_S, want_z = retention.retention_step(S[layer], z[layer], q,
                                                      k, v, gamma)
    for interpret in (True, False):
        y, (So, zo) = slot_state.retention_step_layer(
            (S, z), jnp.int32(layer), act, q, k, v, gamma,
            interpret=interpret)
        for s in range(ns):
            if active[s]:
                assert float(jnp.abs(y[s] - want_y[s]).max()) < 1e-4
                assert float(jnp.abs(So[layer, s] - want_S[s]).max()) < TOL
                assert float(jnp.abs(zo[layer, s] - want_z[s]).max()) < TOL
            else:
                assert not bool(jnp.any(y[s]))
                assert bool(jnp.all(So[layer, s] == S[layer, s]))
                assert bool(jnp.all(zo[layer, s] == z[layer, s]))
        others = np.asarray([l for l in range(L) if l != layer])
        assert bool(jnp.all(So[others] == S[others]))
        assert bool(jnp.all(zo[others] == z[others]))


def test_a_reused_slots_state_is_overwritten_whole():
    """`write_retention` puts a prefill's state into ONE slot's tiles, all
    layers at once: nothing of the slot's previous tenant is left, and no
    other slot moves; `state_bytes` counts the pair."""
    L, ns = 2, 3
    state = slot_state.empty_retention(L, ns, KVH, D)
    assert slot_state.state_bytes(state) == 4 * L * ns * KVH * (9 * 16 + 16) \
        * 16
    old = tuple(jnp.full_like(a, 5.0) for a in state)
    rows = tuple(jax.random.normal(jax.random.PRNGKey(i), a.shape[:1]
                                   + a.shape[2:]) for i, a in enumerate(old))
    S, z = slot_state.write_retention(old, jnp.int32(1), *rows)
    assert bool(jnp.all(S[:, 1] == rows[0])) and bool(
        jnp.all(z[:, 1] == rows[1]))
    for other in (0, 2):
        assert bool(jnp.all(S[:, other] == 5.0)) and bool(
            jnp.all(z[:, other] == 5.0))


def test_decode_steps_after_a_prompt_are_the_longer_prompt():
    """A prompt's state carried through the step kernel, a token at a time:
    the rows are the attention form's of the whole sequence."""
    q, k, v, gamma = _inputs(3)
    cut = 29
    attn = retention.retention_attention(q, k, v, gamma)
    _, S, z = retention.retention_prompt(q[:, :32], k[:, :32], v[:, :32],
                                         gamma[:32], jnp.int32(cut),
                                         interpret=True)
    S, z = S[None, None], z[None, None]     # one layer, one slot
    for t in range(cut, W):
        y, S, z = retention.retention_state_step(
            S, z, jnp.int32(0), jnp.ones(1, bool), q[:, t][None],
            k[:, t][None], v[:, t][None], gamma[t][None], interpret=True)
        assert float(jnp.abs(y[0] - attn[:, t]).max()) < TOL, t

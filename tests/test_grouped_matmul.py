"""`ops/moe.py::grouped_matmul`: the Pallas kernel, interpreted on this CPU,
against `jax.lax.ragged_dot`; and the dispatch between the two."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention, moe

TM = moe._ROW_TILE
assert TM == 256   # the cases below are written for it


def _second_block(sizes, rows):
    """`_share_experts`' groups clipped to its second block of `rows` rows."""
    sizes = np.asarray(sizes)
    ends = np.cumsum(sizes)
    return (np.clip(ends, rows, 2 * rows)
            - np.clip(ends - sizes, rows, 2 * rows)).tolist()


# (rows, K, N, group sizes), written for a row tile of 256 in halves of 128
CASES = {
    "every-group-live": (1024, 256, 128, [256, 200, 312, 256]),
    "serving-stack-other-layers-empty":
        (1024, 256, 128, [0] * 4 + [90, 400, 30, 192] + [0] * 4),
    "empty-group-between-live-ones": (512, 256, 128, [140, 0, 0, 260, 112]),
    "a-group-over-tiles-and-a-tile-of-groups":
        (1024, 256, 128, [600, 5, 7, 20, 180]),
    "rows-in-no-group-at-the-end":        # a share's block, 4 x the even load
        (1024, 256, 128, [0] * 3 + [80, 50, 66, 60] + [0] * 5),
    "second-block-clipped":               # [0, 88, 300, 60]
        (512, 256, 128, _second_block([400, 200, 300, 60], 512)),
    "nothing-grouped": (512, 256, 128, [0, 0, 0]),
    "a-decode-steps-rows":                # one tile of 64 rows, halves of 32
        (64, 256, 128, [1, 0, 2, 1, 0, 0, 3, 1, 0, 0, 0, 29, 4, 0, 1, 0]),
    "gate-up-widths": (512, 512, 256, [200, 312]),
    "down-widths": (512, 256, 512, [200, 312]),
}


def _operands(m, k, n, sizes, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)) / k ** 0.5,
                    jnp.bfloat16)
    return xs, w, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("tn", [None, 128], ids=["whole-N", "N-in-tiles"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot(case, tn):
    m, k, n, sizes = CASES[case]
    xs, w, groups = _operands(m, k, n, sizes)
    want = jax.lax.ragged_dot(xs, w, groups)
    if tn is None:
        before = attention.attention_path_counts()
        got = moe.grouped_matmul(xs, w, groups, interpret=True)
        after = attention.attention_path_counts()
        assert after["experts_grouped_pallas"] == before.get(
            "experts_grouped_pallas", 0) + 1
    else:
        got = moe._grouped_pallas(xs, w, groups, tm=min(TM, m), tn=tn,
                                  interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    live = sum(sizes)
    # bfloat16 round-off of a float32 accumulation: an ulp at most
    np.testing.assert_allclose(np.asarray(got[:live], np.float32),
                               np.asarray(want[:live], np.float32),
                               rtol=2 ** -7, atol=2 ** -9)


def test_a_rows_result_is_that_rows_alone():
    """The same row, alone in its group and tile and beside other rows and
    groups, at another place of its tile: the same bits."""
    m, k, n = 2 * TM, 256, 128
    xs, w, _ = _operands(m, k, n, [0, 0, 0], seed=1)
    row = xs[37]
    alone = moe.grouped_matmul(
        jnp.zeros_like(xs).at[0].set(row), w,
        jnp.asarray([0, 1, 0], jnp.int32), interpret=True)[0]
    beside = moe.grouped_matmul(
        xs.at[TM + 3].set(row), w, jnp.asarray([TM - 9, 40, 60], jnp.int32),
        interpret=True)[TM + 3]
    assert np.array_equal(np.asarray(alone.view(jnp.uint16)),
                          np.asarray(beside.view(jnp.uint16)))


def test_dispatch_is_ragged_dot_off_the_tpu_and_under_grad(monkeypatch):
    xs, w, groups = _operands(2 * TM, 256, 128, [100, 156])

    def moved(fn):
        before = attention.attention_path_counts()
        fn()
        after = attention.attention_path_counts()
        return {k: v - before.get(k, 0) for k, v in after.items()
                if k.startswith("experts_") and v != before.get(k, 0)}

    def loss(a, b):
        return jnp.sum(moe.grouped_matmul(a, b, groups).astype(jnp.float32))

    # this CPU: ragged_dot, plain and differentiated, and the values hold
    assert moved(lambda: moe.grouped_matmul(xs, w, groups)) == {
        "experts_ragged_dot": 1}
    assert moved(lambda: jax.grad(loss)(xs, w)) == {"experts_ragged_dot": 1}
    # a TPU (traced, never run here): the kernel, but ragged_dot and its VJP
    # under differentiation, and where the rows are no whole tiles
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert moved(lambda: jax.make_jaxpr(
        lambda a, b: moe.grouped_matmul(a, b, groups))(xs, w)) == {
            "experts_grouped_pallas": 1}
    assert moved(lambda: jax.make_jaxpr(jax.grad(loss))(xs, w)) == {
        "experts_ragged_dot": 1}
    assert moved(lambda: jax.make_jaxpr(
        lambda a, b: moe.grouped_matmul(a, b, groups))(xs[:40], w)) == {
            "experts_ragged_dot": 1}
    got = jax.grad(loss, argnums=(0, 1))
    monkeypatch.setattr(attention, "_on_tpu", lambda: False)
    want = jax.grad(lambda a, b: jnp.sum(jax.lax.ragged_dot(
        a, b, groups).astype(jnp.float32)), argnums=(0, 1))(xs, w)
    for g, h in zip(got(xs, w), want):
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(h, np.float32))


@pytest.mark.parametrize("held", [None, (4, 4)], ids=["every-expert", "share"])
def test_moe_ffn_through_the_kernel(held, monkeypatch):
    """The whole sparse feed-forward over the serving stacks, its grouped
    matmuls through the interpreted kernel, against the same through
    `ragged_dot`; a share whose routing is skewed enough to need a second
    block of rows included."""
    tokens, d, f, n_experts, top_k, layers = 256, 128, 128, 32, 4, 2
    rng = np.random.default_rng(3)
    x = rng.standard_normal((tokens, d)).astype(np.float32)
    gate_w = rng.standard_normal((d, n_experts)).astype(np.float32)
    if held:        # a freak routing: most tokens choose the held experts
        x[:, 0] = 6.0
        gate_w[0, held[0]:held[0] + held[1]] = 8.0
    x = jnp.asarray(x, jnp.bfloat16)
    n = held[1] if held else n_experts
    w_up, w_gate = (jnp.asarray(rng.standard_normal((layers, n, d, f))
                                / d ** 0.5, jnp.bfloat16) for _ in range(2))
    w_down = jnp.asarray(rng.standard_normal((layers, n, f, d)) / f ** 0.5,
                         jnp.bfloat16)
    run = jax.jit(lambda: moe.moe_ffn(
        x, jnp.asarray(gate_w, jnp.bfloat16), w_up, w_gate, w_down,
        top_k=top_k, layer=jnp.int32(1), held=held))
    want, _, want_counts = run()
    monkeypatch.setattr(moe, "grouped_matmul", functools.partial(
        moe.grouped_matmul, interpret=True))
    before = attention.attention_path_counts().get("experts_grouped_pallas", 0)
    got, _, counts = jax.jit(lambda: moe.moe_ffn(
        x, jnp.asarray(gate_w, jnp.bfloat16), w_up, w_gate, w_down,
        top_k=top_k, layer=jnp.int32(1), held=held))()
    assert attention.attention_path_counts()["experts_grouped_pallas"] \
        == before + 3
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    if held:        # more local rows than one block of 4 x the even load
        assert int(counts.sum()) > 4 * tokens * top_k * n // n_experts
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -6, atol=2 ** -6)


# ---------------------------------------------------------------------------
# A share's combine (`_share_experts`): the local kernel, interpreted here,
# and the gather that stands off the chip
# ---------------------------------------------------------------------------

FORMS = ["kernel", "off-chip"]


def _share(form, monkeypatch, tokens=64, seed=5, lean=0.0, held=(4, 4),
           x=None, poison=False):
    """-> (out [tokens, d] float32 bits as the layer returns them, counts,
    x): a share of 4 of 32 experts over one layer's weights, `lean` added to
    the held experts' router columns (at 200 every assignment is local, at -200
    none), through the local combine's kernel or through the gather. With
    `poison` every grouped matmul's rows in no group come back NaN."""
    d, f, n_experts, top_k = 128, 128, 32, 4
    rng = np.random.default_rng(seed)
    if x is None:
        x = rng.standard_normal((tokens, d)).astype(np.float32)
        x[:, 0] = 1.0
    else:
        rng.standard_normal((tokens, d))
    gate_w = rng.standard_normal((d, n_experts)).astype(np.float32)
    gate_w[0, held[0]:held[0] + held[1]] += lean
    w_up, w_gate = (jnp.asarray(rng.standard_normal((held[1], d, f))
                                / d ** 0.5, jnp.bfloat16) for _ in range(2))
    w_down = jnp.asarray(rng.standard_normal((held[1], f, d)) / f ** 0.5,
                         jnp.bfloat16)
    if form == "kernel":
        monkeypatch.setattr(moe, "local_combine", functools.partial(
            moe.local_combine, interpret=True))
    if poison:
        real = moe.grouped_matmul

        def poisoned(xs, w, groups, **kw):
            y = real(xs, w, groups, **kw)
            return jnp.where((jnp.arange(y.shape[0]) < jnp.sum(groups))[
                :, None], y, jnp.nan)

        monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    name = "share_combine_local" if form == "kernel" \
        else "share_combine_gather"
    before = attention.attention_path_counts().get(name, 0)
    out, _, counts = jax.jit(lambda a: moe.moe_ffn(
        a, jnp.asarray(gate_w, jnp.bfloat16), w_up, w_gate, w_down,
        top_k=top_k, held=held))(jnp.asarray(x, jnp.bfloat16))
    assert attention.attention_path_counts().get(name, 0) == before + 1
    return np.asarray(out.view(jnp.uint16)), np.asarray(counts), x


@pytest.mark.parametrize("form", FORMS)
def test_a_tokens_part_from_a_share_is_that_tokens_alone(form, monkeypatch):
    """Bit for bit the same in a batch, in that batch reversed and with
    nobody beside it (the other rows zeros): the sum over a token's local
    assignments is taken in an order its own routing fixes."""
    batch, counts, x = _share(form, monkeypatch, lean=10.0)
    assert counts.sum() > 64          # tokens with two and more local rows
    turned, _, _ = _share(form, monkeypatch, lean=10.0, x=x[::-1])
    assert np.array_equal(turned[::-1], batch) and batch.any()
    for t in (0, 17, 63):
        alone = np.zeros_like(x)
        alone[5] = x[t]
        got, _, _ = _share(form, monkeypatch, lean=10.0, x=alone)
        assert np.array_equal(got[5], batch[t])


@pytest.mark.parametrize("case", ["no-local-assignment", "every-one-local",
                                  "rows-in-no-group-are-NaN"])
@pytest.mark.parametrize("form", FORMS)
def test_a_shares_combine_at_the_edges(form, case, monkeypatch):
    if case == "no-local-assignment":
        # one block that holds every row, and two that are never walked
        for block in (100, moe._SHARE_BLOCK):
            monkeypatch.setattr(moe, "_SHARE_BLOCK", block)
            out, counts, _ = _share(form, monkeypatch, lean=-200.0)
            assert counts.sum() == 0 and not out.any()
    elif case == "every-one-local":
        # the freak routing: two blocks of 4 x the even load give what one
        # block of every row gives
        out, counts, _ = _share(form, monkeypatch, lean=200.0)
        assert counts.sum() == 64 * 4
        monkeypatch.setattr(moe, "_SHARE_BLOCK", 100)
        want, want_counts, _ = _share(form, monkeypatch, lean=200.0)
        assert np.array_equal(counts, want_counts)
        assert np.array_equal(out, want) and out.any()
    else:
        want, _, _ = _share(form, monkeypatch, lean=10.0)
        out, _, _ = _share(form, monkeypatch, lean=10.0, poison=True)
        assert np.array_equal(out, want) and want.any()
        assert np.isfinite(out.view(jnp.bfloat16).astype(np.float32)).all()

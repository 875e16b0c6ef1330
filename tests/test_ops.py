"""Unit tests for ray_tpu.ops kernels against reference implementations."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import (attention_path_counts, attention_reference,
                                   flash_attention, repeat_kv)
from ray_tpu.ops.moe import moe_ffn, top_k_routing
from ray_tpu.ops.norms import apply_rope, rms_norm, rope_frequencies
from ray_tpu.ops.paged_kv import paged_decode_attention
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel import MeshConfig, build_mesh


def _qkv(b=2, h=4, s=64, d=32, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (b, h, s, d), dtype),
            jax.random.normal(k2, (b, h, s, d), dtype),
            jax.random.normal(k3, (b, h, s, d), dtype))


class TestFlashAttention:
    def test_forward_matches_reference(self):
        q, k, v = _qkv()
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, True)),
            np.asarray(attention_reference(q, k, v, causal=True)),
            atol=2e-5)

    def test_non_causal(self):
        q, k, v = _qkv()
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, False)),
            np.asarray(attention_reference(q, k, v, causal=False)),
            atol=2e-5)

    def test_gradients_match_reference(self):
        q, k, v = _qkv()
        for argnum in range(3):
            g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a, True)),
                          argnum)(q, k, v)
            g2 = jax.grad(lambda *a: jnp.sum(attention_reference(
                *a, causal=True)), argnum)(q, k, v)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=2e-5)

    def test_repeat_kv(self):
        x = jnp.arange(2 * 2 * 3 * 4, dtype=jnp.float32).reshape(2, 2, 3, 4)
        y = repeat_kv(x, 3)
        assert y.shape == (2, 6, 3, 4)
        np.testing.assert_array_equal(np.asarray(y[:, 0]), np.asarray(y[:, 1]))
        np.testing.assert_array_equal(np.asarray(y[:, 0]), np.asarray(x[:, 0]))


class TestPagedDecodeAttention:
    """`paged_decode_attention`: the Pallas kernel in INTERPRET mode (an
    argument, so the CPU runs the kernel's own code) against the XLA
    reference path and against plain attention over each slot's history
    laid out in order."""

    PAGE, MAXP, KVH, HD, LAYERS = 16, 6, 2, 128, 3
    # idle, one token, a page boundary - 1 / on it / + 1, the full table
    LENGTHS = (0, 1, PAGE - 1, PAGE, PAGE + 1, MAXP * PAGE)

    def _arena(self, groups, dtype, seed=0):
        """-> (q, kc, vc, layer, block_table, lengths, histories): pages
        handed out in a shuffled order, every page no slot holds (the null
        page 0 first of all) full of NaN, and so are the OTHER layers."""
        ns, page, maxp = len(self.LENGTHS), self.PAGE, self.MAXP
        rng = np.random.default_rng(seed)
        n_pages = 1 + ns * maxp
        shape = (self.LAYERS, n_pages, self.KVH, page, self.HD)
        kc = np.full(shape, np.nan, np.float32)
        vc = np.full(shape, np.nan, np.float32)
        free = list(rng.permutation(np.arange(1, n_pages)))
        bt = np.zeros((ns, maxp), np.int32)
        layer, hist = 1, []
        for s, n in enumerate(self.LENGTHS):
            pages = [free.pop() for _ in range(-(-n // page))]
            bt[s, :len(pages)] = pages
            # a live page is finite to its end: the tail past `n` is read
            # and masked, as the engine's recycled pages are
            k = rng.standard_normal((len(pages) * page, self.KVH, self.HD))
            v = rng.standard_normal((len(pages) * page, self.KVH, self.HD))
            for i, pg in enumerate(pages):
                rows = slice(i * page, (i + 1) * page)
                kc[layer, pg] = k[rows].transpose(1, 0, 2)
                vc[layer, pg] = v[rows].transpose(1, 0, 2)
            hist.append((k[:n], v[:n]))
        q = rng.standard_normal((ns, self.KVH * groups, self.HD))
        cast = lambda x: jnp.asarray(x, dtype)  # noqa: E731
        return (cast(q), cast(kc), cast(vc), jnp.int32(layer),
                jnp.asarray(bt), jnp.asarray(self.LENGTHS, jnp.int32), hist)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("groups", [4, 1], ids=["gqa4", "mha"])
    def test_kernel_in_interpret_mode_matches_the_reference(self, groups,
                                                            dtype):
        *args, hist = self._arena(groups, dtype)
        before = attention_path_counts()
        ref = jax.jit(paged_decode_attention)(*args)
        # two pages a block: the page is smaller than the block, the full
        # table is three blocks, and PAGE + 1 ends one page into a block
        out = jax.jit(functools.partial(
            paged_decode_attention, interpret=True,
            pages_per_block=2))(*args)
        after = attention_path_counts()
        assert after.get("decode_reference", 0) == \
            before.get("decode_reference", 0) + 1
        assert after.get("decode_pallas", 0) == \
            before.get("decode_pallas", 0) + 1
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        assert np.isfinite(out).all() and np.isfinite(ref).all()
        tol = 1e-5 if dtype == jnp.float32 else 2e-2   # one bf16 ulp at 2
        np.testing.assert_allclose(out, ref, atol=tol)
        # and both against attention over the history in its logical order
        q = np.asarray(args[0], np.float32)
        for s, (k, v) in enumerate(hist):
            if not len(k):
                assert (out[s] == 0).all() and (ref[s] == 0).all()
                continue
            k = np.asarray(jnp.asarray(k, dtype), np.float32)
            v = np.asarray(jnp.asarray(v, dtype), np.float32)
            for h in range(q.shape[1]):
                sc = k[:, h // groups] @ q[s, h] / np.sqrt(self.HD)
                w = np.exp(sc - sc.max())
                want = (w / w.sum()) @ v[:, h // groups]
                np.testing.assert_allclose(out[s, h], want, atol=tol)

    def test_default_block_covers_the_whole_table(self):
        """With no `pages_per_block` the block is sized in bytes and capped
        at the table: one block here, every page of it conditional."""
        *args, _ = self._arena(4, jnp.float32, seed=1)
        out = paged_decode_attention(*args, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(paged_decode_attention(*args)),
            atol=1e-5)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, devices8, causal):
        mesh = build_mesh(MeshConfig(sp=8))
        q, k, v = _qkv(s=64)
        ring = jax.shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=causal),
            mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None), axis_names={"sp"})
        out = jax.jit(ring)(q, k, v)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_gradients(self, devices8):
        mesh = build_mesh(MeshConfig(sp=4))
        q, k, v = _qkv(s=32)
        ring = jax.shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None), axis_names={"sp"})
        gk1 = jax.grad(lambda k: jnp.sum(ring(q, k, v)))(k)
        gk2 = jax.grad(lambda k: jnp.sum(attention_reference(
            q, k, v, causal=True)))(k)
        np.testing.assert_allclose(np.asarray(gk1), np.asarray(gk2), atol=2e-5)


class TestNorms:
    def test_rms_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
        w = jnp.full((8,), 2.0)
        out = rms_norm(x, w)
        expected = x / np.sqrt(np.mean(np.asarray(x) ** 2, -1,
                                       keepdims=True) + 1e-5) * 2.0
        np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)

    def test_rope_rotation_preserves_norm(self):
        cos, sin = rope_frequencies(32, 128)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16, 32))
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x), axis=-1),
            np.linalg.norm(np.asarray(y), axis=-1), atol=1e-4)

    def test_rope_position_offset(self):
        cos, sin = rope_frequencies(16, 64)
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 8, 16))
        full = apply_rope(jnp.tile(x, (1, 1, 2, 1)), cos, sin)
        shifted = apply_rope(x, cos, sin, positions=jnp.arange(8, 16))
        np.testing.assert_allclose(np.asarray(full[:, :, 8:]),
                                   np.asarray(shifted), atol=1e-5)


class TestMoE:
    @pytest.mark.parametrize("norm", [True, False],
                             ids=["renormalised", "softmax-over-all"])
    def test_top_k_routing(self, norm):
        """The k largest of the float32 softmax over ALL experts; either left
        as they are (OLMoE) or renormalised to sum to one, which is the
        softmax over the selected logits (Mixtral)."""
        logits = jnp.array([[1.0, 3.0, 2.0], [0.0, -1.0, 5.0]])
        w, idx = top_k_routing(logits, 2, norm_topk_prob=norm)
        assert idx.shape == (2, 2)
        assert int(idx[0, 0]) == 1 and int(idx[1, 0]) == 2
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        picked = np.take_along_axis(probs, np.asarray(idx), axis=-1)
        if norm:
            np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(w), np.asarray(jax.nn.softmax(
                    jnp.take_along_axis(logits, idx, axis=-1), axis=-1)),
                atol=1e-6)
        else:
            np.testing.assert_allclose(np.asarray(w), picked, atol=1e-7)
            assert float(w.sum(-1).max()) < 1.0

    def test_moe_matches_dense_when_one_expert(self):
        key = jax.random.PRNGKey(0)
        t, d, f = 6, 8, 16
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (t, d))
        gate_w = jnp.zeros((d, 1))
        w_up = jax.random.normal(ks[1], (1, d, f))
        w_gate = jax.random.normal(ks[2], (1, d, f))
        w_down = jax.random.normal(ks[3], (1, f, d))
        out, aux, counts = moe_ffn(x, gate_w, w_up, w_gate, w_down, top_k=1)
        dense = jax.nn.silu(x @ w_gate[0]) * (x @ w_up[0]) @ w_down[0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=1e-5)
        assert list(np.asarray(counts)) == [t]

    @pytest.mark.parametrize("norm", [True, False])
    def test_sorted_dispatch_matches_reference_combine(self, norm):
        """The sort, grouped matmuls and gather must equal the
        straightforward dense-combine computation, every assignment kept."""
        key = jax.random.PRNGKey(1)
        t, d, f, e, k = 16, 8, 12, 4, 2
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (t, d))
        gate_w = jax.random.normal(ks[4], (d, e))
        w_up = jax.random.normal(ks[1], (e, d, f))
        w_gate = jax.random.normal(ks[2], (e, d, f))
        w_down = jax.random.normal(ks[3], (e, f, d))
        live = jnp.arange(t) < 10
        out, aux, counts = moe_ffn(x, gate_w, w_up, w_gate, w_down, top_k=k,
                                   norm_topk_prob=norm, live=live)

        # Reference: dense every-expert-sees-every-token combine.
        logits = x @ gate_w
        weights, idx = top_k_routing(logits, k, norm)
        one_hot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        combine = jnp.einsum("tk,tke->te", weights, one_hot)
        h = jax.nn.silu(jnp.einsum("td,edf->etf", x, w_gate)) * \
            jnp.einsum("td,edf->etf", x, w_up)
        expert_out = jnp.einsum("etf,efd->etd", h, w_down)
        dense = jnp.einsum("etd,te->td", expert_out, combine)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=2e-4, atol=1e-5)
        # the count is of the live rows' assignments; every row is computed
        np.testing.assert_array_equal(
            np.asarray(counts), np.asarray(one_hot[:10].sum((0, 1)), np.int32))

    @pytest.mark.parametrize("layer", [0, 2])
    def test_stack_with_layer_index_equals_the_slice(self, layer):
        """Serving hands `moe_ffn` every layer's experts and the layer's index
        (the other layers are empty groups of the grouped matmul); training
        hands it the layer's slice. One computation, bit for bit."""
        t, d, f, e, k, n_layers = 12, 8, 12, 4, 2, 3
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        x = jax.random.normal(ks[0], (t, d))
        gate_w = jax.random.normal(ks[4], (d, e))
        w_up = jax.random.normal(ks[1], (n_layers, e, d, f))
        w_gate = jax.random.normal(ks[2], (n_layers, e, d, f))
        w_down = jax.random.normal(ks[3], (n_layers, e, f, d))
        sliced = jax.jit(lambda l: moe_ffn(
            x, gate_w, w_up[l], w_gate[l], w_down[l], top_k=k,
            norm_topk_prob=False))(jnp.int32(layer))
        stacked = jax.jit(lambda l: moe_ffn(
            x, gate_w, w_up, w_gate, w_down, top_k=k, norm_topk_prob=False,
            layer=l))(jnp.int32(layer))
        for a, b in zip(sliced, stacked):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_no_token_dropped_under_skew(self):
        """A router of zeros sends EVERY token to expert 0 (top_k breaks the
        tie to the lowest index): the skew that capacity_factor 1.25 answered
        by dropping 6 of 8 tokens. Every token still gets its expert's
        output, and the op still differentiates."""
        t, d, f, e = 8, 4, 8, 2
        key = jax.random.PRNGKey(2)
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (t, d))
        gate_w = jnp.zeros((d, e))
        w_up = jax.random.normal(ks[1], (e, d, f))
        w_gate = jax.random.normal(ks[2], (e, d, f))
        w_down = jax.random.normal(ks[3], (e, f, d))
        out, _, counts = moe_ffn(x, gate_w, w_up, w_gate, w_down, top_k=1,
                                 norm_topk_prob=False)
        assert list(np.asarray(counts)) == [t, 0]
        # weight 1/2: the softmax over both experts, not renormalised
        dense = 0.5 * (jax.nn.silu(x @ w_gate[0]) * (x @ w_up[0])) @ w_down[0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=1e-5, atol=1e-6)

        def loss(xx, gw):
            o, aux, _ = moe_ffn(xx, gw, w_up, w_gate, w_down, top_k=1,
                                norm_topk_prob=False)
            return jnp.sum(o ** 2) + aux

        gx, gg = jax.grad(loss, argnums=(0, 1))(x, gate_w)
        assert np.isfinite(np.asarray(gx)).all()
        assert np.abs(np.asarray(gx)).min(axis=-1).max() > 0   # every row
        assert np.isfinite(np.asarray(gg)).all() and np.abs(gg).max() > 0


def test_flash_attention_pallas_backward_tpu():
    """Pallas bwd kernels vs reference grads — runs only on real TPU (the
    CI suite forces the CPU platform, where the XLA fallback is used)."""
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        pytest.skip("requires TPU (Pallas kernels)")
    import numpy as np
    from ray_tpu.ops.attention import attention_reference, flash_attention

    B, H, S, D = 2, 4, 512, 128
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, S, D) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, S, D) * 0.5, jnp.float32)
    for causal in (True, False):
        gf = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal)),
            argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                attention_reference(q, k, v, causal=causal)),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gf, gr):
            err = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))
            assert err < 2e-2, (causal, err)

"""`ray_tpu/ops/paged_kv.py` alone, no engine around it: the layout's three
writers and its reader against plain attention. (The kernel's own cases, page
boundaries and dtypes, are in tests/test_ops.py, which tier-1 leaves out.)"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_kv
from ray_tpu.ops.attention import attention_reference, repeat_kv
from ray_tpu.ops.paged_kv import paged_decode_attention


class TestPagedKvLayout:
    """`ops/paged_kv.py` alone, no engine around it: what `write_prompt` and
    `write_token` put into the arena is what `paged_decode_attention` reads
    back, and it equals plain attention over the same keys and values."""

    PAGE, MAXP, HD, LAYERS, LAYER = 8, 4, 128, 2, 1

    @pytest.mark.parametrize("path", ["reference", "kernel"])
    @pytest.mark.parametrize("heads", [(8, 2), (4, 4), (20, 1)],
                             ids=["gqa4", "mha", "mqa20"])
    def test_prompt_then_tokens_then_attention(self, heads, path):
        H, KVH = heads
        page, maxp, hd = self.PAGE, self.MAXP, self.HD
        rng = np.random.default_rng(0)
        # slot 0: a prompt that ends inside its second page, then decode
        # steps across the boundary into the third; slot 1: from nothing,
        # inside one page; slot 2: idle throughout.
        prompt, steps, ns = page + 5, 5, 3
        bt = np.zeros((ns, maxp), np.int32)
        bt[0, :3] = (7, 2, 5)
        bt[1, :1] = (4,)
        kc, vc = paged_kv.empty(self.LAYERS, 9, KVH, page, hd, jnp.float32)
        assert kc.shape == vc.shape == (self.LAYERS, 9, KVH, page, hd)
        # the prompt, padded to a bucket of 16 (W), every layer of it
        ks = rng.standard_normal((self.LAYERS, 16, KVH, hd)).astype("f4")
        vs = rng.standard_normal((self.LAYERS, 16, KVH, hd)).astype("f4")
        ks[:, prompt:] = vs[:, prompt:] = 0.0
        kc, vc = jax.jit(paged_kv.write_prompt)(
            kc, vc, jnp.asarray(bt[0]), jnp.asarray(ks), jnp.asarray(vs))
        hist_k = [list(ks[self.LAYER, :prompt]), [], []]
        hist_v = [list(vs[self.LAYER, :prompt]), [], []]
        active = np.array([True, True, False])
        write = jax.jit(paged_kv.write_token)
        attend = jax.jit(functools.partial(
            paged_decode_attention, interpret=path == "kernel"))
        for step in range(steps):
            w = np.array([prompt + step, step, 3], np.int32)
            k = rng.standard_normal((ns, KVH, hd)).astype("f4")
            v = rng.standard_normal((ns, KVH, hd)).astype("f4")
            null_before = (np.asarray(kc[:, 0]), np.asarray(vc[:, 0]))
            held_before = np.asarray(kc[:, [1, 3, 6, 8]])
            kc, vc = write(kc, vc, jnp.int32(self.LAYER), jnp.asarray(bt),
                           jnp.asarray(w), jnp.asarray(active),
                           jnp.asarray(k), jnp.asarray(v))
            # the idle slot wrote its row to the null page, this layer, and
            # nothing else changed there; pages no table names are untouched
            want_k, want_v = (x.copy() for x in null_before)
            want_k[self.LAYER, :, 0], want_v[self.LAYER, :, 0] = k[2], v[2]
            np.testing.assert_array_equal(np.asarray(kc[:, 0]), want_k)
            np.testing.assert_array_equal(np.asarray(vc[:, 0]), want_v)
            np.testing.assert_array_equal(np.asarray(kc[:, [1, 3, 6, 8]]),
                                          held_before)
            for s in (0, 1):
                hist_k[s].append(k[s])
                hist_v[s].append(v[s])
            q = rng.standard_normal((ns, H, hd)).astype("f4")
            out = np.asarray(attend(
                jnp.asarray(q), kc, vc, jnp.int32(self.LAYER),
                jnp.asarray(bt), jnp.asarray(np.where(active, w + 1, 0))))
            assert (out[2] == 0).all()
            for s in (0, 1):
                # [1, H, 1, hd] against [1, H, n, hd], the last position
                kk = repeat_kv(jnp.asarray(np.stack(hist_k[s]))
                               .transpose(1, 0, 2)[None], H // KVH)
                vv = repeat_kv(jnp.asarray(np.stack(hist_v[s]))
                               .transpose(1, 0, 2)[None], H // KVH)
                want = attention_reference(
                    jnp.asarray(q[s])[None, :, None], kk, vv, causal=False)
                np.testing.assert_allclose(out[s], np.asarray(want[0, :, 0]),
                                           atol=2e-5)
        # the prompt's padding went to the null page or the tail of its own
        # last page, never to a page of another slot
        np.testing.assert_array_equal(
            np.asarray(kc[self.LAYER, 4, :, steps:]), 0.0)


    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_twenty_query_heads_on_one_kv_head(self, dtype):
        """MQA at `groups` 20 (a hybrid model's attention layers): the
        kernel's rows are the 20 query heads of the one kv head, padded to
        whole tiles (24 rows of float32; 2 x 20 -> 48 of bfloat16, whose
        softmax weights go through the MXU in two halves), against
        `_paged_decode_reference`; and `repeat_kv` at 20 is each query head
        reading that one head."""
        H, page, maxp, hd, ns = 20, self.PAGE, self.MAXP, self.HD, 3
        rng = np.random.default_rng(3)
        kc = jnp.asarray(rng.standard_normal((2, 9, 1, page, hd)), dtype)
        vc = jnp.asarray(rng.standard_normal((2, 9, 1, page, hd)), dtype)
        bt = np.zeros((ns, maxp), np.int32)
        bt[0], bt[1, :2] = (7, 2, 5, 1), (4, 8)
        lengths = jnp.asarray([4 * page, page + 3, 0], jnp.int32)
        q = jnp.asarray(rng.standard_normal((ns, H, hd)), dtype)
        args = (q, kc, vc, jnp.int32(1), jnp.asarray(bt), lengths)
        got = paged_decode_attention(*args, interpret=True)
        want = paged_kv._paged_decode_reference(*args, sm_scale=hd ** -0.5)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
        assert got.shape == (ns, H, hd) and not np.asarray(got[2]).any()
        k = jnp.asarray(rng.standard_normal((1, 1, 5, hd)), dtype)
        rep = repeat_kv(k, H)
        assert rep.shape == (1, H, 5, hd)
        np.testing.assert_array_equal(
            np.asarray(rep, np.float32),
            np.broadcast_to(np.asarray(k, np.float32), (1, H, 5, hd)))


class TestRowsByTokenAndIndexerKeys:
    """The layout a reader that gathers positions asks for (K and V by
    token), and the second kind of row under the same block table (an
    indexer's keys): the same pages, the same null page, the same writers'
    contract as the arena above."""

    PAGE, MAXP, HD, KVH, ID, LAYERS = 8, 4, 16, 2, 4, 2

    def test_by_token_holds_what_the_head_major_arena_holds(self):
        page, hd, KVH, L = self.PAGE, self.HD, self.KVH, self.LAYERS
        rng = np.random.default_rng(1)
        bt = np.zeros((2, self.MAXP), np.int32)
        bt[0, :3], bt[1, :1] = (7, 2, 5), (4,)
        ks = jnp.asarray(rng.standard_normal((L, 16, KVH, hd)), jnp.float32)
        vs = jnp.asarray(rng.standard_normal((L, 16, KVH, hd)), jnp.float32)
        arenas = {}
        for by_token in (False, True):
            kc, vc = paged_kv.empty(L, 9, KVH, page, hd, jnp.float32,
                                    by_token=by_token)
            assert kc.shape == ((L, 9, page, KVH * hd) if by_token
                                else (L, 9, KVH, page, hd))
            kc, vc = jax.jit(paged_kv.write_prompt)(
                kc, vc, jnp.asarray(bt[0]), ks, vs)
            for step in range(3):      # slot 0 crosses into its third page
                w = np.array([14 + step, step], np.int32)
                k = jnp.asarray(rng.standard_normal((2, KVH, hd)), jnp.float32)
                kc, vc = jax.jit(paged_kv.write_token)(
                    kc, vc, jnp.int32(1), jnp.asarray(bt), jnp.asarray(w),
                    jnp.asarray([True, step < 2]), k, -k)
            arenas[by_token] = (np.asarray(kc), np.asarray(vc))
            rng = np.random.default_rng(1)     # the same draws again
            rng.standard_normal((2, L, 16, KVH, hd))
        for a, b in zip(arenas[False], arenas[True]):
            np.testing.assert_array_equal(
                a.transpose(0, 1, 3, 2, 4).reshape(b.shape), b)
        assert arenas[True][0][1, 5, 0].any()          # position 16, layer 1

    def test_indexer_keys_go_where_k_and_v_go(self):
        page, L, dim = self.PAGE, self.LAYERS, self.ID
        rng = np.random.default_rng(2)
        bt = np.zeros((3, self.MAXP), np.int32)
        bt[0, :3], bt[1, :1] = (7, 2, 5), (4,)
        ic = paged_kv.empty_index(L, 9, page, dim, jnp.float32)
        assert ic.shape == (L, 9, page, dim)
        iks = rng.standard_normal((L, 16, dim)).astype("f4")
        iks[:, 13:] = 0.0                  # a prompt of 13 in a bucket of 16
        ic = jax.jit(paged_kv.write_prompt_rows)(ic, jnp.asarray(bt[0]),
                                                  jnp.asarray(iks))
        np.testing.assert_array_equal(np.asarray(ic[:, 7]), iks[:, :page])
        np.testing.assert_array_equal(np.asarray(ic[:, 2]), iks[:, page:])
        held = np.asarray(ic)
        ik = rng.standard_normal((3, dim)).astype("f4")
        ic = jax.jit(paged_kv.write_token_rows)(
            ic, jnp.int32(1), jnp.asarray(bt),
            jnp.asarray([13, 0, 3], np.int32),
            jnp.asarray([True, True, False]), jnp.asarray(ik))
        got = np.asarray(ic)
        np.testing.assert_array_equal(got[1, 2, 13 - page], ik[0])
        np.testing.assert_array_equal(got[1, 4, 0], ik[1])
        np.testing.assert_array_equal(got[1, 0, 0], ik[2])   # idle: null page
        changed = got != held
        assert changed.sum() <= 3 * dim and not changed[0].any()

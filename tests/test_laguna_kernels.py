"""The prefill kernels a mixed stack runs at Laguna's shapes, in interpret
mode on the CPU against `mixed_attention_reference`: a window of SEVERAL
blocks read by groups of nine query heads a kv head (`window_blocks_fwd`), a
key of ONE 128-wide part (both kernels), and the two halves of a full layer's
head joined where the dispatch finds them no whole tiles apart."""

import functools

import numpy as np
import pytest

import jax

from ray_tpu.ops import attention


def _qkv(S, H, kvh, dn, dr, dv=128, seed=0):
    """(q_n, q_r, k_n, k_r, v), the passed parts None where dn is 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = [(1, H, S, dn), (1, H, S, dr), (1, kvh, S, dn), (1, kvh, S, dr),
              (1, kvh, S, dv)]
    return [jax.random.normal(k, s) if s[-1] else None
            for k, s in zip(ks, shapes)]


@pytest.mark.parametrize("window,S,H,kvh,dn,dr", [
    (512, 1024, 18, 2, 0, 128), (512, 640, 9, 1, 0, 128),
    (512, 256, 9, 1, 0, 128), (200, 512, 6, 2, 0, 128),
    (512, 64, 9, 1, 0, 128), (128, 384, 4, 2, 64, 64),
    (0, 384, 12, 2, 64, 64), (0, 256, 6, 1, 0, 128), (0, 32, 6, 1, 64, 64)],
    ids=["window-512-group-9-eight-blocks", "window-512-five-blocks",
         "a-prompt-inside-the-window", "window-200", "under-one-block",
         "window-joined-halves", "full-joined-halves-group-6",
         "full-one-part", "full-under-one-block"])
def test_one_part_kernels_are_their_reference_path(window, S, H, kvh, dn, dr):
    """`window_blocks_fwd` (queries whose window's far edge falls inside the
    oldest block they touch, blocks before position 0, a prompt shorter than
    the window and one shorter than a block) and `full_flash_fwd` on a key
    of one part (K and V read by kv head, 6 and 9 query heads a group) in
    interpret mode against every score under a mask."""
    q_n, q_r, k_n, k_r, v = _qkv(S, H, kvh, dn, dr)
    before = attention.attention_path_counts()
    got = attention.mixed_flash_attention(
        q_n, q_r, k_n, k_r, v, 128 ** -0.5, window=window, interpret=True)
    kind = "window" if window else "full"
    after = attention.attention_path_counts()
    assert after[f"{kind}_fwd_pallas"] == before.get(f"{kind}_fwd_pallas",
                                                     0) + 1
    want = attention.mixed_attention_reference(
        q_n, q_r, k_n, k_r, v, 128 ** -0.5, window)
    assert got.shape == (1, H, S, 128)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6


def test_a_window_of_blocks_never_reads_past_its_window():
    """Keys and values older than the window overwritten with garbage: the
    rows of `window_blocks_fwd`, and its reference's, that no longer see them
    do not move."""
    _, q, _, k, v = _qkv(1024, 9, 1, 0, 128, seed=3)
    old = 1024 - 512
    trash = [t.at[:, :, :old].set(1e4) for t in (k, v)]
    for fn in (functools.partial(attention.mixed_flash_attention,
                                 interpret=True, window=512),
               lambda *a: attention.mixed_attention_reference(*a, 512)):
        clean = np.asarray(fn(None, q, None, k, v, 128 ** -0.5))
        dirty = np.asarray(fn(None, q, None, *trash, 128 ** -0.5))
        assert (clean[:, :, old + 511:] == dirty[:, :, old + 511:]).all()
        assert np.abs(clean[:, :, :old] - dirty[:, :, :old]).max() > 1.0


def test_the_dispatch_counts_a_fall_to_the_reference_and_says_why():
    """A head of 96 = 32 passed + 64 turned is no kernel's, interpreted or
    not: the path count says `reference`, and `mixed_kernel_refusal` names
    the shape (what `Engine` raises with on a TPU, tests/test_laguna.py)."""
    q_n, q_r, k_n, k_r, v = _qkv(128, 4, 2, 32, 64)
    before = attention.attention_path_counts().get("full_fwd_reference", 0)
    attention.mixed_flash_attention(q_n, q_r, k_n, k_r, v, 96 ** -0.5,
                                    interpret=True)
    assert attention.attention_path_counts()["full_fwd_reference"] \
        == before + 1
    why = attention.mixed_kernel_refusal(128, 32, 64, 128, 0)
    assert "32 passed + 64 turned" in why and "whole tiles of 128" in why
    assert "no sink" in attention.mixed_kernel_refusal(256, 0, 128, 128, 512,
                                                       sink=True)
    assert "values" in attention.mixed_kernel_refusal(256, 0, 128, 64, 512)

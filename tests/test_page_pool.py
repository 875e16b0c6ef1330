"""`serve.page_pool.PagePool` alone: the host side of the paged KV cache,
with no engine and no device around it."""

import numpy as np
import pytest

from ray_tpu.serve.page_pool import PagePool


@pytest.mark.parametrize("n_slots,max_seq,page_size,want", [
    (4, 128, 16, (16, 8, 17)),      # null + half of 4 x 8
    (1, 128, 16, (16, 8, 9)),       # never less than one max_seq request
    (3, 100, 64, (64, 2, 4)),       # max_seq not a multiple of the page
    (8, 32, 64, (32, 1, 5)),        # a page never longer than max_seq
], ids=["half-worst-case", "one-request", "ragged", "short-seq"])
def test_default_size(n_slots, max_seq, page_size, want):
    pool = PagePool(n_slots, max_seq, page_size)
    assert (pool.page, pool.maxp, pool.n_pages) == want
    assert pool.free == pool.n_pages - 1 and pool.in_use() == 0
    assert pool.block_table.shape == (n_slots, pool.maxp)
    assert pool.block_table.dtype == np.int32


def test_too_few_pages_for_one_request_is_refused():
    with pytest.raises(ValueError, match="cannot hold one max_seq request"):
        PagePool(4, 128, 16, n_pages=8)
    assert PagePool(4, 128, 16, n_pages=9).free == 8


@pytest.mark.parametrize("prompt,max_tokens,want", [
    (1, 1, 1), (10, 6, 1), (10, 7, 2), (50, 14, 4), (100, 100, 8),
    (127, 1, 8)])
def test_reservation_covers_every_position_a_request_can_reach(
        prompt, max_tokens, want):
    assert PagePool(4, 128, 16).pages_for(prompt, max_tokens) == want


def test_grant_never_hands_out_the_null_page_or_a_held_page():
    pool = PagePool(4, 128, 16)
    rows = [pool.grant(slot, 4) for slot in range(4)]
    held = np.concatenate([r[:4] for r in rows])
    assert 0 not in held and len(set(held)) == 16 and pool.free == 0
    for slot, row in enumerate(rows):
        assert row.dtype == np.int32 and row.shape == (pool.maxp,)
        assert (row[4:] == 0).all()          # padded with the null page
        np.testing.assert_array_equal(pool.block_table[slot], row)
    # a copy: the table moves on without the row a program was handed
    pool.release(0)
    assert (rows[0][:4] != 0).all()


def test_grant_then_release_returns_the_pages_and_clears_the_row():
    pool = PagePool(4, 128, 16)
    pool.grant(2, 5)
    assert (pool.free, pool.in_use()) == (11, 5)
    pool.release(2)
    assert (pool.free, pool.in_use()) == (16, 0)
    assert (pool.block_table == 0).all()
    pool.release(2)                          # holding nothing: nothing to do
    assert pool.free == 16


@pytest.mark.parametrize("slot,need", [(1, 13), (0, 1)],
                         ids=["too-many", "slot-still-holds"])
def test_a_grant_that_does_not_fit_is_refused_and_changes_nothing(slot, need):
    pool = PagePool(4, 128, 16)
    pool.grant(0, 4)
    table, free = pool.block_table.copy(), list(pool._free)
    with pytest.raises(RuntimeError, match="pages asked"):
        pool.grant(slot, need)
    np.testing.assert_array_equal(pool.block_table, table)
    assert pool._free == free and pool.in_use() == 4


def test_pages_are_granted_in_the_order_the_engine_always_granted_them():
    """Recorded from the engine's own free list at PR 28 (pop from the end
    of `range(n_pages - 1, 0, -1)`, finishes appended): the same requests
    get the same physical pages."""
    pool = PagePool(4, 128, 16)
    recorded = [
        ("admit", 0, 20, 30, [1, 2, 3, 4]),
        ("admit", 1, 5, 3, [5]),
        ("admit", 2, 100, 100, [6, 7, 8, 9, 10, 11, 12, 13]),
        ("finish", 1),
        ("admit", 1, 40, 8, [5, 14, 15]),
        ("finish", 0),
        ("finish", 2),
        ("admit", 3, 1, 127, [13, 12, 11, 10, 9, 8, 7, 6]),
        ("admit", 0, 33, 31, [4, 3, 2, 1]),
    ]
    for op, slot, *rest in recorded:
        if op == "finish":
            pool.release(slot)
            continue
        prompt, max_tokens, pages = rest
        need = pool.pages_for(prompt, max_tokens)
        assert need == len(pages) <= pool.free
        row = pool.grant(slot, need)
        assert list(row[:need]) == pages and (row[need:] == 0).all()
    assert pool.in_use() == 15 and pool.free == 1

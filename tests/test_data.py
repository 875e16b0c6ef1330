"""Data subsystem: blocks stream through generator tasks, transforms fuse,
iterators batch, splits coordinate, and the host path is zero-copy.

Mirrors the reference's data tests (reference: python/ray/data/tests/
test_basic.py-style coverage of map_batches/iter_batches/streaming_split,
test_streaming_executor.py backpressure) at this framework's scale.
"""

import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
import ray_tpu.data as rdata
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.data.iterator import (PRODUCER_THREAD, iter_batches_from_refs,
                                   iter_jax_batches_from_refs)


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(num_nodes=1, resources={"CPU": 8})
    c.connect()
    yield c
    c.shutdown()


def test_range_count_take(cluster):
    ds = rdata.range(1000, num_blocks=4)
    assert ds.count() == 1000
    assert ds.num_blocks() == 4
    rows = ds.take(5)
    assert [r["id"] for r in rows] == [0, 1, 2, 3, 4]


def test_map_batches_and_filter(cluster):
    ds = (rdata.range(100, num_blocks=4)
          .map_batches(lambda b: {"id": b["id"] * 2})
          .filter(lambda r: r["id"] % 4 == 0))
    got = sorted(r["id"] for r in ds.take_all())
    assert got == [i * 2 for i in range(100) if (i * 2) % 4 == 0]


def test_map_and_flat_map_rows(cluster):
    ds = rdata.from_items([1, 2, 3], num_blocks=2).map(lambda x: x + 10)
    assert sorted(ds.take_all()) == [11, 12, 13]
    ds2 = rdata.from_items([1, 2]).flat_map(lambda x: [x, x])
    assert sorted(ds2.take_all()) == [1, 1, 2, 2]


def test_iter_batches_exact_batching(cluster):
    ds = rdata.range(100, num_blocks=3)
    sizes = [len(b["id"]) for b in ds.iter_batches(batch_size=32)]
    assert sum(sizes) == 100
    assert all(s == 32 for s in sizes[:-1])  # re-chunked across blocks


def test_streaming_overlap(cluster, tmp_path):
    """Blocks must be consumable before the whole pipeline finishes.
    Asserted as a HANDSHAKE, not wall-clock ratios (host-load-immune):
    the LAST block's task blocks until the consumer proves it received
    the FIRST batch — if outputs only surfaced after a full drain, the
    pipeline would wedge on that handshake and trip the deadline."""
    marker = str(tmp_path / "first-batch-consumed")

    def slow_stage(batch, marker=marker):
        import os as _os
        import time as _t
        if int(batch["id"][0]) // 64 == 7:
            # Final block: wait (bounded) for the consumer's receipt of
            # the first batch — only possible when earlier outputs are
            # consumable while this task is still RUNNING.
            deadline = _t.monotonic() + 30.0
            while not _os.path.exists(marker):
                if _t.monotonic() > deadline:
                    raise RuntimeError(
                        "consumer never saw the first batch while the "
                        "last block was in flight: no streaming overlap")
                _t.sleep(0.05)
        else:
            _t.sleep(0.05)
        return batch

    # Warm the worker pool first: on a loaded 1-core host, 8 cold worker
    # spawns (~0.5s each, serialized) would swamp the overlap signal.
    rdata.range(8, num_blocks=8).map_batches(lambda b: b).take_all()

    ds = rdata.range(8 * 64, num_blocks=8).map_batches(slow_stage)
    it = iter(ds.iter_batches(batch_size=None))
    first = next(it)
    open(marker, "w").close()      # receipt: unblocks the final block
    n_rest = sum(1 for _ in it)
    assert len(first["id"]) == 64 and n_rest == 7


def test_materialize_and_split(cluster):
    ds = rdata.range(100, num_blocks=4).materialize()
    parts = ds.split(2)
    counts = [p.count() for p in parts]
    assert sum(counts) == 100
    assert all(c > 0 for c in counts)


def test_repartition_and_shuffle(cluster):
    ds = rdata.range(90, num_blocks=3).repartition(5)
    assert ds.num_blocks() == 5
    assert ds.count() == 90
    sh = rdata.range(50, num_blocks=2).random_shuffle(seed=0)
    ids = [r["id"] for r in sh.take_all()]
    assert sorted(ids) == list(range(50))
    assert ids != list(range(50))  # actually permuted


def test_streaming_split_equal(cluster):
    ds = rdata.range(96, num_blocks=8)
    its = ds.streaming_split(2, equal=True)
    import threading
    out = [None, None]

    def consume(i):
        out[i] = [r["id"] for b in its[i].iter_batches(batch_size=None)
                  for r in rdata.BlockAccessor(b).to_rows()]

    ts = [threading.Thread(target=consume, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert sorted(out[0] + out[1]) == list(range(96))
    # equal=True: same number of blocks each (8 blocks / 2 consumers)
    assert len(out[0]) == len(out[1]) == 48


def test_streaming_split_equal_nondivisible(cluster):
    """equal=True must give identical block AND row counts even when the
    upstream block count does not divide the consumer count (SPMD loops
    run a collective per batch; unequal steps would hang them)."""
    ds = rdata.range(90, num_blocks=5)  # 5 blocks / 2 consumers
    its = ds.streaming_split(2, equal=True)
    rows = [[], []]
    for i in (0, 1):
        for b in its[i].iter_batches(batch_size=None):
            rows[i].extend(r["id"] for r in rdata.BlockAccessor(b).to_rows())
    assert len(rows[0]) == len(rows[1])  # strict row parity
    assert len(rows[0]) + len(rows[1]) >= 88  # at most n-1 dropped per block
    assert not set(rows[0]) & set(rows[1])  # disjoint shards


def test_parquet_roundtrip(cluster, tmp_path):
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as pq

    table = pa.table({"x": np.arange(100), "y": np.arange(100) * 0.5})
    path = os.path.join(tmp_path, "t.parquet")
    pq.write_table(table, path)
    ds = rdata.read_parquet(path)
    assert ds.count() == 100
    batch = next(iter(ds.iter_batches(batch_size=None)))
    np.testing.assert_array_equal(batch["x"], np.arange(100))


def test_zero_copy_host_path(cluster):
    """Blocks deserialized from the shm store must be VIEWS into the mmap
    (no host copy) — the north-star ingest property."""
    big = {"x": np.arange(200_000, dtype=np.float64)}  # 1.6MB: store path
    ds = rdata.from_numpy(big["x"])
    [ref] = list(ds.iter_block_refs())
    block = ray_tpu.get(ref)
    arr = block["data"]
    assert not arr.flags["OWNDATA"], "block array was copied on the host path"
    np.testing.assert_array_equal(arr, big["x"])


def test_iter_jax_batches(cluster):
    ds = rdata.range(64, num_blocks=2)
    batches = list(ds.iter_jax_batches(batch_size=16))
    assert len(batches) == 4
    import jax
    assert isinstance(batches[0]["id"], jax.Array)
    total = sum(int(b["id"].sum()) for b in batches)
    assert total == sum(range(64))


def test_trainer_ingests_via_data(cluster):
    """North-star slice: JaxTrainer workers pull their shard through
    streaming_split and train on jax batches."""
    from ray_tpu.train import JaxTrainer, ScalingConfig

    ds = rdata.range(64, num_blocks=4).map_batches(
        lambda b: {"x": b["id"].astype(np.float32)})

    def loop(config):
        import jax.numpy as jnp

        import ray_tpu.train as rt
        it = rt.get_dataset_shard("train")
        total = 0.0
        n = 0
        for batch in it.iter_jax_batches(batch_size=8):
            total += float(jnp.sum(batch["x"]))
            n += 1
        rt.report({"sum": total, "batches": n})

    trainer = JaxTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
        datasets={"train": ds},
        worker_env={"JAX_PLATFORMS": "cpu"},
    )
    result = trainer.fit()
    # Both workers together consumed the whole range exactly once.
    hist = result.metrics_history
    assert hist, "no metrics reported"
    # rank 0's history only contains its own shard sum; grab both via total
    # reported metric from rank0 + assert structure instead.
    assert hist[-1]["batches"] == 4  # 32 rows / batch 8 on rank 0's shard


def test_map_batches_actor_compute(cluster):
    """concurrency=N runs the transform on a pool of actors; a callable
    CLASS is constructed once per actor (reference:
    ActorPoolMapOperator + map_batches(CallableClass, concurrency=N))."""
    import os

    class AddPid:
        def __init__(self, offset):
            self.offset = offset
            self.pid = os.getpid()

        def __call__(self, batch):
            return {"id": batch["id"] + self.offset,
                    "pid": np.full_like(batch["id"], self.pid)}

    ds = rdata.range(120, num_blocks=6).map_batches(
        AddPid, concurrency=2, fn_constructor_args=(1000,))
    rows = ds.take_all()
    assert sorted(r["id"] for r in rows) == [1000 + i for i in range(120)]
    pids = {r["pid"] for r in rows}
    assert 1 <= len(pids) <= 2, pids  # exactly the pool's actors

    # Chained fused transform downstream of the actor stage.
    ds2 = (rdata.range(40, num_blocks=4)
           .map_batches(AddPid, concurrency=2, fn_constructor_args=(0,))
           .filter(lambda r: r["id"] % 2 == 0))
    got = sorted(r["id"] for r in ds2.take_all())
    assert got == [i for i in range(40) if i % 2 == 0]


def test_union_and_sort(cluster):
    a = rdata.range(10, num_blocks=2)
    b = rdata.range(10, num_blocks=2).map_batches(
        lambda x: {"id": x["id"] + 100})
    u = a.union(b)
    assert u.num_blocks() == 4
    ids = sorted(r["id"] for r in u.take_all())
    assert ids == list(range(10)) + [100 + i for i in range(10)]

    sh = rdata.range(30, num_blocks=3).random_shuffle(seed=1)
    asc = [r["id"] for r in sh.sort("id").take_all()]
    assert asc == list(range(30))
    desc = [r["id"] for r in sh.sort("id", descending=True).take_all()]
    assert desc == list(range(29, -1, -1))


def test_union_with_downstream_transform_and_empty_sort(cluster):
    u = rdata.range(6, num_blocks=2).union(rdata.range(6, num_blocks=2))
    doubled = sorted(r["id"] for r in u.map_batches(
        lambda b: {"id": b["id"] * 2}).take_all())
    assert doubled == sorted([2 * i for i in range(6)] * 2)
    assert rdata.from_items([]).sort("id").take_all() == []


def test_read_text_and_binary(cluster, tmp_path):
    p1 = tmp_path / "a.txt"
    p1.write_text("alpha\nbeta\ngamma\n")
    p2 = tmp_path / "b.bin"
    p2.write_bytes(b"\x00\x01payload")
    ds = rdata.read_text(str(p1))
    assert [r["text"] for r in ds.take_all()] == ["alpha", "beta", "gamma"]
    bs = rdata.read_binary_files(str(p2), include_paths=True)
    rows = bs.take_all()
    assert rows[0]["bytes"] == b"\x00\x01payload"
    assert rows[0]["path"].endswith("b.bin")


def test_read_images(cluster, tmp_path):
    from PIL import Image
    for i in range(3):
        Image.new("RGB", (8, 6), color=(i * 10, 0, 0)).save(
            tmp_path / f"img{i}.png")
    ds = rdata.read_images(str(tmp_path), size=(4, 4), mode="L")
    imgs = [r["image"] for r in ds.take_all()]
    assert len(imgs) == 3
    assert all(im.shape == (4, 4) for im in imgs)


def test_writers_roundtrip(cluster, tmp_path):
    """write_parquet/csv/json produce one file per block; reading them
    back yields the same rows (reference: Dataset.write_* datasinks)."""
    ds = rdata.range(40, num_blocks=4).map_batches(
        lambda b: {"id": b["id"], "sq": b["id"] ** 2})

    pq_files = ds.write_parquet(str(tmp_path / "pq"))
    assert len(pq_files) == 4
    back = rdata.read_parquet(str(tmp_path / "pq"))
    assert sorted(r["id"] for r in back.take_all()) == list(range(40))

    csv_files = ds.write_csv(str(tmp_path / "csv"))
    assert len(csv_files) == 4
    back = rdata.read_csv(str(tmp_path / "csv"))
    assert sorted(r["sq"] for r in back.take_all()) == \
        [i ** 2 for i in range(40)]

    js_files = ds.write_json(str(tmp_path / "js"))
    import json
    rows = [json.loads(line) for f in js_files for line in open(f)]
    assert sorted(r["id"] for r in rows) == list(range(40))


def test_dataset_stats_exposes_operator_metrics(cluster):
    ds = rdata.range(40, num_blocks=4).map_batches(lambda b: b)
    assert ds.stats()["plan"] == ["_Read", "_Fused"]
    assert ds.count() == 40
    ops = ds.stats()["operators"]
    assert ops["read->map"]["tasks_launched"] == 4
    assert ops["read->map"]["blocks_out"] == 4


# -- the next batch is made ahead (data/iterator.py::_ahead) -----------------
# This module is a slow one (conftest._SLOW_MODULES); these are light and are
# marked `fast`, so tier-1 runs them.


def _producers():
    return {t for t in threading.enumerate()
            if t.name == PRODUCER_THREAD and t.is_alive()}


def _until(cond, seconds=10.0):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


class _Source:
    """Block references handed out one at a time, counted; `fail_at` raises
    where the n-th would have been."""

    def __init__(self, n_blocks, rows=4, fail_at=None):
        self.refs = [ray_tpu.put({"id": np.arange(i * rows, (i + 1) * rows)})
                     for i in range(n_blocks)]
        self.asked, self.fail_at = 0, fail_at

    def __iter__(self):
        for i, ref in enumerate(self.refs):
            self.asked += 1
            if i == self.fail_at:
                raise RuntimeError(f"block {i} is lost")
            yield ref


@pytest.mark.fast
@pytest.mark.parametrize("api", ["iter_batches", "iter_jax_batches"])
@pytest.mark.parametrize("rows_a_block", [8, 9],
                         ids=["blocks_divide", "blocks_do_not_divide"])
@pytest.mark.parametrize("drop_last", [False, True])
@pytest.mark.parametrize("prefetch_batches", [0, 1, 3])
def test_batches_made_ahead_are_the_inline_paths_in_its_order(
        cluster, prefetch_batches, drop_last, rows_a_block, api):
    ds = rdata.range(5 * rows_a_block, num_blocks=5)
    want = [np.arange(s, min(s + 4, 5 * rows_a_block))
            for s in range(0, 5 * rows_a_block, 4)]
    if drop_last:
        want = [w for w in want if len(w) == 4]
    before = _producers()
    inline, got = ([b["id"] for b in getattr(ds, api)(
        batch_size=4, drop_last=drop_last, prefetch_batches=n)]
        for n in (0, prefetch_batches))
    assert len(got) == len(inline) == len(want)
    for g, i, w in zip(got, inline, want):
        assert type(g) is type(i) and g.dtype == i.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(i), w)
    if api == "iter_jax_batches":
        import jax
        assert all(isinstance(g, jax.Array) for g in got)
    assert _until(lambda: _producers() <= before)    # the end ends the thread


@pytest.mark.fast
@pytest.mark.parametrize("prefetch_batches", [1, 3])
def test_the_producer_is_at_most_prefetch_batches_plus_one_ahead(
        cluster, prefetch_batches):
    """A block a batch and no reference held, so the source's count is the
    batches made: the one the consumer took, the queue's, and the one the
    producer holds while it waits for room."""
    src = _Source(12)
    it = iter_batches_from_refs(iter(src), batch_size=4, prefetch_blocks=0,
                                prefetch_batches=prefetch_batches)
    assert src.asked == 0 and isinstance(next(it)["id"], np.ndarray)
    assert _until(lambda: src.asked == 1 + prefetch_batches + 1)
    time.sleep(0.2)
    assert src.asked == 1 + prefetch_batches + 1
    next(it)
    assert _until(lambda: src.asked == 2 + prefetch_batches + 1)
    time.sleep(0.2)
    assert src.asked == 2 + prefetch_batches + 1
    assert [int(b["id"][0]) for b in it] == list(range(8, 48, 4))


@pytest.mark.fast
@pytest.mark.parametrize("jax_batches", [False, True])
def test_a_sources_exception_reaches_the_consumer_where_it_happened(
        cluster, jax_batches):
    src = _Source(6, fail_at=3)
    before = _producers()
    it = (iter_jax_batches_from_refs if jax_batches
          else iter_batches_from_refs)(iter(src), batch_size=4,
                                       prefetch_blocks=0, prefetch_batches=2)
    got = []
    with pytest.raises(RuntimeError, match="block 3 is lost"):
        for b in it:
            got.append(int(b["id"][0]))
    assert got == [0, 4, 8] and src.asked == 4
    assert _until(lambda: _producers() <= before)
    assert list(it) == []                    # and the iterator is at its end

    # A `get` that fails (the block's task raised) arrives the same way.
    @ray_tpu.remote
    def lost():
        raise ValueError("no such block")

    refs = [ray_tpu.put({"id": np.arange(4)}), lost.remote()]
    it = iter_batches_from_refs(iter(refs), batch_size=4, prefetch_batches=1)
    assert list(next(it)["id"]) == [0, 1, 2, 3]
    with pytest.raises(Exception, match="no such block"):
        next(it)
    assert _until(lambda: _producers() <= before)


@pytest.mark.fast
@pytest.mark.parametrize("how", ["close", "del", "break"])
def test_an_iterator_that_is_let_go_stops_its_thread_and_asks_no_more(
        cluster, how):
    src = _Source(12)
    before = _producers()
    it = iter_batches_from_refs(iter(src), batch_size=4, prefetch_blocks=0,
                                prefetch_batches=1)
    if how == "break":
        for _ in it:
            break
    else:
        next(it)
    (mine,) = _producers() - before
    assert _until(lambda: src.asked == 3)    # taken, queued, held: it waits
    if how == "close":
        it.close()
    else:
        del it
    assert _until(lambda: not mine.is_alive(), seconds=1.0)
    time.sleep(0.1)
    assert src.asked == 3


@pytest.mark.fast
def test_an_iterator_never_started_starts_no_thread(cluster):
    src = _Source(3)
    before = _producers()
    it = iter_batches_from_refs(iter(src), batch_size=4, prefetch_batches=1)
    time.sleep(0.05)
    assert src.asked == 0 and _producers() <= before
    del it
    # The row-at-a-time consumers take the inline path: no thread to reap.
    ds = rdata.range(40, num_blocks=4)
    assert [r["id"] for r in ds.take(3)] == [0, 1, 2]
    assert next(iter(ds.iter_rows()))["id"] == 0
    assert len(ds.take_all()) == 40
    assert _producers() <= before


@pytest.mark.fast
def test_streaming_split_equal_pair_sees_equal_batches_made_ahead(cluster):
    """Each consumer of `equal=True` has its own queue at the coordinator:
    one that runs ahead, or quits, takes nothing from the other."""
    its = rdata.range(96, num_blocks=8).streaming_split(2, equal=True)
    out = [[], []]

    def consume(i):
        for b in its[i].iter_jax_batches(batch_size=4, prefetch_batches=2):
            out[i].append(np.asarray(b["id"]))

    ts = [threading.Thread(target=consume, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert len(out[0]) == len(out[1]) == 12
    assert sorted(int(x) for o in out for b in o for x in b) == list(range(96))

    # One consumer quits after a batch; the other still gets its whole half.
    its = rdata.range(96, num_blocks=8).streaming_split(2, equal=True)
    before = _producers()
    quitter = its[0].iter_batches(batch_size=4)
    next(quitter)
    quitter.close()
    assert sum(len(b["id"]) for b in its[1].iter_batches(batch_size=4)) == 48
    assert _until(lambda: _producers() <= before)


@pytest.mark.fast
def test_many_iterators_let_go_at_any_point_leave_no_thread(cluster):
    """More consumers than cores under a short switch interval, each let go
    after a batch count of its own: every producer ends, none made more than
    the batches taken, the queue's, the one in its hands and the one a close
    may race, and what was taken is the source's head in order."""
    import sys
    before = _producers()
    srcs = [_Source(10) for _ in range(24)]
    heads = [None] * len(srcs)

    def consume(i):
        it = iter_batches_from_refs(iter(srcs[i]), batch_size=4,
                                    prefetch_blocks=0, prefetch_batches=2)
        heads[i] = [int(next(it)["id"][0]) for _ in range(i % 7)]
        it.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=consume, args=(i,))
              for i in range(len(srcs))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
        assert _until(lambda: _producers() <= before, seconds=5.0)
    finally:
        sys.setswitchinterval(interval)
    for i, src in enumerate(srcs):
        assert heads[i] == [4 * k for k in range(i % 7)]
        assert src.asked <= i % 7 + 2 + 1 + 1

"""Operator-graph streaming executor: topology lowering, composite
plans, and backpressure under a slow consumer.

Mirrors the reference's executor coverage (reference:
python/ray/data/tests/test_streaming_executor.py select_operator_to_run /
backpressure assertions, test_backpressure_policies.py) against this
framework's pull-driven executor.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
import ray_tpu.data as rdata
from ray_tpu.core.cluster_utils import Cluster


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(num_nodes=1, resources={"CPU": 8})
    c.connect()
    yield c
    c.shutdown()


def test_plan_lowering_shapes(cluster):
    """The planner fuses map chains and lowers actor maps / exchanges /
    unions to their own operators."""
    ds = (rdata.range(10, num_blocks=2)
          .map_batches(lambda b: b)
          .filter(lambda r: True))
    states = ds._build_states()
    names = [s.name for s in states]
    assert names == ["input", "read->map"]  # everything fused

    ds2 = ds.random_shuffle(seed=0).map_batches(lambda b: b)
    names2 = [s.name for s in ds2._build_states()]
    assert names2 == ["input", "read->map", "random_shuffle", "map"]

    class Ident:
        def __call__(self, b):
            return b

    ds3 = ds.map_batches(Ident, concurrency=2).filter(lambda r: True)
    names3 = [s.name for s in ds3._build_states()]
    assert names3 == ["input", "read->map", "map(actors)", "map"]


def test_shuffle_actor_map_streaming_split(cluster):
    """The round-3 review's composite: shuffle -> actor-pool map ->
    streaming_split runs end-to-end through the operator graph."""

    class AddOffset:
        def __init__(self, off):
            self.off = off

        def __call__(self, batch):
            return {"id": batch["id"] + self.off}

    ds = (rdata.range(96, num_blocks=8)
          .random_shuffle(seed=0)
          .map_batches(AddOffset, concurrency=2,
                       fn_constructor_args=(1000,)))
    its = ds.streaming_split(2, equal=True)
    rows = [[], []]
    import threading

    def consume(i):
        for b in its[i].iter_batches(batch_size=None):
            rows[i].extend(int(v) for v in b["id"])

    ts = [threading.Thread(target=consume, args=(i,)) for i in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
    assert sorted(rows[0] + rows[1]) == [1000 + i for i in range(96)]
    assert len(rows[0]) == len(rows[1])


def test_union_through_concat_operator(cluster):
    a = rdata.range(6, num_blocks=2)
    b = rdata.range(6, num_blocks=2).map_batches(
        lambda x: {"id": x["id"] + 100})
    u = a.union(b).map_batches(lambda x: {"id": x["id"] * 2})
    got = [r["id"] for r in u.take_all()]
    # Concat preserves branch order: part a's blocks precede part b's.
    assert got[:6] == [0, 2, 4, 6, 8, 10]
    assert sorted(got[6:]) == [200 + 2 * i for i in range(6)]


def test_slow_consumer_stalls_producer(cluster, tmp_path):
    """Bounded memory under a slow consumer: with the consumer parked,
    the executor must stop dispatching source tasks — in-flight work
    stays at the task budget, not the input size (reference:
    backpressure_policy/concurrency_cap_backpressure_policy.py)."""
    marker = os.path.join(str(tmp_path), "ran.log")

    def counting(batch):
        with open(marker, "a") as f:
            f.write("x\n")
        return batch

    n_blocks = 24
    budget = 2
    ds = rdata.range(n_blocks * 4, num_blocks=n_blocks).map_batches(counting)
    it = ds.iter_block_refs(window=budget)
    first = next(it)
    assert ray_tpu.get(first) is not None
    # Consumer stalls; any already-dispatched tasks may finish, but no
    # NEW dispatches can happen while we sleep.
    time.sleep(2.0)
    with open(marker) as f:
        ran = len(f.readlines())
    assert ran <= budget + 2, \
        f"{ran} of {n_blocks} source tasks ran during a consumer stall " \
        f"(budget {budget}: producers must stall, not run ahead)"
    # Draining the iterator completes the remaining work.
    rest = list(it)
    assert 1 + len(rest) == n_blocks
    with open(marker) as f:
        assert len(f.readlines()) == n_blocks


def test_executor_metrics_exposed(cluster):
    from ray_tpu.data.streaming_executor import StreamingExecutor

    ds = rdata.range(20, num_blocks=4).map_batches(lambda b: b)
    ex = StreamingExecutor(ds._build_states(), task_budget=2)
    refs = list(ex.run())
    assert len(refs) == 4
    m = ex.metrics()
    assert m["read->map"].tasks_launched == 4
    assert m["read->map"].tasks_finished == 4
    assert m["read->map"].blocks_out == 4


def test_early_abandonment_shuts_down(cluster):
    """take(k) closes the ref iterator mid-stream; the executor must shut
    operators down (actor pools reaped) without hanging."""

    class Ident:
        def __call__(self, b):
            return b

    ds = rdata.range(200, num_blocks=20).map_batches(Ident, concurrency=2)
    rows = ds.take(5)
    assert [r["id"] for r in rows] == [0, 1, 2, 3, 4]


def test_byte_budget_bounds_inflight_memory(cluster):
    """Skewed block sizes: a map producing ~1.5 MB blocks under a small
    byte budget must stall dispatch so in-flight block bytes stay
    bounded — slot budgets alone would launch 8 tasks and buffer ~12x
    more (reference: resource_manager.py ReservationOpResourceAllocator,
    whose core abstraction is memory, not slots)."""
    import numpy as np

    from ray_tpu.data.streaming_executor import StreamingExecutor

    def widen(batch):
        return {"big": [np.zeros(190_000, np.int64)
                        for _ in range(len(batch["id"]))]}

    n_blocks = 12
    ds = rdata.range(n_blocks, num_blocks=n_blocks).map_batches(widen)
    budget = 4 * 1024 * 1024  # ~2-3 blocks of headroom
    ex = StreamingExecutor(ds._build_states(), task_budget=8,
                           memory_budget=budget)
    seen = 0
    for _ in ex.run():  # slow consumer: one block per loop pass
        seen += 1
        import time
        time.sleep(0.05)
    assert seen == n_blocks
    # The executor's own accounting never exceeded budget + one block
    # (the +1 is the block a just-finishing task materializes).
    assert ex._rm.peak_mem_used <= budget + 1_700_000, \
        ex._rm.peak_mem_used
    # And the budget actually bit: peak stayed FAR below what 8
    # unconstrained tasks x 1.5MB would have buffered.
    assert ex._rm.peak_mem_used < 8 * 1_500_000


def test_byte_budget_does_not_throttle_small_blocks(cluster):
    """Tiny blocks under the default budget: the byte constraint must
    never be the limiter (throughput regression guard)."""
    from ray_tpu.data.streaming_executor import StreamingExecutor

    ds = rdata.range(100, num_blocks=10).map_batches(lambda b: b)
    ex = StreamingExecutor(ds._build_states(), task_budget=4)
    refs = list(ex.run())
    assert len(refs) == 10
    assert ex.metrics()["read->map"].tasks_finished == 10

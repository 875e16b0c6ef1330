"""Streaming generators + task cancellation.

Mirrors the reference's coverage (reference: python/ray/tests/
test_streaming_generator.py, test_cancel.py): items stream without
materializing the whole output, backpressure stalls the producer, errors
surface mid-stream, and cancel drops queued/running tasks.
"""

import time

import pytest

import ray_tpu
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.core.common import TaskCancelledError, TaskError


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(num_nodes=1, resources={"CPU": 4})
    c.connect()
    yield c
    c.shutdown()


def test_generator_streams_in_order(cluster):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    out = [ray_tpu.get(ref) for ref in gen.remote(10)]
    assert out == [i * 10 for i in range(10)]


def test_generator_large_items_via_store(cluster):
    import numpy as np

    @ray_tpu.remote(num_returns="streaming")
    def gen_blocks(n, sz):
        for i in range(n):
            yield np.full(sz, i, dtype=np.float64)

    refs = list(gen_blocks.remote(4, 200_000))  # 1.6MB each: store path
    assert len(refs) == 4
    for i, r in enumerate(refs):
        block = ray_tpu.get(r)
        assert block.shape == (200_000,)
        assert block[0] == i


def test_generator_streams_before_completion(cluster):
    """First item must be consumable while the producer is still running."""
    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(3):
            yield i
            time.sleep(0.5)

    it = iter(slow_gen.remote())
    t0 = time.monotonic()
    first = ray_tpu.get(next(it))
    elapsed = time.monotonic() - t0
    assert first == 0
    assert elapsed < 1.2  # did not wait for the full ~1.5s generator
    assert [ray_tpu.get(r) for r in it] == [1, 2]


def test_generator_backpressure(cluster):
    """An unconsumed stream must not run arbitrarily far ahead."""
    @ray_tpu.remote(num_returns="streaming")
    def counted():
        for i in range(500):
            yield i

    g = counted.remote()
    it = iter(g)
    first = next(it)
    assert ray_tpu.get(first) == 0
    time.sleep(1.0)  # producer would finish all 500 without backpressure
    from ray_tpu import api
    cw = api._cw()
    st = cw._streams.get(g.task_id)
    assert st is not None, "stream completed despite an idle consumer"
    # window (16) + send window (4) + small slack
    assert st.produced <= 32, f"produced {st.produced} items ahead"
    # Draining afterwards still yields everything.
    rest = [ray_tpu.get(r) for r in it]
    assert rest == list(range(1, 500))


def test_generator_error_mid_stream(cluster):
    @ray_tpu.remote(num_returns="streaming")
    def boom():
        yield 1
        yield 2
        raise ValueError("mid-stream failure")

    it = iter(boom.remote())
    assert ray_tpu.get(next(it)) == 1
    assert ray_tpu.get(next(it)) == 2
    with pytest.raises(TaskError):
        for _ in range(5):  # remaining iteration surfaces the task error
            next(it)


def test_generator_release_unblocks_producer(cluster):
    @ray_tpu.remote(num_returns="streaming")
    def infinite():
        i = 0
        while True:
            yield i
            i += 1

    g = infinite.remote()
    it = iter(g)
    assert ray_tpu.get(next(it)) == 0
    g.release()  # consumer walks away; producer must be told to stop
    # The worker drains and becomes reusable: a fresh task completes.
    @ray_tpu.remote
    def probe():
        return "ok"

    assert ray_tpu.get(probe.remote(), timeout=30) == "ok"


def test_actor_streaming_method(cluster):
    @ray_tpu.remote
    class Streamer:
        def tokens(self, n):
            for i in range(n):
                yield f"tok{i}"

    s = Streamer.remote()
    gen = s.tokens.options(num_returns="streaming").remote(4)
    assert [ray_tpu.get(r) for r in gen] == ["tok0", "tok1", "tok2", "tok3"]


def test_async_actor_streams_run_all_at_once_off_the_default_executor(
        cluster):
    """40 streaming calls on one async actor each hold a producer thread of
    their own until every one has started and the actor is told to let go:
    none waits for a thread that another stream's END frees, however few
    the loop's default executor has (2 here; min(32, cpus + 4) otherwise,
    which is 17 on the 13-core chip host), nor for one of the caller's 32
    pushes in flight. Meanwhile the actor's sync method answers, and so does
    a sync callable that an async method sends to the default executor, as
    `serve.replica.Replica.handle_request` does: no stream holds a thread of
    that either."""
    import asyncio
    import threading
    from concurrent.futures import ThreadPoolExecutor

    n = 40

    @ray_tpu.remote
    class Gate:
        def __init__(self):
            self.lock = threading.Lock()
            self.started = 0
            self.let_go = threading.Event()

        async def shrink_default_executor(self, workers):
            asyncio.get_running_loop().set_default_executor(
                ThreadPoolExecutor(max_workers=workers))

        def stream(self, i):
            with self.lock:
                self.started += 1
            assert self.let_go.wait(90), "never let go"
            yield i

        def started_now(self):
            return self.started

        async def open(self):
            def sync_callable():
                self.let_go.set()
                return self.started

            return await asyncio.get_running_loop().run_in_executor(
                None, sync_callable)

    gate = Gate.remote()
    ray_tpu.get(gate.shrink_default_executor.remote(2), timeout=30)
    streams = []
    deadline = time.monotonic() + 90
    for i in range(n):
        # One push a stream (the caller coalesces none), each in flight
        # until the gate opens: the caller's cap on pushes in flight to one
        # actor (32) must not count them, or the 33rd never starts and the
        # plain call behind it never returns.
        streams.append(
            gate.stream.options(num_returns="streaming").remote(i))
        while ray_tpu.get(gate.started_now.remote(), timeout=30) <= i:
            assert time.monotonic() < deadline, f"stream {i} never started"
            time.sleep(0.01)
    assert ray_tpu.get(gate.open.remote(), timeout=30) == n
    assert sorted(ray_tpu.get(ref) for gen in streams for ref in gen) \
        == list(range(n))


def test_cancel_running_task(cluster):
    @ray_tpu.remote
    def spin():
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            pass
        return "finished"

    ref = spin.remote()
    time.sleep(1.0)  # let it start executing
    ray_tpu.cancel(ref)
    with pytest.raises((TaskCancelledError, TaskError)):
        ray_tpu.get(ref, timeout=30)


def test_cancel_queued_task(cluster):
    @ray_tpu.remote(num_cpus=4)
    def hog():
        time.sleep(3)
        return "hog"

    @ray_tpu.remote(num_cpus=4)
    def queued():
        return "queued"

    h = hog.remote()
    time.sleep(0.3)
    q = queued.remote()  # stuck behind the hog (needs all 4 CPUs)
    ray_tpu.cancel(q)
    with pytest.raises(TaskCancelledError):
        ray_tpu.get(q, timeout=30)
    assert ray_tpu.get(h) == "hog"  # victimless cancel

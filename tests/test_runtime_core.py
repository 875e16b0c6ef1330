"""Integration tests for the distributed runtime core (tasks/actors/objects).

Mirrors the reference's test strategy for core semantics (reference:
python/ray/tests/test_basic.py, test_actor.py, test_multi_node.py,
test_object_reconstruction.py) on the in-one-box Cluster harness.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.core.common import ActorDiedError, TaskError


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(num_nodes=1, resources={"CPU": 8})
    c.connect()
    yield c
    c.shutdown()


@ray_tpu.remote
def _echo(x):
    return x


def test_task_basic(cluster):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(2, 3)) == 5
    # kwargs + multiple tasks
    refs = [add.remote(i, b=i) for i in range(5)]
    assert ray_tpu.get(refs) == [0, 2, 4, 6, 8]


def test_chained_refs(cluster):
    @ray_tpu.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(4):
        ref = inc.remote(ref)  # ObjectRef passed as arg
    assert ray_tpu.get(ref) == 5


def test_put_get_large_roundtrip(cluster):
    arr = np.random.RandomState(0).rand(500_000)
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(arr, out)


def test_task_error_propagates(cluster):
    @ray_tpu.remote
    def boom():
        raise ValueError("kaboom")

    with pytest.raises(TaskError, match="kaboom"):
        ray_tpu.get(boom.remote())


def test_nested_refs_in_value(cluster):
    inner = ray_tpu.put(41)
    outer = ray_tpu.put({"ref": inner})
    got = ray_tpu.get(outer)
    assert ray_tpu.get(got["ref"]) == 41


def test_wait(cluster):
    @ray_tpu.remote
    def fast():
        return 1

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return 2

    refs = [fast.remote(), slow.remote()]
    ready, not_ready = ray_tpu.wait(refs, num_returns=1, timeout=10)
    assert len(ready) == 1 and len(not_ready) == 1
    assert ray_tpu.get(ready[0]) == 1


def test_actor_basic_and_ordering(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def incr(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote(start=100)
    results = ray_tpu.get([c.incr.remote() for _ in range(20)])
    assert results == list(range(101, 121))  # strict submission order


def test_named_actor(cluster):
    @ray_tpu.remote
    class Store:
        def __init__(self):
            self.d = {}

        def set(self, k, v):
            self.d[k] = v
            return True

        def get(self, k):
            return self.d.get(k)

    Store.options(name="kvstore").remote()
    h = ray_tpu.get_actor("kvstore")
    assert ray_tpu.get(h.set.remote("a", 1))
    assert ray_tpu.get(h.get.remote("a")) == 1


def test_actor_task_error(cluster):
    @ray_tpu.remote
    class Fragile:
        def ok(self):
            return "ok"

        def fail(self):
            raise RuntimeError("actor method failed")

    f = Fragile.remote()
    assert ray_tpu.get(f.ok.remote()) == "ok"
    with pytest.raises(TaskError, match="actor method failed"):
        ray_tpu.get(f.fail.remote())
    # actor still alive afterwards
    assert ray_tpu.get(f.ok.remote()) == "ok"


def test_actor_kill(cluster):
    @ray_tpu.remote
    class Victim:
        def ping(self):
            return "pong"

    v = Victim.remote()
    assert ray_tpu.get(v.ping.remote()) == "pong"
    ray_tpu.kill(v)
    with pytest.raises((ActorDiedError, TaskError)):
        ray_tpu.get(v.ping.remote())


def test_actor_restart_after_crash(cluster):
    @ray_tpu.remote
    class Phoenix:
        def __init__(self):
            self.calls = 0

        def crash(self):
            os._exit(1)

        def ping(self):
            self.calls += 1
            return self.calls

    # max_task_retries=0: the crash task must NOT be retried (it would kill
    # every new incarnation too — at-least-once semantics).
    p = Phoenix.options(max_restarts=1, max_task_retries=0).remote()
    assert ray_tpu.get(p.ping.remote()) == 1
    try:
        ray_tpu.get(p.crash.remote())
    except Exception:
        pass
    # restarted actor: state reset, still serving
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            assert ray_tpu.get(p.ping.remote()) >= 1
            break
        except Exception:
            time.sleep(0.5)
    else:
        pytest.fail("actor did not come back after restart")


def test_task_retry_after_worker_crash(cluster):
    marker = f"/tmp/ray_tpu_retry_{os.getpid()}"

    @ray_tpu.remote(max_retries=2)
    def flaky():
        if not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)  # simulate worker crash (not a user exception)
        return "recovered"

    try:
        assert ray_tpu.get(flaky.remote()) == "recovered"
    finally:
        if os.path.exists(marker):
            os.remove(marker)


def test_contained_arg_refs_released(cluster):
    """Refs nested inside an inline task arg are released after the task
    completes — they must not pin the owned object forever."""
    from ray_tpu.core.ref import get_core_worker

    cw = get_core_worker()

    @ray_tpu.remote
    def read(d):
        return ray_tpu.get(d["ref"]) + 1

    inner = ray_tpu.put(41)
    k = inner.binary()
    assert ray_tpu.get(read.remote({"ref": inner})) == 42
    assert k in cw.objects
    del inner
    deadline = time.time() + 10
    while time.time() < deadline and k in cw.objects:
        time.sleep(0.1)
    assert k not in cw.objects, "contained arg ref leaked"


def test_contained_put_refs_released(cluster):
    """Borrows taken by put() on contained refs are dropped when the outer
    object is freed."""
    from ray_tpu.core.ref import get_core_worker

    cw = get_core_worker()
    inner = ray_tpu.put("nested")
    outer = ray_tpu.put({"ref": inner})
    k = inner.binary()
    del inner  # only the outer object's borrow keeps it alive
    time.sleep(0.3)
    assert k in cw.objects, "borrow by containing object should pin it"
    del outer
    deadline = time.time() + 10
    while time.time() < deadline and k in cw.objects:
        time.sleep(0.1)
    assert k not in cw.objects, "contained put borrow leaked"


def test_concurrent_task_burst(cluster):
    """A burst of concurrent tasks pipelines through cached worker leases
    (reference: normal_task_submitter.cc lease reuse) — must complete well
    under per-task worker-spawn time."""
    @ray_tpu.remote
    def sq(x):
        return x * x

    t0 = time.time()
    out = ray_tpu.get([sq.remote(i) for i in range(200)])
    dt = time.time() - t0
    assert out == [i * i for i in range(200)]
    assert dt < 30, f"200-task burst took {dt:.1f}s (lease caching broken?)"


def test_actor_method_num_returns(cluster):
    """Multiple returns from actor methods via .options(num_returns=N)
    (reference parity: the review flagged this as unsupported in round 1)."""
    @ray_tpu.remote
    class Splitter:
        def pair(self, x):
            return x, x * 10

    s = Splitter.remote()
    a, b = s.pair.options(num_returns=2).remote(4)
    assert ray_tpu.get(a) == 4
    assert ray_tpu.get(b) == 40


def test_dependent_actor_calls_no_batch_deadlock(cluster):
    """A call whose arg is the ref of the immediately-preceding call to
    the SAME actor must not coalesce into one RPC with its upstream
    (the owner can only mark the upstream ready when the batch replies)."""
    @ray_tpu.remote
    class Chain:
        def f(self):
            return 1

        def g(self, x):
            return x + 1

    a = Chain.remote()
    ray_tpu.get(a.f.remote())  # warm
    r2 = a.g.remote(a.f.remote())
    assert ray_tpu.get(r2, timeout=30) == 2
    # Longer dependent chains too.
    r = a.f.remote()
    for _ in range(5):
        r = a.g.remote(r)
    assert ray_tpu.get(r, timeout=30) == 6


def test_dependent_actor_calls_nested_ref_no_batch_deadlock(cluster):
    """Same-method dependent calls where the ref is NESTED in a container
    arg (wire kind 'v' with contained refs) must also never coalesce with
    their upstream into one batch RPC (advisor r3 medium finding)."""
    @ray_tpu.remote
    class Chain:
        def g(self, x):
            if isinstance(x, list):
                x = ray_tpu.get(x[0])  # in-body get on the nested ref
            return x + 1

    a = Chain.remote()
    ray_tpu.get(a.g.remote(0))  # warm
    # Adjacent submissions, same actor, same method: upstream + dependent
    # with the upstream's ref hidden inside a list.
    up = a.g.remote(0)
    down = a.g.remote([up])
    assert ray_tpu.get(down, timeout=30) == 2
    # A longer same-method chain of nested-ref dependents.
    r = a.g.remote(0)
    for _ in range(4):
        r = a.g.remote([r])
    assert ray_tpu.get(r, timeout=30) == 5


def test_async_actor_signal_concurrency(cluster):
    """A parked async method must not block the push of the call that
    unblocks it (multiple in-flight pushes per actor)."""
    import time as _time

    @ray_tpu.remote
    class Sig:
        def __init__(self):
            import asyncio
            self.ev = asyncio.Event()

        async def wait(self):
            await self.ev.wait()
            return "released"

        async def send(self):
            self.ev.set()
            return "sent"

    s = Sig.remote()
    w = s.wait.remote()
    _time.sleep(0.3)  # let wait() park inside the actor
    assert ray_tpu.get(s.send.remote(), timeout=15) == "sent"
    assert ray_tpu.get(w, timeout=15) == "released"

"""graftprof: the always-on continuous profiling plane.

Covers the sampler itself (a hot function dominates its task's wall
stacks), the native GIL probe (a C-extension-style GIL hold measured
from outside the interpreter), the controller-side folded-profile
merge math, the add-only/dead-worker invariant, end-to-end task and
async-actor-method attribution on a live cluster, and subprocess
parity with RAY_TPU_GRAFTPROF=0.
"""

import os
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core._native import graftprof
from ray_tpu.core._native.graftprof import ProfStore
from ray_tpu.core.cluster_utils import Cluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# in-process: wall-stack sampler accuracy
# ---------------------------------------------------------------------------

def _hot_leaf(n=20000):
    x = 1
    for i in range(n):
        x = (x * 31 + i) % 1000003
    return x


def _hot_task(deadline, task_id, name, enough=lambda: False):
    graftprof.set_task_context(task_id, "", name)
    try:
        while time.monotonic() < deadline and not enough():
            _hot_leaf()
    finally:
        graftprof.clear_task_context()


def _stacks_for(payload, task_id):
    """[(joined_stack, n), ...] for one task from a flush payload."""
    frames = payload["frames"]
    return [(";".join(frames[i] for i in idxs), n)
            for t, a, nm, idxs, n in payload["stacks"] if t == task_id]


@pytest.mark.skipif(not graftprof.available(), reason="native lib missing")
def test_sampler_hot_function_dominates():
    # The task runs until the sampler has the samples the shares below
    # need, not for a fixed wall time: how fast they come is the overhead
    # governor's business (it down-clocks while the suite has the host
    # contended, and a fresh process starts down-clocked), but it must
    # never starve a hot task. At its ceiling (`_THROTTLE_MAX` periods of
    # 5 ms) 40 samples take 12.8 s; the limit leaves that twice over.
    want, limit_s = 40, 30.0
    assert graftprof.start(hz=200)
    try:
        th = threading.Thread(
            target=_hot_task,
            args=(time.monotonic() + limit_s, "acc-task-1", "hotfn",
                  lambda: graftprof._sampler.accum.samples >= want))
        th.start()
        th.join()
        payload = graftprof.collect_flush()
    finally:
        graftprof.stop()
    assert payload is not None
    rows = _stacks_for(payload, "acc-task-1")
    total = sum(n for _, n in rows)
    assert total >= want, \
        f"sampler starved: {total} samples in {limit_s:.0f} s"
    hot = sum(n for st, n in rows if st.endswith("_hot_leaf"))
    assert hot >= 0.8 * total, \
        f"hot leaf got {hot}/{total} samples: {rows}"
    # The task row carries the same sample count plus CPU attribution.
    trow = [r for r in payload["tasks"] if r[0] == "acc-task-1"]
    assert trow and trow[0][2] == "hotfn" and trow[0][3] == total


@pytest.mark.skipif(not graftprof.available(), reason="native lib missing")
def test_native_ring_roundtrip_and_thread_registry():
    assert graftprof.start(hz=200)
    try:
        # start() already registered this thread as "py-main";
        # registration is idempotent and returns the same slot.
        slot = graftprof.register_current_thread("py-test")
        assert slot >= 0
        deadline = time.monotonic() + 0.6
        while time.monotonic() < deadline:
            _hot_leaf()
        recs = graftprof.drain_records()
        kinds = {r.kind for r in recs}
        assert graftprof.PROF_TICK in kinds
        assert graftprof.PROF_THREAD_CPU in kinds
        # This thread just burned ~0.6 s of CPU; its slot must show it.
        cpu = graftprof.thread_cpu_ns()
        names = graftprof.thread_names()
        assert len(cpu) == len(names) and names[slot]
        assert cpu[slot] > 100_000_000
    finally:
        graftprof.stop()


# ---------------------------------------------------------------------------
# in-process: GIL probe under a C-extension-style hold
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not graftprof.available(), reason="native lib missing")
def test_gil_probe_times_c_extension_hold():
    import random
    # One C call that never releases the GIL and burns CPU under it — a C
    # extension crunching under the lock: `list.sort` on floats, ~0.2 s a
    # call. The wall-stack sampler is blind to these windows (it needs the
    # GIL to run); the native probe times exactly them. (A hold that SLEEPS
    # under the lock, `PyDLL(None).usleep`, leaves every tick idle: the
    # sampler then stretches its period 16-fold and probes every 1.28 s, and
    # whether one probe landed inside a 600 ms window was luck of the phase:
    # the test passed or failed in streaks with the order of the suite.)
    rng = random.Random(0)
    data = [rng.random() for _ in range(400_000)]
    before = graftprof.gil_wait_ns()
    assert graftprof.start(hz=100)
    try:
        for _ in range(4):
            sorted(data)
    finally:
        graftprof.stop()
    waited = graftprof.gil_wait_ns() - before
    assert graftprof.gil_probes() > 0
    assert waited > 50_000_000, \
        f"GIL probe saw only {waited} ns across the holds"


# ---------------------------------------------------------------------------
# controller-side ProfStore: merge math, bounds, dead-worker invariant
# ---------------------------------------------------------------------------

def _payload(task="t1", name="f", frames=("a", "b"), idxs=(0, 1), n=3,
             samples=10, oncpu=1000, gil=100, hz=100):
    return {"pid": 1, "wall_ns": 2_000_000_000, "hz": hz,
            "samples": n, "frames": list(frames),
            "stacks": [[task, "", name, list(idxs), n]],
            "tasks": [[task, "", name, samples, oncpu, gil]],
            "threads": [], "oncpu_ns": oncpu, "gil_ns": gil, "dropped": 0}


def test_profstore_merge_on_fold_math():
    st = ProfStore()
    st.ingest("node-a", _payload(n=3), wall_s=100.0)
    # Same stack arrives with a different interning order: must merge.
    st.ingest("node-b", _payload(frames=("b", "a"), idxs=(1, 0), n=2),
              wall_s=101.0)
    assert st.collapsed(task="t1") == ["a;b 5"]
    top = st.top(task="t1")
    assert top["total_samples"] == 5
    leaf = top["rows"][0]
    assert leaf["func"] == "b" and leaf["self"] == 5 and leaf["cum"] == 5
    assert leaf["self_pct"] == 100.0
    flame = st.flame(task="t1")
    assert flame["value"] == 5
    assert flame["children"][0]["name"] == "a"
    assert flame["children"][0]["children"][0]["name"] == "b"
    assert flame["children"][0]["children"][0]["value"] == 5
    # Task totals: sums plus the sampled-wall estimate samples/hz.
    ts = st.task_stats("t1")
    assert ts["samples"] == 5 and ts["oncpu_ns"] == 2000
    assert ts["gil_ns"] == 200 and ts["name"] == "f"
    assert ts["wall_ns"] == 2 * (10 * 1_000_000_000 // 100)
    # The --task filter matches by name too.
    assert st.task_stats("f") == ts


def test_profstore_time_window_and_node_filter():
    st = ProfStore()
    now = time.time()
    st.ingest("node-a", _payload(frames=("old",), idxs=(0,), n=7),
              wall_s=now - 3600)
    st.ingest("node-a", _payload(frames=("new",), idxs=(0,), n=2),
              wall_s=now)
    st.ingest("node-b", _payload(frames=("other",), idxs=(0,), n=4),
              wall_s=now)
    assert st.collapsed(seconds=60.0) == ["other 4", "new 2"]
    assert st.collapsed(node="node-b") == ["other 4"]
    # No window: the merged task table sees everything.
    assert st.top(task="t1")["total_samples"] == 13


def test_profstore_stack_cap_evicts_coldest():
    st = ProfStore(stack_cap=16)
    for i in range(40):
        st.ingest("n", _payload(frames=(f"f{i}",), idxs=(0,), n=i + 1),
                  wall_s=float(i))
    rec = st._tasks[("t1", "")]
    assert len(rec["stacks"]) <= 16
    assert "f39" in rec["stacks"] and "f0" not in rec["stacks"]
    # Totals still count every ingested sample (eviction is per-stack,
    # not retroactive accounting).
    assert rec["samples"] == sum(range(1, 41))


def test_native_thread_cpu_aggregates_in_top():
    st = ProfStore()
    p = _payload()
    p["threads"] = [["graftrpc-reactor", 1000], ["store-reaper", 50]]
    st.ingest("node-a", p, wall_s=100.0)
    q = _payload()
    q["threads"] = [["graftrpc-reactor", 500]]
    st.ingest("node-b", q, wall_s=100.0)
    assert st.top()["native_threads"] == [("graftrpc-reactor", 1500),
                                          ("store-reaper", 50)]
    assert st.top(node="node-b")["native_threads"] == \
        [("graftrpc-reactor", 500)]
    st.forget_node("node-a")
    assert st.top()["native_threads"] == [("graftrpc-reactor", 500)]


def test_dead_worker_drop_is_add_only():
    st = ProfStore()
    st.ingest("node-a", _payload(n=5), wall_s=100.0)
    st.ingest("node-b", _payload(n=3), wall_s=100.0)
    before = st.top(task="t1")["total_samples"]
    # A dead node just stops contributing; its merged history stays.
    st.forget_node("node-a")
    assert st.collapsed(node="node-a") == []
    after = st.top(task="t1")["total_samples"]
    assert after == before == 8
    assert all(n > 0 for _, n in
               (r.rsplit(" ", 1) for r in st.collapsed(task="t1"))
               for n in [int(n)])
    s = st.stats()
    assert s["nodes"] == 1 and s["ingested"] == 2


def test_profstore_task_cap_lru():
    st = ProfStore(task_cap=8)
    for i in range(20):
        st.ingest("n", _payload(task=f"task-{i:02d}"), wall_s=float(i))
    assert st.stats()["tasks"] == 8
    assert st.task_stats("task-19")["samples"] == 3
    assert st.task_stats("task-00") == {}


def test_profstore_ignores_garbage():
    st = ProfStore()
    st.ingest("n", "not a dict")
    st.ingest("n", {"frames": ["a"], "stacks": [["t", "", "f"]],
                    "tasks": [[1, 2]]}, wall_s=1.0)  # short rows
    st.ingest("n", {"frames": ["a"],
                    "stacks": [["t", "", "f", [99], 1]]},
              wall_s=1.0)  # frame index out of range
    assert st.top()["total_samples"] == 0


# ---------------------------------------------------------------------------
# live cluster: task + async actor method attribution, end to end
# ---------------------------------------------------------------------------

@pytest.fixture()
def prof_cluster():
    from ray_tpu.utils.config import GlobalConfig
    GlobalConfig.initialize({"prof_hz": 101})
    c = Cluster(num_nodes=1, resources={"CPU": 2})
    c.connect()
    yield c
    c.shutdown()
    GlobalConfig._overrides.clear()
    GlobalConfig._cache.clear()


def test_task_and_async_actor_attribution(prof_cluster):
    from ray_tpu import state

    @ray_tpu.remote
    def prof_burn(sec):
        t = time.monotonic()
        x = 0
        while time.monotonic() - t < sec:
            x = (x * 31 + 7) % 1000003
        return x

    @ray_tpu.remote
    class Spinner:
        async def spin(self, sec):
            t = time.monotonic()
            x = 0
            while time.monotonic() - t < sec:
                x = (x * 17 + 3) % 1000003
            return x

    a = Spinner.remote()
    ray_tpu.get([prof_burn.remote(1.5), a.spin.remote(1.5)])

    # Profiles ride the 2 s flush: poll until the controller has both.
    deadline = time.monotonic() + 30
    burn = spin = {}
    while time.monotonic() < deadline:
        burn = state.prof_task_stats("prof_burn")
        spin = state.prof_task_stats("Spinner.spin")
        if burn.get("samples", 0) >= 20 and spin.get("samples", 0) >= 20:
            break
        time.sleep(0.5)
    assert burn.get("samples", 0) >= 20, burn
    assert spin.get("samples", 0) >= 20, spin
    # Both were pure CPU spins: on-CPU time must be substantial and
    # the sampled-wall denominator sane (within [0.2 s, 60 s]).
    for rec in (burn, spin):
        assert rec["oncpu_ns"] > 200_000_000, rec
        assert 200_000_000 < rec["wall_ns"] < 60_000_000_000, rec

    # The hot frame dominates each task's flamegraph when filtered.
    top = state.prof_top(task="prof_burn", limit=5)
    assert top["total_samples"] >= 20
    assert "prof_burn" in top["rows"][0]["func"], top["rows"][:3]
    top = state.prof_top(task="Spinner.spin", limit=5)
    assert "spin" in top["rows"][0]["func"], top["rows"][:3]

    # C-plane attribution: the native sidecar threads' CPU table rode
    # the same flushes.
    native = dict(state.prof_top()["native_threads"])
    assert native, "no native thread CPU reported"

    # The collapsed/flame exports agree with top on the totals.
    flame = state.prof_flame(task="prof_burn")
    col = state.prof_collapsed(task="prof_burn")
    assert flame["value"] == sum(int(l.rsplit(" ", 1)[1]) for l in col)

    # stack --profile: each worker folds a live 1 s capture window and
    # reports its native sidecar-thread CPU times alongside.
    dump = state.stack(profile_s=1.0)
    folded = [w for node in dump.values() for w in node.values()
              if isinstance(w, dict)
              and isinstance(w.get("stacks"), dict)]
    assert folded, dump
    assert any(w["stacks"].get("samples", 0) > 0 for w in folded)
    assert any(w["stacks"].get("thread_cpu_ns") for w in folded)


# ---------------------------------------------------------------------------
# RAY_TPU_GRAFTPROF=0 parity: everything works, no profiling plumbing
# ---------------------------------------------------------------------------

_PARITY_SCRIPT = """
import time
import ray_tpu
from ray_tpu.core._native import graftprof

assert graftprof.enabled() is False
ray_tpu.init(resources={"CPU": 2})

@ray_tpu.remote
def sq(x):
    t = time.monotonic()
    while time.monotonic() - t < 0.2:
        pass
    return x * x

assert ray_tpu.get([sq.remote(i) for i in range(4)]) == \
    [i * i for i in range(4)]
assert graftprof.running() is False

time.sleep(3)  # two flush ticks: nothing may arrive
from ray_tpu import state
s = state.prof_stats()
assert s["ingested"] == 0 and s["tasks"] == 0, s
assert state.prof_top()["total_samples"] == 0
ray_tpu.shutdown()
print("PARITY-OK")
"""


def test_graftprof_disabled_subprocess_parity():
    env = dict(os.environ, RAY_TPU_GRAFTPROF="0", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _PARITY_SCRIPT],
                         capture_output=True, text=True, timeout=180,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PARITY-OK" in out.stdout

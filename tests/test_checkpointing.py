"""Checkpointing: sharded save/restore correctness, commit atomicity,
top-K retention, and trainer crash-resume.

Mirrors the reference's checkpoint coverage (reference:
train/v2/tests/test_checkpoint_manager.py + SURVEY §5.4's Orbax-style
per-host shard writes + commit barrier) on the virtual 8-device CPU mesh.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.train.checkpointing import (Checkpoint, CheckpointManager,
                                         load_checkpoint_host,
                                         restore_checkpoint,
                                         save_checkpoint)


def _sharded_state():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh, P("dp", "tp")))
    b = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P("tp")))
    rep = jax.device_put(jnp.float32(3.5), NamedSharding(mesh, P()))
    return {"layer": {"w": w, "b": b}, "scale": rep, "step": 7}


def test_sharded_save_restore_roundtrip(tmp_path):
    state = _sharded_state()
    ckpt = save_checkpoint(str(tmp_path), state, step=7)
    assert ckpt.is_valid()

    # Restore into a zeroed target with the SAME shardings.
    import jax
    import jax.numpy as jnp
    target = jax.tree.map(
        lambda x: jnp.zeros_like(x) if isinstance(x, jax.Array) else 0,
        state)
    restored = restore_checkpoint(ckpt, target)
    np.testing.assert_array_equal(np.asarray(restored["layer"]["w"]),
                                  np.arange(64.0).reshape(8, 8))
    np.testing.assert_array_equal(np.asarray(restored["layer"]["b"]),
                                  np.arange(8.0))
    assert float(restored["scale"]) == 3.5
    assert int(restored["step"]) == 7
    # Shardings preserved.
    assert restored["layer"]["w"].sharding == state["layer"]["w"].sharding


def test_host_assembly(tmp_path):
    state = _sharded_state()
    ckpt = save_checkpoint(str(tmp_path), state, step=1)
    host = load_checkpoint_host(ckpt)
    np.testing.assert_array_equal(host["layer.w"],
                                  np.arange(64.0).reshape(8, 8))
    np.testing.assert_array_equal(host["layer.b"], np.arange(8.0))


def test_uncommitted_checkpoint_rejected(tmp_path):
    state = _sharded_state()
    ckpt = save_checkpoint(str(tmp_path), state, step=2)
    os.unlink(os.path.join(ckpt.path, "COMMIT"))
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(ckpt, state)
    # And the manager must not discover it.
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.latest() is None


def test_trash_recovery_after_swap_crash(tmp_path):
    """A crash between the two commit-swap renames leaves the committed
    step only in _trash-step-N; save/restore/discover must rename it
    back (advisor r3 low finding)."""
    state = _sharded_state()
    ckpt = save_checkpoint(str(tmp_path), state, step=3)
    # Simulate a crash mid-swap: step-3 moved to trash, new dir lost.
    trash = os.path.join(str(tmp_path), "_trash-step-3")
    os.rename(ckpt.path, trash)
    assert not os.path.isdir(ckpt.path)
    # restore_checkpoint recovers the trashed committed dir.
    restored = restore_checkpoint(ckpt.path, state)
    assert int(restored["step"]) == 7
    # Again for discovery: manager sees the recovered checkpoint.
    os.rename(ckpt.path, trash)
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.latest() is not None and mgr.latest().step == 3
    # A fresh save of the same step also recovers first (no data loss if
    # that save crashes pre-commit).
    os.rename(os.path.join(str(tmp_path), "step-3"), trash)
    save_checkpoint(str(tmp_path), state, step=3)
    assert not os.path.isdir(trash)


def test_manager_topk_by_metric(tmp_path):
    state = {"x": np.arange(4.0)}
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2, metric="loss",
                            mode="min")
    paths = []
    for step, loss in [(1, 5.0), (2, 2.0), (3, 9.0), (4, 1.0)]:
        c = save_checkpoint(str(tmp_path), state, step,
                            metrics={"loss": loss})
        mgr.register(c)
        paths.append(c.path)
    kept = {c.step for c in mgr.checkpoints()}
    assert kept == {2, 4}  # two lowest losses survive
    assert mgr.best().step == 4
    assert not os.path.exists(paths[0])  # pruned from disk
    # A fresh manager over the same dir rediscovers the survivors.
    mgr2 = CheckpointManager(str(tmp_path), max_to_keep=2)
    assert {c.step for c in mgr2.checkpoints()} == {2, 4}
    assert mgr2.latest().step == 4


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(num_nodes=1, resources={"CPU": 8})
    c.connect()
    yield c
    c.shutdown()


def test_trainer_crash_resume(cluster, tmp_path):
    """Kill the train loop mid-run; the restarted group must resume from
    the last committed checkpoint and CONTINUE (not restart from step 0)."""
    from ray_tpu.train import (FailureConfig, JaxTrainer, RunConfig,
                               ScalingConfig)

    storage = str(tmp_path)

    def loop(config):
        import jax.numpy as jnp

        import ray_tpu.train as rt
        ctx = rt.get_context()
        start_step = 0
        w = jnp.zeros(4)
        prev = ctx.get_checkpoint()
        if prev is not None:
            host = rt.load_checkpoint_host(prev)
            start_step = int(host["step"]) + 1
            w = jnp.asarray(host["w"])
        for step in range(start_step, 6):
            w = w + 1.0  # "training"
            ckpt = rt.save_checkpoint({"w": w, "step": step}, step,
                                      metrics={"step": step})
            rt.report({"step": step, "w0": float(w[0]),
                       "resumed_from": start_step}, checkpoint=ckpt)
            if step == 2 and prev is None:
                raise RuntimeError("simulated crash after step 2")

    trainer = JaxTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=False),
        run_config=RunConfig(name="resume_test", storage_path=storage,
                             failure_config=FailureConfig(max_failures=1)),
        worker_env={"JAX_PLATFORMS": "cpu"},
    )
    result = trainer.fit()
    hist = result.metrics_history
    # Second attempt resumed at step 3 (not 0) and finished at step 5.
    resumed = [m for m in hist if m["resumed_from"] > 0]
    assert resumed, f"never resumed from checkpoint: {hist}"
    assert resumed[0]["resumed_from"] == 3
    assert hist[-1]["step"] == 5
    # w accumulated across the crash: step k ends with w0 == k+1.
    assert hist[-1]["w0"] == 6.0


def test_profile_captures_trace(tmp_path):
    """ray_tpu.train.profile() writes an XPlane trace dir (SURVEY §5.1)."""
    import os

    import jax.numpy as jnp

    from ray_tpu.train import session as sess

    ctx = sess.TrainContext(0, 1, "proftest", "")
    sess._start_session(ctx)
    try:
        # No storage path and no directory: it refuses, and writes nowhere.
        with pytest.raises(RuntimeError, match="needs a directory"):
            with sess.profile():
                pass
        with sess.profile(str(tmp_path)) as out:
            x = jnp.ones((64, 64))
            (x @ x).block_until_ready()
        assert out.startswith(str(tmp_path))
        found = []
        for root, _dirs, files in os.walk(out):
            found.extend(files)
        assert found, f"no trace files under {out}"
    finally:
        sess._end_session()


def test_async_save_overlaps_training(tmp_path, monkeypatch):
    """AsyncCheckpointer: save() returns after the device->host snapshot;
    the write + commit happen in the background while 'training'
    continues (SURVEY §5.4 Orbax async pattern)."""
    import threading

    import numpy as _np

    from ray_tpu.train import checkpointing as C

    gate = threading.Event()

    class SlowNP:
        def __getattr__(self, name):
            return getattr(_np, name)

        def save(self, *a, **kw):
            gate.wait(timeout=60)  # writes stall until the test releases
            return _np.save(*a, **kw)

    state = _sharded_state()
    ckptr = C.AsyncCheckpointer()
    monkeypatch.setattr(C, "np", SlowNP())
    try:
        fut = ckptr.save(str(tmp_path), state, step=1)
        # Returned BEFORE any file write finished: nothing committed yet.
        assert not fut.done()
        assert not os.path.exists(
            os.path.join(str(tmp_path), "step-1", "COMMIT"))
        # "training" continues on this thread while the writer is stuck.
        acc = sum(range(1000))
        assert acc == 499500
        gate.set()
        ckpt = fut.result(timeout=60)
        assert ckpt.is_valid()
    finally:
        gate.set()
        monkeypatch.setattr(C, "np", _np)
        ckptr.close()
    restored = restore_checkpoint(ckpt, state)
    np.testing.assert_array_equal(np.asarray(restored["layer"]["w"]),
                                  np.arange(64.0).reshape(8, 8))


def test_kill_mid_async_save_keeps_previous_commit(tmp_path):
    """A save that never completes (crash mid-write) leaves NO COMMIT for
    its step; the previous committed step stays the restore point."""
    from ray_tpu.train import checkpointing as C

    state = _sharded_state()
    prev = save_checkpoint(str(tmp_path), state, step=1)
    assert prev.is_valid()

    # Simulate the crash: snapshot taken, some files written, no commit.
    snap = C._snapshot(state, 2, None)
    tmp2 = os.path.join(str(tmp_path), "_tmp-step-2")
    os.makedirs(tmp2)
    fname, arr = snap["writes"][0]
    np.save(os.path.join(tmp2, fname), arr)
    # (process dies here)

    mgr = CheckpointManager(str(tmp_path))
    assert mgr.latest().step == 1
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(os.path.join(str(tmp_path), "step-2"), state)
    restored = restore_checkpoint(mgr.latest(), state)
    assert int(restored["step"]) == 7


def test_async_marker_barrier_multiprocess(tmp_path):
    """The async commit barrier is rank marker files: process 0 commits
    only after EVERY rank's writes are durable (no device collectives on
    the writer thread)."""
    import threading

    from ray_tpu.train import checkpointing as C

    state = _sharded_state()
    snap = C._snapshot(state, 3, {"loss": 1.0})
    snap0 = {**snap, "proc": 0, "nprocs": 2}
    snap1 = {**snap, "proc": 1, "nprocs": 2, "writes": []}

    out = {}

    def rank0():
        out["ckpt"] = C._write_snapshot(str(tmp_path), snap0,
                                        barrier_timeout=60)

    t = threading.Thread(target=rank0)
    t.start()
    time.sleep(0.5)
    # Rank 1 hasn't arrived: no commit yet.
    assert not os.path.exists(
        os.path.join(str(tmp_path), "step-3", "COMMIT"))
    assert t.is_alive()
    C._write_snapshot(str(tmp_path), snap1)
    t.join(timeout=60)
    assert out["ckpt"].is_valid()
    assert out["ckpt"].metrics == {"loss": 1.0}


def test_session_async_save(tmp_path):
    """ray_tpu.train.save_checkpoint(block=False) returns a
    Future[Checkpoint] through the worker session."""
    from ray_tpu.train import session as sess

    ctx = sess.TrainContext(0, 1, "async_sess", str(tmp_path))
    sess._start_session(ctx)
    try:
        state = {"x": np.arange(4.0)}
        fut = sess.save_checkpoint(state, 0, block=False)
        ckpt = fut.result(timeout=60)
        assert ckpt.is_valid() and ckpt.step == 0
        # A second async save serializes behind the first and lands too.
        fut2 = sess.save_checkpoint(state, 1, block=False)
        assert fut2.result(timeout=60).step == 1
    finally:
        sess._end_session()

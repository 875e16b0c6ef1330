"""Elastic Train resize — own module: the test must OWN the driver
connection (ray_tpu.init no-ops when a shared module-fixture cluster is
still connected, and a CPU-8 cluster would satisfy max_workers at
attempt start, never exercising the mid-run JOIN path).
"""

import time

import pytest

import ray_tpu
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

def test_elastic_resize_grows_mid_run(tmp_path):
    """Elastic Train (reference: controller.py:171
    _execute_resize_decision): a node JOIN mid-run re-gangs the job at a
    larger world size, resuming from the latest committed checkpoint —
    never from step 0."""
    import threading

    from ray_tpu.train.scaling_policy import ElasticScalingPolicy

    c = Cluster(num_nodes=1, resources={"CPU": 1})
    c.connect()
    try:
        storage = str(tmp_path)

        def loop(config):
            import time as _t

            import jax.numpy as jnp

            import ray_tpu.train as rt
            ctx = rt.get_context()
            start_step = 0
            w = jnp.zeros(2)
            prev = ctx.get_checkpoint()
            if prev is not None:
                host = rt.load_checkpoint_host(prev)
                start_step = int(host["step"]) + 1
                w = jnp.asarray(host["w"])
            for step in range(start_step, 20):
                w = w + 1.0
                _t.sleep(0.5)  # slow enough for the resize to land
                ckpt = rt.save_checkpoint({"w": w, "step": step}, step)
                rt.report({"step": step, "world": ctx.get_world_size(),
                           "resumed_from": start_step,
                           "w0": float(w[0])}, checkpoint=ckpt)

        trainer = JaxTrainer(
            loop, train_loop_config={},
            scaling_config=ScalingConfig(num_workers=1, max_workers=2),
            run_config=RunConfig(name="elastic", storage_path=storage),
            worker_env={"JAX_PLATFORMS": "cpu"})

        # Join a second node once the first checkpoint is committed (the
        # run is provably past step 0 at that point).
        import os

        def join_later():
            run = os.path.join(storage, "elastic")
            deadline = time.time() + 60
            while time.time() < deadline:
                if os.path.exists(os.path.join(run, "step-0", "COMMIT")):
                    c.add_node(resources={"CPU": 1})
                    return
                time.sleep(0.05)

        t = threading.Thread(target=join_later)
        t.start()
        result = trainer.fit()
        t.join(timeout=10)

        assert result.error is None, result.error
        hist = result.metrics_history
        worlds = [m["world"] for m in hist]
        assert worlds[0] == 1, hist[:2]
        assert worlds[-1] == 2, f"never grew to 2 workers: {worlds}"
        # The post-resize attempt resumed from a checkpoint, not step 0.
        resumed = [m for m in hist if m["world"] == 2]
        assert resumed[0]["resumed_from"] > 0, resumed[:2]
        assert hist[-1]["step"] == 19
        # Progress accumulated across the resize: w0 == step + 1.
        assert hist[-1]["w0"] == 20.0

        # Policy unit sanity: growth uses AVAILABLE resources, shrink
        # uses TOTAL; dead nodes count for neither.
        pol = ElasticScalingPolicy(1, 8)
        nodes = [{"state": "ALIVE", "resources_total": {"CPU": 3.0},
                  "resources_available": {"CPU": 2.0}},
                 {"state": "DEAD", "resources_total": {"CPU": 8.0},
                  "resources_available": {"CPU": 8.0}},
                 {"state": "ALIVE", "resources_total": {"CPU": 1.0},
                  "resources_available": {"CPU": 1.0}}]
        # current=1, 3 more bundles reservable -> 4 (cap_total 4).
        assert pol.target_workers(1, nodes, {"CPU": 1.0}) == 4
        # Bigger bundle: cap_total=1 -> shrink a 4-world job to 1.
        assert pol.target_workers(4, nodes, {"CPU": 2.0, "TPU": 0}) == 1
        # Other jobs holding resources bound growth: only 1 extra fits.
        nodes[0]["resources_available"] = {"CPU": 0.0}
        assert pol.target_workers(1, nodes, {"CPU": 1.0}) == 2
    finally:
        c.shutdown()

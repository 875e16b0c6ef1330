"""End-to-end JaxTrainer tests: gang-scheduled JAX worker processes with
jax.distributed over localhost — the SURVEY §7 "minimum slice" (reference
analogue: python/ray/train/v2/tests/test_data_parallel_trainer.py, with the
CPU multi-process substitution of SURVEY §4 implication (c)).

These tests spawn REAL separate worker processes through the actor runtime;
each worker is its own JAX process (JAX_PLATFORMS=cpu, 2 virtual devices)
joined into one global mesh via jax.distributed + gloo collectives.
"""

import time

import pytest

import ray_tpu
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.train import (FailureConfig, JaxTrainer, RunConfig,
                           ScalingConfig)

# Env for each CPU train worker: the CPU platform with 2 virtual devices
# per process.
CPU_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(num_nodes=1, resources={"CPU": 8})
    c.connect()
    yield c
    c.shutdown()




def test_jax_trainer_multiprocess_dp(cluster):
    def _mlp_loop(config):
        """Tiny data-parallel MLP regression over the GLOBAL device mesh."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        import ray_tpu.train as train

        ctx = train.get_context()
        mesh = Mesh(np.array(jax.devices()).reshape(-1), ("dp",))
        repl = NamedSharding(mesh, P())
        data_sh = NamedSharding(mesh, P("dp"))

        rng = np.random.RandomState(0)
        w_true = rng.rand(8, 1).astype(np.float32)
        params = {
            "w1": jax.device_put(rng.rand(8, 16).astype(np.float32) * 0.1, repl),
            "w2": jax.device_put(rng.rand(16, 1).astype(np.float32) * 0.1, repl),
        }

        def loss_fn(p, x, y):
            h = jnp.tanh(x @ p["w1"])
            pred = h @ p["w2"]
            return jnp.mean((pred - y) ** 2)

        @jax.jit
        def step(p, x, y):
            loss, g = jax.value_and_grad(loss_fn)(p, x, y)
            return jax.tree.map(lambda a, b: a - 0.05 * b, p, g), loss

        n_global = 64
        per_proc = n_global // ctx.get_world_size()
        for it in range(config["steps"]):
            xs = rng.rand(per_proc, 8).astype(np.float32)
            ys = xs @ w_true
            x = jax.make_array_from_process_local_data(data_sh, xs)
            y = jax.make_array_from_process_local_data(data_sh, ys)
            params, loss = step(params, x, y)
            train.report({"loss": float(loss), "step": it,
                          "world": ctx.get_world_size(),
                          "global_devices": jax.device_count()})

    trainer = JaxTrainer(
        _mlp_loop, train_loop_config={"steps": 12},
        scaling_config=ScalingConfig(num_workers=2),
        worker_env=CPU_WORKER_ENV)
    result = trainer.fit()
    hist = result.metrics_history
    assert len(hist) == 12
    # Two processes x two virtual devices = one 4-device global mesh.
    assert hist[0]["global_devices"] == 4
    assert hist[0]["world"] == 2
    # Loss must decrease (training is real).
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.5, hist




def test_jax_trainer_llama_spmd(cluster):
    def _llama_loop(config):
        """Train the tiny Llama through the framework SPMD stack across
        processes: dp axis spans the global (multi-process) mesh."""
        import jax
        import numpy as np

        import ray_tpu.train as train
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.parallel import MeshConfig, ParallelContext
        from ray_tpu.train.spmd import make_train_fns

        ctx_t = train.get_context()
        lcfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
                           n_kv_heads=2, d_ff=64, max_seq=32, dtype=np.float32)
        pctx = ParallelContext.create(MeshConfig(dp=jax.device_count()))
        init, step = make_train_fns(lcfg, pctx)
        state = init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(1 + ctx_t.get_world_rank())
        per = 4 // ctx_t.get_world_size()
        for it in range(config["steps"]):
            local = rng.randint(0, lcfg.vocab_size, (per, 32), dtype=np.int32)
            toks = jax.make_array_from_process_local_data(
                pctx.batch_sharding(), local)
            state, metrics = step(state, toks)
            train.report({"loss": float(metrics["loss"]), "step": it})

    trainer = JaxTrainer(
        _llama_loop, train_loop_config={"steps": 8},
        scaling_config=ScalingConfig(num_workers=2),
        worker_env=CPU_WORKER_ENV)
    result = trainer.fit()
    hist = result.metrics_history
    assert len(hist) == 8
    assert hist[-1]["loss"] < hist[0]["loss"], hist




def test_failure_policy_restarts_group(cluster, tmp_path):
    def _flaky_loop(config):
        import os

        import ray_tpu.train as train

        ctx = train.get_context()
        marker = config["marker"]
        if ctx.get_world_rank() == 0 and not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)  # hard crash: worker process dies mid-training
        for it in range(3):
            train.report({"loss": 1.0 / (it + 1), "restarted": True})

    marker = str(tmp_path / "crash_once")
    trainer = JaxTrainer(
        _flaky_loop, train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=2)),
        worker_env=CPU_WORKER_ENV)
    result = trainer.fit()
    assert result.metrics_history, "no metrics after restart"
    assert result.metrics_history[-1]["restarted"]


def test_failure_policy_exhausted(cluster):
    def always_fail(config):
        raise RuntimeError("intentional boom")

    trainer = JaxTrainer(
        always_fail,
        train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=1)),
        worker_env=CPU_WORKER_ENV)
    from ray_tpu.train.controller import TrainingFailedError
    with pytest.raises(TrainingFailedError, match="intentional boom"):
        trainer.fit()


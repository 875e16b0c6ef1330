"""The train loop a cell's JaxTrainer workers run: user code of Ray Train, as
`chip_smoke.py::_train_loop` is. It builds the program's model config from the
configuration file through the architecture's adapter (benchmark/models/),
takes `make_train_fns` and the `ray_tpu.data` iterator as they are, checks the
model against the adapter's reference in set-up, warms the step, and then
steps until the window ends.

Times are CLOCK_MONOTONIC, which the workers and the parent share on one
machine. Every rank reports; the controller keeps rank 0's.
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Dict

from benchmark import models


def _agree_to_stop(stop: bool, world: int) -> bool:
    """One answer for every process: ranks read their clocks at slightly
    different instants, and a rank that stopped alone would leave the others
    inside a collective."""
    if world == 1:
        return stop
    import numpy as np
    from jax.experimental import multihost_utils
    return bool(multihost_utils.broadcast_one_to_all(np.int32(stop)))


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _with_leaf(tree, path, value):
    if not path:
        return value
    return dict(tree, **{path[0]: _with_leaf(tree[path[0]], path[1:], value)})


def _check_against_reference(adapter, params, tokens, cfg, pctx, model,
                             positions):
    """The system's loss and the gradients of the adapter's `CHECK_LEAVES`
    (`adapter.loss_fn`: kernels, remat, bf16 activations) on the first
    `positions` of the first batch, against the adapter's plain reference on
    the same weights. A leaf stacked over the layers is compared at the last
    layer, as `last_<name>`."""
    import jax

    leaves = adapter.CHECK_LEAVES
    reference = adapter.reference()

    def system(picked, p, toks):
        for name, path in leaves.items():
            p = _with_leaf(p, path, picked[name])
        return adapter.loss_fn(p, toks[:, :positions], cfg, pctx)[0]

    sys_loss, sys_g = jax.jit(jax.value_and_grad(system))(
        {name: _leaf(params, path) for name, path in leaves.items()},
        params, tokens)
    ref_loss, ref_g = jax.jit(lambda p, t: reference.loss_and_check_grads(
        p, model, t[:, :positions]))(params, tokens)

    return dict(
        compare(leaves, float(sys_loss), sys_g, float(ref_loss), ref_g),
        param_dtypes=sorted({str(x.dtype) for x in jax.tree.leaves(params)}))


def compare(leaves, loss, grads, ref_loss, ref_grads) -> Dict[str, Any]:
    """Relative errors of a loss and of the gradients of `leaves` against the
    reference's; a leaf stacked over the layers at its last layer."""
    import jax.numpy as jnp

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))

    grad_rel_err = {}
    for name, path in leaves.items():
        if path[0] == "layers":
            grad_rel_err["last_" + name] = rel(grads[name][-1],
                                               ref_grads[name][-1])
        else:
            grad_rel_err[name] = rel(grads[name], ref_grads[name])
    return {"loss": loss, "ref_loss": ref_loss,
            "loss_rel_err": abs(loss - ref_loss) / abs(ref_loss),
            "grad_rel_err": grad_rel_err}


def loop(config: Dict[str, Any]) -> None:
    import jax

    import ray_tpu.train as train
    from ray_tpu.ops.attention import attention_path_counts
    from ray_tpu.parallel import MeshConfig, ParallelContext
    from ray_tpu.train.spmd import make_train_fns

    compiles = {"n": 0}

    def on_event(event: str, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            compiles["n"] += 1

    jax.monitoring.register_event_listener(on_event)
    tctx = train.get_context()
    model, dep, mix = config["model"], config["deployment"], config["traffic"]
    dev = jax.devices()[0]
    if config["chips"] and (dev.platform != "tpu"
                            or jax.device_count() != config["chips"]):
        raise RuntimeError(f"this cell needs {config['chips']} TPU chip(s); "
                           f"jax sees {jax.device_count()} x {dev.platform}")
    adapter = models.adapter(model["arch"])
    cfg = adapter.build_config(model, model["dtypes"], dep["max_seq"])
    pctx = ParallelContext.create(MeshConfig(**dep.get("mesh", {})))
    init, step = make_train_fns(cfg, pctx)
    state = jax.block_until_ready(
        init(jax.random.PRNGKey(config["seed"] % (2 ** 31 - 1))))
    world = tctx.world_size
    local_batch = config["global_batch"] // world
    feed = iter(train.get_dataset_shard("train").iter_jax_batches(
        batch_size=local_batch, sharding=pctx.batch_sharding(),
        drop_last=True, global_batch=world > 1))
    first = next(feed)["tokens"]

    check = _check_against_reference(
        adapter, state["params"], first, cfg, pctx, model,
        config["check_positions"])
    # Warm the step (its compile is set-up), and the stop broadcast.
    losses = []
    batch = first
    for _ in range(config["warm_steps"]):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
        batch = next(feed)["tokens"]
    _agree_to_stop(False, world)
    trace = config["trace"]
    trace_dir = os.path.join(config["out_dir"], "trace", f"rank{tctx.rank}")
    tracing = False
    marks = {}

    compiles_before = compiles["n"]
    steps = []
    t0 = time.monotonic()
    t_end = t0 + config["seconds"]
    while True:
        now = time.monotonic()
        if trace and not tracing and "trace_stop" not in marks \
                and now - t0 >= trace["start_s"]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, marks["trace_start"] = True, time.monotonic()
        t_a = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.next_feed"):
            batch = next(feed)["tokens"]
        t_b = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.step_dispatch"):
            state, metrics = step(state, batch)
        with jax.profiler.TraceAnnotation("bench.step_wait"):
            loss = float(jax.device_get(metrics["loss"]))  # waits for the step
        t_c = time.monotonic()
        steps.append({"t_done": t_c, "loss": loss, "feed_wait_s": t_b - t_a})
        if tracing and t_c - marks["trace_start"] >= trace["seconds"]:
            jax.profiler.stop_trace()
            tracing, marks["trace_stop"] = False, time.monotonic()
        if _agree_to_stop(t_c >= t_end, world):
            break
    if tracing:
        jax.profiler.stop_trace()
        marks["trace_stop"] = time.monotonic()
    mem = jax.local_devices()[0].memory_stats() or {}
    train.report({
        "rank": tctx.rank, "t0": t0, "steps": steps, "warm_losses": losses,
        "check": check, "marks": marks,
        "compiles_in_window": compiles["n"] - compiles_before,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))},
        "memory_limit_bytes": int(mem.get("bytes_limit", 0)),
        "attention_paths": attention_path_counts(),
        "mesh_devices": [int(d.id) for d in pctx.mesh.devices.flat],
        "all_finite": all(math.isfinite(s["loss"]) for s in steps),
    })

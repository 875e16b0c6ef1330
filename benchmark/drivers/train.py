"""A Ray Train job: `JaxTrainer` workers fed by `ray_tpu.data`, stepping until
the window ends. The configuration's `deployment` says how many workers hold
how many chips and over which mesh axes the state is sharded."""

from __future__ import annotations

import math
import os
import shutil
from typing import Any, Dict

from benchmark import cluster, trace, traffic


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import ray_tpu.data as rd
    from benchmark import train_loop
    from ray_tpu.train import JaxTrainer, ScalingConfig

    config, mix = ctx["config"], ctx["traffic"]
    dep = config["deployment"]
    rehearse = ctx["rehearse"]
    workers, per_worker = int(dep["num_workers"]), int(dep["chips_per_worker"])
    chips = ctx["chips"]
    n_dev = workers * per_worker
    global_batch = int(mix["batch_per_chip"]) * n_dev
    seq = max(8, int(mix["seq_tokens"] * ctx["length_scale"]))
    dep = dict(dep, max_seq=seq if rehearse else dep["max_seq"])
    # A rehearsal keeps the layout: as many CPU processes, as many (virtual)
    # devices each, the same mesh.
    worker_env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                  f"--xla_force_host_platform_device_count={per_worker}"} \
        if rehearse else None
    # Rows enough for the window: a feed that runs dry fails the run.
    budget = float(mix["steps_per_second_budget"]) * (100 if rehearse else 1)
    n_rows = global_batch * (int(mix["warm_steps"]) + 2
                             + math.ceil(ctx["seconds"] * budget))
    plan = trace.plan(ctx)
    if plan:
        shutil.rmtree(os.path.join(ctx["out_dir"], "trace"), ignore_errors=True)
    try:
        cluster.start(cpus=workers + 3, chips=chips)
        rows = traffic.rows(mix, n_rows, ctx["seed"], config["vocab_size"],
                            ctx["length_scale"])
        # One block a global batch: each worker's shard of a step arrives
        # together.
        ds = rd.from_numpy({"tokens": rows}, num_blocks=n_rows // global_batch)
        trainer = JaxTrainer(
            train_loop.loop,
            train_loop_config={
                "model": dict(config), "deployment": dep, "traffic": mix,
                "seed": ctx["seed"], "seconds": ctx["seconds"],
                "chips": n_dev if chips else 0, "global_batch": global_batch,
                "warm_steps": int(mix["warm_steps"]),
                "check_positions": max(8, int(mix["check"]["positions"]
                                              * ctx["length_scale"])),
                "trace": plan, "out_dir": ctx["out_dir"]},
            scaling_config=ScalingConfig(
                num_workers=workers, use_tpu=not rehearse,
                chips_per_worker=per_worker),
            datasets={"train": ds}, worker_env=worker_env)
        report = trainer.fit().metrics_history[-1]
    finally:
        stray = cluster.stop()

    chk, tol = report["check"], mix["check"]
    check_ok = (chk["loss_rel_err"] <= tol["loss_rel_tol"]
                and all(v <= tol["grad_rel_tol"]
                        for v in chk["grad_rel_err"].values())
                and chk["param_dtypes"] == [config["dtypes"]["params"]])
    steps = report["steps"]
    device = dict(report["device"])
    run = {
        "cell": ctx["cell"], "seed": ctx["seed"], "seconds": ctx["seconds"],
        "config": config, "traffic": mix,
        "setup_s": report["t0"] - ctx["t_process_start"], "t0": report["t0"],
        "steps": steps, "tokens_per_step": global_batch * seq, "seq": seq,
        "chips": n_dev, "check": dict(chk, tolerance=tol, ok=check_ok),
        "worker": {k: report[k] for k in (
            "warm_losses", "marks", "compiles_in_window", "memory_limit_bytes",
            "attention_paths", "mesh_devices")},
        "stray": stray, "attempted": len(steps),
        "failed": sum(1 for s in steps if not math.isfinite(s["loss"])),
        "device": device, "trace_data": None,
    }
    if report["compiles_in_window"]:
        print(f"{report['compiles_in_window']} compilation(s) inside the "
              f"measured window", flush=True)
    run["correct"] = bool(check_ok and report["all_finite"]
                          and not report["compiles_in_window"])
    if plan:
        data = trace.load(os.path.join(ctx["out_dir"], "trace"))
        if data is None and not ctx["rehearse"]:
            raise RuntimeError("no device plane in the trace")
        trace.attach(run, data)
    return run

"""Benchmark: Llama training throughput on one TPU chip — through the
FRAMEWORK (JaxTrainer actor + Ray-Data streaming ingest) and raw SPMD.

Prints one JSON line per metric; the LAST line is the headline
{"metric", "value", "unit", "vs_baseline"}.

The reference publishes no train-throughput number (BASELINE.md "Not
published"); the north-star target from BASELINE.json is >=40% MFU for
Llama-family DDP training with Ray Data streaming ingest on v5e.
``vs_baseline`` is measured MFU divided by the 0.40 target (>1.0 beats
the target). Phase A routes the identical train step through the actor
runtime (gang-scheduled JaxTrainer worker process) fed by
``iter_jax_batches`` over a streaming dataset shard; phase B is the raw
single-process SPMD loop. The delta is the framework overhead
(BASELINE.json configs[1]/[2] shape).
"""

from __future__ import annotations

import json
import os
import sys
import time

# Peak dense bf16 matmul FLOP/s per chip, keyed by jax's `device_kind`.
# Source: Google Cloud TPU documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16 per chip). A kind not listed is an error, never a
# default: an MFU against the wrong peak is a wrong number.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_flops(device_kind: str) -> float:
    if device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; "
            f"add it to PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[device_kind]


def _require_tpu():
    """The device this process computes on; anything but a TPU is an
    error — a number from a CPU run is not this benchmark's metric."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU; this process got platform "
            f"{dev.platform!r} ({dev.device_kind})")
    return dev


def _configs():
    model = dict(vocab_size=32000, d_model=2048, n_layers=8,
                 n_heads=16, n_kv_heads=16, d_ff=5504, max_seq=2048,
                 remat_policy="dots_nobatch")
    batch, seq, warmup, steps = 8, 2048, 3, 10
    return model, batch, seq, warmup, steps


def _train_loop(config):
    """Runs inside the JaxTrainer worker actor: the SAME step as phase B,
    fed by the streaming dataset shard."""
    import jax
    import ray_tpu.train as train
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel import MeshConfig, ParallelContext
    from ray_tpu.train.spmd import make_train_fns

    _require_tpu()
    cfg = LlamaConfig(**config["model"])
    ctx = ParallelContext.create(MeshConfig())
    init, step = make_train_fns(cfg, ctx)
    state = init(jax.random.PRNGKey(0))
    it = train.get_dataset_shard("train").iter_jax_batches(
        batch_size=config["batch"], sharding=ctx.batch_sharding(),
        drop_last=True)
    n = 0
    t0 = None
    metrics = None
    for b in it:
        state, metrics = step(state, b["tokens"])
        n += 1
        if n == config["warmup"]:
            jax.block_until_ready(metrics)
            t0 = time.perf_counter()
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0
    timed = n - config["warmup"]
    train.report({
        "tokens_per_sec": config["batch"] * config["seq"] * timed / dt,
        "steps": timed, "loss": float(metrics["loss"]),
    })


def bench_framework(model, batch, seq, warmup, steps) -> float:
    """Phase A: cluster + JaxTrainer actor + Data streaming ingest."""
    import numpy as np

    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu.train import JaxTrainer, ScalingConfig

    ray_tpu.init(resources={"CPU": 4})
    try:
        rng = np.random.RandomState(0)
        total = batch * (warmup + steps)
        rows = [{"tokens": rng.randint(0, model["vocab_size"], (seq,),
                                       dtype=np.int32)}
                for _ in range(total)]
        ds = rd.from_items(rows, num_blocks=max(4, warmup + steps))
        trainer = JaxTrainer(
            _train_loop,
            train_loop_config={"model": model, "batch": batch, "seq": seq,
                               "warmup": warmup},
            # The chip is granted by the scheduler (the agent detects it
            # and pins it to the worker); the driver never imports jax
            # in this phase, so nothing else holds it.
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=1),
            datasets={"train": ds})
        result = trainer.fit()
        return float(result.metrics_history[-1]["tokens_per_sec"])
    finally:
        ray_tpu.shutdown()


def bench_raw(model, batch, seq, warmup, steps):
    """Phase B: the raw single-process SPMD loop (no runtime around it).
    Returns (tokens/s, the device as jax reports it)."""
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel import MeshConfig, ParallelContext
    from ray_tpu.train.spmd import make_train_fns

    dev = _require_tpu()
    cfg = LlamaConfig(**model)
    ctx = ParallelContext.create(MeshConfig())  # single chip
    init, step = make_train_fns(cfg, ctx)
    state = init(jax.random.PRNGKey(0))
    toks = jax.device_put(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, seq),
                                         dtype=np.int32),
        ctx.batch_sharding())

    for _ in range(warmup):
        state, metrics = step(state, toks)
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, toks)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0
    return batch * seq * steps / dt, {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}


def bench_serve_ttft() -> dict:
    """Serve TTFT phase (BASELINE.json's second north star), run as a
    SUBPROCESS so its replica worker — not this process — owns the chip,
    through the full HTTP -> proxy -> pow-2 router -> replica path."""
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    # Own process group: on timeout the WHOLE tree (serve replicas and
    # node agents holding the chip) must die, or bench_raw can't take
    # the chip afterwards.
    proc = subprocess.Popen(
        [sys.executable, os.path.join(here, "bench_serve.py"),
         "--quick", "--ttft-only"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=here, start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=560)
    except subprocess.TimeoutExpired:
        import signal
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait(timeout=30)
        return {"error": "serve TTFT phase timed out"}
    metrics = {}
    for line in stdout.splitlines():
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict) and "metric" in d:
            metrics[d["metric"]] = d.get("value")
    if "serve_llama_ttft_p50" not in metrics:
        metrics["error"] = (stderr or stdout)[-400:]
    return metrics


def main() -> None:
    model, batch, seq, warmup, steps = _configs()

    # Phase A first: the trainer worker process must own the chip (this
    # process has not touched jax yet).
    fw_tps = bench_framework(model, batch, seq, warmup, steps)

    # Serve phase before the raw loop for the same reason — its replica
    # subprocess needs the chip, which bench_raw then takes in-process.
    serve_metrics = bench_serve_ttft()

    raw_tps, dev = bench_raw(model, batch, seq, warmup, steps)

    from ray_tpu.models.llama import LlamaConfig, flops_per_token
    cfg = LlamaConfig(**model)
    overhead_pct = (raw_tps - fw_tps) / raw_tps * 100
    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_framework",
        "value": round(fw_tps, 1), "unit": "tokens/s/chip",
        "note": "JaxTrainer actor + Data streaming ingest, same step",
    }))
    print(json.dumps({
        "metric": "llama_train_framework_overhead",
        "value": round(overhead_pct, 2), "unit": "%",
        "note": "vs raw SPMD loop; target <5%",
    }))
    if "serve_llama_ttft_p50" in serve_metrics:
        print(json.dumps({
            "metric": "serve_ttft_p50_ms",
            "value": serve_metrics["serve_llama_ttft_p50"], "unit": "ms",
            "note": "HTTP->router->replica, continuous-batching engine "
                    "with bucketed prefill; target <250ms",
        }))
        if "serve_llama_ttft_p95" in serve_metrics:
            print(json.dumps({
                "metric": "serve_ttft_p95_ms",
                "value": serve_metrics["serve_llama_ttft_p95"],
                "unit": "ms"}))
        if "serve_llama_decode_tokens_per_s" in serve_metrics:
            print(json.dumps({
                "metric": "serve_decode_tokens_per_s",
                "value": serve_metrics["serve_llama_decode_tokens_per_s"],
                "unit": "tokens/s",
                "note": "single-stream decode rate (pipelined paged-KV "
                        "engine)"}))
        if "serve_llama_decode_agg_tokens_per_s" in serve_metrics:
            print(json.dumps({
                "metric": "serve_decode_agg_tokens_per_s",
                "value":
                    serve_metrics["serve_llama_decode_agg_tokens_per_s"],
                "unit": "tokens/s",
                "note": "8 concurrent streams, paged KV continuous "
                        "batching; target >=120 (10x r4)"}))
    else:
        print(json.dumps({
            "metric": "serve_ttft_p50_ms", "value": None, "unit": "ms",
            "note": f"serve phase failed: "
                    f"{serve_metrics.get('error', 'unknown')[:300]}",
        }))
    mfu = raw_tps * flops_per_token(cfg, seq) / peak_flops(dev["kind"])
    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(raw_tps, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "device": dev,
    }))
    if "serve_llama_ttft_p50" not in serve_metrics:
        sys.exit(1)  # a failed phase is a failed run, whatever else printed


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two paths users pay for, through the entry points they call,
on a real TPU, and checks what comes out:

  kernels  flash_attention forward and jax.grad through it against
           attention_reference, at the model's real attention shape
  train    ray_tpu.init() (chips DETECTED by the node agent) ->
           JaxTrainer(use_tpu=True) fed by ray_tpu.data, at Llama-2-7B
           widths with only n_layers cut
  serve    serve.start(http=True) -> build_llm_app(num_tpus=1) -> streaming
           HTTP requests through the proxy and router, then the first
           tokens against models.llama.forward in a fresh process

    python chip_smoke.py            one chip (what the driver runs)
    python chip_smoke.py --chips 4  ONLY the cross-chip paths and the
                                    one-chip run they are compared with

One JSON line per phase; the last line of stdout is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
with the device as the worker that held the chip reported it. Any failed
check or exception, or no TPU, is a non-zero exit and "ok": false: this
script never carries on on the CPU.

A chip belongs to one process at a time, so THIS process never imports
jax: every phase's chip holder is a child (or a cluster worker) that is
gone, and checked to be gone, before the next phase starts.

Sizes are arguments of the phase functions, so tests/test_chip_smoke.py
runs the same control flow at tiny widths on virtual CPU devices.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import multiprocessing
import os
import signal
import sys
import time
import traceback
import urllib.request
from typing import Any, Callable, Dict, List, Optional, Sequence

# ---------------------------------------------------------------------------
# Sizes. Widths are the models' own; depth is the only cut, and it is printed.
# ---------------------------------------------------------------------------

# (batch, heads, seq, head_dim) of one Llama-2-7B attention call at batch 2.
KERNEL_SHAPE = (2, 32, 2048, 128)

# LlamaConfig.llama2_7b() widths. Resident training state is 12 B/param
# (f32 master weights + two AdamW moments) and gradients add 4 B/param
# while a step runs: 202.4M params a layer + 262.1M for embedding and
# head. compiled.memory_analysis() for v5e (AOT, in the sandbox): 2
# layers at batch 2x2048 = 7.45 GiB of state + 4.27 GiB of temporaries =
# 11.7 GiB of the chip's 16; a third layer does not fit.
LLAMA2_7B_WIDTHS = dict(vocab_size=32000, d_model=4096, n_heads=32,
                        n_kv_heads=32, d_ff=11008, max_seq=2048)
TRAIN = dict(model=dict(LLAMA2_7B_WIDTHS, n_layers=2), batch=2, seq=2048,
             steps=5, cut="n_layers 32 -> 2 (16 GB HBM; widths, seq and "
                          "dtype as LlamaConfig.llama2_7b())")
# The same model and one seeded global batch of 4 sequences, on one chip
# (13.2 GiB by the same analysis) and over four.
TRAIN_4 = dict(TRAIN, batch=4, steps=3)

# The widest model serve/llm.py's preset expresses from d_model=4096: 32
# heads, 16 kv heads, d_ff 11264. Params are f32: 0.70 GiB a layer + 0.98
# GiB; 8 layers (the preset's default depth) = 6.6 GiB. max_seq is not a
# width; 512 keeps the prefill bucket ladder (32..512) short.
SERVE = dict(llm=dict(d_model=4096, vocab_size=32000, max_seq=512,
                      n_layers=8, num_tpus=1),
             prompt_lens=(16, 256), max_tokens=16,
             cut="n_layers = 8, LLMConfig's default depth (f32 params, "
                 "16 GB HBM); max_seq 512")

# Step-0 loss of a randomly initialised model: uniform over the
# vocabulary plus half the variance of its logits. init_params draws
# lm_head with std 0.02 and the final rms_norm gives unit-RMS features,
# so logits have variance d_model * 0.02**2 (1.64 at d_model 4096).
INIT_STD = 0.02
LOSS0_TOL = 0.3
# bf16 activations, gradients reduced in another order: per-step losses of
# the same batches on 1 and on 4 chips agree to about three digits.
PARITY_TOL = 0.05
# As tests/test_ops.py's kernel test: max error over the reference's max.
KERNEL_TOL = 2e-2

# The processes that can hold a chip: workers, and the agent that spawns them.
CLUSTER_MARKERS = ("ray_tpu.core.worker_main", "ray_tpu.core.node_agent")
# A child that only computes (device probe, kernels, reference forward).
CHILD_TIMEOUT_S = 300


# Where the JSON lines go. main() keeps the real stdout for them and
# points sys.stdout at stderr: the runtime forwards every worker's prints
# to the driver's sys.stdout, and stdout must hold nothing but the records.
_json_out = sys.stdout


def emit(record: Dict[str, Any]) -> None:
    print(json.dumps(record, default=str), file=_json_out, flush=True)


# ---------------------------------------------------------------------------
# Children: the only processes that touch jax
# ---------------------------------------------------------------------------

def _child_main(conn, fn: Callable, kwargs: Dict[str, Any]) -> None:
    try:
        conn.send(("ok", fn(**kwargs)))
    except BaseException:
        conn.send(("error", traceback.format_exc()))


def in_child(fn: Callable, timeout: float, **kwargs) -> Any:
    """fn(**kwargs) in a fresh spawned process that is gone on return."""
    ctx = multiprocessing.get_context("spawn")
    recv, send = ctx.Pipe(duplex=False)
    proc = ctx.Process(target=_child_main, args=(send, fn, kwargs))
    proc.start()
    send.close()
    try:
        if not recv.poll(timeout):
            raise TimeoutError(f"{fn.__name__} gave no result in {timeout}s")
        try:
            status, payload = recv.recv()
        except EOFError:
            raise RuntimeError(f"{fn.__name__}'s process died without a "
                               f"result (exit code {proc.exitcode})")
    finally:
        proc.join(timeout=20)
        if proc.is_alive():
            proc.kill()
            proc.join()
        recv.close()
    if status != "ok":
        raise RuntimeError(f"{fn.__name__} failed in its process:\n{payload}")
    return payload


def _device_report() -> Dict[str, Any]:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(),
            "local_count": jax.local_device_count(),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "process_bounds": os.environ.get("TPU_PROCESS_BOUNDS"),
            "pid": os.getpid()}


def _count_cache_events() -> Dict[str, int]:
    """Live counters of this process's persistent-compile-cache hits and
    misses (a miss is a compile that was written to the cache)."""
    import jax
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def _kernels_child(shape: Sequence[int], seed: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import (attention_path_counts,
                                       attention_reference, flash_attention)

    cache = _count_cache_events()
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randn(*shape) * 0.5, jnp.bfloat16)
               for _ in range(3))

    def flash(q, k, v):
        return flash_attention(q, k, v, True)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=True)

    def grads_of(f):
        return jax.grad(lambda q, k, v: jnp.sum(f(q, k, v).astype(
            jnp.float32)), argnums=(0, 1, 2))

    def rel_err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))

    fwd, bwd = jax.jit(flash), jax.jit(grads_of(flash))
    t0 = time.perf_counter()
    fwd_text = fwd.lower(q, k, v).as_text()
    bwd_text = bwd.lower(q, k, v).as_text()
    out = jax.block_until_ready(fwd(q, k, v))
    g = jax.block_until_ready(bwd(q, k, v))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready((fwd(q, k, v), bwd(q, k, v)))
    run_s = time.perf_counter() - t0
    out_ref = jax.jit(ref)(q, k, v)
    g_ref = jax.jit(grads_of(ref))(q, k, v)
    errs = {"fwd": rel_err(out, out_ref)}
    errs.update({f"d{n}": rel_err(a, b)
                 for n, a, b in zip("qkv", g, g_ref)})
    finite = all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
                 for x in (out, *g))
    return {"device": _device_report(), "shape": list(shape),
            "dtype": "bfloat16", "causal": True, "rel_err": errs,
            "finite": finite,
            "fwd_has_tpu_custom_call": "tpu_custom_call" in fwd_text,
            "bwd_has_tpu_custom_call": "tpu_custom_call" in bwd_text,
            "attention_paths": attention_path_counts(),
            "compile_and_first_run_s": round(compile_s, 3),
            "second_run_s": round(run_s, 4), "cache": dict(cache)}


def _serve_reference_child(llm: Dict[str, Any],
                           prompts: List[List[int]]) -> Dict[str, Any]:
    """Top-3 next tokens of models.llama.forward, on the same seeded
    weights the replica built, for each prompt."""
    import jax
    import numpy as np

    from ray_tpu.models.llama import forward
    from ray_tpu.serve.llm import LLMConfig, _model_from_cfg

    mcfg, params = _model_from_cfg(LLMConfig(**llm))
    fwd = jax.jit(lambda p, t: forward(p, t, mcfg, None)[0, -1])
    top3 = []
    for ids in prompts:
        logits = np.asarray(fwd(params, np.asarray([ids], np.int32)))
        top3.append([int(i) for i in np.argsort(-logits)[:3]])
    return {"device": _device_report(), "top3": top3}


# ---------------------------------------------------------------------------
# Cluster hygiene
# ---------------------------------------------------------------------------

def _cluster_processes(session_dir: str) -> Dict[int, str]:
    """Live runtime processes of one ray_tpu session: pid -> command."""
    found: Dict[int, str] = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            if not any(m in cmd for m in CLUSTER_MARKERS):
                continue
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = f.read().decode(errors="replace")
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except OSError:
            continue  # gone, or not ours to read
        if state != "Z" and (session_dir in cmd or session_dir in env):
            found[int(pid)] = cmd.strip()
    return found


def stop_cluster(grace_s: float = 20.0) -> List[str]:
    """Shut serve and the cluster down; return the commands of any of the
    session's processes that outlived `grace_s` (and kill them: the next
    phase needs the chip, and this script stops whatever it starts)."""
    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu import api

    node = api._global_node
    if not ray_tpu.is_initialized():
        return []
    try:
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    if node is None:
        return []
    deadline = time.monotonic() + grace_s
    while True:
        left = _cluster_processes(node.session_dir)
        if not left or time.monotonic() > deadline:
            break
        time.sleep(0.25)
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    return sorted(left.values())


def native_planes() -> Dict[str, bool]:
    """Build libraytpu_store.so from what git commits (first import does)
    and say which native planes came up; a plane that disabled itself
    is a fault here, not a detail."""
    from ray_tpu.core._native import graftcopy, graftrpc, graftshm
    return {"graftshm": graftshm.available(),
            "graftcopy": graftcopy.available(),
            "graftrpc": graftrpc.available()}


def _ship_by_value() -> None:
    """Workers unpickle the loops below; send the code, not an import path
    (their sys.path need not hold this file)."""
    import cloudpickle
    cloudpickle.register_pickle_by_value(sys.modules[__name__])


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def _train_loop(config: Dict[str, Any]) -> None:
    """Runs in every JaxTrainer worker: the train loop of
    benchmark/train_loop.py (make_train_fns + iter_jax_batches), with the
    compile made explicit so that its time, its text and its memory are on
    record."""
    import jax
    import numpy as np

    import ray_tpu.train as train
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.ops.attention import attention_path_counts
    from ray_tpu.parallel import MeshConfig, ParallelContext
    from ray_tpu.train.spmd import make_train_fns

    cache = _count_cache_events()
    cfg = LlamaConfig(**config["model"])
    pctx = ParallelContext.create(MeshConfig(dp=config["dp"]))
    init, step = make_train_fns(cfg, pctx)
    t0 = time.perf_counter()
    state = jax.block_until_ready(init(jax.random.PRNGKey(config["seed"])))
    init_s = time.perf_counter() - t0
    batch, seq, steps = config["batch"], config["seq"], config["steps"]

    def batches():
        if config["feed"] == "dataset":
            it = train.get_dataset_shard("train").iter_jax_batches(
                batch_size=batch, sharding=pctx.batch_sharding(),
                drop_last=True)
            for b in it:
                yield b["tokens"]
            return
        # One seeded GLOBAL batch per step, whatever the layout: every
        # process draws all of it and supplies the rows its devices hold.
        toks = np.random.RandomState(config["seed"]).randint(
            0, cfg.vocab_size, (steps, batch, seq), dtype=np.int32)
        for i in range(steps):
            yield jax.make_array_from_callback(
                (batch, seq), pctx.batch_sharding(),
                lambda idx, i=i: toks[i][idx])

    feed = batches()
    first = next(feed)
    t0 = time.perf_counter()
    lowered = step.lower(state, first)
    lowered_text = lowered.as_text()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    info = {
        "device": _device_report(),
        "mesh_device_ids": [int(d.id) for d in pctx.mesh.devices.flat],
        "param_shard_device_ids": sorted(
            int(s.device.id)
            for s in state["params"]["embed"].addressable_shards),
        "step_has_tpu_custom_call": "tpu_custom_call" in lowered_text,
        "step_has_all_reduce": "all-reduce" in compiled.as_text(),
        "attention_paths": attention_path_counts(),
        "init_s": round(init_s, 3), "compile_s": round(compile_s, 3),
        "cache": dict(cache),
        "program_gib": round((mem.argument_size_in_bytes
                              + mem.output_size_in_bytes
                              + mem.temp_size_in_bytes
                              - mem.alias_size_in_bytes) / 2**30, 3),
    }
    i = 0
    while first is not None:
        t0 = time.perf_counter()
        state, metrics = compiled(state, first)
        metrics = jax.device_get(metrics)  # waits for the step
        dt = time.perf_counter() - t0
        row = {"step": i, "loss": float(metrics["loss"]),
               "grad_norm": float(metrics["grad_norm"]),
               "step_s": round(dt, 4)}
        if i == 0:
            stats = jax.local_devices()[0].memory_stats() or {}
            info["peak_gib"] = round(
                stats.get("peak_bytes_in_use", 0) / 2**30, 3)
            row["info"] = info
        train.report(row)
        first = next(feed, None)
        i += 1


def phase_train(*, model: Dict[str, Any], batch: int, seq: int, steps: int,
                seed: int, num_workers: int = 1, chips_per_worker: int = 1,
                feed: str = "dataset", name: str = "train",
                cut: str = "", reference: Optional[List[float]] = None
                ) -> Dict[str, Any]:
    """A JaxTrainer job over `num_workers` x `chips_per_worker` chips, dp
    over all of them. feed="dataset" streams ray_tpu.data rows through
    iter_jax_batches; feed="seeded" uses one seeded global batch per step
    so that `reference` (the same steps' losses from another layout) can
    be compared."""
    import numpy as np

    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu.train import JaxTrainer, ScalingConfig

    chips = num_workers * chips_per_worker
    cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
    entries_before = _cache_entries(cache_dir)
    _ship_by_value()
    t_phase = time.perf_counter()
    ray_tpu.init(resources={"CPU": float(num_workers + 3)})
    try:
        detected = int(ray_tpu.cluster_resources().get("TPU", 0))
        if detected < chips:
            raise RuntimeError(
                f"the node agent detected {detected} TPU chip(s) "
                f"(accelerators.num_tpu_chips); {name} needs {chips}")
        datasets = {}
        if feed == "dataset":
            rng = np.random.RandomState(seed)
            rows = [{"tokens": rng.randint(0, model["vocab_size"], (seq,),
                                           dtype=np.int32)}
                    for _ in range(batch * steps)]
            datasets["train"] = rd.from_items(rows, num_blocks=steps)
        trainer = JaxTrainer(
            _train_loop,
            train_loop_config={"model": model, "batch": batch, "seq": seq,
                               "steps": steps, "seed": seed, "dp": chips,
                               "feed": feed},
            scaling_config=ScalingConfig(num_workers=num_workers,
                                         use_tpu=True,
                                         chips_per_worker=chips_per_worker),
            datasets=datasets)
        history = trainer.fit().metrics_history
    finally:
        stray = stop_cluster()
    info = history[0]["info"]
    dev = info["device"]
    losses = [h["loss"] for h in history]
    gnorms = [h["grad_norm"] for h in history]
    loss0_expected = (math.log(model["vocab_size"])
                      + 0.5 * model["d_model"] * INIT_STD ** 2)
    checks = {
        "platform_is_tpu": dev["platform"] == "tpu",
        "device_count": dev["count"] == chips
        and dev["local_count"] == chips_per_worker,
        # A worker that holds the whole host is left libtpu's own view of
        # it; one that shares the host is pinned to its chips.
        "chips_pinned": (dev["visible_chips"] is None
                         if chips_per_worker == detected else
                         len((dev["visible_chips"] or "").split(","))
                         == chips_per_worker),
        "mesh_spans_chips": len(set(info["mesh_device_ids"])) == chips,
        "all_steps_ran": len(history) == steps,
        "loss0_near_init": abs(losses[0] - loss0_expected) < LOSS0_TOL,
        "losses_finite": all(map(math.isfinite, losses)),
        "grad_norm_finite_nonzero": all(
            math.isfinite(g) and g > 0 for g in gnorms),
        "step_has_tpu_custom_call": info["step_has_tpu_custom_call"],
        "no_stray_processes": not stray,
    }
    if chips > 1:
        checks["step_has_all_reduce"] = info["step_has_all_reduce"]
    if chips_per_worker > 1:
        checks["params_on_every_chip"] = \
            len(set(info["param_shard_device_ids"])) == chips_per_worker
    record = {
        "phase": name, "device": dev, "tpu_chips_detected": detected,
        "sizes": dict(model, batch=batch, seq=seq, steps=steps,
                      dtype="bfloat16 activations, float32 params"),
        "cut": cut,
        "layout": {"workers": num_workers,
                   "chips_per_worker": chips_per_worker, "dp": chips,
                   "feed": feed, "mesh_device_ids": info["mesh_device_ids"],
                   "param_shard_device_ids": info["param_shard_device_ids"]},
        "losses": losses, "grad_norms": gnorms,
        "loss0_expected": round(loss0_expected, 4),
        "init_s": info["init_s"], "compile_s": info["compile_s"],
        "step_s": [h["step_s"] for h in history],
        "phase_s": round(time.perf_counter() - t_phase, 2),
        "program_gib": info["program_gib"], "peak_gib": info["peak_gib"],
        "attention_paths": info["attention_paths"],
        "cache": dict(info["cache"], dir=cache_dir,
                      entries_before=entries_before,
                      entries_after=_cache_entries(cache_dir)),
        "stray_processes": stray,
    }
    if reference is not None:
        diffs = [abs(a - b) for a, b in zip(losses, reference)]
        record["reference_losses"] = reference
        record["max_loss_diff"] = max(diffs)
        checks["losses_match_one_chip"] = (
            len(losses) == len(reference) and max(diffs) < PARITY_TOL)
    record["checks"] = checks
    record["ok"] = all(checks.values())
    return record


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------

def _http_stream(url: str, prompt: List[int], max_tokens: int
                 ) -> Dict[str, Any]:
    """One greedy streaming completion, as benchmark/http_load.py sends
    it."""
    req = urllib.request.Request(
        url, data=json.dumps({"prompt": prompt,
                              "max_tokens": max_tokens}).encode(),
        headers={"x-serve-stream": "1"})
    t0 = time.perf_counter()
    ttft = None
    body = b""
    with urllib.request.urlopen(req, timeout=300) as resp:
        while True:  # read(1): chunked read(n) would wait across chunks
            chunk = resp.read(1)
            if not chunk:
                break
            if ttft is None:
                ttft = time.perf_counter() - t0
            body += chunk
    words = body.split()
    if not words or not all(w.isdigit() for w in words):
        raise RuntimeError(f"stream is not integer tokens: {body[:200]!r}")
    return {"tokens": [int(w) for w in words],
            "ttft_s": round(ttft, 4),
            "total_s": round(time.perf_counter() - t0, 4)}


def phase_serve(*, llm: Dict[str, Any], prompt_lens: Sequence[int],
                max_tokens: int, seed: int, num_replicas: int = 1,
                name: str = "serve", cut: str = "") -> Dict[str, Any]:
    """`num_replicas` one-chip LLMServer replicas behind the HTTP proxy
    and the router. One replica: first tokens are checked against
    models.llama.forward in a fresh process after serve is gone. Several:
    every replica is also asked directly, and all must agree."""
    import cloudpickle
    import numpy as np

    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.serve.llm import LLMConfig, build_llm_app

    cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
    entries_before = _cache_entries(cache_dir)
    rng = np.random.RandomState(seed)
    prompts = [[int(t) for t in rng.randint(1, llm["vocab_size"], n)]
               for n in prompt_lens]
    t_phase = time.perf_counter()
    ray_tpu.init(resources={"CPU": float(num_replicas + 7)})
    try:
        detected = int(ray_tpu.cluster_resources().get("TPU", 0))
        need = num_replicas * int(llm["num_tpus"])
        if detected < need:
            raise RuntimeError(
                f"the node agent detected {detected} TPU chip(s) "
                f"(accelerators.num_tpu_chips); {name} needs {need}")
        controller = serve.start(http=True)
        t0 = time.perf_counter()
        serve.run(build_llm_app(LLMConfig(num_replicas=num_replicas, **llm)),
                  name="llm")
        while ray_tpu.get(controller.ready_replicas.remote("llm"),
                          timeout=30) < num_replicas:
            if time.perf_counter() - t0 > 600:
                raise TimeoutError("not every replica became ready")
            time.sleep(0.5)
        startup_s = time.perf_counter() - t0
        url = f"http://127.0.0.1:{serve.get_proxy().port}/llm"
        # Each prompt twice through proxy and router (more when there
        # are replicas for the router to spread them over).
        streams = [[_http_stream(url, p, max_tokens)
                    for _ in range(2 * num_replicas)] for p in prompts]
        # Every replica, asked directly: where it runs, and its tokens.
        table = ray_tpu.get(controller.routing_table.remote(), timeout=30)
        replicas = table["deployments"]["llm"]

        def ask(replica, method, *args):
            return ray_tpu.get(replica.handle_request.remote(
                method, cloudpickle.dumps((args, {})), ""), timeout=300)

        infos = [ask(r, "device_info") for r in replicas]
        direct = [[[int(w) for w in ask(
            r, "complete", {"prompt": p, "max_tokens": max_tokens}
        )["choices"][0]["text"].split()] for p in prompts]
            for r in replicas]
    finally:
        stray = stop_cluster()

    tokens = [s[0]["tokens"] for s in streams]
    widths = [next(w for w in infos[0]["warm_buckets"] if w >= n)
              for n in prompt_lens]
    checks = {
        "platform_is_tpu": all(i["platform"] == "tpu" for i in infos),
        "replica_count": len(infos) == num_replicas,
        "one_device_per_replica": all(
            i["device_count"] == int(llm["num_tpus"]) for i in infos),
        # Replicas that share a host are each pinned to their own chips.
        "distinct_chips": (infos[0]["visible_chips"] is None
                           if need == detected and num_replicas == 1 else
                           len({i["visible_chips"] for i in infos})
                           == num_replicas
                           and all(i["visible_chips"] for i in infos)),
        "every_stream_max_tokens": all(
            len(r["tokens"]) == max_tokens for s in streams for r in s),
        "same_prompt_same_tokens": all(
            r["tokens"] == s[0]["tokens"] for s in streams for r in s),
        "replicas_agree": all(d == tokens for d in direct),
        # A 128-aligned prefill width compiles to the Pallas kernel; a
        # 16-token prompt's 32-wide bucket never does.
        "aligned_prefill_has_tpu_custom_call": all(
            i["prefill_has_tpu_custom_call"][w] for i in infos
            for w in i["warm_buckets"] if w % 128 == 0),
        "no_stray_processes": not stray,
    }
    record = {
        "phase": name,
        "device": {"platform": infos[0]["platform"],
                   "kind": infos[0]["device_kind"],
                   "count": infos[0]["device_count"]},
        "tpu_chips_detected": detected,
        "sizes": dict(llm, derived="n_heads d_model/128, n_kv_heads "
                      "d_model/256, d_ff 2.75*d_model (serve/llm.py)",
                      dtype="bfloat16 activations, float32 params",
                      prompt_lens=list(prompt_lens), max_tokens=max_tokens,
                      replicas=num_replicas),
        "cut": cut, "prefill_bucket_for_prompt": widths,
        "replicas": [{k: i[k] for k in (
            "pid", "visible_chips", "device_count", "warm_buckets",
            "prefill_has_tpu_custom_call", "attention_paths")}
            for i in infos],
        "tokens": tokens,
        "startup_s_incl_compile": round(startup_s, 2),
        "ttft_s": [[r["ttft_s"] for r in s] for s in streams],
        "total_s": [[r["total_s"] for r in s] for s in streams],
        "phase_s": round(time.perf_counter() - t_phase, 2),
        "cache": {"dir": infos[0]["compile_cache_dir"],
                  "entries_before": entries_before,
                  "entries_after": _cache_entries(cache_dir)},
        "stray_processes": stray,
    }
    if num_replicas == 1:
        # Random weights make near-ties, so "among the top 3", not argmax.
        ref = in_child(_serve_reference_child, CHILD_TIMEOUT_S, llm=llm,
                       prompts=prompts)
        record["reference_top3"] = ref["top3"]
        record["reference_device"] = ref["device"]
        checks["first_token_in_reference_top3"] = all(
            t[0] in top for t, top in zip(tokens, ref["top3"]))
    record["checks"] = checks
    record["ok"] = all(checks.values())
    return record


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def phase_kernels(*, shape: Sequence[int], seed: int) -> Dict[str, Any]:
    cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
    entries_before = _cache_entries(cache_dir)
    r = in_child(_kernels_child, CHILD_TIMEOUT_S, shape=tuple(shape),
                 seed=seed)
    checks = {
        "platform_is_tpu": r["device"]["platform"] == "tpu",
        "finite": r["finite"],
        "matches_attention_reference": all(
            e < KERNEL_TOL for e in r["rel_err"].values()),
        "fwd_has_tpu_custom_call": r["fwd_has_tpu_custom_call"],
        "bwd_has_tpu_custom_call": r["bwd_has_tpu_custom_call"],
    }
    r["cache"] = dict(r["cache"], dir=cache_dir,
                      entries_before=entries_before,
                      entries_after=_cache_entries(cache_dir))
    return dict(r, phase="kernels", tolerance=KERNEL_TOL, checks=checks,
                ok=all(checks.values()))


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

class _Deadline:
    """SIGALRM guard: a phase that hangs (a process waiting for a chip
    another one holds) fails instead of eating the time limit."""

    def __init__(self, seconds: int, what: str):
        self.seconds, self.what = seconds, what

    def _expired(self, signum, frame):
        raise TimeoutError(f"{self.what} exceeded {self.seconds}s")

    def __enter__(self):
        self._old = signal.signal(signal.SIGALRM, self._expired)
        signal.alarm(self.seconds)

    def __exit__(self, *exc):
        signal.alarm(0)
        signal.signal(signal.SIGALRM, self._old)


def run_phase(fn: Callable[..., Dict[str, Any]], deadline_s: int,
              **kwargs) -> Dict[str, Any]:
    """Run one phase, print its line, never raise: an exception is a
    failed phase."""
    name = kwargs.get("name") or fn.__name__.replace("phase_", "")
    try:
        with _Deadline(deadline_s, name):
            record = fn(**kwargs)
    except Exception:
        record = {"phase": name, "ok": False,
                  "error": traceback.format_exc()[-3000:]}
        try:
            record["stray_processes"] = stop_cluster()
        except Exception:
            record["cleanup_error"] = traceback.format_exc()[-1000:]
    emit(record)
    return record


def smoke_one_chip(seed: int) -> List[Dict[str, Any]]:
    records = [run_phase(phase_kernels, 300, shape=KERNEL_SHAPE, seed=seed)]
    if records[-1]["ok"]:
        records.append(run_phase(phase_train, 420, seed=seed, **TRAIN))
    if records[-1]["ok"]:
        records.append(run_phase(phase_serve, 600, seed=seed, **SERVE))
    return records


def smoke_four_chips(seed: int) -> List[Dict[str, Any]]:
    """Only what exists across chips, and what it is compared with."""
    one = run_phase(phase_train, 420, seed=seed, feed="seeded",
                    name="train_1x1_reference", **TRAIN_4)
    records = [one]
    if not one["ok"]:
        return records
    # (a) one worker holding four chips; (b) the north-star layout, four
    # one-chip workers joined by jax.distributed. Same batches, same losses.
    for name, workers, per in (("a_train_1x4", 1, 4), ("b_train_4x1", 4, 1)):
        records.append(run_phase(
            phase_train, 420, seed=seed, feed="seeded", name=name,
            num_workers=workers, chips_per_worker=per,
            reference=one["losses"], **TRAIN_4))
    # (c) four one-chip replicas behind the router.
    records.append(run_phase(phase_serve, 600, seed=seed, num_replicas=4,
                             name="c_serve_4_replicas", **SERVE))
    return records


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels, train, serve on one chip (default). "
                         "4: only the cross-chip paths and their one-chip "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds weights, batches and prompts")
    args = ap.parse_args(argv)
    global _json_out
    _json_out, sys.stdout = sys.stdout, sys.stderr

    from ray_tpu import accelerators
    cache_dir = accelerators.compile_cache_env(os.environ)
    device = {"platform": None, "kind": None, "count": None}
    ok = False
    try:
        planes = native_planes()
        probe = in_child(_device_report, CHILD_TIMEOUT_S)
        emit({"phase": "start", "chips_asked": args.chips, "seed": args.seed,
              "native_planes": planes, "probe": probe,
              # What the node agent will advertise as the TPU resource,
              # and what it and libtpu have to go on.
              "agent_will_detect_chips": accelerators.num_tpu_chips(),
              "dev_nodes": sorted(glob.glob("/dev/accel*")
                                  + glob.glob("/dev/vfio/*")),
              "env": {k: v for k, v in sorted(os.environ.items())
                      if k.startswith(("TPU_", "JAX_", "XLA_", "LIBTPU"))},
              "compile_cache_dir": cache_dir,
              "compile_cache_entries": _cache_entries(cache_dir),
              "ok": all(planes.values()) and probe["platform"] == "tpu"
              and probe["count"] >= args.chips})
        device = {k: probe[k] for k in ("platform", "kind", "count")}
        if not all(planes.values()):
            raise RuntimeError(f"native planes not live: {planes}")
        if probe["platform"] != "tpu" or probe["count"] < args.chips:
            raise RuntimeError(
                f"chip_smoke.py --chips {args.chips} needs that many TPU "
                f"chips; jax found {probe['count']} x {probe['platform']} "
                f"({probe['kind']}). It does not run on the CPU.")
        records = (smoke_one_chip if args.chips == 1
                   else smoke_four_chips)(args.seed)
        # The chip belonged to the workers only if this process kept off jax.
        records.append({"phase": "parent", "ok": "jax" not in sys.modules,
                        "checks": {"never_imported_jax":
                                   "jax" not in sys.modules}})
        ok = all(r["ok"] for r in records)
        # The device, as the worker that held the chip(s) reported it.
        held = [r["device"] for r in records
                if r.get("device", {}).get("count") == args.chips]
        if held:
            device = {k: held[-1][k] for k in ("platform", "kind", "count")}
        if not ok:
            emit({"failed": {r["phase"]: [c for c, v in r.get(
                "checks", {"exception": False}).items() if not v]
                for r in records if not r["ok"]}})
    except Exception:
        emit({"phase": "start", "ok": False,
              "error": traceback.format_exc()[-3000:]})
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

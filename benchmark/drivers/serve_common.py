"""What the two serve drivers share: deploy `BenchLLMServer` behind the HTTP
proxy and the router, wait until every bucket is warm, check the outputs
against the reference, offer the cell's load, collect the record.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from benchmark import cluster, http_load, trace, traffic
from benchmark.traffic import Request

READY_TIMEOUT_S = 1100.0


class Replica:
    """The harness's side channel to the one replica."""

    def __init__(self, actor):
        self._actor = actor

    def ask(self, method: str, *args, timeout: float = 600.0):
        import cloudpickle

        import ray_tpu
        return ray_tpu.get(self._actor.handle_request.remote(
            method, cloudpickle.dumps((args, {})), ""), timeout=timeout)


def deploy(ctx: Dict[str, Any]):
    """Cluster, serve, one replica of the configuration. Returns
    (Replica, host, port, path)."""
    import ray_tpu
    import ray_tpu.serve as serve
    from benchmark.serve_app import BenchLLMServer

    config, dep = ctx["config"], ctx["config"]["deployment"]
    cluster.start(cpus=8, chips=ctx["chips"])
    controller = serve.start(http=True)
    spec = {"arch": config["arch"], "model": config, "dtypes": config["dtypes"],
            "engine": dep["engine"], "seed": ctx["seed"],
            "num_tpus": ctx["chips"]}
    app = serve.deployment(
        num_replicas=1, num_tpus=ctx["chips"],
        max_ongoing_requests=dep["engine"]["n_slots"])(BenchLLMServer)
    serve.run(app.bind(json.dumps(spec)), name="llm")
    table = ray_tpu.get(controller.routing_table.remote(), timeout=30)
    replica = Replica(table["deployments"]["llm"][0])
    deadline = time.monotonic() + READY_TIMEOUT_S
    while True:
        state = replica.ask("bench_ready")
        if state["ready"]:
            break
        if state["fault"] or time.monotonic() > deadline:
            raise RuntimeError(f"replica never became ready: {state}")
        time.sleep(0.5)
    replica.ask("check_health")
    return replica, "127.0.0.1", serve.get_proxy().port, "/llm"


def check_outputs(ctx, replica: Replica, host, port, path) -> Dict[str, Any]:
    """Greedy tokens served over HTTP for one seeded prompt per prefill bucket
    the cell uses, held to the reference's logits: prefill gave the first
    token, decoding through the paged cache the rest."""
    chk = ctx["traffic"]["check"]
    lengths = [max(2, int(n * ctx["length_scale"]))
               for n in chk["prompt_lengths"]]
    prompts = traffic.sample_prompts(lengths, ctx["seed"],
                                     ctx["config"]["vocab_size"])
    reqs = [Request(-1 - i, 0.0, p, int(chk["tokens"]))
            for i, p in enumerate(prompts)]
    served: List[List[int]] = []

    async def fetch(r: Request) -> List[int]:
        out = http_load.Outcome(r.index, 0.0, len(r.prompt), r.max_tokens)
        toks: List[int] = []
        await http_load.stream(host, port, path, r, out, toks)
        return toks

    for r in reqs:
        served.append(asyncio.run(fetch(r)))
    gaps = replica.ask("bench_check", [
        {"prompt": r.prompt, "served": s} for r, s in zip(reqs, served)],
        timeout=900.0)
    verdict = judge(gaps, chk)
    return dict(verdict, prompt_lengths=lengths, logit_gaps=gaps,
                ok=verdict["ok"] and all(len(s) == chk["tokens"]
                                         for s in served))


def judge(gaps: List[List[float]], chk: Dict[str, Any]) -> Dict[str, Any]:
    """The limits of a traffic file's `check` applied to the gaps of a run
    (or of its control, benchmark/control.py)."""
    flat = [g for case in gaps for g in case]
    worst, mean = max(flat), sum(flat) / len(flat)
    return {"worst_gap": worst, "mean_gap": mean,
            "tolerance": chk["logit_tolerance"],
            "mean_tolerance": chk["mean_logit_tolerance"],
            "ok": worst <= chk["logit_tolerance"]
            and mean <= chk["mean_logit_tolerance"]}


def run(ctx: Dict[str, Any],
        offer: Callable[[str, int, str, List[Request]], Any]) -> Dict[str, Any]:
    trace_dir = os.path.join(ctx["out_dir"], "trace")
    marks: Dict[str, float] = {}
    try:
        replica, host, port, path = deploy(ctx)
        check = check_outputs(ctx, replica, host, port, path)
        reqs = traffic.requests(ctx["traffic"], ctx["seconds"], ctx["seed"],
                                ctx["config"]["vocab_size"],
                                ctx["length_scale"])
        plan = trace.plan(ctx)
        tracer = None
        if plan:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)

            def traced():
                time.sleep(plan["start_s"])
                replica.ask("bench_trace_start", trace_dir)
                marks["trace_start"] = time.monotonic()
                time.sleep(plan["seconds"])
                marks["trace_stop"] = time.monotonic()
                replica.ask("bench_trace_stop")

            tracer = threading.Thread(target=traced, name="bench-trace")
        replica.ask("bench_mark")
        setup_s = time.monotonic() - ctx["t_process_start"]
        if tracer:
            tracer.start()
        t0, outcomes = offer(host, port, path, reqs)
        if tracer:
            tracer.join()
        stats = replica.ask("bench_stats")
    finally:
        stray = cluster.stop()
    return assemble(ctx, setup_s, t0, outcomes, stats, check, stray,
                    trace_dir if plan else None, marks)


def assemble(ctx, setup_s, t0, outcomes, stats, check, stray, trace_dir,
             marks) -> Dict[str, Any]:
    import dataclasses
    device = {"platform": stats["platform"], "kind": stats["kind"],
              "count": stats["count"],
              "memory_peak_bytes": stats["memory_peak_bytes"]}
    run = {
        "cell": ctx["cell"], "seed": ctx["seed"], "seconds": ctx["seconds"],
        "config": ctx["config"], "traffic": ctx["traffic"],
        "setup_s": setup_s, "t0": t0,
        "outcomes": [dataclasses.asdict(o) for o in outcomes],
        "replica": stats, "check": check, "stray": stray, "marks": marks,
        "attempted": sum(1 for o in outcomes if not o.abandoned),
        "failed": sum(1 for o in outcomes if not o.ok and not o.abandoned),
        "device": device, "trace_data": None,
    }
    if stats["compiles_in_window"]:
        print(f"{stats['compiles_in_window']} compilation(s) inside the "
              f"measured window", flush=True)
    run["correct"] = bool(check["ok"] and not stats["compiles_in_window"]
                          and run["failed"] == 0)
    if trace_dir:
        data = trace.load(trace_dir)
        if data is None and not ctx["rehearse"]:
            raise RuntimeError("no device plane in the trace")
        trace.attach(run, data)
    return run

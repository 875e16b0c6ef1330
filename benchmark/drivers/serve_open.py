"""Open loop: seeded arrivals at the mix's one fixed rate, whatever the
server does. With `--rates a,b,c` it offers each rate for one window instead
(one set-up, several windows) and prints one line a rate: the sweep that
finds the knee, made once when a cell is defined."""

from __future__ import annotations

import copy
import json
import sys
from typing import Any, Dict, Optional

from benchmark import cluster, http_load, traffic
from benchmark.drivers import serve_common
from benchmark.stats import percentile


def run(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if ctx["rates"]:
        return sweep(ctx)
    drain = float(ctx["traffic"]["drain_s"])
    return serve_common.run(
        ctx, lambda host, port, path, reqs: http_load.run_open(
            host, port, path, reqs, ctx["seconds"], drain))


def sweep(ctx: Dict[str, Any]) -> None:
    """The knee is the highest rate at which the backlog does not grow over
    the window: compare the requests in flight when the window closes, and
    the later half's time to first token with the earlier half's."""
    try:
        replica, host, port, path = serve_common.deploy(ctx)
        for rate in ctx["rates"]:
            mix = copy.deepcopy(ctx["traffic"])
            mix["arrivals"]["rate_per_s"] = rate
            reqs = traffic.requests(mix, ctx["seconds"], ctx["seed"],
                                    ctx["config"]["vocab_size"],
                                    ctx["length_scale"])
            t0, outs = http_load.run_open(host, port, path, reqs,
                                          ctx["seconds"], float(mix["drain_s"]))
            t_end = t0 + ctx["seconds"]
            ttft = [(o.t_first - o.t_sched) * 1e3 for o in outs if o.t_first]
            half = len(ttft) // 2
            tpot = [(o.t_last - o.t_first) / (o.tokens - 1) * 1e3
                    for o in outs if o.ok and o.tokens > 1]
            print(json.dumps({
                "rate_per_s": rate, "offered": len(outs),
                "failed": sum(1 for o in outs if not o.ok),
                "in_flight_at_close": sum(
                    1 for o in outs if o.t_last is None or o.t_last > t_end),
                "drain_s": max([o.t_last - t_end for o in outs if o.t_last]
                               + [0.0]),
                "ttft_p50_ms_first_half": percentile(ttft[:half], 50),
                "ttft_p50_ms_second_half": percentile(ttft[half:], 50),
                "ttft_p95_ms": percentile(ttft, 95),
                "tpot_p50_ms": percentile(tpot, 50),
                "tpot_p95_ms": percentile(tpot, 95),
                "tokens_per_s": sum(o.tokens for o in outs) / ctx["seconds"],
            }), file=sys.__stdout__, flush=True)
    finally:
        cluster.stop()
    return None

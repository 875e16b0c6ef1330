#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data that this file finds by name:

  BENCHMARK.json                       the cell: configuration, traffic, chips
  benchmark/configs/<config>.json      sizes, `arch`, dtypes, deployment
  benchmark/traffic/<traffic>.json     the mix, and `kind`: the driver
  benchmark/drivers/<kind>.py          brings the system up, offers the load
  benchmark/models/<arch>.py           published keys -> the program's config;
                                       the reference, the check's leaves, the
                                       counts and the rehearsal widths
  benchmark/end_to_end/<metric>.py     reader: run record -> value
  benchmark/layer_metrics/<metric>.py  reader: run record -> value or None

The last line of standard output is the result object and nothing else goes
there; the details go to benchmark/out/<cell>/<seed>/run.json.

This process never initialises a JAX backend: the replica or the train worker
holds the chip. No chip, no result: there is no CPU fallback. `--rehearse`
(tiny widths on the CPU, for the sandbox) reports platform `cpu` and exits 3.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    """The cell with its configuration and traffic files read in."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = dict(cells[name])
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cell["config_file"] = entry["file"]
    return cell


def metrics_of(manifest: Dict[str, Any], group: str, cell: str) -> List[Dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(bench_dir: str, group_dir: str, name: str) -> Callable:
    """benchmark/<group_dir>/<name>.py::read, loaded by path so that a name
    may hold `.` and `-`."""
    path = os.path.join(bench_dir, group_dir, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_reader_{group_dir}_{name}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def apply_rehearsal(bench_dir: str, config: Dict[str, Any]) -> float:
    """Tiny widths for the sandbox; returns the factor for traffic lengths."""
    from benchmark import models
    r = load_json(bench_dir, "rehearse.json")
    config.update(models.adapter(config["arch"]).REHEARSE)
    dep = config["deployment"]
    dep["max_seq"] = r["max_seq"]
    if "engine" in dep:
        dep["engine"].update(r["engine"])
    return float(r["length_scale"])


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: control flow only")
    ap.add_argument("--rates", default="",
                    help="serve_open only: offer these rates (comma list), "
                         "one window each, to find the knee; no result line")
    args = ap.parse_args(argv)

    root, bench_dir = ROOT, HERE
    os.chdir(root)
    if root not in sys.path:
        sys.path.insert(0, root)
    # Workers are spawned with this environment and this working directory.
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [root] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p])
    # Fixed, inside the checkout, whatever the operator's environment says:
    # two checkouts that are compared must share no compiled program.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")

    manifest = load_json(root, "BENCHMARK.json")
    cell = find_cell(manifest, args.workload)
    config = load_json(root, cell["config_file"])
    mix = load_json(bench_dir, "traffic", cell["traffic"] + ".json")
    scale = apply_rehearsal(bench_dir, config) if args.rehearse else 1.0
    out_dir = os.path.join(bench_dir, "out", args.workload, str(args.seed))
    os.makedirs(out_dir, exist_ok=True)

    # The runtime forwards every worker's prints to sys.stdout: keep the real
    # one for the result line.
    result_out, sys.stdout = sys.stdout, sys.stderr
    ctx = {
        "cell": args.workload, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "rehearse": args.rehearse,
        "chips": 0 if args.rehearse else int(cell["chips"]),
        "config": config, "traffic": mix, "length_scale": scale,
        "out_dir": out_dir, "t_process_start": T_PROCESS_START,
        "rates": [float(r) for r in args.rates.split(",") if r],
    }
    driver = importlib.import_module(f"benchmark.drivers.{mix['kind']}")
    try:
        run = driver.run(ctx)
    except Exception:
        traceback.print_exc()
        from benchmark import cluster
        try:
            cluster.stop()
        except Exception:
            traceback.print_exc()
        return 1
    if run is None:          # a sweep: it printed its own lines
        return 0
    # Each number the correctness check compared, beside its limit.
    print("check:", json.dumps(
        {k: v for k, v in run["check"].items() if k != "logit_gaps"},
        default=str), flush=True)

    group, reader_dir = (("per_layer", "layer_metrics") if args.trace
                         else ("end_to_end", "end_to_end"))
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in metrics_of(manifest, group, args.workload):
        try:
            value = load_reader(bench_dir, reader_dir, m["name"])(run)
        except KeyError:
            if not args.rehearse:   # e.g. no peaks for the sandbox's CPU
                raise
            value = None
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics,
              "device": run["device"]}
    if run.get("breakdown"):
        result["breakdown"] = run["breakdown"]
    record = {k: v for k, v in run.items() if k not in ("trace_data",)}
    record["result"] = result
    with open(os.path.join(out_dir, f"run-trace{args.trace}.json"), "w") as f:
        json.dump(record, f, default=str)
    if run["stray"]:
        print(f"processes outlived the run and were killed: {run['stray']}",
              file=sys.stderr)
        return 1
    if args.rehearse:
        print(json.dumps(result), file=sys.stderr)
        return 3             # a rehearsal is never a result
    if run["device"]["platform"] != "tpu":
        return 1
    print(json.dumps(result), file=result_out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

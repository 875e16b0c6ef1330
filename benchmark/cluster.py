"""Cluster bring-up and tear-down for a run (adapted from chip_smoke.py,
which later PRs may change; this copy is the benchmark's).

The parent process never initialises a JAX backend: the replica or the train
worker holds the chip. Every process a run starts is stopped, waited for, and
checked to be gone.
"""

from __future__ import annotations

import os
import signal
import time
from typing import Dict, List

# The processes that can hold a chip: workers, and the agent that spawns them.
CLUSTER_MARKERS = ("ray_tpu.core.worker_main", "ray_tpu.core.node_agent",
                   "ray_tpu.core.controller")


def start(cpus: float, chips: int) -> int:
    """ray_tpu.init(); returns the TPU chips the node agent detected and
    fails if they are fewer than the cell needs."""
    import ray_tpu

    ray_tpu.init(resources={"CPU": float(cpus)})
    detected = int(ray_tpu.cluster_resources().get("TPU", 0))
    if detected < chips:
        raise RuntimeError(
            f"the node agent detected {detected} TPU chip(s); this cell needs "
            f"{chips}. The benchmark does not fall back to the CPU.")
    return detected


def _session_processes(session_dir: str) -> Dict[int, str]:
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


def stop(grace_s: float = 20.0) -> List[str]:
    """Shut serve and the cluster down, wait until the session's processes
    are gone, kill what outlives `grace_s`, and return those commands."""
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
        left = _session_processes(node.session_dir)
        if not left or time.monotonic() > deadline:
            break
        time.sleep(0.25)
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    return sorted(left.values())

"""chip_smoke.py off the chip: it refuses to run here, and its phases —
the same functions, at tiny widths on virtual CPU devices — run the same
control flow the chip run takes.

What only a chip can show (platform == "tpu", a `tpu_custom_call` in the
programs) is exactly the set of checks allowed to fail here.
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from ray_tpu import accelerators  # noqa: E402
from ray_tpu.utils.config import GlobalConfig  # noqa: E402

CHIP_ONLY = {"platform_is_tpu", "fwd_has_tpu_custom_call",
             "bwd_has_tpu_custom_call", "step_has_tpu_custom_call",
             "aligned_prefill_has_tpu_custom_call"}

TINY_MODEL = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=128, max_seq=128)
TINY_LLM = dict(d_model=256, vocab_size=512, max_seq=256, n_layers=2,
                num_tpus=1)


def _failed(record):
    return {name for name, ok in record["checks"].items() if not ok}


@pytest.fixture
def four_fake_chips(monkeypatch, tmp_path):
    """A host whose node agent "detects" four chips (the operator
    override of accelerators.num_tpu_chips), whose processes each see as
    many virtual CPU devices as the test says, and whose compile cache is
    where JAX_COMPILATION_CACHE_DIR points."""
    cache = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    before = GlobalConfig.tpu_chips_per_host
    GlobalConfig.initialize({"tpu_chips_per_host": 4})

    def devices_per_process(n):
        monkeypatch.setenv(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")

    yield devices_per_process, str(cache)
    GlobalConfig.initialize({"tpu_chips_per_host": before})


# ---------------------------------------------------------------------------
# (a) no CPU fallback
# ---------------------------------------------------------------------------

def test_smoke_refuses_to_run_without_a_chip():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    # It stopped at the probe, within seconds: no phase ran and no
    # cluster was started.
    assert {line.get("phase") for line in lines[:-1]} == {"start"}
    assert "ray_tpu.controller" not in proc.stderr
    assert time.monotonic() - t0 < 60


# ---------------------------------------------------------------------------
# (b) the phases' control flow, at tiny widths on virtual CPU devices
# ---------------------------------------------------------------------------

def test_kernels_phase_control_flow(four_fake_chips):
    record = chip_smoke.phase_kernels(shape=(1, 2, 128, 128), seed=0)
    assert _failed(record) <= CHIP_ONLY, record
    assert record["attention_paths"] == {"fwd_reference": 2,
                                         "bwd_reference": 1}
    assert max(record["rel_err"].values()) < chip_smoke.KERNEL_TOL


def test_train_phase_control_flow(four_fake_chips):
    devices_per_process, cache = four_fake_chips
    devices_per_process(1)
    record = chip_smoke.phase_train(model=TINY_MODEL, batch=2, seq=128,
                                    steps=3, seed=0)
    assert _failed(record) <= CHIP_ONLY, record
    assert record["device"]["visible_chips"] == "0"  # pinned by the agent
    assert record["layout"]["feed"] == "dataset" and len(
        record["losses"]) == 3
    # The env named a cache dir: it reached the worker untouched.
    assert record["cache"]["dir"] == cache
    assert record["stray_processes"] == []


def test_four_chip_train_layouts_match_one_chip(four_fake_chips):
    """(a) one worker x four devices and (b) four workers x one device
    joined by jax.distributed take the same seeded batches to the same
    losses as one device does."""
    devices_per_process, _ = four_fake_chips
    size = dict(model=TINY_MODEL, batch=4, seq=128, steps=3, seed=0,
                feed="seeded")
    devices_per_process(1)
    one = chip_smoke.phase_train(name="ref", **size)
    assert _failed(one) <= CHIP_ONLY, one
    devices_per_process(4)
    a = chip_smoke.phase_train(name="a", num_workers=1, chips_per_worker=4,
                               reference=one["losses"], **size)
    assert _failed(a) <= CHIP_ONLY, a
    assert a["layout"]["param_shard_device_ids"] == [0, 1, 2, 3]
    assert a["device"]["visible_chips"] is None  # the whole host
    devices_per_process(1)
    b = chip_smoke.phase_train(name="b", num_workers=4, chips_per_worker=1,
                               reference=one["losses"], **size)
    assert _failed(b) <= CHIP_ONLY, b
    assert b["device"]["count"] == 4 and b["device"]["local_count"] == 1
    assert b["device"]["process_bounds"] == "2,2,1"  # one topology
    assert max(a["max_loss_diff"], b["max_loss_diff"]) < 1e-3


@pytest.mark.parametrize("replicas", [1, 4])
def test_serve_phase_control_flow(four_fake_chips, replicas):
    devices_per_process, _ = four_fake_chips
    devices_per_process(1)
    record = chip_smoke.phase_serve(llm=TINY_LLM, prompt_lens=(16, 200),
                                    max_tokens=8, seed=0,
                                    num_replicas=replicas)
    assert _failed(record) <= CHIP_ONLY, record
    assert sorted(r["visible_chips"] for r in record["replicas"]) == \
        [str(i) for i in range(replicas)]
    assert all(len(t) == 8 for t in record["tokens"])
    assert ("reference_top3" in record) == (replicas == 1)
    assert record["stray_processes"] == []


# ---------------------------------------------------------------------------
# (c) the compile-cache rule
# ---------------------------------------------------------------------------

def test_compile_cache_env_set_is_left_alone():
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    assert accelerators.compile_cache_env(env) == "/somewhere/else"
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}


def test_compile_cache_default_is_fixed_and_in_the_checkout(tmp_path):
    fixed = os.path.join(REPO, ".jax_cache")
    env = {}
    assert accelerators.compile_cache_env(env) == fixed
    assert env == {"JAX_COMPILATION_CACHE_DIR": fixed}
    # Identical in two other processes, wherever they start from.
    code = ("import os; from ray_tpu import accelerators; env = {}; "
            "print(accelerators.compile_cache_env(env))")
    child_env = {k: v for k, v in os.environ.items()
                 if k != "JAX_COMPILATION_CACHE_DIR"}
    child_env["PYTHONPATH"] = REPO
    seen = {subprocess.run([sys.executable, "-c", code], cwd=cwd,
                           env=child_env, capture_output=True, text=True,
                           check=True).stdout.strip()
            for cwd in (REPO, str(tmp_path))}
    assert seen == {fixed}


def test_no_code_sets_the_cache_dir_through_jax_config():
    offenders = []
    # Not `benchmark/`: its int8 control (benchmark/control.py, a tool of
    # its own that no cell runs) sets the option, from the variable first.
    for root in ("ray_tpu", "chip_smoke.py"):
        path = os.path.join(REPO, root)
        files = ([path] if os.path.isfile(path) else
                 [os.path.join(d, f) for d, _, fs in os.walk(path)
                  for f in fs if f.endswith(".py")])
        offenders += [f for f in files
                      if "jax_compilation_cache_dir" in open(f).read()]
    assert offenders == []

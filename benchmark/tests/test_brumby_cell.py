"""The cell `serve-generate-brumby` (PR 59) as the harness finds it: its files
by name from a COPY of the manifest, the adapter's refusals, the parent's
failure in `run.py`'s own process, and its five new readers on a fixture
built by hand (each share under 100) and on traces recorded on the chip from
programs that have none of its scopes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_brumby_cell.py -q
"""

import importlib
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import models, program_trace, run  # noqa: E402
from benchmark.tests import test_benchmark as cases  # noqa: E402

CELL = "serve-generate-brumby"
CONFIG = "benchmark/configs/brumby-14b-base-serve.json"
NEW = ("decode_retention_ms", "retention_state_roofline_pct",
       "prefill_retention_ms_per_ktok", "retention_prefill_roofline_pct",
       "retention_decode_mfu_pct")
# A SUBSET of the cell's per-layer metrics, never a slice of the list: a later
# PR's reader that lists this cell comes after them.
READERS = set(NEW) | {"prefill_ms_per_ktok", "prefill_stall_pct",
                      "engine_slot_refill_ms", "decode_sample_ms"}


def test_the_cells_files_are_found_by_name_in_a_copy_of_the_manifest(
        tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    manifest = run.load_json(root, "BENCHMARK.json")
    cell = run.find_cell(manifest, CELL)
    assert cell["config_file"] == CONFIG
    config = run.load_json(root, cell["config_file"])
    mix = run.load_json(root, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(root, "benchmark", "drivers",
                                       mix["kind"] + ".py"))
    adapter = models.adapter(config["arch"])
    assert not [n for n in cases.CONTRACT if not hasattr(adapter, n)]
    for name in ("prefill_flops", "decode_step_ops_bytes", "total_params",
                 "decode_state_bytes", "retention_prompt_ops_bytes",
                 "retention_step_ops_bytes"):
        assert callable(getattr(adapter.counts, name)), name
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        mine = [m["name"] for m in run.metrics_of(manifest, group, CELL)]
        assert mine, group
        for name in mine:
            assert callable(run.load_reader(
                os.path.join(root, "benchmark"), folder, name))
    per_layer = {m["name"] for m in run.metrics_of(manifest, "per_layer",
                                                   CELL)}
    assert READERS <= per_layer
    e2e = [m["name"] for m in run.metrics_of(manifest, "end_to_end", CELL)]
    assert e2e == ["batch_tokens_per_s", "setup_s"]
    # importing the adapter imported neither jax's backend nor the program
    assert importlib.import_module("benchmark.models.brumby") is adapter


def test_the_adapter_takes_the_configuration_and_refuses_a_neighbour():
    adapter = models.adapter("brumby")
    config = run.load_json(ROOT, CONFIG)
    adapter.check_supported(config)
    adapter.check_supported(dict(config, **adapter.REHEARSE))
    for change, said in ((dict(tie_word_embeddings=True), "tied embeddings"),
                         (dict(sliding_window=4096), "sliding window"),
                         (dict(retention_degree=4), "degree"),
                         (dict(head_dim=None), "head_dim")):
        with pytest.raises(ValueError, match=said):
            adapter.check_supported(dict(config, **change))
    with pytest.raises(NotImplementedError, match="served, not trained"):
        adapter.reference().loss_and_check_grads(None, config, None)


def test_a_program_without_the_models_fields_is_refused_by_name(monkeypatch):
    """What the parent commit does under this PR's benchmark files:
    `build_config`, which the cell's driver calls in `run.py`'s own process
    before any cluster starts, names the fields `LlamaConfig` lacks."""
    import dataclasses

    from ray_tpu.models import llama
    adapter = models.adapter("brumby")
    config = run.load_json(ROOT, CONFIG)
    new = ("mixer", "retention_degree")
    older = dataclasses.make_dataclass("LlamaConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)
        if f.name not in new])
    monkeypatch.setattr(llama, "LlamaConfig", older)
    with pytest.raises(ValueError, match=".*".join(new)):
        adapter.build_config(config, config["dtypes"], 2048)


def test_the_five_readers_on_a_fixture_and_each_share_is_under_100(
        monkeypatch):
    """A prefill of 600 prompt tokens in 30 ms, 8 of them under `retention`,
    and a decode chunk of 8 steps of 48 slots in 224 ms, 176 under
    `retention`: the kernel's share of HBM's peak, the prompt operator's
    share of its least work and the whole step's share of the chip."""
    Span = program_trace.Span
    ms = 1_000_000
    dispatch = dict(useful=384, capacity=384, active=48, live_kv_tokens=0)
    spans = [Span("serve.engine.admit", 900, 950, dict(
                 rid=1, kind="prefill", prompt_tokens=600, bucket=1024,
                 queue_wait_us=1, decoding=0, slot_idle_us=0)),
             Span("serve.engine.emit", 31 * ms, 31 * ms + 10,
                  dict(rid=1, kind="first")),
             Span("serve.engine.decode_dispatch", 32 * ms, 32 * ms + 10,
                  dispatch)]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 1000 + 30 * ms),
               ("jit_decode", 40 * ms, 264 * ms),
               ("jit_poke", 300 * ms, 300 * ms + 10)]
    pre = "jit(prefill)/layers/while/body/"
    dec = "jit(decode)/while/body/layers/while/body/"
    ops = [(pre + "ret_in/qkv/dot_general:", 1000, 1000 + 5 * ms),
           (pre + "retention/jit(_state_pallas)/pallas_call:",
            1000 + 5 * ms, 1000 + 13 * ms),
           (pre + "mlp/dot_general:", 1000 + 13 * ms, 1000 + 30 * ms),
           (dec + "ret_in/qkv/dot_general:", 40 * ms, 48 * ms),
           (dec + "retention/jit(_step_pallas)/pallas_call:", 48 * ms,
            224 * ms),
           (dec + "mlp/dot_general:", 224 * ms, 264 * ms)]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    record = {"config": run.load_json(ROOT, CONFIG), "cell": CELL, "seed": 0,
              "trace_data": None, "device": {"kind": "TPU v5 lite"}}
    got = {name: run.load_reader(BENCH, "layer_metrics", name)(record)
           for name in NEW}
    assert got["decode_retention_ms"] == pytest.approx(22.0)
    assert got["prefill_retention_ms_per_ktok"] == pytest.approx(8 / 0.6)
    # 13.09 GB of state a step at 819 GB/s is 15.98 ms of the 22
    assert got["retention_state_roofline_pct"] == pytest.approx(72.6, abs=0.1)
    # 17.29 GB a step is 21.1 ms of the 28
    assert got["retention_decode_mfu_pct"] == pytest.approx(75.4, abs=0.1)
    assert all(0 < got[n] < 100 for n in NEW if n.endswith("_pct")), got


@pytest.mark.parametrize("fixture", ["tiny24.xplane.pb", "tiny.xplane.pb"])
def test_the_new_readers_on_a_trace_without_the_mixers_scopes(
        fixture, monkeypatch):
    """The traces recorded on the chip at PR 24 and PR 23: a dense model's
    programs, no retention scope. No new reader finds anything to read, and
    none raises: what the parent commit gives under this PR's files."""
    with open(os.path.join(HERE, fixture), "rb") as f:
        t = program_trace.parse(f.read())
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    for config in (CONFIG, "benchmark/configs/mistral-7b-v0.3-serve.json"):
        record = {"config": run.load_json(ROOT, config), "cell": "x",
                  "seed": 0, "trace_data": None,
                  "device": {"kind": "TPU v5 lite"}}
        for name in NEW:
            assert run.load_reader(BENCH, "layer_metrics", name)(record) \
                is None

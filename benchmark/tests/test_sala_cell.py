"""The cell `serve-longdoc-sala` (PR 65) as the harness finds it: its files by
name from a COPY of the manifest, and its nine new readers on traces recorded
on the chip from programs that have none of their scopes. (Its traffic, its
counts, the readers on a synthetic trace and its rehearsal through `run.py`
are tests/test_benchmark_adapters_minicpm_sala.py's.)

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_sala_cell.py -q
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

CELL = "serve-longdoc-sala"
READERS = ["prefill_linear_attn_ms_per_ktok", "decode_linear_attn_ms",
           "linear_prefill_roofline_pct", "linear_state_roofline_pct",
           "prefill_block_select_ms_per_ktok", "decode_block_sparse_attn_ms",
           "block_sparse_prefill_roofline_pct",
           "block_sparse_decode_roofline_pct", "selected_block_share_pct"]


def test_the_cells_files_are_found_by_name_in_a_copy_of_the_manifest(
        tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    manifest = run.load_json(root, "BENCHMARK.json")
    cell = run.find_cell(manifest, CELL)
    assert cell["config_file"] == "benchmark/configs/minicpm-sala-serve.json"
    config = run.load_json(root, cell["config_file"])
    mix = run.load_json(root, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(root, "benchmark", "drivers",
                                       mix["kind"] + ".py"))
    adapter = models.adapter(config["arch"])
    assert not [n for n in cases.CONTRACT if not hasattr(adapter, n)]
    assert not [n for n in cases.COUNTS
                if not callable(getattr(adapter.counts, n))]
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        mine = [m["name"] for m in run.metrics_of(manifest, group, CELL)]
        assert mine, group
        for name in mine:
            assert callable(run.load_reader(
                os.path.join(root, "benchmark"), folder, name))
    per_layer = [m["name"] for m in run.metrics_of(manifest, "per_layer",
                                                   CELL)]
    assert set(READERS) <= set(per_layer)
    e2e = [m["name"] for m in run.metrics_of(manifest, "end_to_end", CELL)]
    assert e2e == ["batch_tokens_per_s", "setup_s"]
    # importing the adapter imported neither jax's backend nor the program
    assert importlib.import_module("benchmark.models.minicpm_sala") is adapter
    assert "jax" not in getattr(adapter, "__dict__", {})


def test_a_program_that_cannot_say_the_model_is_refused_by_the_fields_names(
        monkeypatch):
    """The parent of PR 65 under this PR's benchmark files: `build_config`
    names the `LlamaConfig` fields that are missing and raises before any
    cluster starts (`drivers/serve_closed_checked.py` calls it in the parent
    process), so the cell fails there in a second with exit code 1."""
    import dataclasses

    from ray_tpu.models import llama
    adapter = models.adapter("minicpm_sala")
    config = run.load_json(BENCH, "configs", "minicpm-sala-serve.json")
    kept = [f for f in dataclasses.fields(llama.LlamaConfig)
            if f.name not in ("mixer_types", "dense_len")]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: kept)
    with pytest.raises(ValueError, match="needs LlamaConfig fields "
                       r"\['mixer_types', 'dense_len'\]"):
        adapter.build_config(config, config["dtypes"], 12288)


@pytest.mark.parametrize("fixture", ["tiny24.xplane.pb", "tiny.xplane.pb"])
def test_every_new_reader_is_silent_on_a_trace_without_its_scopes(
        fixture, monkeypatch):
    """The traces recorded on the chip at PR 24 and PR 23: a dense model's
    programs, no `linear_attn`, no `block_select`."""
    with open(os.path.join(HERE, fixture), "rb") as f:
        t = program_trace.parse(f.read())
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    config = run.load_json(BENCH, "configs", "minicpm-sala-serve.json")
    record = {"config": config, "cell": "x", "seed": 0, "trace_data": None,
              "device": {"kind": "TPU v5 lite"}}
    for name in READERS:
        assert run.load_reader(BENCH, "layer_metrics", name)(record) is None, \
            name

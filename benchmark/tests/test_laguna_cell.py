"""The cell `serve-longdoc-laguna` (PR 62) as the harness finds it: its files
by name from a COPY of the manifest, the traffic's parameters as ISSUE 62
gives them, and its two new readers on a trace recorded on the chip from a
program that has none of their scopes. (Its rehearsal through `run.py` is
tests/test_benchmark_adapters_laguna.py's.)

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_laguna_cell.py -q
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

CELL = "serve-longdoc-laguna"
READERS = ["prefill_attn_gate_ms_per_ktok", "decode_attn_gate_ms"]


def test_the_cells_files_are_found_by_name_in_a_copy_of_the_manifest(
        tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    manifest = run.load_json(root, "BENCHMARK.json")
    cell = run.find_cell(manifest, CELL)
    assert cell["config_file"] == "benchmark/configs/laguna-s-2.1-serve.json"
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
    assert per_layer[-2:] == READERS and len(per_layer) == 29
    e2e = [m["name"] for m in run.metrics_of(manifest, "end_to_end", CELL)]
    assert e2e == ["batch_tokens_per_s", "setup_s"]
    # importing the adapter imported neither jax's backend nor the program
    assert importlib.import_module("benchmark.models.laguna") is adapter


def test_the_traffic_is_the_issues():
    mix = run.load_json(BENCH, "traffic", "longdoc-qa-laguna.json")
    assert mix["kind"] == "serve_closed_checked"
    assert mix["arrivals"] == {"process": "closed", "clients": 64,
                               "pool_per_client_second": 0.25}
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 6144,
                                    "max": 8064}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 64, "max": 128}
    assert mix["trace"] == {"start_s": 8, "seconds": 4}
    chk = mix["check"]
    assert chk["prompt_lengths"][:2] == [8000, 7000]
    assert chk["prompt_lengths"][2:] == [600] * 62 and chk["tokens"] == 32
    assert 0 < chk["mean_logit_tolerance"] < chk["logit_tolerance"]
    config = run.load_json(BENCH, "configs", "laguna-s-2.1-serve.json")
    eng = config["deployment"]["engine"]
    assert eng["n_slots"] == 32 and eng["max_seq"] == 8192
    assert max(chk["prompt_lengths"]) + chk["tokens"] <= eng["max_seq"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= eng["max_seq"]


@pytest.mark.parametrize("fixture", ["tiny24.xplane.pb", "tiny.xplane.pb"])
def test_every_new_reader_is_silent_on_a_trace_without_its_scopes(
        fixture, monkeypatch):
    """The traces recorded on the chip at PR 24 and PR 23: a dense model's
    programs, no `attn_gate`, no `window_attn`."""
    with open(os.path.join(HERE, fixture), "rb") as f:
        t = program_trace.parse(f.read())
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    config = run.load_json(BENCH, "configs", "laguna-s-2.1-serve.json")
    record = {"config": config, "cell": "x", "seed": 0, "trace_data": None,
              "device": {"kind": "TPU v5 lite"}}
    for name in READERS:
        assert run.load_reader(BENCH, "layer_metrics", name)(record) is None, \
            name

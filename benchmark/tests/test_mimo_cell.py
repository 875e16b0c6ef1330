"""The cell `serve-longdoc-mimo-v2` (PR 42) as the harness finds it: its
files by name from a COPY of the manifest, a rehearsal at the adapter's
`REHEARSE` widths through `run.py`, and its nine readers on a trace recorded
on the chip from a program that has none of their scopes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mimo_cell.py -q
"""

import importlib
import json
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

CELL = "serve-longdoc-mimo-v2"
READERS = ["prefill_window_attn_ms_per_ktok", "prefill_full_attn_ms_per_ktok",
           "decode_window_attn_ms", "decode_full_attn_ms",
           "window_prefill_roofline_pct", "window_decode_roofline_pct",
           "full_prefill_attn_roofline_pct", "full_decode_attn_roofline_pct",
           "window_kv_share_pct"]


def test_the_cells_files_are_found_by_name_in_a_copy_of_the_manifest(
        tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    manifest = run.load_json(root, "BENCHMARK.json")
    cell = run.find_cell(manifest, CELL)
    assert cell["config_file"] == "benchmark/configs/mimo-v2-flash-serve.json"
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
    assert per_layer[-9:] == READERS
    e2e = [m["name"] for m in run.metrics_of(manifest, "end_to_end", CELL)]
    assert e2e == ["batch_tokens_per_s", "setup_s"]
    # importing the adapter imported neither jax's backend nor the program
    assert importlib.import_module("benchmark.models.mimo") is adapter


def test_the_cell_rehearses_through_the_adapters_widths():
    """`run.py --rehearse`: the adapter's `REHEARSE` over the configuration,
    `rehearse.json`'s engine, the whole control flow on the CPU. The prompts
    (192-252 at the rehearsal's scale) are longer than its `max_seq` of 128,
    as `serve-longdoc-keye`'s are, so requests come back short and the line
    reads `correct` false (PERF.md section 7): what is asked here is that the
    run reaches its end, checks 64 prompts and reports."""
    result = cases._rehearse(ROOT, CELL, 0, "4")
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
    rec = json.load(open(os.path.join(
        BENCH, "out", CELL, "2147483999", "run-trace0.json")))
    assert rec["config"]["hidden_size"] == 64           # REHEARSE's
    assert rec["config"]["hybrid_layer_pattern"] == [0, 1, 1, 0]
    assert len(rec["check"]["prompt_lengths"]) == 64
    assert rec["replica"]["attention_paths"].get("window_decode_reference")


@pytest.mark.parametrize("fixture", ["tiny24.xplane.pb", "tiny.xplane.pb"])
def test_every_new_reader_is_silent_on_a_trace_without_its_scopes(
        fixture, monkeypatch):
    """The traces recorded on the chip at PR 24 and PR 23: a dense model's
    programs, spans without `window_kv_tokens`, no `window_attn`."""
    with open(os.path.join(HERE, fixture), "rb") as f:
        t = program_trace.parse(f.read())
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    config = run.load_json(BENCH, "configs", "mimo-v2-flash-serve.json")
    record = {"config": config, "cell": "x", "seed": 0, "trace_data": None,
              "device": {"kind": "TPU v5 lite"}}
    for name in READERS:
        assert run.load_reader(BENCH, "layer_metrics", name)(record) is None, \
            name

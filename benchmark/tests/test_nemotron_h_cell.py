"""The cell `serve-batch-nemotron3` (PR 55) as the harness finds it: its files
by name from a COPY of the manifest, the adapter's refusals, the parent's
failure in `run.py`'s own process, a rehearsal at the adapter's `REHEARSE`
widths through `run.py`, and its new readers on traces recorded on the chip
from programs that have none of its scopes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_nemotron_h_cell.py -q
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

CELL = "serve-batch-nemotron3"
CONFIG = "benchmark/configs/nemotron-3-nano-30b-a3b-serve.json"
# A SUBSET of the cell's per-layer metrics, never a slice of the list: a later
# PR's reader that lists this cell comes after them (PERF.md section 7).
READERS = {"prefill_mfu_pct", "prefill_attn_ms_per_ktok",
           "prefill_ms_per_ktok", "prefill_ssm_ms_per_ktok",
           "prefill_moe_ms_per_ktok", "scan_roofline_pct", "decode_ssm_ms",
           "decode_moe_ms", "decode_state_roofline_pct", "decode_mfu_pct",
           "moe_share_experts_roofline_pct", "local_assignment_share_pct",
           "expert_load_max_over_mean", "kv_pages_peak_pct"}


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
    assert not [n for n in cases.COUNTS
                if not callable(getattr(adapter.counts, n))]
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
    assert importlib.import_module("benchmark.models.nemotron_h") is adapter


def test_the_adapter_takes_the_configuration_and_refuses_a_neighbour():
    adapter = models.adapter("nemotron_h")
    config = run.load_json(ROOT, CONFIG)
    adapter.check_supported(config)
    adapter.check_supported(dict(config, **adapter.REHEARSE))
    for change, said in ((dict(hybrid_override_pattern="MEMEM*EMEMEM-EME"),
                          "a letter other than"),
                         (dict(n_group=8, topk_group=0), "group limit"),
                         (dict(time_step_limit=[0.0, 0.1]), "clamp"),
                         (dict(tie_word_embeddings=True), "tied head")):
        with pytest.raises(ValueError, match=said):
            adapter.check_supported(dict(config, **change))
    with pytest.raises(NotImplementedError, match="serves only"):
        adapter.reference().loss_and_check_grads(None, config, None)


def test_a_program_without_the_models_fields_is_refused_by_name(monkeypatch):
    """What the parent commit does under this PR's benchmark files:
    `build_config`, which the cell's driver calls in `run.py`'s own process
    before any cluster starts, names the fields `LlamaConfig` lacks."""
    import dataclasses

    from ray_tpu.models import llama
    adapter = models.adapter("nemotron_h")
    config = run.load_json(ROOT, CONFIG)
    new = ("layer_parts", "ssm_groups", "ssm_head_dim", "ffn")
    older = dataclasses.make_dataclass("LlamaConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)
        if f.name not in new])
    monkeypatch.setattr(llama, "LlamaConfig", older)
    with pytest.raises(ValueError, match=".*".join(new)):
        adapter.build_config(config, config["dtypes"], 4096)


def test_the_cell_rehearses_through_the_adapters_widths():
    """`run.py --rehearse`, end to end and traced: the run reaches its end
    (exit 3) and the line holds what a CPU trace can give of this cell's
    readers: the counters'."""
    result = cases._rehearse(ROOT, CELL, 1, "6")
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    rec = json.load(open(os.path.join(
        BENCH, "out", CELL, "2147483999", "run-trace1.json")))
    assert rec["config"]["hidden_size"] == 96           # REHEARSE's
    assert rec["replica"]["attention_paths"].get("ssd_chunked")
    assert rec["replica"]["inflight_peak"] > 8


@pytest.mark.parametrize("fixture", ["tiny24.xplane.pb", "tiny.xplane.pb"])
def test_the_new_readers_on_a_trace_without_the_mixers_scopes(
        fixture, monkeypatch):
    """The traces recorded on the chip at PR 24 and PR 23: a dense model's
    programs, no state-space scope, an adapter whose counts have no
    `layers`. Neither new reader finds anything to read, and neither
    raises."""
    with open(os.path.join(HERE, fixture), "rb") as f:
        t = program_trace.parse(f.read())
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    dense = run.load_json(ROOT, "benchmark/configs/mistral-7b-v0.3-serve.json")
    record = {"config": dense, "cell": "x", "seed": 0, "trace_data": None,
              "device": {"kind": "TPU v5 lite"}}
    for name in ("prefill_mfu_pct", "prefill_attn_ms_per_ktok"):
        assert run.load_reader(BENCH, "layer_metrics", name)(record) is None

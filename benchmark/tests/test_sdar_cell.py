"""The cell `serve-generate-sdar` (PR 53) as the harness finds it: its files
by name from a COPY of the manifest, the adapter's refusals, what the parent
commit does under this PR's benchmark files, and its five new readers on a
trace built by hand (and their silence on a trace recorded on the chip from a
program that dispatches no blocks).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_sdar_cell.py -q
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

from benchmark import models, peaks, program_trace, run  # noqa: E402
from benchmark.tests import test_benchmark as cases  # noqa: E402

CELL = "serve-generate-sdar"
CONFIG = "benchmark/configs/sdar-30b-a3b-chat-serve.json"
NEW = ["denoise_forwards_per_token", "decode_forward_ms", "decode_unmask_ms",
       "block_decode_attn_roofline_pct", "block_prefill_attn_roofline_pct"]
# A SUBSET of the cell's per-layer metrics, never a slice of the list: a later
# PR's reader that lists this cell comes after them.
READERS = set(NEW) | {"decode_moe_ms", "expert_load_max_over_mean",
                      "decode_sample_ms", "kv_pages_peak_pct"}


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
    per_layer = {m["name"]: m for m in run.metrics_of(manifest, "per_layer",
                                                      CELL)}
    assert READERS <= set(per_layer)
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL] \
            and per_layer[name]["moves"] == "batch_tokens_per_s"
    e2e = [m["name"] for m in run.metrics_of(manifest, "end_to_end", CELL)]
    assert e2e == ["batch_tokens_per_s", "setup_s"]
    # importing the adapter imported neither jax's backend nor the program
    assert importlib.import_module("benchmark.models.sdar") is adapter


def test_a_program_without_the_models_fields_is_refused_by_name(monkeypatch):
    """What the parent commit does under this PR's benchmark files:
    `build_config`, which the cell's driver calls in `run.py`'s own process
    before any cluster starts, names the fields `LlamaConfig` lacks."""
    import dataclasses

    from ray_tpu.models import llama
    adapter = models.adapter("sdar")
    config = run.load_json(ROOT, CONFIG)
    older = dataclasses.make_dataclass("LlamaConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)
        if f.name not in ("block_length", "denoise_steps", "mask_id")])
    monkeypatch.setattr(llama, "LlamaConfig", older)
    with pytest.raises(ValueError, match="block_length.*denoise_steps.*"
                                         "mask_id"):
        adapter.build_config(config, config["dtypes"], 2048)
    with pytest.raises(NotImplementedError, match="serves only"):
        adapter.reference().loss_and_check_grads(None, config, None)


def _trace():
    """A prefill of 1,003 prompt tokens (1,000 kept) and two decode chunks of
    two blocks, 6 forwards each, under the block step's scopes."""
    Span = program_trace.Span
    dispatch = dict(useful=500, capacity=512, active=64,
                    live_kv_tokens=64000, experts_touched=6 * 6 * 100,
                    expert_tokens="1:2", blocks=2, forwards=6, rows=256,
                    committed=512)
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=1003, bucket=1024,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.emit", 2100, 2110, dict(rid=7, kind="first")),
        Span("serve.engine.decode_dispatch", 2200, 2210, dispatch),
        Span("serve.engine.decode_dispatch", 3200, 3210,
             dict(dispatch, useful=510, expert_tokens="3:6")),
    ]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 2300, 3500), ("jit_decode", 3600, 4800),
               ("jit_poke", 5000, 5010)]
    pre = "jit(prefill)/layers/while/body/"
    ops = [(pre + "attn/block_flash_fwd:", 1000, 1300),
           (pre + "mlp/experts/pallas_call:", 1300, 2000)]
    for base in (2300, 3600):
        dec = "jit(decode)/while/body/"
        ops += [(dec + "layers/while/body/attn/paged_decode:", base,
                 base + 200),
                (dec + "layers/while/body/mlp/experts/pallas_call:",
                 base + 200, base + 700),
                (dec + "head/dot_general:", base + 700, base + 800),
                # the sampler's conditional encloses its branch
                (dec + "unmask/sample/cond:", base + 800, base + 860),
                (dec + "unmask/sample/branch/argmax:", base + 810,
                 base + 850),
                (dec + "unmask/reduce_max:", base + 860, base + 900),
                (dec + "commit/layers/while/body/attn/paged_decode:",
                 base + 900, base + 1000)]
    return program_trace.ProgramTrace(spans, modules, sorted(
        ops, key=lambda o: (o[1], -o[2])))


def test_the_new_readers_on_a_trace_built_by_hand(monkeypatch):
    t = _trace()
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    m = run.load_json(ROOT, CONFIG)
    record = {"config": m, "cell": "x", "seed": 0, "trace_data": None,
              "device": {"kind": "TPU v5 lite"}}
    got = {name: run.load_reader(BENCH, "layer_metrics", name)(record)
           for name in NEW}
    counts = models.adapter("sdar").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")

    def least(ops_bytes):
        return max(ops_bytes[0] / f, ops_bytes[1] / b)

    assert got["denoise_forwards_per_token"] == pytest.approx(
        2 * 6 * 64 / 1010)
    assert got["decode_forward_ms"] == pytest.approx(1200 / 1e6 / 6)
    # the `unmask` scope with what lies under it (the sampler), a chunk's
    # four denoising forwards
    assert got["decode_unmask_ms"] == pytest.approx(100 / 1e6 / 4)
    layers = m["num_hidden_layers"]
    want = layers * 3 * sum(
        least(counts.decode_attn_ops_bytes(m, 64000 + 64 * 4 * (j + 1), 64,
                                           2)) for j in range(2))
    assert got["block_decode_attn_roofline_pct"] == pytest.approx(
        100 * want / 300e-9)
    assert got["block_prefill_attn_roofline_pct"] == pytest.approx(
        100 * layers * least(counts.prefill_attn_ops_bytes(m, 1003, 2))
        / 300e-9)
    # the kept rows are the prompt's whole blocks
    assert counts.kept_rows(m, 1003) == 1000
    # an execution at the head of the trace whose request left no span there
    # (the device's line begins before the host's) shifts no pair: the
    # block step's pairing goes by the emitter's spans, not by position
    from benchmark import block_trace
    head = program_trace.ProgramTrace(
        t.spans, [("jit_prefill", 100, 800)] + t.modules, t.ops)
    assert [(a.args["rid"], r[1]) for a, r in block_trace.prefills(head)] \
        == [(7, 1000)]
    assert not head.prefills()          # by position: dropped, or wrong
    # spans without the block's counters (another stack's, the parent's)
    bare = program_trace.ProgramTrace(
        [program_trace.Span(s.name, s.start, s.end, {
            k: v for k, v in s.args.items()
            if k not in ("blocks", "forwards", "rows", "committed")})
         for s in t.spans], t.modules, t.ops)
    monkeypatch.setattr(program_trace, "load", lambda run: bare)
    for name in NEW[:4]:
        assert run.load_reader(BENCH, "layer_metrics", name)(record) is None
    # a model that yields a token a step reads nothing from any of them
    other = run.load_json(ROOT, "benchmark/configs/lfm2-24b-a2b-serve.json")
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    for name in NEW:
        assert run.load_reader(BENCH, "layer_metrics", name)(
            dict(record, config=other)) is None
    monkeypatch.setattr(program_trace, "load", lambda run: None)
    for name in NEW:
        assert run.load_reader(BENCH, "layer_metrics", name)(record) is None


@pytest.mark.parametrize("fixture", ["tiny24.xplane.pb", "tiny.xplane.pb"])
def test_the_new_readers_are_silent_on_a_trace_without_blocks(
        fixture, monkeypatch):
    """The traces recorded on the chip at PR 24 and PR 23: a dense model's
    programs, a token a step."""
    with open(os.path.join(HERE, fixture), "rb") as f:
        t = program_trace.parse(f.read())
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    config = run.load_json(ROOT, CONFIG)
    record = {"config": config, "cell": "x", "seed": 0, "trace_data": None,
              "device": {"kind": "TPU v5 lite"}}
    for name in NEW[:4]:
        assert run.load_reader(BENCH, "layer_metrics", name)(record) is None

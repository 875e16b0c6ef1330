"""The adapter `laguna` (benchmark/models/laguna.py) as tests/
test_benchmark_adapters.py sees the others, and what PR 62 adds beside it;
a file of its own so that its `run.py --rehearse` subprocess, the minute of
the file, runs beside the other families' on another worker. The block
against its reference is tests/test_laguna.py.
"""

import pytest

from benchmark import models, program_trace
from test_benchmark_adapters import ROOT, _reader, cases, rehearse

CELL = "serve-longdoc-laguna"
CONFIG = "laguna-s-2.1-serve"
GATE_READERS = ["prefill_attn_gate_ms_per_ktok", "decode_attn_gate_ms"]
WINDOW_READERS = ["prefill_window_attn_ms_per_ktok",
                  "prefill_full_attn_ms_per_ktok", "decode_window_attn_ms",
                  "decode_full_attn_ms", "window_prefill_roofline_pct",
                  "window_decode_roofline_pct",
                  "full_prefill_attn_roofline_pct",
                  "full_decode_attn_roofline_pct", "window_kv_share_pct"]


def test_adapter_exposes_the_whole_contract():
    cases.test_adapter_exposes_the_whole_contract("laguna", None)
    counts = models.adapter("laguna").counts
    for name in ("attention_layers", "prefill_attn_ops_bytes",
                 "decode_attn_bytes", "experts_ops_bytes", "expected_local",
                 "layers"):
        assert callable(getattr(counts, name)), name


def test_manifest_entries_are_the_catalogs_row_and_the_issues_traffic():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    # (PR 65 appended its configuration, its cell and nine readers after
    # this PR's: nothing of these moved)
    entry = manifest["configs"][12]
    assert entry["name"] == CONFIG
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] and cfg["arch"] == "laguna"
    assert entry["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert len(cfg["assumed"]) >= 10 and cfg["expert_parallel"]["chips"] == 8
    cell = manifest["workloads"][13]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "longdoc-qa-laguna", 1)
    assert "1/8 of its load" in cell["why"] and len(manifest["workloads"]) == 15
    mix = cases.load(cases.BENCH, "traffic", "longdoc-qa-laguna.json")
    mimo = cases.load(cases.BENCH, "traffic", "longdoc-qa-mimo-v2.json")
    assert mix["kind"] == "serve_closed_checked"
    assert mix["arrivals"] == mimo["arrivals"] == {
        "process": "closed", "clients": 64, "pool_per_client_second": 0.25}
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 6144,
                                    "max": 8064}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 64, "max": 128}
    assert mix["shape_seed"] not in (mimo["shape_seed"], 4201)
    assert mix["trace"] == {"start_s": 8, "seconds": 4}
    chk = mix["check"]
    assert chk["prompt_lengths"] == [8000, 7000] + [600] * 62 \
        and chk["tokens"] == 32
    # every checked stream is longer than the window and wraps its ring
    assert min(chk["prompt_lengths"]) > cfg["sliding_window"]
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    assert list(lists)[-11:-9] == GATE_READERS
    assert all(lists[n] == [CELL] for n in GATE_READERS)
    for name in WINDOW_READERS + [
            "prefill_ms_per_ktok", "kv_pages_peak_pct", "decode_moe_ms",
            "prefill_moe_ms_per_ktok", "expert_load_max_over_mean",
            "engine_slot_refill_ms", "prefill_stall_pct", "decode_sample_ms",
            "moe_share_experts_roofline_pct", "local_assignment_share_pct",
            "prefill_mfu_pct", "setup_boot_s", "setup_warm_s",
            "setup_compile_s", "setup_check_s", "decode_occupancy_window_pct",
            "engine_slot_refill_window_ms", "engine_window_tokens_per_s"]:
        assert CELL in lists[name][-2:], name
    # the whole decode step's share reads another stack's scopes
    # (benchmark/conv_trace.py): not this adapter's to serve (PERF.md, 7)
    assert CELL not in lists["decode_mfu_pct"]
    e2e = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert e2e["batch_tokens_per_s"][-2] == CELL


def test_the_counts_are_the_live_pairs_at_each_kinds_heads():
    """A window layer's pairs `sum_i min(i + 1, 512)` at 72 heads, a full
    layer's the causal triangle at 48, 4 x 128 operations a pair; a decode
    step reads 8 kv heads of 2 x 128 numbers a cached row in either kind;
    a prompt's `local` is its count over the sparse layers."""
    counts = models.adapter("laguna").counts
    m = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    assert (counts.heads(m, True), counts.heads(m, False)) == (72, 48)
    s = 7000
    ops, byts = counts.prefill_attn_ops_bytes(m, s, True, 2)
    assert ops == 4 * 128 * 72 * (512 * 513 / 2 + (s - 512) * 512)
    assert byts == s * 2 * 128 * (72 + 8) * 2
    ops, byts = counts.prefill_attn_ops_bytes(m, s, False, 2)
    assert ops == 4 * 128 * 48 * s * (s + 1) / 2
    assert byts == s * 2 * 128 * (48 + 8) * 2
    assert counts.decode_attn_bytes(m, 1000, True, 2) \
        == counts.decode_attn_bytes(m, 1000, False, 2) == 1000 * 8 * 256 * 2
    even = counts.prefill_flops(m, s)
    assert counts.prefill_flops(m, s, local=1.25 * 7 * s) \
        == pytest.approx(even)
    assert counts.prefill_flops(m, s, local=2.5 * 7 * s) - even \
        == pytest.approx(2 * 1.25 * 7 * s * 3 * 3072 * 1024)
    # 1.76 GFLOP a prompt token at the cell's lengths (ISSUE 62's reckoning)
    assert 1.7e9 < even / s < 1.8e9
    ops, byts = counts.decode_step_ops_bytes(m, [7000] * 32, 2, 2,
                                             experts_touched=32)
    assert byts > 2 * counts.total_params(m) * 0.95
    with pytest.raises(NotImplementedError, match="serves only"):
        counts.train_flops_per_token(m, 8)


def test_the_gates_readers_on_a_synthetic_trace(monkeypatch):
    """The two readers PR 62 adds on a trace built by hand: the time under
    `attn_gate`, inside `attn`, is theirs and no longer `attn`'s; the window
    readers beside them read what they read; a trace without the
    mixed stack's scopes (the parent, every other model) reads None and
    raises nothing."""
    from benchmark import gate_trace, window_trace
    Span = program_trace.Span
    dispatch = dict(useful=16, capacity=16, active=2, live_kv_tokens=14000,
                    window_kv_tokens=1024)
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=7000, bucket=7168,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.emit", 2100, 2110, dict(rid=7, kind="first")),
        Span("serve.engine.decode_dispatch", 2200, 2210, dispatch),
        Span("serve.engine.decode_dispatch", 3200, 3210, dispatch)]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 2300, 3000), ("jit_poke", 4000, 4010)]
    pre = "jit(prefill)/layers/while/body/"
    dec = "jit(decode)/while/body/layers/while/body/"

    def ops(gate):
        return [(pre + "qkv/dot_general:", 1000, 1200),
                (pre + "attn/window_attn/pallas_call:", 1200, 1300),
                (pre + "attn/full_attn/pallas_call:", 1300, 1700),
                (pre + f"attn/{gate}mul:", 1700, 1770),
                (pre + "mlp/experts/ragged_dot:", 1770, 2000),
                (dec + "window_write/select_n:", 2300, 2340),
                (dec + "attn/window_attn/dot_general:", 2340, 2400),
                (dec + "attn/full_attn/pallas_call:", 2450, 2700),
                (dec + f"attn_out/{gate}mul:", 2700, 2720),
                (dec + "mlp/experts/ragged_dot:", 2720, 3000)]

    m = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    m["deployment"]["engine"]["decode_chunk"] = 2
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": None,
           "device": {"kind": "TPU v5 lite"}}
    t = program_trace.ProgramTrace(spans, modules, ops("attn_gate/"))
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    assert _reader("prefill_attn_gate_ms_per_ktok")(run) \
        == pytest.approx(70 / 1e6 / 7.0)
    assert _reader("decode_attn_gate_ms")(run) == pytest.approx(20 / 1e6 / 2)
    assert _reader("prefill_window_attn_ms_per_ktok")(run) \
        == pytest.approx(100 / 1e6 / 7.0)
    assert 0 < _reader("window_prefill_roofline_pct")(run)
    assert window_trace.VOCABULARY + ("attn_gate",) == gate_trace.VOCABULARY
    assert "attn_gate" not in window_trace.VOCABULARY     # put back
    # a mixed stack's program with nothing under the gate's scope reads 0
    t = program_trace.ProgramTrace(spans, modules, ops(""))
    assert [_reader(n)(run) for n in GATE_READERS] == [0.0, 0.0]
    t = program_trace.ProgramTrace([], [], [])
    assert [_reader(n)(run) for n in GATE_READERS] == [None, None]


def test_the_programs_name_the_gates_scope_and_the_kinds(monkeypatch):
    """`attn_gate` in both programs' lowered text beside `window_attn`,
    `full_attn`, `attn_out`, `experts` and `shared_expert`; the dispatch span
    keeps the per-kind arguments MiMo's stack gives."""
    import jax

    from ray_tpu.models import serving
    from ray_tpu.serve import engine as engine_mod
    from ray_tpu.serve.engine import Engine
    adapter, _, cfg, params = _tiny()
    eng = Engine(jax.tree.map(lambda x: x, params), cfg, n_slots=2,
                 decode_chunk=2, page_size=16)
    try:
        for text in (
                eng._programs.prefill.lower(*eng.prefill_shapes(32)).as_text(
                    debug_info=True),
                eng._programs.decode.lower(*eng.decode_shapes()).as_text(
                    debug_info=True)):
            for scope in ("attn_gate", "window_attn", "full_attn",
                          "attn_out", "experts", "shared_expert", "router"):
                assert f"{scope}/" in text, scope
        assert set(eng.counters()) >= {"window_kv_tokens", "live_kv_tokens",
                                       "window_cache_bytes",
                                       "full_cache_bytes",
                                       "local_assignments"}
    finally:
        eng.stop()
    src = open(engine_mod.__file__).read() + open(serving.__file__).read()
    assert "window_kv_tokens" in src


def _tiny():
    import json
    import os
    adapter = models.adapter("laguna")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        model = dict(json.load(f), **adapter.REHEARSE)
    cfg = adapter.build_config(model, {"params": "float32",
                                       "activations": "float32"}, 128)
    return adapter, model, cfg, adapter.init_params(cfg, 3)


@pytest.mark.timeout(630)
def test_the_laguna_cell_rehearses_through_run_py():
    """`run.py --rehearse`: the adapter's `REHEARSE` over the configuration,
    `rehearse.json`'s engine, the whole control flow on the CPU through the
    cluster, the proxy and the engine. The prompts (192-252 at the
    rehearsal's scale) are longer than its `max_seq` of 128, as
    `serve-longdoc-mimo-v2`'s are, so requests come back short and the line
    reads `correct` false: what is asked here is that the run reaches its
    end, checks 64 prompts through both caches and reports."""
    result, rec = rehearse(CELL)
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
    assert rec["config"]["hidden_size"] == 64           # REHEARSE's
    assert rec["config"]["num_attention_heads_per_layer"] == [4, 6, 6, 4, 6,
                                                              6]
    assert len(rec["check"]["prompt_lengths"]) == 64
    # bfloat16 at tiny widths against the float32 reference
    assert rec["check"]["mean_gap"] < 0.01
    paths = rec["replica"]["attention_paths"]
    assert paths.get("window_decode_reference") \
        and paths.get("window_fwd_reference") \
        and paths.get("full_fwd_reference")

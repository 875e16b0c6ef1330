"""The adapter `minicpm_sala` (benchmark/models/minicpm_sala.py) as tests/
test_benchmark_adapters.py sees the others, and what PR 65 adds beside it; a
file of its own so that its `run.py --rehearse` subprocess, the minute of the
file, runs beside the other families' on another worker. The block against
its reference is tests/test_minicpm_sala.py.
"""

import pytest

from benchmark import models, program_trace
from test_benchmark_adapters import (ROOT, TIMELINE_READERS_OF_A_BATCH_CELL,
                                     _reader, cases, rehearse)

CELL = "serve-longdoc-sala"
CONFIG = "minicpm-sala-serve"
NEW = ["prefill_linear_attn_ms_per_ktok", "decode_linear_attn_ms",
       "linear_prefill_roofline_pct", "linear_state_roofline_pct",
       "prefill_block_select_ms_per_ktok", "decode_block_sparse_attn_ms",
       "block_sparse_prefill_roofline_pct",
       "block_sparse_decode_roofline_pct", "selected_block_share_pct"]
SERVED = ["prefill_ms_per_ktok", "prefill_mfu_pct", "kv_pages_peak_pct",
          "prefill_stall_pct", "engine_slot_refill_ms", "decode_sample_ms"]
# other stacks' scopes and counters: not this adapter's to serve
NOT = ["decode_mfu_pct", "prefill_attn_gate_ms_per_ktok",
       "decode_attn_gate_ms", "selected_share_pct", "decode_retention_ms",
       "retention_state_roofline_pct", "decode_moe_ms",
       "decode_attn_roofline_pct", "decode_rider_share_pct"]


def test_adapter_exposes_the_whole_contract():
    cases.test_adapter_exposes_the_whole_contract("minicpm_sala", None)
    counts = models.adapter("minicpm_sala").counts
    for name in ("layers", "mixer_layers", "linear_prompt_ops_bytes",
                 "linear_step_ops_bytes", "decode_state_bytes",
                 "block_sparse_prompt_ops_bytes", "block_select_ops_bytes",
                 "block_sparse_decode_ops_bytes"):
        assert callable(getattr(counts, name)), name


def test_manifest_entries_are_the_catalogs_row_and_the_issues_cell():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = manifest["configs"][-1]
    assert entry["name"] == CONFIG
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] \
        and cfg["arch"] == "minicpm_sala"
    assert entry["reduced"] == list(cfg["reduced"]) == ["num_hidden_layers"]
    assert len(entry["why"]) <= 200
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "longdoc-qa-sala", 1)
    assert "64 clients on 32 slots" in cell["why"] \
        and "dense_len" in cell["why"] and len(cell["why"]) <= 200
    # fifteen cells of 24, thirteen configurations' models in them
    assert len(manifest["workloads"]) == 15 and len(manifest["configs"]) == 14
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    e2e = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert e2e["batch_tokens_per_s"][-1] == CELL
    assert list(lists)[-9:] == NEW
    for name in NEW:
        new = next(p for p in manifest["per_layer"] if p["name"] == name)
        assert (new["moves"], new["workloads"]) == ("batch_tokens_per_s",
                                                    [CELL])
        assert new["layer"] in ("kernel", "model step (decode)",
                                "model step (prefill)", "scheduler (serve)")
        assert (new["unit"] == "%") == ("pct" in name)
        assert (new["source"] == "program_counter") == ("share" in name)
    mine = [n for n, cells in lists.items() if CELL in cells]
    assert set(mine) == set(SERVED) | set(NEW) \
        | TIMELINE_READERS_OF_A_BATCH_CELL
    assert all(lists[n][-1] == CELL for n in mine)
    assert not [n for n in NOT if CELL in lists[n]]


def test_the_traffic_is_the_issues_and_every_prompt_is_past_dense_len():
    mix = cases.load(cases.BENCH, "traffic", "longdoc-qa-sala.json")
    laguna = cases.load(cases.BENCH, "traffic", "longdoc-qa-laguna.json")
    cfg = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    eng = cfg["deployment"]["engine"]
    assert mix["kind"] == "serve_closed_checked"
    assert mix["arrivals"] == laguna["arrivals"] == {
        "process": "closed", "clients": 64, "pool_per_client_second": 0.25}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 64, "max": 128}
    assert mix["trace"] == {"start_s": 8, "seconds": 4}
    assert mix["shape_seed"] not in (laguna["shape_seed"], 4201)
    lengths = mix["prompt_tokens"]
    assert lengths["dist"] == "uniform"
    # EVERY context lies past the model's dense length, and the longest
    # stream fits the engine
    assert lengths["min"] == 8256 > cfg["sparse_config"]["dense_len"]
    assert lengths["max"] + mix["output_tokens"]["max"] == eng["max_seq"]
    # the rung of the lump rule that stands is one of the issue's three
    assert (lengths["max"], eng["max_seq"], eng["kv_pages"]) in (
        (12160, 12288, 6145), (10112, 10240, 5121), (9088, 9216, 4609))
    assert eng["n_slots"] == 32
    chk = mix["check"]
    assert chk["tokens"] == 32 and len(chk["prompt_lengths"]) == eng["n_slots"]
    # one prompt in each rung the mix uses, one whose 32 tokens CROSS
    # dense_len, and short ones on the dense path
    assert 8176 in chk["prompt_lengths"] and 8176 < 8192 < 8176 + 32
    assert chk["prompt_lengths"].count(600) == eng["n_slots"] - len(
        [n for n in chk["prompt_lengths"] if n != 600])
    assert max(chk["prompt_lengths"]) + chk["tokens"] <= eng["max_seq"]
    assert max(chk["prompt_lengths"]) > lengths["max"] - 2048
    assert 0 < chk["mean_logit_tolerance"] < chk["logit_tolerance"]
    assert "control" in chk["why"] and "R = " in mix["what"]


def test_the_counts_are_the_recurrence_and_the_keys_read():
    """A linear layer's position is `4 d^2` operations a head and its state
    `heads x d x d` float32; a sparse layer's query reads every earlier key
    under dense_len and 64 blocks from there on, its own to its own
    position, and scores the pooled keys it may see; 2.29 GFLOP a prompt
    token at the cell's lengths."""
    counts = models.adapter("minicpm_sala").counts
    m = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    assert counts.layers(m) == (4, 0) and counts.mixer_layers(m) == (1, 3)
    assert counts.linear_token_flops(m) == 4 * 32 * 128 * 128
    assert counts.linear_state_bytes(m) == 32 * 128 * 128 * 4 == 2_097_152
    assert counts.decode_state_bytes(m, 32 * 8) == 2 * 3 * 2_097_152 * 256
    ops, byts = counts.linear_prompt_ops_bytes(m, 10000, 2)
    assert ops == 10000 * 4 * 32 * 128 * 128
    assert byts == 10000 * 4 * 4096 * 2 + 2_097_152
    assert counts.keys_read(m, 8190) == 8191            # dense to the last
    assert counts.keys_read(m, 8191) == 63 * 64 + 64    # 64 blocks, own whole
    assert counts.keys_read(m, 12000) == 63 * 64 + 12000 % 64 + 1
    assert counts.pooled_seen(m, 8190) == 0
    assert counts.pooled_seen(m, 8191) == (8192 - 32) // 16 + 1 == 511
    read, scored = counts.sparse_prompt_pairs(m, 9000)
    assert read == sum(range(1, 8192)) + sum(
        63 * 64 + t % 64 + 1 for t in range(8191, 9000))
    assert scored == sum((t + 1 - 32) // 16 + 1 for t in range(8191, 9000))
    ops, byts = counts.block_sparse_decode_ops_bytes(m, 64 * 2, 2)
    assert byts == 2 * 128 * 64 * 128 * 2        # K and V of 128 pages of 64
    assert ops == 4 * 16 * 128 * 128 * 64
    s = 12160
    assert 2.25e9 < counts.prefill_flops(m, s) / s < 2.35e9
    ops, byts = counts.decode_step_ops_bytes(m, [10000] * 32, 2, 2)
    assert byts > 2 * (counts.total_params(m) - counts.head_params(m))
    assert byts < 2 * counts.total_params(m)
    with pytest.raises(NotImplementedError, match="served, not trained"):
        counts.train_flops_per_token(m, 8)


def test_the_readers_on_a_synthetic_trace(monkeypatch):
    """The nine readers PR 65 adds on a trace built by hand: the time under
    `linear_attn` (its kernels' scopes inside), `compress`, `block_select`
    and `block_sparse_attn`, all inside `attn`, is theirs and no longer
    `attn`'s; the shares come from the adapter's counts and stay under 100;
    a trace without these scopes (the parent, every other model) reads None
    and raises nothing."""
    from benchmark import sala_trace
    Span = program_trace.Span
    dispatch = dict(useful=16, capacity=16, active=2, blocks_selected=256,
                    blocks_visible=640, dense_rows=0, state_bytes=1)
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=10000, bucket=10240,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.emit", 4.0e8, 4.0e8 + 10, dict(rid=7,
                                                          kind="first")),
        Span("serve.engine.decode_dispatch", 4.1e8, 4.1e8 + 10, dispatch),
        Span("serve.engine.decode_dispatch", 5.0e8, 5.0e8 + 10, dispatch)]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 3.0e8),
               ("jit_decode", 4.2e8, 4.3e8), ("jit_poke", 6e8, 6e8 + 10)]
    pre = "jit(prefill)/layers/while/body/"
    dec = "jit(decode)/while/body/layers/while/body/"
    ops = [(pre + "qkv/dot_general:", 1000, 1.0e8),
           (pre + "attn/linear_attn/linear_chunk/pallas_call:", 1.0e8, 1.3e8),
           (pre + "attn/linear_attn/transpose:", 1.3e8, 1.4e8),
           (pre + "attn/compress/reduce_sum:", 1.4e8, 1.5e8),
           (pre + "attn/block_select/while/body/dot_general:", 1.5e8, 1.7e8),
           (pre + "attn/block_sparse_attn/pallas_call:", 1.7e8, 2.2e8),
           (pre + "attn/attn_gate/mul:", 2.2e8, 2.3e8),
           (pre + "mlp/dot_general:", 2.3e8, 3.0e8),
           (dec + "attn/linear_attn/linear_step/pallas_call:", 4.2e8, 4.22e8),
           (dec + "attn/compress/select_n:", 4.22e8, 4.225e8),
           (dec + "attn/block_select/sort:", 4.225e8, 4.23e8),
           (dec + "attn/block_sparse_attn/pallas_call:", 4.23e8, 4.24e8),
           (dec + "mlp/dot_general:", 4.24e8, 4.3e8)]
    m = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    m["deployment"]["engine"]["decode_chunk"] = 2
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": None,
           "device": {"kind": "TPU v5 lite"}}
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    ms = 1e6
    assert _reader("prefill_linear_attn_ms_per_ktok")(run) \
        == pytest.approx(0.4e8 / ms / 10.0)
    assert _reader("prefill_block_select_ms_per_ktok")(run) \
        == pytest.approx(0.3e8 / ms / 10.0)
    assert _reader("decode_linear_attn_ms")(run) \
        == pytest.approx(0.02e8 / ms / 2)
    assert _reader("decode_block_sparse_attn_ms")(run) \
        == pytest.approx(0.02e8 / ms / 2)
    assert _reader("selected_block_share_pct")(run) == pytest.approx(40.0)
    counts = models.adapter("minicpm_sala").counts
    # (bound by bytes: q, k, v and the rows once, the state once)
    want = 100 * 3 * (10000 * 4 * 4096 * 2 + 2_097_152) / 819e9 / 0.04
    assert _reader("linear_prefill_roofline_pct")(run) == pytest.approx(want)
    want = 100 * counts.decode_state_bytes(m, 2 * 2) / 819e9 / 0.002
    assert _reader("linear_state_roofline_pct")(run) == pytest.approx(want)
    for name in ("block_sparse_prefill_roofline_pct",
                 "block_sparse_decode_roofline_pct"):
        assert 0 < _reader(name)(run) < 100, name
    # `retention_trace`'s own vocabulary is put back after every call
    from benchmark import retention_trace
    assert "linear_attn" not in retention_trace.VOCABULARY
    assert set(sala_trace.SCOPES) <= set(sala_trace.VOCABULARY)
    t = program_trace.ProgramTrace([], [], [])
    assert [_reader(n)(run) for n in NEW] == [None] * 9
    # a dense model's trace from the chip: silent too
    t = program_trace.ProgramTrace(spans[:2], modules, [
        (pre + "attn/pallas_call:", 1000, 2.0e8)])
    assert [_reader(n)(run) for n in NEW] == [None] * 9


def test_the_programs_name_the_scopes_and_count_the_blocks():
    """The scopes in both programs' lowered text, all inside `attn`; the
    engine's counters beside every model's."""
    from ray_tpu.serve.engine import Engine
    adapter = models.adapter("minicpm_sala")
    model = dict(cases.load(ROOT, f"benchmark/configs/{CONFIG}.json"),
                 **adapter.REHEARSE)
    cfg = adapter.build_config(model, {"params": "float32",
                                       "activations": "float32"}, 128)
    eng = Engine(adapter.init_params(cfg, 3), cfg, n_slots=2, decode_chunk=2,
                 page_size=64)
    try:
        texts = (
            eng._programs.prefill.lower(*eng.prefill_shapes(128)).as_text(
                debug_info=True),
            eng._programs.decode.lower(*eng.decode_shapes()).as_text(
                debug_info=True))
        for text, kernel in zip(texts, ("linear_chunk", "linear_step")):
            for scope in ("attn/linear_attn/" + kernel, "attn/compress",
                          "attn/block_select", "attn/block_sparse_attn",
                          "attn/attn_gate", "attn_out", "mlp"):
                assert f"{scope}/" in text, scope
        assert set(eng.counters()) >= {
            "decode_blocks_selected", "decode_blocks_visible",
            "decode_dense_rows", "state_bytes_moved", "state_writes",
            "linear_state_bytes", "pooled_key_bytes"}
    finally:
        eng.stop()


@pytest.mark.timeout(630)
def test_the_sala_cell_rehearses_through_run_py():
    """`run.py --rehearse`: the adapter's `REHEARSE` over the configuration,
    `rehearse.json`'s engine, the whole control flow on the CPU through the
    cluster, the proxy and the engine. The prompts (258-380 at the
    rehearsal's scale) are longer than its `max_seq` of 128, as the other
    `serve-longdoc-*` cells' are, so requests come back short and the line
    reads `correct` false: what is asked here is that the run reaches its
    end, checks 32 prompts through the pages, the pooled keys and the state,
    and reports."""
    result, rec = rehearse(CELL)
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
    assert rec["config"]["hidden_size"] == 64           # REHEARSE's
    assert rec["config"]["sparse_config"]["block_size"] == 64
    assert len(rec["config"]["mixer_types"]) == 32      # as published
    assert len(rec["check"]["prompt_lengths"]) == 32
    # bfloat16 at tiny widths against the float32 reference
    assert rec["check"]["mean_gap"] < 0.01
    paths = rec["replica"]["attention_paths"]
    assert paths.get("linear_reference") and paths.get("decode_reference")
    assert not any(k.endswith("_pallas") for k in paths)

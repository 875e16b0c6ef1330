"""The benchmark's adapter contract for `granitemoehybrid` (PR 49), seen by
tier-1: what tests/test_benchmark_adapters.py says of every adapter, for this
one; a file of its own so that its `run.py --rehearse` subprocess, the minute
of the family, runs beside the other cells' and not after them (`--dist
loadfile` keeps a file on one worker)."""

import re

import pytest

from benchmark import models, program_trace
from test_benchmark_adapters import (ROOT,
                                     TIMELINE_READERS_OF_A_BATCH_CELL,
                                     _reader, cases, rehearse)

# ---------------------------------------------------------------------------
# granitemoehybrid: Mamba-2 layers beside NoPE attention over a share of the
# experts and a shared expert, the family's four multipliers (PR 49)
# ---------------------------------------------------------------------------

GRANITE_CELL = "serve-generate-granite4h"
GRANITE_CONFIG = "granite-4.0-h-small-serve"
# The readers that were there and serve this stack unchanged, and its own.
GRANITE_SERVED = [
    "kv_pages_peak_pct", "decode_sample_ms", "decode_ssm_ms",
    "decode_state_roofline_pct", "decode_moe_ms", "expert_load_max_over_mean",
    "local_assignment_share_pct", "moe_share_experts_roofline_pct",
    "decode_mfu_pct"]
# The readers of a prefill or an admission serve this stack too (the synthetic
# trace below), and the cell's window, 4 s from second 8 as ISSUE 49 names it,
# holds neither: at 25 ms a step no slot frees before second 13. They do not
# list the cell (PERF.md section 7).
GRANITE_SILENT = [
    "prefill_ms_per_ktok", "prefill_moe_ms_per_ktok",
    "prefill_ssm_ms_per_ktok", "scan_roofline_pct", "engine_slot_refill_ms",
    "prefill_stall_pct"]


def test_granite_manifest_entries_are_the_catalogs_row_and_the_issues_cell():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == GRANITE_CONFIG)
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] \
        and cfg["arch"] == "granitemoehybrid" and len(entry["source"]) <= 200
    assert entry["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "layer_types", "num_local_experts",
        "vocab_size"]
    assert isinstance(cfg["assumed"], list) and len(cfg["assumed"]) >= 8
    cell = next(w for w in manifest["workloads"]
                if w["name"] == GRANITE_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        GRANITE_CONFIG, "generate-long-granite4h", 1)
    assert "1 layer of 10" in cell["why"] and "half its load" in cell["why"] \
        and "host share" in cell["why"] and len(cell["why"]) <= 200
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    e2e = {p["name"]: p.get("workloads", []) for p in manifest["end_to_end"]}
    assert GRANITE_CELL in e2e["batch_tokens_per_s"]
    assert all(GRANITE_CELL in lists[n] for n in GRANITE_SERVED)
    assert lists["decode_state_share_pct"] == [GRANITE_CELL]
    new = next(p for p in manifest["per_layer"]
               if p["name"] == "decode_state_share_pct")
    assert (new["layer"], new["moves"], new["better"], new["source"]) == (
        "scheduler (serve)", "batch_tokens_per_s", "lower",
        "program_counter")
    mine = [n for n, cells in lists.items() if GRANITE_CELL in cells]
    assert set(mine) == set(GRANITE_SERVED) | {"decode_state_share_pct"} \
        | TIMELINE_READERS_OF_A_BATCH_CELL
    assert not [n for n in GRANITE_SILENT if GRANITE_CELL in lists[n]]
    # no reader of another stack's vocabulary, and no whole-model share
    for name in ("moe_experts_roofline_pct", "hybrid_experts_roofline_pct",
                 "decode_attn_roofline_pct", "decode_conv_ms"):
        assert GRANITE_CELL not in lists[name]


def test_granite_traffic_is_lfm2s_with_its_own_seed_check_and_rung():
    """`generate-long-lfm2`'s arrivals, lengths and trace letter for letter,
    a shape_seed and a check of its own, and the lengths the rule's rung
    gives for the R written into the file (ISSUE 49: (a) prompts to 1,024
    while R >= 4,096, (b) to 768 while R >= 3,072, (c) to 512)."""
    mix = cases.load(cases.BENCH, "traffic", "generate-long-granite4h.json")
    lfm2 = cases.load(cases.BENCH, "traffic", "generate-long-lfm2.json")
    for key in ("kind", "arrivals", "output_tokens", "drain_s"):
        assert mix.get(key) == lfm2.get(key), key
    assert mix["shape_seed"] == 4901 != lfm2["shape_seed"]
    # ... and its trace: 4 s from second 8, decode alone at this model's step
    assert mix["trace"] == lfm2["trace"] == {"start_s": 8, "seconds": 4}
    found = re.search(r"R = ([\d,]+\.?\d*)", mix["what"])
    assert found, "the traffic file's `what` states R"
    r = float(found.group(1).replace(",", ""))
    rung = 1024 if r >= 4096 else 768 if r >= 3072 else 512
    assert r >= 2048
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": rung}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 512,
                                    "max": 1024}
    chk = mix["check"]
    assert len(chk["prompt_lengths"]) * chk["tokens"] == 1024
    assert max(chk["prompt_lengths"]) <= rung and chk["tokens"] == 128
    assert chk["logit_tolerance"] > chk["mean_logit_tolerance"] > 0
    assert "control" in chk["why"] and "1.5" in chk["why"]


def test_granite_readers_on_a_synthetic_trace(monkeypatch):
    """`decode_state_share_pct` and the accepted readers that serve this
    stack unchanged, on a trace built by hand: a prefill of 1,000 prompt
    tokens and one decode chunk of 2 steps under the mixer's scopes, the
    share's counters on the spans. A program without the counters reads
    None and raises nothing."""
    from benchmark import peaks
    Span = program_trace.Span
    dispatch = dict(useful=128, capacity=128, active=64,
                    live_kv_tokens=64000, experts_touched=2 * 10 * 35,
                    local_assignments=2 * 10 * 320,
                    routed_assignments=2 * 10 * 640, expert_tokens="1:2")
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=1000, bucket=1024,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.prefill_experts", 2050, 2060,
             dict(rid=7, touched=10 * 36, local=50000, routed=100000)),
        Span("serve.engine.emit", 2100, 2110, dict(rid=7, kind="first")),
        Span("serve.engine.decode_dispatch", 2200, 2210, dispatch),
        Span("serve.engine.decode_dispatch", 3200, 3210,
             dict(dispatch, expert_tokens="3:6")),
    ]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 2300, 3000), ("jit_poke", 4000, 4010)]
    pre = "jit(prefill)/layers/while/body/"
    dec = "jit(decode)/while/body/layers/while/body/"
    ops = [(pre + "ssm_in/dot_general:", 1000, 1100),
           (pre + "conv/mul:", 1100, 1150),
           (pre + "scan/while/body/dot_general:", 1150, 1400),
           (pre + "ssm_out/dot_general:", 1400, 1500),
           (pre + "mlp/experts/pallas_call:", 1500, 2000),
           (dec + "ssm_in/dot_general:", 2300, 2340),
           (dec + "conv/select_n:", 2340, 2360),
           (dec + "scan/mul:", 2360, 2600),
           (dec + "ssm_out/dot_general:", 2600, 2650),
           (dec + "mlp/experts/pallas_call:", 2650, 3000)]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    m = cases.load(ROOT, f"benchmark/configs/{GRANITE_CONFIG}.json")
    m["deployment"]["engine"]["decode_chunk"] = 2
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": None,
           "device": {"kind": "TPU v5 lite"}}
    names = ["decode_state_share_pct", "prefill_ssm_ms_per_ktok",
             "decode_ssm_ms", "scan_roofline_pct",
             "decode_state_roofline_pct", "decode_mfu_pct",
             "local_assignment_share_pct", "moe_share_experts_roofline_pct",
             "expert_load_max_over_mean"]
    got = {name: _reader(name)(run) for name in names}
    counts = models.adapter("granitemoehybrid").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")

    def least(ops_bytes):
        return max(ops_bytes[0] / f, ops_bytes[1] / b)

    step = counts.decode_step_ops_bytes(m, [1000.0] * 64, 2, 2,
                                        experts_touched=35.0)
    state = counts.decode_state_bytes(m, 64, 2)
    assert got["decode_state_share_pct"] == pytest.approx(
        100 * state / step[1])
    assert 30 < got["decode_state_share_pct"] < 36
    assert got["prefill_ssm_ms_per_ktok"] == pytest.approx(500 / 1e6 / 1.0)
    assert got["decode_ssm_ms"] == pytest.approx(350 / 1e6 / 2)
    assert got["scan_roofline_pct"] == pytest.approx(
        100 * 9 * least(counts.selective_scan_ops_bytes(m, 1000, 2))
        / 250e-9)
    assert got["decode_state_roofline_pct"] == pytest.approx(
        100 * counts.decode_state_bytes(m, 64 * 2, 2) / b / 240e-9)
    assert got["decode_mfu_pct"] == pytest.approx(
        100 * least(step) / (700e-9 / 2))
    assert got["local_assignment_share_pct"] == pytest.approx(
        100 * (50000 + 2 * 6400) / (100000 + 2 * 12800))
    want = 10 * least(counts.experts_ops_bytes(m, 5000, 36, 2, 2)) \
        + 2 * 10 * least(counts.experts_ops_bytes(m, 320, 35, 2, 2))
    assert got["moe_share_experts_roofline_pct"] == pytest.approx(
        100 * want / 850e-9)
    assert got["expert_load_max_over_mean"] == pytest.approx(4 / 3)
    # spans without the counters (the parent's, a dense model's), no trace
    bare = program_trace.ProgramTrace(
        [Span(s.name, s.start, s.end, {k: v for k, v in s.args.items()
                                       if k in ("rid", "kind",
                                                "prompt_tokens", "bucket")})
         for s in spans], modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: bare)
    assert _reader("decode_state_share_pct")(run) is None
    monkeypatch.setattr(program_trace, "load", lambda run: None)
    assert _reader("decode_state_share_pct")(run) is None
    # a model whose counts have no recurrent state reads nothing either
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    other = cases.load(ROOT, "benchmark/configs/lfm2-24b-a2b-serve.json")
    assert _reader("decode_state_share_pct")(dict(run, config=other)) is None


def test_the_engines_spans_carry_what_the_granite_readers_read():
    """The names `benchmark/ssm_trace.py` and the share's readers look for
    are the ones the program emits: the mixer's five scopes beside the sparse
    feed-forward's in the lowered programs of this stack, the span arguments
    and the counter in the engine."""
    import jax
    import jax.numpy as jnp

    from benchmark import moe_trace, ssm_trace
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models import serving
    from ray_tpu.models.serving import build_programs
    from ray_tpu.serve import engine as engine_mod

    adapter = models.adapter("granitemoehybrid")
    m = cases.load(ROOT, f"benchmark/configs/{GRANITE_CONFIG}.json")
    cfg = adapter.build_config(dict(m, **adapter.REHEARSE), {
        "params": "float32", "activations": "float32"}, 128)
    built = build_programs(cfg, 2, 2, 16, 17)
    assert built.by_slot and not built.adopts and built.takes_riders
    params = jax.eval_shape(lambda: fuse_qkv(
        init_params(cfg, jax.random.PRNGKey(0)), cfg))
    caches = jax.eval_shape(built.empty)
    assert built.books(caches).share

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    scopes = ssm_trace.SCOPES + moe_trace.MOE_SCOPES + (
        "shared_expert", "qkv", "attn", "attn_out")
    text = built.decode.lower(
        params, caches, arg((2, 8), jnp.int32), arg((2,), jnp.int32),
        arg((2,), jnp.int32), arg((2,), jnp.bool_), arg((2,), jnp.float32),
        arg((2,), jnp.int32), arg((2, 2), jnp.uint32)
        ).as_text(debug_info=True)
    for scope in scopes + ("kv_write",):
        assert f"{scope}/" in text, scope
    text = built.prefill.lower(
        params, caches, arg((8,), jnp.int32), arg((1, 64), jnp.int32), 1,
        0.0, 0, arg((2,), jnp.uint32), 0).as_text(debug_info=True)
    for scope in scopes:
        assert f"{scope}/" in text, scope
    src = open(engine_mod.__file__).read() + open(serving.__file__).read()
    for name in ("state_bytes", "live_kv_tokens", "experts_touched",
                 "touched", "local", "routed", "local_assignments",
                 "routed_assignments", "active"):
        assert f'"{name}"' in src or f"{name}=" in src, name


@pytest.mark.timeout(630)
def test_the_granite_cell_rehearses_through_run_py():
    """`run.py --rehearse`: the adapter's `REHEARSE` over the configuration,
    `rehearse.json`'s engine, the whole control flow on the CPU through the
    cluster, the proxy and the engine: the run reaches its end (exit 3),
    serves its check's streams through the recurrent state and the pages,
    and reports. (Its `correct` reads false: the check asks 128 tokens after
    each prompt and the rehearsal's `max_seq` is 128.)"""
    result, rec = rehearse(GRANITE_CELL)
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
    assert rec["config"]["hidden_size"] == 128          # REHEARSE's
    assert rec["config"]["layer_types"] == ["mamba", "attention", "mamba",
                                            "mamba"]
    assert len(rec["check"]["prompt_lengths"]) == 8
    paths = rec["replica"]["attention_paths"]
    assert paths.get("ssd_chunked") and paths.get("decode_reference") \
        and paths.get("share_combine_gather")

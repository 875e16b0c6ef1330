"""The benchmark's adapter contract for `nemotron_h` (PR 55), seen by
tier-1: what tests/test_benchmark_adapters.py says of every adapter, for this
one; a file of its own so that its `run.py --rehearse` subprocess, the minute
of the family, runs beside the other cells' and not after them (`--dist
loadfile` keeps a file on one worker)."""

import dataclasses
import re

import pytest

from benchmark import models, program_trace
from test_benchmark_adapters import (ROOT,
                                     TIMELINE_READERS_OF_A_BATCH_CELL,
                                     _reader, cases, rehearse)

# ---------------------------------------------------------------------------
# nemotron_h: layers of ONE part each, Mamba-2 with groups, ungated relu^2
# experts under a sigmoid router, a share held (PR 55)
# ---------------------------------------------------------------------------

NEMOTRON_CELL = "serve-batch-nemotron3"
NEMOTRON_CONFIG = "nemotron-3-nano-30b-a3b-serve"
NEMOTRON_NEW = ["prefill_mfu_pct", "prefill_attn_ms_per_ktok"]
# The readers that were there and serve this stack unchanged.
NEMOTRON_SERVED = [
    "prefill_ms_per_ktok", "prefill_ssm_ms_per_ktok", "scan_roofline_pct",
    "prefill_moe_ms_per_ktok", "decode_ssm_ms", "decode_state_roofline_pct",
    "decode_moe_ms", "decode_sample_ms", "decode_mfu_pct",
    "moe_share_experts_roofline_pct",
    "local_assignment_share_pct", "expert_load_max_over_mean",
    "kv_pages_peak_pct", "engine_slot_refill_ms", "prefill_stall_pct"]
# ISSUE 55 names these two as well; both move `tpot_p95_ms`, which this cell
# does not report, so the manifest's rule keeps the cell off their lists
# (PERF.md section 7).
NEMOTRON_NOT = ["decode_step_ms", "decode_attn_ms", "moe_experts_roofline_pct",
                "hybrid_experts_roofline_pct", "decode_attn_roofline_pct",
                "decode_conv_ms", "prefill_conv_ms_per_ktok"]
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_nemotron_manifest_entries_are_the_catalogs_row_and_the_issues_cell():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == NEMOTRON_CONFIG)
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] \
        and cfg["arch"] == "nemotron_h" and len(cfg["source"]) <= 200
    assert entry["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    for key, (published, run) in {
            "num_hidden_layers": (52, 16),
            "hybrid_override_pattern": (PUBLISHED_PATTERN,
                                        PUBLISHED_PATTERN[:16]),
            "n_routed_experts": (128, 64),
            "vocab_size": (131072, 65536)}.items():
        cut = cfg["reduced"][key]
        assert (cut["published"], cut["run"], cfg[key]) == (
            published, run, run) and cut["decided_by"]
    # every published width, unchanged
    assert {k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "n_groups", "conv_kernel", "moe_intermediate_size",
        "num_experts_per_tok", "moe_shared_expert_intermediate_size",
        "routed_scaling_factor", "mlp_hidden_act", "expand",
        "chunk_size")} == dict(
        hidden_size=2688, num_attention_heads=32, num_key_value_heads=2,
        head_dim=128, mamba_num_heads=64, mamba_head_dim=64,
        ssm_state_size=128, n_groups=8, conv_kernel=4,
        moe_intermediate_size=1856, num_experts_per_tok=6,
        moe_shared_expert_intermediate_size=3712, routed_scaling_factor=2.5,
        mlp_hidden_act="relu2", expand=2, chunk_size=128)
    assert cfg["expert_parallel"]["routed_experts_total"] == 128
    said = " ".join(cfg["assumed"])
    assert isinstance(cfg["assumed"], list) and len(cfg["assumed"]) >= 10
    for word in ("NO rotary", "mamba_num_heads x mamba_head_dim", "NO clamp",
                 "each group's 512", "BALANCED", "262,144",
                 "routed experts' W_down, at 0.02 x 0.2"):
        assert word in said, word
    eng = cfg["deployment"]["engine"]
    assert (eng["max_seq"], eng["n_slots"], eng["decode_chunk"],
            eng["page_size"], eng["kv_pages"]) == (4096, 32, 8, 64,
                                                   32 * 64 + 1)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == NEMOTRON_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NEMOTRON_CONFIG, "batch-summarize-nemotron3", 1)
    assert "64 clients on 32 slots" in cell["why"] \
        and "half its load" in cell["why"] and len(cell["why"]) <= 200
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    e2e = {p["name"]: p.get("workloads", []) for p in manifest["end_to_end"]}
    assert NEMOTRON_CELL in e2e["batch_tokens_per_s"]
    assert all(NEMOTRON_CELL in lists[n] for n in NEMOTRON_SERVED)
    for name in NEMOTRON_NEW:
        new = next(p for p in manifest["per_layer"] if p["name"] == name)
        assert (new["layer"], new["moves"], new["source"],
                new["workloads"]) == (
            "model step (prefill)", "batch_tokens_per_s", "device_trace",
            # (PR 62's and PR 65's cells report the whole prefill's share
            # too)
            [NEMOTRON_CELL] + ["serve-longdoc-laguna", "serve-longdoc-sala"]
            * (name == "prefill_mfu_pct"))
    mine = [n for n, cells in lists.items() if NEMOTRON_CELL in cells]
    assert set(mine) == set(NEMOTRON_SERVED) | set(NEMOTRON_NEW) \
        | TIMELINE_READERS_OF_A_BATCH_CELL
    assert not [n for n in NEMOTRON_NOT if NEMOTRON_CELL in lists[n]]
    # appended after the eleven cells that were there: nothing moved
    assert manifest["workloads"].index(cell) == 11 \
        and manifest["configs"].index(entry) == 10


def test_nemotron_traffic_is_batch_summarizes_but_for_the_clients():
    """`batch-summarize`'s lengths, pool and trace letter for letter, 64
    clients, a shape_seed and a check of its own, and the lengths the rule's
    rung gives for the R written into the file (ISSUE 55: (a) prompts to
    3,584 while R >= 14,336, (b) to 2,560 while R >= 10,240, (c) to 2,048
    while R >= 8,192)."""
    mix = cases.load(cases.BENCH, "traffic", "batch-summarize-nemotron3.json")
    base = cases.load(cases.BENCH, "traffic", "batch-summarize.json")
    assert mix["kind"] == "serve_closed_checked"
    for key in ("output_tokens", "trace", "drain_s"):
        assert mix.get(key) == base.get(key), key
    assert mix["arrivals"] == dict(base["arrivals"], clients=64)
    assert mix["shape_seed"] not in (base["shape_seed"], 3511, 4901)
    found = re.search(r"R = ([\d,]+\.?\d*)", mix["what"])
    assert found, "the traffic file's `what` states R"
    r = float(found.group(1).replace(",", ""))
    rung = 3584 if r >= 14336 else 2560 if r >= 10240 else 2048
    assert r >= 8192
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 1024,
                                    "max": rung}
    chk = mix["check"]
    assert chk["prompt_lengths"][:3] == [1024, 2000, 3500][
        :3 if rung == 3584 else 2]
    assert len(chk["prompt_lengths"]) * chk["tokens"] >= 1024
    assert chk["logit_tolerance"] > chk["mean_logit_tolerance"] > 0
    assert "control" in chk["why"]


def test_nemotron_counts_reproduce_the_issues_arithmetic():
    counts = models.adapter("nemotron_h").counts
    m = cases.load(ROOT, f"benchmark/configs/{NEMOTRON_CONFIG}.json")
    assert (counts.layer_params(m, "M"), counts.layer_params(m, "E"),
            counts.layer_params(m, "*"), counts.layer_params(m, "E", 128)) \
        == (38_744_896, 658_885_376, 23_399_040, 1_297_468_160)
    assert counts.expert_params(m) == 9_977_856
    assert counts.total_params(m) == 5_282_534_208
    assert (counts.layers(m), counts.mamba_layers(m),
            counts.attention_layers(m)) == ((0, 7), 7, 2)
    whole = {k: v for k, v in m.items() if k != "expert_parallel"}
    whole.update(num_hidden_layers=52,
                 hybrid_override_pattern=PUBLISHED_PATTERN,
                 n_routed_experts=128, vocab_size=131072)
    assert counts.total_params(whole) == 31_577_940_288
    assert (counts.layers(whole), counts.mamba_layers(whole),
            counts.attention_layers(whole)) == ((0, 23), 23, 6)
    # a state of 128 x 4,096 float32 a slot a layer, whatever the groups
    assert counts.slot_state_bytes(m, 2) == 128 * 4096 * 4 + 3 * 6144 * 2
    assert counts.decode_state_bytes(dict(m, n_groups=1), 32, 2) \
        == pytest.approx(counts.decode_state_bytes(m, 32, 2), rel=0.01)
    # TWO matrices an expert; the recurrence as the least that computes it
    ops, byts = counts.experts_ops_bytes(m, 100, 10, 2, 2)
    assert ops == 2 * 9_977_856 * 100
    assert byts == 10 * 9_977_856 * 2 + 2 * 100 * 2688 * 2
    ops, byts = counts.selective_scan_ops_bytes(m, 1000, 2)
    assert ops == 1000 * (5 * 4096 * 128 + 3 * 64)
    assert byts == 1000 * (2 * 4096 * 2 + 64 * 4 + 2 * 8 * 128 * 2) \
        + 2 * 4096 * 128 * 4 + 2 * 64 * 4
    # the program's count of local assignments takes the expectation's place
    even = counts.prefill_flops(m, 1000)
    assert counts.prefill_flops(m, 1000, local=7 * 1000 * 3) \
        == pytest.approx(even)
    assert counts.prefill_flops(m, 1000, local=0) < even
    # the program's own parameter count at these widths is the adapter's
    from ray_tpu.models.llama import param_count
    adapter = models.adapter("nemotron_h")
    assert param_count(adapter.build_config(m, m["dtypes"], 4096)) \
        == 5_282_534_208


@pytest.mark.parametrize("change,said", [
    (dict(hybrid_override_pattern="MEMEM*EMEMEM-EME"), "a letter other than"),
    (dict(hybrid_override_pattern="MEMEM*EM"), "a letter for each"),
    (dict(n_group=4, topk_group=0), "without the group limit"),
    (dict(time_step_limit=[0.0, 0.1]), "a clamp on the time step"),
    (dict(tie_word_embeddings=True), "a tied head"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(use_conv_bias=False), "convolution without bias"),
    (dict(mamba_proj_bias=True), "projection bias"),
    (dict(n_groups=7), "n_groups does not divide"),
    (dict(moe_shared_expert_intermediate_size=2000), "ONE shared expert"),
    (dict(expert_parallel={"chips": 3, "rank": 0,
                           "routed_experts_total": 128}), "expert_parallel"),
])
def test_nemotron_adapter_refuses_what_the_block_does_not_compute(change,
                                                                  said):
    adapter = models.adapter("nemotron_h")
    m = cases.load(ROOT, f"benchmark/configs/{NEMOTRON_CONFIG}.json")
    adapter.check_supported(m)
    adapter.check_supported(dict(m, **adapter.REHEARSE))
    adapter.check_supported(dict(m, n_group=4, topk_group=2))
    with pytest.raises(ValueError, match=said):
        adapter.check_supported(dict(m, **change))


def test_nemotron_build_config_names_the_fields_an_older_program_lacks(
        monkeypatch):
    """What the parent commit does under this PR's benchmark files:
    `build_config`, which the cell's driver calls in `run.py`'s own process
    before any cluster starts, names the fields `LlamaConfig` lacks."""
    from ray_tpu.models import llama
    adapter = models.adapter("nemotron_h")
    m = cases.load(ROOT, f"benchmark/configs/{NEMOTRON_CONFIG}.json")
    new = ("layer_parts", "ssm_groups", "ssm_head_dim", "ffn")
    older = dataclasses.make_dataclass("LlamaConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)
        if f.name not in new])
    monkeypatch.setattr(llama, "LlamaConfig", older)
    with pytest.raises(ValueError, match=".*".join(new)):
        adapter.build_config(m, m["dtypes"], 4096)


def test_nemotron_readers_on_a_synthetic_trace(monkeypatch):
    """The two new readers and the accepted ones that serve this stack
    unchanged, on a trace built by hand: a prefill of 1,000 prompt tokens and
    one decode chunk of 2 steps under the mixer's, the experts' and
    attention's scopes, the share's counters on the spans. A program without
    the scopes or the counters reads None and raises nothing."""
    from benchmark import peaks
    Span = program_trace.Span
    dispatch = dict(useful=64, capacity=64, active=32, live_kv_tokens=64000,
                    experts_touched=2 * 7 * 60, local_assignments=2 * 7 * 96,
                    routed_assignments=2 * 7 * 192, expert_tokens="1:2")
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=1000, bucket=1024,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.prefill_experts", 2050, 2060,
             dict(rid=7, touched=7 * 64, local=21000, routed=42000)),
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
           (pre + "mlp/experts/pallas_call:", 1500, 1800),
           (pre + "mlp/shared_expert/dot_general:", 1800, 1850),
           ("jit(prefill)/layers/qkv/dot_general:", 1850, 1900),
           ("jit(prefill)/layers/attn/pallas_call:", 1900, 2000),
           (dec + "ssm_in/dot_general:", 2300, 2340),
           (dec + "conv/select_n:", 2340, 2360),
           (dec + "scan/pallas_call:", 2360, 2600),
           (dec + "ssm_out/dot_general:", 2600, 2650),
           (dec + "mlp/experts/pallas_call:", 2650, 3000)]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    m = cases.load(ROOT, f"benchmark/configs/{NEMOTRON_CONFIG}.json")
    m["deployment"]["engine"]["decode_chunk"] = 2
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": None,
           "device": {"kind": "TPU v5 lite"}}
    names = NEMOTRON_NEW + [
        "prefill_ms_per_ktok", "prefill_ssm_ms_per_ktok",
        "prefill_moe_ms_per_ktok", "scan_roofline_pct", "decode_ssm_ms",
        "decode_state_roofline_pct", "decode_mfu_pct",
        "decode_state_share_pct", "local_assignment_share_pct",
        "moe_share_experts_roofline_pct", "expert_load_max_over_mean"]
    got = {name: _reader(name)(run) for name in names}
    assert not [n for n, v in got.items() if v is None]
    counts = models.adapter("nemotron_h").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")

    def least(ops_bytes):
        return max(ops_bytes[0] / f, ops_bytes[1] / b)

    assert got["prefill_mfu_pct"] == pytest.approx(
        100 * counts.prefill_flops(m, 1000, local=21000) / f / 1000e-9)
    assert got["prefill_attn_ms_per_ktok"] == pytest.approx(100 / 1e6)
    assert got["prefill_ms_per_ktok"] == pytest.approx(1000 / 1e6)
    assert got["prefill_ssm_ms_per_ktok"] == pytest.approx(500 / 1e6)
    assert got["prefill_moe_ms_per_ktok"] == pytest.approx(300 / 1e6)
    assert got["scan_roofline_pct"] == pytest.approx(
        100 * 7 * least(counts.selective_scan_ops_bytes(m, 1000, 2))
        / 250e-9)
    assert got["decode_ssm_ms"] == pytest.approx(350 / 1e6 / 2)
    assert got["decode_state_roofline_pct"] == pytest.approx(
        100 * counts.decode_state_bytes(m, 32 * 2, 2) / b / 240e-9)
    step = counts.decode_step_ops_bytes(m, [2000.0] * 32, 2, 2,
                                        experts_touched=60.0)
    assert got["decode_mfu_pct"] == pytest.approx(
        100 * least(step) / (700e-9 / 2))
    assert got["decode_state_share_pct"] == pytest.approx(
        100 * counts.decode_state_bytes(m, 32, 2) / step[1])
    assert got["local_assignment_share_pct"] == pytest.approx(
        100 * (21000 + 2 * 7 * 96 * 2) / (42000 + 2 * 7 * 192 * 2))
    want = 7 * least(counts.experts_ops_bytes(m, 3000, 64, 2, 2)) \
        + 2 * 7 * least(counts.experts_ops_bytes(m, 96, 60, 2, 2))
    assert got["moe_share_experts_roofline_pct"] == pytest.approx(
        100 * want / 650e-9)
    # a program without the mixer's scopes, spans without the counters, no
    # trace at all: the new readers read nothing and raise nothing
    plain = program_trace.ProgramTrace(spans, modules, [
        (path.replace("ssm_in", "qkv").replace("conv", "qkv")
         .replace("scan", "attn").replace("ssm_out", "attn_out"), s, e)
        for path, s, e in ops])
    monkeypatch.setattr(program_trace, "load", lambda run: plain)
    assert _reader("prefill_attn_ms_per_ktok")(run) is None
    bare = program_trace.ProgramTrace(
        [Span(s.name, s.start, s.end, {k: v for k, v in s.args.items()
                                       if k in ("rid", "kind",
                                                "prompt_tokens", "bucket")})
         for s in spans], modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: bare)
    assert _reader("prefill_mfu_pct")(run) == pytest.approx(
        100 * counts.prefill_flops(m, 1000) / f / 1000e-9)
    monkeypatch.setattr(program_trace, "load", lambda run: None)
    assert [_reader(n)(run) for n in NEMOTRON_NEW] == [None, None]
    # another stack's adapter (no `layers` in its counts): nothing
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    other = cases.load(ROOT, "benchmark/configs/mistral-7b-v0.3-serve.json")
    assert _reader("prefill_mfu_pct")(dict(run, config=other)) is None


def test_the_engines_spans_carry_what_the_nemotron_readers_read():
    """The names the readers look for are the ones the program emits: the
    mixer's five scopes, the sparse feed-forward's, the shared expert's and
    attention's in the lowered programs of this stack, each kind of layer its
    own alone; the span arguments and the counters in the engine."""
    import jax
    import jax.numpy as jnp

    from benchmark import moe_trace, ssm_trace
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models import serving
    from ray_tpu.models.serving import build_programs
    from ray_tpu.serve import engine as engine_mod

    adapter = models.adapter("nemotron_h")
    m = cases.load(ROOT, f"benchmark/configs/{NEMOTRON_CONFIG}.json")
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
        "shared_expert", "qkv", "attn", "attn_out", "mlp_norm")
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
                 "routed_assignments", "active", "prompt_tokens", "bucket"):
        assert f'"{name}"' in src or f"{name}=" in src, name


@pytest.mark.timeout(630)
def test_the_nemotron_cell_rehearses_through_run_py():
    """`run.py --rehearse`: the adapter's `REHEARSE` over the configuration,
    `rehearse.json`'s engine, the whole control flow on the CPU through the
    cluster, the proxy and the engine: the run reaches its end (exit 3),
    serves its check's streams through the recurrent state, the pages and
    the share, and reports. (Its `correct` reads false: the check's longest
    prompt and its 32 tokens pass the rehearsal's `max_seq` of 128.)"""
    result, rec = rehearse(NEMOTRON_CELL)
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
    assert rec["config"]["hidden_size"] == 96           # REHEARSE's
    assert rec["config"]["hybrid_override_pattern"] == "MEM*EM"
    assert len(rec["check"]["prompt_lengths"]) == 32
    # bfloat16 at tiny widths against the float32 reference
    assert rec["check"]["mean_gap"] < 0.01
    paths = rec["replica"]["attention_paths"]
    assert paths.get("ssd_chunked") and paths.get("decode_reference") \
        and paths.get("share_combine_gather")

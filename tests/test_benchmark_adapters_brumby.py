"""The benchmark's adapter contract for `brumby` (PR 59), seen by tier-1:
what tests/test_benchmark_adapters.py says of every adapter, for this one; a
file of its own so that its `run.py --rehearse` subprocess, the minute of the
family, runs beside the other cells' and not after them (`--dist loadfile`
keeps a file on one worker)."""

import dataclasses
import re

import pytest

from benchmark import models, program_trace
from test_benchmark_adapters import (ROOT,
                                     TIMELINE_READERS_OF_A_BATCH_CELL,
                                     _reader, cases, rehearse)

# ---------------------------------------------------------------------------
# brumby: power retention of degree 2 in every layer, no K and V cache, a
# state of 34 MB a slot a layer (PR 59)
# ---------------------------------------------------------------------------

CELL = "serve-generate-brumby"
CONFIG = "brumby-14b-base-serve"
NEW = ["decode_retention_ms", "retention_state_roofline_pct",
       "prefill_retention_ms_per_ktok", "retention_prefill_roofline_pct",
       "retention_decode_mfu_pct"]
# The readers that were there and serve this stack unchanged.
SERVED = ["prefill_ms_per_ktok", "prefill_stall_pct", "engine_slot_refill_ms",
          "decode_sample_ms"]
# No pages, no expert: these find nothing to read (ISSUE 59).
NOT = ["kv_pages_peak_pct", "decode_state_share_pct", "decode_mfu_pct",
       "decode_ssm_ms", "decode_state_roofline_pct", "decode_attn_ms",
       "decode_step_ms"]


def test_brumby_manifest_entries_are_the_catalogs_row_and_the_issues_cell():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] \
        and cfg["arch"] == "brumby" and len(cfg["source"]) <= 200
    assert entry["reduced"] == list(cfg["reduced"]) == ["num_hidden_layers"]
    cut = cfg["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["run"], cfg["num_hidden_layers"]) \
        == (40, 4, 4) and "pipeline stages" in cut["decided_by"]
    # every published width, unchanged
    assert {k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size", "rope_theta",
        "rms_norm_eps", "max_position_embeddings", "tie_word_embeddings")} \
        == dict(hidden_size=5120, num_attention_heads=40,
                num_key_value_heads=8, head_dim=128, intermediate_size=17408,
                vocab_size=151936, rope_theta=1000000, rms_norm_eps=1e-06,
                max_position_embeddings=32768, tie_word_embeddings=False)
    said = " ".join(cfg["assumed"])
    for word in ("retention_degree 2", "ONE output a KV head", "b_g",
                 "eps = 1e-6", "inside the square", "q/k norm and RoPE",
                 "float32", "max_window_layers"):
        assert word in said, word
    assert cfg["dtypes"] == {"params": "bfloat16", "activations": "bfloat16"}
    eng = cfg["deployment"]["engine"]
    assert (eng["max_seq"], eng["decode_chunk"], eng["page_size"],
            eng["kv_pages"]) == (2048, 8, 64, 33)
    assert eng["n_slots"] in (48, 40, 32) and "pages" in eng["why"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "generate-long-brumby", 1)
    mix = cases.load(cases.BENCH, "traffic", "generate-long-brumby.json")
    assert f"{mix['arrivals']['clients']} clients on {eng['n_slots']} slots" \
        in cell["why"] and len(cell["why"]) <= 200
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    e2e = {p["name"]: p.get("workloads", []) for p in manifest["end_to_end"]}
    assert CELL in e2e["batch_tokens_per_s"]
    assert all(CELL in lists[n] for n in SERVED)
    for name in NEW:
        new = next(p for p in manifest["per_layer"] if p["name"] == name)
        assert (new["moves"], new["source"], new["workloads"]) == (
            "batch_tokens_per_s", "device_trace", [CELL])
        assert new["layer"] in ("kernel", "model step (decode)",
                                "model step (prefill)")
        assert (new["unit"] == "%") == ("pct" in name)
    mine = [n for n, cells in lists.items() if CELL in cells]
    assert set(mine) == set(SERVED) | set(NEW) \
        | TIMELINE_READERS_OF_A_BATCH_CELL
    assert not [n for n in NOT if CELL in lists[n]]
    # appended after the twelve cells that were there: nothing moved
    assert manifest["workloads"].index(cell) == 12 \
        and manifest["configs"].index(entry) == 11
    # (PR 62 appended its two after them, PR 65 its nine after those)
    assert [p["name"] for p in manifest["per_layer"][-16:-11]] == NEW


def test_brumby_traffic_is_generate_long_granite4hs_but_for_the_clients():
    """`generate-long-granite4h`'s pool, outputs and greedy streams letter
    for letter, 1.5 clients a slot, a shape_seed, a check and a trace window
    of its own, and the prompt lengths the rule's rung gives for the R
    written into the file (ISSUE 49's rule: (a) prompts to 1,024 while R >=
    4,096, (b) to 768 while R >= 3,072, (c) to 512 while R >= 2,048)."""
    mix = cases.load(cases.BENCH, "traffic", "generate-long-brumby.json")
    base = cases.load(cases.BENCH, "traffic", "generate-long-granite4h.json")
    cfg = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    assert mix["kind"] == "serve_closed_checked"
    for key in ("output_tokens", "drain_s"):
        assert mix.get(key) == base.get(key), key
    slots = cfg["deployment"]["engine"]["n_slots"]
    assert mix["arrivals"] == dict(base["arrivals"], clients=slots * 3 // 2)
    assert mix["shape_seed"] not in (base["shape_seed"], 3511)
    found = re.search(r"R = ([\d,]+\.?\d*)", mix["what"])
    assert found, "the traffic file's `what` states R"
    r = float(found.group(1).replace(",", ""))
    rung = 1024 if r >= 4096 else 768 if r >= 3072 else 512
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": rung}
    chk = mix["check"]
    assert chk["tokens"] == 128
    assert len(chk["prompt_lengths"]) * chk["tokens"] >= 1024
    assert max(chk["prompt_lengths"]) <= rung
    assert chk["logit_tolerance"] > chk["mean_logit_tolerance"] > 0
    assert "control" in chk["why"]
    # the window opens after the first wave's shortest answer can end
    assert mix["trace"]["seconds"] == 4 and mix["trace"]["start_s"] >= 13
    assert "512 steps" in mix["what"]


def test_brumby_build_config_names_the_fields_an_older_program_lacks(
        monkeypatch):
    """What the parent commit does under this PR's benchmark files:
    `build_config`, which the cell's driver calls in `run.py`'s own process
    before any cluster starts, names the fields `LlamaConfig` lacks."""
    from ray_tpu.models import llama
    adapter = models.adapter("brumby")
    m = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    new = ("mixer", "retention_degree")
    older = dataclasses.make_dataclass("LlamaConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)
        if f.name not in new])
    monkeypatch.setattr(llama, "LlamaConfig", older)
    with pytest.raises(ValueError, match=".*".join(new)):
        adapter.build_config(m, m["dtypes"], 2048)


def test_brumby_readers_on_a_synthetic_trace(monkeypatch):
    """The five new readers on a trace built by hand: a prefill of 600 prompt
    tokens and one decode chunk of 2 steps under the mixer's three scopes,
    `active` on the dispatch spans. Each share is under 100. A program
    without the scopes (the parent's) reads None and raises nothing."""
    from benchmark import peaks
    Span = program_trace.Span
    dispatch = dict(useful=96, capacity=96, active=48, live_kv_tokens=0,
                    state_bytes=1)
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=600, bucket=1024,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.emit", 30000100, 30000110,
             dict(rid=7, kind="first")),
        Span("serve.engine.decode_dispatch", 30000200, 30000210, dispatch),
        Span("serve.engine.decode_dispatch", 90000200, 90000210, dispatch),
    ]
    ms = 1_000_000
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 1000 + 30 * ms),
               ("jit_decode", 31 * ms, 87 * ms), ("jit_poke", 88 * ms,
                                                  88 * ms + 10)]
    pre = "jit(prefill)/layers/while/body/"
    dec = "jit(decode)/while/body/layers/while/body/"
    ops = [(pre + "ret_in/qkv/dot_general:", 1000, 1000 + 4 * ms),
           (pre + "ret_in/logistic:", 1000 + 4 * ms, 1000 + 5 * ms),
           (pre + "retention/while/body/dot_general:", 1000 + 5 * ms,
            1000 + 9 * ms),
           (pre + "retention/jit(_state_pallas)/pallas_call:",
            1000 + 9 * ms, 1000 + 13 * ms),
           (pre + "ret_out/dot_general:", 1000 + 13 * ms, 1000 + 15 * ms),
           (pre + "mlp/dot_general:", 1000 + 15 * ms, 1000 + 30 * ms),
           (dec + "ret_in/qkv/dot_general:", 31 * ms, 32 * ms),
           (dec + "retention/jit(_step_pallas)/pallas_call:", 32 * ms,
            76 * ms),
           (dec + "ret_out/dot_general:", 76 * ms, 77 * ms),
           (dec + "mlp/dot_general:", 77 * ms, 87 * ms)]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    m = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    m["deployment"]["engine"]["decode_chunk"] = 2
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": None,
           "device": {"kind": "TPU v5 lite"}}
    got = {name: _reader(name)(run) for name in NEW}
    assert not [n for n, v in got.items() if v is None]
    counts = models.adapter("brumby").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")

    def least(ops_bytes):
        return max(ops_bytes[0] / f, ops_bytes[1] / b)

    assert got["decode_retention_ms"] == pytest.approx(44 / 2)
    assert got["retention_state_roofline_pct"] == pytest.approx(
        100 * counts.decode_state_bytes(m, 48 * 2) / b / 44e-3)
    assert got["prefill_retention_ms_per_ktok"] == pytest.approx(8 / 0.6)
    assert got["retention_prefill_roofline_pct"] == pytest.approx(
        100 * 4 * least(counts.retention_prompt_ops_bytes(m, 600, 2)) / 8e-3)
    assert got["retention_decode_mfu_pct"] == pytest.approx(
        100 * least(counts.decode_step_ops_bytes(m, [0] * 48, 2)) / 28e-3)
    assert all(0 < got[n] < 100 for n in NEW if n.endswith("_pct")), got
    # a program without the mixer's scopes (the parent's), no trace at all,
    # another stack's adapter: the new readers read nothing and raise nothing
    plain = program_trace.ProgramTrace(spans, modules, [
        (path.replace("ret_in/", "").replace("retention", "attn")
         .replace("ret_out", "attn_out"), s, e) for path, s, e in ops])
    monkeypatch.setattr(program_trace, "load", lambda run: plain)
    assert [_reader(n)(run) for n in NEW] == [None] * 5
    monkeypatch.setattr(program_trace, "load", lambda run: None)
    assert [_reader(n)(run) for n in NEW] == [None] * 5
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    other = cases.load(ROOT, "benchmark/configs/mistral-7b-v0.3-serve.json")
    assert [_reader(n)(dict(run, config=other)) for n in NEW
            if "roofline" in n or "mfu" in n] == [None] * 3


def test_the_engines_spans_carry_what_the_brumby_readers_read():
    """The names the readers look for are the ones the program emits: the
    mixer's three scopes in both lowered programs of this stack, and the span
    arguments in the engine and its model's books."""
    import jax
    import jax.numpy as jnp

    from benchmark import retention_trace
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models import serving
    from ray_tpu.models.serving import build_programs
    from ray_tpu.serve import engine as engine_mod

    adapter = models.adapter("brumby")
    m = cases.load(ROOT, f"benchmark/configs/{CONFIG}.json")
    cfg = adapter.build_config(dict(m, **adapter.REHEARSE), {
        "params": "float32", "activations": "float32"}, 128)
    built = build_programs(cfg, 2, 2, 16, 17)
    assert built.by_slot and not built.adopts and not built.takes_riders \
        and not built.paged
    params = jax.eval_shape(lambda: fuse_qkv(
        init_params(cfg, jax.random.PRNGKey(0)), cfg))
    caches = jax.eval_shape(built.empty)
    assert "state_bytes" in built.books(caches).counters()

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    scopes = retention_trace.SCOPES + ("qkv", "qk_norm", "mlp", "state_write")
    text = built.decode.lower(
        params, caches, arg((2, 8), jnp.int32), arg((2,), jnp.int32),
        arg((2,), jnp.int32), arg((2,), jnp.bool_), arg((2,), jnp.float32),
        arg((2,), jnp.int32), arg((2, 2), jnp.uint32)
        ).as_text(debug_info=True)
    for scope in scopes[:-1]:
        assert f"{scope}/" in text, scope
    assert "kv_write" not in text and "attn/" not in text
    text = built.prefill.lower(
        params, caches, arg((8,), jnp.int32), arg((1, 64), jnp.int32), 1,
        0.0, 0, arg((2,), jnp.uint32), 0).as_text(debug_info=True)
    for scope in scopes:
        assert f"{scope}/" in text, scope
    assert "kv_write" not in text
    src = open(engine_mod.__file__).read() + open(serving.__file__).read()
    for name in ("state_bytes", "live_kv_tokens", "active", "prompt_tokens",
                 "bucket"):
        assert f'"{name}"' in src or f"{name}=" in src, name


@pytest.mark.timeout(630)
def test_the_brumby_cell_rehearses_through_run_py():
    """`run.py --rehearse`: the adapter's `REHEARSE` over the configuration,
    `rehearse.json`'s engine, the whole control flow on the CPU through the
    cluster, the proxy and the engine: the run reaches its end (exit 3),
    serves its check's streams through the recurrent state with no page
    reserved, and reports. (Its `correct` reads false: the check asks 128
    tokens after each prompt, the rehearsal's `max_seq` is 128.)"""
    result, rec = rehearse(CELL)
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
    assert rec["config"]["hidden_size"] == 64           # REHEARSE's
    assert rec["config"]["head_dim"] == 16
    assert len(rec["check"]["prompt_lengths"]) == 8
    # bfloat16 at tiny widths against the float32 reference
    assert rec["check"]["mean_gap"] < 0.01
    paths = rec["replica"]["attention_paths"]
    assert paths.get("retention_state_reference") \
        and paths.get("retention_step_reference")
    assert not any("decode_" in p or p.startswith("fwd") for p in paths)
    assert rec["replica"]["peak_pages_used"] == 0

"""The benchmark's adapter contract for `sdar` (PR 53), seen by
tier-1: what tests/test_benchmark_adapters.py says of every adapter, for this
one; a file of its own so that its `run.py --rehearse` subprocess, the minute
of the family, runs beside the other cells' and not after them (`--dist
loadfile` keeps a file on one worker)."""

import re

import pytest

from benchmark import models
from test_benchmark_adapters import (ROOT,
                                     TIMELINE_READERS_OF_A_BATCH_CELL,
                                     cases, rehearse)

# ---------------------------------------------------------------------------
# sdar: a Qwen3-MoE decoder that generates by diffusion over blocks (PR 53)
# ---------------------------------------------------------------------------

SDAR_CELL = "serve-generate-sdar"
SDAR_CONFIG = "sdar-30b-a3b-chat-serve"
SDAR_NEW = ["denoise_forwards_per_token", "decode_forward_ms",
            "decode_unmask_ms", "block_decode_attn_roofline_pct",
            "block_prefill_attn_roofline_pct"]
# The readers that were there and serve this stack unchanged.
SDAR_SERVED = [
    "decode_moe_ms", "moe_experts_roofline_pct", "expert_load_max_over_mean",
    "kv_pages_peak_pct", "decode_sample_ms", "engine_slot_refill_ms"]
# Readers that would need an edit to serve the cell (PERF.md section 7):
# `decode_mfu_pct` asks `conv_trace.decodes`, which wants a conv operator's
# scopes in the program; the three readers of a prefill pair executions with
# admissions by position from the head of the trace, which this cell's
# admissions (one every 105 ms behind two chunks of 90) shift by one in most
# traces (`benchmark/block_trace.py::prefills` pairs by the emitter's spans).
# The causal kernels' shares read kernels this stack does not run.
SDAR_NOT = ["decode_mfu_pct", "prefill_ms_per_ktok",
            "prefill_moe_ms_per_ktok", "prefill_stall_pct",
            "decode_attn_roofline_pct",
            "attn_kernel_roofline", "hybrid_experts_roofline_pct",
            "head64_decode_attn_roofline_pct", "decode_rider_share_pct"]


def test_sdar_manifest_entries_are_the_catalogs_row_and_the_issues_cell():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    # (the manifest's last entries when PR 53 wrote this; later PRs append)
    entry = next(c for c in manifest["configs"] if c["name"] == SDAR_CONFIG)
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
        "config.json") and cfg["arch"] == "sdar"
    assert entry["reduced"] == list(cfg["reduced"]) == ["num_hidden_layers"]
    assert isinstance(cfg["assumed"], list) and len(cfg["assumed"]) >= 8
    cell = next(w for w in manifest["workloads"] if w["name"] == SDAR_CELL)
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        SDAR_CELL, SDAR_CONFIG, "generate-block-sdar", 1)
    for said in ("blocks of 4", "2 denoising forwards", "0.75 forwards",
                 "4 rows a slot"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    e2e = {p["name"]: p.get("workloads", []) for p in manifest["end_to_end"]}
    assert SDAR_CELL in e2e["batch_tokens_per_s"]
    assert all(SDAR_CELL in lists[n] for n in SDAR_SERVED)
    names = [p["name"] for p in manifest["per_layer"]]
    first = names.index(SDAR_NEW[0])
    assert names[first:first + 5] == SDAR_NEW
    for p in manifest["per_layer"][first:first + 5]:
        assert p["workloads"] == [SDAR_CELL] \
            and p["moves"] == "batch_tokens_per_s"
    mine = [n for n, cells in lists.items() if SDAR_CELL in cells]
    assert set(mine) == set(SDAR_SERVED) | set(SDAR_NEW) \
        | TIMELINE_READERS_OF_A_BATCH_CELL
    assert not [n for n in SDAR_NOT if SDAR_CELL in lists[n]]


def test_sdar_traffic_is_lfm2s_with_its_own_seed_and_a_check_of_every_tail():
    mix = cases.load(cases.BENCH, "traffic", "generate-block-sdar.json")
    lfm2 = cases.load(cases.BENCH, "traffic", "generate-long-lfm2.json")
    for key in ("kind", "arrivals", "prompt_tokens", "output_tokens",
                "drain_s", "trace"):
        assert mix.get(key) == lfm2.get(key), key
    assert mix["shape_seed"] == 5301 != lfm2["shape_seed"]
    found = re.search(r"R = ([\d,]+\.?\d*)", mix["what"])
    assert found, "the traffic file's `what` states R"
    assert float(found.group(1).replace(",", "")) >= 4096
    chk = mix["check"]
    assert chk["prompt_lengths"] == [301, 502, 1003, 100, 101, 102, 103, 100]
    assert {n % 4 for n in chk["prompt_lengths"]} == {0, 1, 2, 3}
    assert len(chk["prompt_lengths"]) * chk["tokens"] == 1024
    assert chk["logit_tolerance"] > chk["mean_logit_tolerance"] > 0
    assert "control" in chk["why"] and "3 of 3" in chk["why"]


def test_the_engines_spans_and_scopes_carry_what_the_sdar_readers_read():
    """The names `benchmark/block_trace.py` and the five readers look for are
    the ones the program emits: the block step's scopes in the lowered decode
    program beside the sparse feed-forward's, under the program names every
    trace reader asks for, and the span arguments and counters in the
    engine."""
    import jax
    import jax.numpy as jnp

    from benchmark import moe_trace
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs
    from ray_tpu.serve import engine as engine_mod

    adapter = models.adapter("sdar")
    m = cases.load(ROOT, f"benchmark/configs/{SDAR_CONFIG}.json")
    cfg = adapter.build_config(dict(m, **adapter.REHEARSE), {
        "params": "float32", "activations": "float32"}, 128)
    built = build_programs(cfg, 2, 8, 16, 17)
    assert built.block == 4 \
        and not built.adopts and not built.takes_riders \
        and not built.by_slot
    params = jax.eval_shape(lambda: fuse_qkv(
        init_params(cfg, jax.random.PRNGKey(0)), cfg))
    caches = jax.eval_shape(built.empty)
    # a chunk of 8 positions: two blocks of two forwards
    assert built.books(caches).dispatch(
        [0, 0], [False, False], 8, [], False)["forwards"] == 4

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    lowered = built.decode.lower(
        params, caches, arg((2, 8), jnp.int32), arg((2, 8), jnp.int32),
        arg((2,), jnp.int32), arg((2,), jnp.bool_), arg((2,), jnp.float32),
        arg((2,), jnp.int32), arg((2, 2), jnp.uint32))
    text = lowered.as_text(debug_info=True)
    assert lowered.as_text().startswith("module @jit_decode ")
    for scope in moe_trace.MOE_SCOPES + (
            "qkv", "attn", "attn_out", "kv_write", "head", "unmask",
            "unmask/sample", "commit/layers"):
        assert f"{scope}/" in text, scope
    lowered = built.prefill.lower(
        params, caches, arg((8,), jnp.int32), arg((1, 64), jnp.int32), 1,
        0.0, 0, arg((2,), jnp.uint32), None)
    assert lowered.as_text().startswith("module @jit_prefill ")
    from ray_tpu.models import serving
    src = open(engine_mod.__file__).read() + open(serving.__file__).read()
    for name in ("blocks", "forwards", "rows", "committed", "commits_rode",
                 "denoise_forwards", "block_tokens", "tail_tokens"):
        assert f'"{name}"' in src or f"{name}=" in src, name
    assert 'kind="opening"' in src


@pytest.mark.timeout(630)
def test_the_sdar_cell_rehearses_through_run_py():
    """`run.py --rehearse`: the adapter's `REHEARSE` over the configuration,
    `rehearse.json`'s engine, the whole control flow on the CPU through the
    cluster, the proxy and the engine: the run reaches its end (exit 3),
    serves its check's streams block by block, and reports. (Its `correct`
    reads false: the check asks 128 tokens after each prompt and the
    rehearsal's `max_seq` is 128.)"""
    result, rec = rehearse(SDAR_CELL)
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
    assert rec["config"]["hidden_size"] == 64           # REHEARSE's
    assert (rec["config"]["block_length"], rec["config"]["mask_id"]) == (
        4, 255)
    assert len(rec["check"]["prompt_lengths"]) == 8
    # bfloat16 at tiny widths against the float32 reference, every tail
    assert rec["check"]["mean_gap"] < 0.01
    paths = rec["replica"]["attention_paths"]
    assert paths.get("block_fwd_reference") \
        and paths.get("block_decode_reference") \
        and not paths.get("decode_reference")

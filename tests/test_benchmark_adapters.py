"""The benchmark's adapter contract (benchmark/models/<arch>.py), seen by
tier-1: the cluster-free cases of benchmark/tests/test_benchmark.py, imported
by name and run for the adapters `llama`, `olmoe`, `keye` and `jamba`, and what
`olmoe` adds: its refusals, its counts against a hand count, its readers on a
synthetic trace and on the engine's own spans; and for `keye` its manifest
entries against the catalog's row and the sparse-attention readers on a
synthetic trace (its block against its reference is tests/test_keye.py);
and for `jamba` the same three (its stack against its reference is
tests/test_jamba.py), as for `dots`, `mimo` and `lfm2`. No cluster, no port,
no clock. The three families whose cell rehearses through `run.py` (a
subprocess of a minute and more, with a cluster) have a file each, so that
three workers take the rehearsals at once: tests/
test_benchmark_adapters_granite.py, _sdar.py and _nemotron.py, which import
this file's constants and helpers.
"""

import dataclasses
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import models, program_trace  # noqa: E402
from benchmark.tests import test_benchmark as cases  # noqa: E402

ARCHS = ["llama", "olmoe", "keye", "jamba", "dots", "mimo", "lfm2",
         "granitemoehybrid", "sdar", "nemotron_h", "brumby"]
# config.json of allenai/OLMoE-1B-7B-0125-Instruct, as the catalog beside the
# model-configs guide has it.
OLMOE_PUBLISHED = dict(
    attention_bias=False, clip_qkv=None, hidden_act="silu", hidden_size=2048,
    intermediate_size=1024, max_position_embeddings=4096, model_type="olmoe",
    norm_topk_prob=False, num_attention_heads=16, num_experts=64,
    num_experts_per_tok=8, num_hidden_layers=16, num_key_value_heads=16,
    rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    tie_word_embeddings=False, vocab_size=50304)

# PR 51's readers over the session's timeline that a closed-loop serve cell
# lists: the set-up's four and the window's three.
TIMELINE_READERS_OF_A_BATCH_CELL = {
    "setup_boot_s", "setup_warm_s", "setup_compile_s", "setup_check_s",
    "decode_occupancy_window_pct", "engine_slot_refill_window_ms",
    "engine_window_tokens_per_s"}

def rehearse(cell):
    """`run.py --rehearse` of a cell in a subprocess (the adapter's `REHEARSE`
    over the configuration, `rehearse.json`'s engine, the whole control flow
    on the CPU through the cluster, the proxy and the engine): the line it
    reports and the record it leaves. The window is 12 s: under six workers,
    with the other cells' rehearsals beside it, a window of 4 s has closed
    before the first request ended, and a run that attempted nothing shows
    nothing. The result is the last JSON line: an interpreter's warning at
    exit may follow it on a loaded machine."""
    import json
    p = cases._run_rehearsal(ROOT, cell, 0, "12")
    assert p.returncode == 3, p.stderr[-3000:]   # a rehearsal is not a result
    assert p.stdout.strip() == ""
    result = json.loads([line for line in p.stderr.splitlines()
                         if line.startswith('{"correct"')][-1])
    with open(os.path.join(cases.BENCH, "out", cell, "2147483999",
                           "run-trace0.json")) as f:
        return result, json.load(f)


@pytest.mark.parametrize("arch", ARCHS)
def test_adapter_exposes_the_whole_contract(arch):
    cases.test_adapter_exposes_the_whole_contract(arch, None)


def test_llama_reference_agrees_with_the_program_at_rehearsal_widths():
    cases.test_reference_agrees_with_the_program_at_tiny_widths("llama", None)


def test_olmoe_reference_agrees_with_the_program_at_rehearsal_widths():
    """As the case above, through the contract alone: the serve check's gaps
    and the train check's comparison, program against reference on the same
    float32 weights."""
    import jax.numpy as jnp

    from benchmark.train_loop import _check_against_reference
    from ray_tpu.models import llama

    adapter, model, cfg, params = cases._tiny("olmoe")
    assert (cfg.n_experts, cfg.top_k_experts, cfg.d_ff, cfg.norm_topk_prob,
            cfg.qk_norm, cfg.moe_aux_weight) == (8, 2, 32, False, True, 0.0)
    toks = np.random.default_rng(0).integers(0, 256, (2, 96), dtype=np.int32)
    want = np.asarray(llama.forward(params, jnp.asarray(toks[:1]), cfg))[0]
    ref = adapter.reference()
    prompt, tail = [int(t) for t in toks[0, :80]], [int(t) for t in toks[0, 80:]]
    gaps = ref.served_token_gaps(params, model, prompt, tail + [5])
    rows, served = want[79:], np.asarray(tail + [5])
    assert np.allclose(gaps, rows.max(-1) - rows[np.arange(17), served],
                       atol=2e-4)
    chk = _check_against_reference(adapter, params, jnp.asarray(toks), cfg,
                                   None, model, 64)
    assert chk["loss_rel_err"] < 1e-5 and chk["param_dtypes"] == ["float32"]
    assert set(chk["grad_rel_err"]) == {
        "final_norm", "last_attn_norm", "last_mlp_norm", "last_router",
        "last_q_norm", "last_k_norm"}
    assert max(chk["grad_rel_err"].values()) < 1e-4, chk


def test_dense_counts_are_reached_through_the_adapter():
    cases.test_dense_counts_are_reached_through_the_adapter()


def test_olmoe_counts_against_a_hand_count():
    """At the published widths; `intermediate_size` is ONE expert's width."""
    counts = models.adapter("olmoe").counts
    m = dict(OLMOE_PUBLISHED)
    attn = 4 * 2048 * 2048                      # q, k, v, o: MHA, 16 x 128
    expert = 3 * 2048 * 1024
    assert (attn, expert) == (16_777_216, 6_291_456)
    active = attn + 2048 * 64 + 8 * expert      # + the router + 8 experts
    assert active == 67_239_936
    layer = attn + 2048 * 64 + 64 * expert + 2 * 2048 + 2 * 2048
    assert layer == 419_569_664                 # ISSUE 27: "419.6 M a layer"
    head = 2048 * 50304
    assert counts.total_params(m) == 16 * layer + 2 * head + 2048 \
        == 6_919_161_856                        # the 7 B of the model's name
    causal = 4.0 * 16 * 128 * (4096 * 4097 / 2)
    assert counts.prefill_flops(m, 4096) == \
        2.0 * 16 * active * 4096 + 16 * causal + 2.0 * head
    assert counts.train_flops_per_token(m, 4096) == \
        3 * (2.0 * (16 * active + head) + 16 * causal / 4096)
    # one layer's grouped matmuls: 8 assignments a token, 55 experts touched
    ops, byts = counts.experts_ops_bytes(m, 16 * 8, 55, 2, 2)
    assert ops == 2.0 * expert * 128 == 1_610_612_736
    assert byts == 55 * expert * 2 + 2 * 128 * 2048 * 2
    # a decode step: the touched experts' weights, never all 64 by assumption
    ops, byts = counts.decode_step_ops_bytes(m, [100, 28], 2, 2,
                                             experts_touched=12.5)
    assert ops == 2 * 2.0 * (16 * active + head) + 16 * 4.0 * 16 * 128 * 128
    assert byts == 2 * (16 * (attn + 2048 * 64 + 4 * 2048 + 12.5 * expert)
                        + head + 2048) + 16 * (2 * 16 * 128 * 2) * 128
    with pytest.raises(TypeError):
        counts.decode_step_ops_bytes(m, [100], 2, 2)


def test_manifest_is_consistent_with_the_files():
    cases.test_manifest_is_consistent_with_the_files()
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "olmoe-1b-7b-serve")
    cfg = cases.load(ROOT, entry["file"])
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    differs = {k for k, v in OLMOE_PUBLISHED.items() if cfg.get(k, "-") != v}
    assert differs == {"num_hidden_layers"}
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 16
    assert cfg["reduced"]["num_hidden_layers"]["run"] == \
        cfg["num_hidden_layers"]
    assert entry["source"] == cfg["source_url"]
    eng = cfg["deployment"]["engine"]
    assert eng["kv_pages"] == 1 + eng["n_slots"] * eng["max_seq"] // eng["page_size"]
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "serve-batch-olmoe")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("olmoe-1b-7b-serve", "batch-summarize-olmoe", 1)
    # its mix is `serve-batch`'s number for number: the driver (which asks
    # the adapter before a cluster starts), the words and the check differ.
    # The check keeps a prompt for each bucket of the mix, reads 1,024 served
    # tokens where 96 let the int8 control pass, and its limits are tighter.
    mine = cases.load(cases.BENCH, "traffic", "batch-summarize-olmoe.json")
    theirs = cases.load(cases.BENCH, "traffic", "batch-summarize.json")
    differ = {k for k in mine if mine[k] != theirs.get(k)}
    assert differ == {"kind", "what", "check"}
    chk, base = mine["check"], theirs["check"]
    assert set(chk) == set(base)
    assert chk["prompt_lengths"][:3] == base["prompt_lengths"]
    assert len(chk["prompt_lengths"]) * chk["tokens"] == 1024
    assert chk["logit_tolerance"] < base["logit_tolerance"]
    assert chk["mean_logit_tolerance"] < base["mean_logit_tolerance"]
    assert mine["kind"] == "serve_closed_checked"


@pytest.mark.parametrize("key,value", [
    ("shared_expert_intermediate_size", 1024), ("attention_bias", True),
    ("clip_qkv", 8.0), ("tie_word_embeddings", True)],
    ids=["shared-expert", "bias", "clip_qkv", "tied-embeddings"])
def test_olmoe_refuses_what_its_block_does_not_compute(key, value):
    adapter = models.adapter("olmoe")
    adapter.check_supported(OLMOE_PUBLISHED)
    with pytest.raises(ValueError, match="cannot run this model"):
        adapter.check_supported(dict(OLMOE_PUBLISHED, **{key: value}))


def test_olmoe_build_config_names_the_field_an_older_program_lacks(monkeypatch):
    """The parent of PR 27 has no `norm_topk_prob` and no `qk_norm`: the
    adapter must say so, not run another block under OLMoE's name."""
    from ray_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Older:
        n_experts: int = 0
        top_k_experts: int = 2
        moe_aux_weight: float = 0.01

    monkeypatch.setattr(llama, "LlamaConfig", Older)
    with pytest.raises(ValueError, match=r"norm_topk_prob.*qk_norm"):
        models.adapter("olmoe").build_config(
            dict(OLMOE_PUBLISHED), {"params": "bfloat16",
                                    "activations": "bfloat16"}, 4096)


# -- the readers of the four `moe` metrics -----------------------------------

def _reader(name):
    from benchmark.run import HERE, load_reader
    return load_reader(HERE, "layer_metrics", name)


def _synthetic_trace():
    """One decode chunk of 2 steps x 1 layer and one prefill, in nanoseconds,
    between edge programs that `whole_modules` drops; `while` bodies enclose
    their instructions as on the chip."""
    P = "jit(prefill)/layers/while/body/"
    D = "jit(decode)/while/body/layers/while/body/"
    ops = [("jit(prefill)/layers/while", 1000, 2000),
           (P + "qkv/dot_general:", 1000, 1100),
           (P + "qkv/qk_norm/mul:", 1100, 1150),
           (P + "mlp/router/dot_general:", 1150, 1200),
           (P + "mlp/moe_dispatch/sort:", 1200, 1300),
           ("", 1300, 1700),              # XLA's ragged-dot kernel: no scope
           (P + "mlp/experts/mul:", 1700, 1800),
           (P + "mlp/moe_combine/gather:", 1800, 1900),
           ("jit(decode)/while", 3000, 4000),
           (D + "attn/dot_general:", 3000, 3300),
           (D + "mlp/experts/ragged_dot:", 3300, 3700),
           (D + "mlp/moe_combine/gather:", 3700, 3800),
           (D + "mlp_norm/mul:", 3800, 3900)]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 3000, 4000), ("jit_poke", 5000, 5010)]
    Span = program_trace.Span
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=2000, bucket=2048,
            queue_wait_us=1)),
        Span("serve.engine.emit", 2010, 2020, dict(rid=7, kind="first")),
        Span("serve.engine.prefill_experts", 2020, 2020, dict(rid=7,
                                                              touched=64)),
        Span("serve.engine.decode_dispatch", 2900, 2950, dict(
            useful=16, capacity=32, active=16, experts_touched=0,
            expert_tokens="0:0:0:0")),
        Span("serve.engine.decode_dispatch", 4100, 4150, dict(
            useful=16, capacity=32, active=16, experts_touched=100,
            expert_tokens="10:40:20:10")),
    ]
    return program_trace.ProgramTrace(spans, modules, ops)


def _device_view(names):
    """`trace.py`'s view of chip 0: events by their HLO text."""
    from benchmark import trace
    return trace.Trace([trace.Chip("chip0", names, [], [])], [], 0.0, 1.0)


def test_moe_readers_on_a_synthetic_trace(monkeypatch):
    from benchmark import moe_trace, peaks

    t = _synthetic_trace()
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    kernel = _device_view([
        ("%ragged-dot-none.2 = bf16[32768,1024]{1,0} custom-call(%a, %b), "
         'custom_call_target="tpu_custom_call"', 1300.9, 1700.2),
        ("%fusion.1 = bf16[4096,2048]{1,0} fusion(%c)", 1700.0, 1800.0)])
    per = moe_trace.by_scope({"trace_data": kernel}, t,
                             [m for m in t.modules if m[0] == "jit_prefill"])
    assert per[0]["experts"] == 500 and "" not in per[0]
    assert moe_trace.self_ns(t, t.modules[1:2])[0][""] == 400
    # program_trace's own vocabulary still charges the sparse scopes to `mlp`
    assert t.scope_ms("jit_decode")["mlp"] == pytest.approx(500 / 1e6)
    per = moe_trace.self_ns(t, t.whole_modules("jit_decode"))
    assert per == [{"attn": 300, "experts": 400, "moe_combine": 100,
                    "mlp_norm": 100, "": 100}]
    m = dict(OLMOE_PUBLISHED, arch="olmoe", num_hidden_layers=1,
             dtypes={"params": "bfloat16", "activations": "bfloat16"},
             deployment={"engine": {"decode_chunk": 2}})
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": kernel,
           "device": {"kind": "TPU v5 lite"}}
    assert _reader("decode_moe_ms")(run) == pytest.approx(500 / 1e6 / 2)
    assert _reader("prefill_moe_ms_per_ktok")(run) == \
        pytest.approx(750 / 1e6 / 2.0)
    assert _reader("expert_load_max_over_mean")(run) == pytest.approx(2.0)
    counts = models.adapter("olmoe").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")

    def least(rows, touched):
        ops, byts = counts.experts_ops_bytes(m, rows * 8, touched, 2, 2)
        return max(ops / f, byts / b)

    want = (least(2000, 64) + 2 * least(16, 50.0)) / ((500 + 400) / 1e9)
    assert _reader("moe_experts_roofline_pct")(run) == \
        pytest.approx(100 * want)
    # a dense program's trace: no such scopes, kernels or counters, no metric
    sparse_only = re.compile("/(experts|moe_combine|moe_dispatch|router)")
    dense = program_trace.ProgramTrace(
        [program_trace.Span(sp.name, sp.start, sp.end, {
            k: v for k, v in sp.args.items()
            if k not in ("experts_touched", "expert_tokens")})
         for sp in t.spans if sp.name != "serve.engine.prefill_experts"],
        t.modules, [(sparse_only.sub("", p), s, e) for p, s, e in t.ops])
    monkeypatch.setattr(program_trace, "load", lambda run: dense)
    for name in ("decode_moe_ms", "prefill_moe_ms_per_ktok",
                 "expert_load_max_over_mean", "moe_experts_roofline_pct"):
        assert _reader(name)(dict(run, trace_data=_device_view([]))) is None


def test_engine_spans_carry_what_the_readers_read(tmp_path, monkeypatch):
    """The engine's own spans through the profiler and back: `:`-joined
    running tokens per expert and the chunk before's distinct experts on
    `serve.engine.decode_dispatch`, `touched` by `rid` on
    `serve.engine.prefill_experts`."""
    import jax

    from ray_tpu.serve.engine import Engine

    adapter, model, cfg, params = cases._tiny("olmoe")
    eng = Engine(params, cfg, n_slots=4, decode_chunk=4, page_size=16)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        outs = [eng.submit(list(range(3, 3 + n)), 24) for n in (20, 33)]
        for q in outs:
            while q.get(timeout=120) is not None:
                pass
        jax.profiler.stop_trace()
        routed = eng.counters()
    finally:
        eng.stop()
    t = program_trace.load_path(str(tmp_path))
    chunks = t.named("serve.engine.decode_dispatch")
    assert len(chunks) >= 6
    last = [int(n) for n in str(chunks[-1].args["expert_tokens"]).split(":")]
    assert len(last) == 8 and 0 < sum(last) <= sum(routed["expert_tokens"])
    # 2 layers x 4 steps x at most 2 slots x 2 experts a token
    assert 2 * 4 * 2 <= chunks[-1].args["experts_touched"] <= 2 * 4 * 2 * 2
    pre = t.named("serve.engine.prefill_experts")
    assert sorted(s.args["rid"] for s in pre) == [0, 1]
    assert all(2 * 2 <= s.args["touched"] <= 2 * 8 for s in pre)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    assert 1.0 <= _reader("expert_load_max_over_mean")({}) <= 8.0
    assert routed["decode_experts_touched"] >= \
        sum(s.args["experts_touched"] for s in chunks)


# -- arch `keye`: the manifest's entries, and the sparse-attention readers ----

# `config` of the catalog's row Keye-VL-2.0-30B-A3B (the language model's keys
# of Kwai-Keye/Keye-VL-2.0-30B-A3B config.json).
KEYE_PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=262144, max_window_layers=48, mlp_only_layers=[],
    model_type="KeyeVL2", moe_intermediate_size=768, norm_topk_prob=True,
    num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=48, num_key_value_heads=4, num_local_experts=128,
    rms_norm_eps=1e-06,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 2048},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=151936)


def test_keye_manifest_entries_are_the_catalogs_row_cut_in_depth_alone():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b-serve")
    cfg = cases.load(ROOT, entry["file"])
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    differs = {k for k, v in KEYE_PUBLISHED.items() if cfg.get(k, "-") != v}
    assert differs == {"num_hidden_layers"}
    cut = cfg["reduced"]["num_hidden_layers"]
    assert cut["published"] == 48 and 4 <= cut["run"] <= 7
    assert cut["run"] == cfg["num_hidden_layers"]
    assert entry["source"] == cfg["source_url"] and cfg["arch"] == "keye"
    eng = cfg["deployment"]["engine"]
    assert eng["kv_pages"] == 1 + eng["n_slots"] * eng["max_seq"] // eng["page_size"]
    models.adapter("keye").check_supported(cfg)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "serve-longdoc-keye")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("keye-vl-2.0-30b-a3b-serve", "longdoc-qa-keye", 1)
    mix = cases.load(cases.BENCH, "traffic", "longdoc-qa-keye.json")
    assert mix["kind"] == "serve_closed_checked"
    # every prompt lands in the engine's widest bucket, past top-k, and a
    # request fits max_seq; the check holds one prompt in that bucket
    top, seq = cfg["sa_config"]["topk"], eng["max_seq"]
    assert seq // 2 < mix["prompt_tokens"]["min"] and top < seq // 2
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= seq
    assert any(seq // 2 < n < seq - mix["check"]["tokens"]
               for n in mix["check"]["prompt_lengths"])
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in manifest[g]
                if "serve-longdoc-keye" in m.get("workloads", ())}
    assert reported >= {
        "batch_tokens_per_s", "prefill_ms_per_ktok", "kv_pages_peak_pct",
        "prefill_moe_ms_per_ktok", "decode_moe_ms",
        "moe_experts_roofline_pct", "expert_load_max_over_mean",
        "prefill_index_ms_per_ktok", "decode_sparse_attn_ms",
        "sparse_decode_roofline_pct", "index_roofline_pct",
        "selected_share_pct"}
    assert "decode_attn_roofline_pct" not in reported  # not its kernel


def test_sparse_attention_readers_on_a_synthetic_trace(monkeypatch):
    from benchmark import peaks, sparse_attn_trace

    P = "jit(prefill)/layers/while/body/"
    D = "jit(decode)/while/body/layers/while/body/"
    ops = [("jit(prefill)/layers/while", 1000, 2000),
           (P + "qkv/dot_general:", 1000, 1100),
           (P + "attn/select/pallas_call:", 1100, 1500),
           (P + "attn/sparse_attn/pallas_call:", 1500, 1800),
           (P + "attn/transpose:", 1800, 1900),
           ("jit(decode)/while", 3000, 4000),
           (D + "attn/indexer/dot_general:", 3000, 3100),
           (D + "attn/select/top_k:", 3100, 3300),
           (D + "attn/sparse_attn/gather:", 3300, 3700),
           (D + "attn/reshape:", 3700, 3750),
           (D + "mlp/experts/ragged_dot:", 3750, 3900)]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 3000, 4000), ("jit_poke", 5000, 5010)]
    Span = program_trace.Span
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=8000, bucket=8192,
            queue_wait_us=1)),
        Span("serve.engine.emit", 2010, 2020, dict(rid=7, kind="first")),
        Span("serve.engine.decode_dispatch", 2900, 2950, dict(
            useful=16, capacity=32, active=16, selected_keys=2 * 16 * 2048,
            live_keys=2 * 16 * 7000))]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    # program_trace's own vocabulary still charges the deeper scopes to `attn`
    assert t.scope_ms("jit_decode")["attn"] == pytest.approx(750 / 1e6)
    per = sparse_attn_trace.by_scope(t, t.whole_modules("jit_decode"))
    assert per == [{"indexer": 100, "select": 200, "sparse_attn": 400,
                    "attn": 50, "experts": 150, "": 100}]
    m = dict(KEYE_PUBLISHED, arch="keye", num_hidden_layers=1,
             dtypes={"params": "bfloat16", "activations": "bfloat16"},
             deployment={"engine": {"decode_chunk": 2}})
    run = {"config": m, "cell": "x", "seed": 0,
           "device": {"kind": "TPU v5 lite"}}
    assert _reader("decode_sparse_attn_ms")(run) == \
        pytest.approx(700 / 1e6 / 2)
    assert _reader("prefill_index_ms_per_ktok")(run) == \
        pytest.approx(400 / 1e6 / 8.0)
    assert _reader("selected_share_pct")(run) == \
        pytest.approx(100 * 2048 / 7000)
    counts = models.adapter("keye").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")
    ops_, byts = counts.sparse_decode_counts(m, 2 * 16 * 2048, 2 * 16 * 7000, 2)
    assert byts / b > ops_ / f             # the gather's bytes bound it
    assert _reader("sparse_decode_roofline_pct")(run) == \
        pytest.approx(100 * (byts / b) / (700 / 1e9))
    ops_, byts = counts.index_select_ops_bytes(m, 8000, 2)
    assert _reader("index_roofline_pct")(run) == \
        pytest.approx(100 * max(ops_ / f, byts / b) / (400 / 1e9))
    # a program without the scopes or counters (the parent; a dense model)
    deeper = re.compile("/(indexer|select|sparse_attn)")
    plain = program_trace.ProgramTrace(
        [Span(sp.name, sp.start, sp.end, {
            k: v for k, v in sp.args.items()
            if k not in ("selected_keys", "live_keys")}) for sp in spans],
        modules, [(deeper.sub("", p), s, e) for p, s, e in ops])
    monkeypatch.setattr(program_trace, "load", lambda run: plain)
    for name in ("decode_sparse_attn_ms", "prefill_index_ms_per_ktok",
                 "selected_share_pct", "sparse_decode_roofline_pct",
                 "index_roofline_pct"):
        assert _reader(name)(run) is None, name


# -- arch `jamba`: the manifest's entries, and the state-space readers --------

# `config` of the catalog's row AI21-Jamba2-3B (ai21labs/AI21-Jamba2-3B
# config.json).
JAMBA_PUBLISHED = dict(
    attn_layer_offset=7, attn_layer_period=14, expert_layer_offset=1,
    expert_layer_period=2, hidden_act="silu", hidden_size=2560,
    intermediate_size=8192, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
    mamba_proj_bias=False, max_position_embeddings=262144,
    model_type="jamba", num_attention_heads=20, num_experts=1,
    num_experts_per_tok=1, num_hidden_layers=28, num_key_value_heads=1,
    num_logits_to_keep=1, rms_norm_eps=1e-06, sliding_window=None,
    tie_word_embeddings=True, use_mamba_kernels=True, vocab_size=65536)


def test_jamba_manifest_entries_are_the_catalogs_row_uncut():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "jamba2-3b-serve")
    cfg = cases.load(ROOT, entry["file"])
    assert entry["reduced"] == [] == list(cfg["reduced"])
    assert not {k for k, v in JAMBA_PUBLISHED.items() if cfg.get(k, "-") != v}
    assert entry["source"] == cfg["source_url"] and cfg["arch"] == "jamba"
    eng = cfg["deployment"]["engine"]
    assert eng["kv_pages"] == 1 + eng["n_slots"] * eng["max_seq"] // eng["page_size"]
    adapter = models.adapter("jamba")
    adapter.check_supported(cfg)
    # the layer pattern 7/14, the tied head, no positions, MQA 20 on 1
    built = adapter.build_config(cfg, cfg["dtypes"], eng["max_seq"])
    assert built.attn_layers == (7, 21) and built.kv_layers == 2
    assert built.segments() == (("mamba", 0, 7), ("attn", 0, 1),
                                ("mamba", 7, 20), ("attn", 1, 2),
                                ("mamba", 20, 26))
    assert built.tie_embeddings and not built.rope
    assert (built.n_heads, built.n_kv_heads, built.head_dim, built.ssm_inner,
            built.ssm_state, built.ssm_dt_rank, built.ssm_conv) == \
        (20, 1, 128, 5120, 16, 160, 4)
    assert adapter.counts.total_params(cfg) == 3_029_337_472
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "serve-batch-jamba2")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("jamba2-3b-serve", "batch-summarize-jamba2", 1)
    mix = cases.load(cases.BENCH, "traffic", "batch-summarize-jamba2.json")
    theirs = cases.load(cases.BENCH, "traffic", "batch-summarize.json")
    assert mix["kind"] == "serve_closed_checked"
    for key in ("arrivals", "prompt_tokens", "output_tokens", "trace"):
        assert mix[key] == theirs[key], key
    assert mix["shape_seed"] != theirs["shape_seed"]
    # a prompt in the widest bucket with padding behind it, and one whose
    # decode crosses a page boundary
    chk, seq, page = mix["check"], eng["max_seq"], eng["page_size"]
    assert any(seq // 2 < n < seq - chk["tokens"]
               for n in chk["prompt_lengths"])
    assert any(n // page != (n + chk["tokens"]) // page
               for n in chk["prompt_lengths"])
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in manifest[g]
                if "serve-batch-jamba2" in m.get("workloads", ())}
    assert reported == {
        "batch_tokens_per_s", "prefill_ms_per_ktok", "kv_pages_peak_pct",
        "prefill_ssm_ms_per_ktok", "scan_roofline_pct", "decode_ssm_ms",
        "decode_state_roofline_pct",
        "engine_slot_refill_ms", "prefill_stall_pct",        # PR 37
        "decode_sample_ms"} | TIMELINE_READERS_OF_A_BATCH_CELL  # PRs 47, 51
    # not `decode_attn_roofline_pct`: its bytes multiply by ALL the layers


def test_jamba_build_config_names_the_field_an_older_program_lacks(monkeypatch):
    """The parent of PR 35 has no state-space fields: the adapter must say
    so in the parent process, in `build_config`, not run another stack under
    Jamba's name nor leave it to a replica's constructor."""
    from ray_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Older:
        n_experts: int = 0
        index_topk: int = 0

    monkeypatch.setattr(llama, "LlamaConfig", Older)
    with pytest.raises(ValueError, match=r"ssm_state.*attn_layers.*tie_emb"):
        models.adapter("jamba").build_config(
            dict(JAMBA_PUBLISHED), {"params": "bfloat16",
                                    "activations": "bfloat16"}, 4096)


def test_state_space_readers_on_a_synthetic_trace(monkeypatch):
    from benchmark import peaks, ssm_trace

    P = "jit(prefill)/layers/while/body/"
    D = "jit(decode)/while/body/layers/while/body/"
    ops = [("jit(prefill)/layers/while", 1000, 2000),
           (P + "ssm_in/dot_general:", 1000, 1100),
           (P + "conv/mul:", 1100, 1150),
           (P + "ssm_params/dot_general:", 1150, 1200),
           (P + "scan/pallas_call:", 1200, 1600),
           (P + "ssm_out/dot_general:", 1600, 1700),
           (P + "mlp/dot_general:", 1700, 1900),
           ("jit(prefill)/layers/attn/pallas_call:", 1900, 1950),
           ("jit(decode)/while", 3000, 4000),
           (D + "ssm_in/dot_general:", 3000, 3100),
           (D + "conv/mul:", 3100, 3120),
           (D + "ssm_params/dot_general:", 3120, 3200),
           (D + "scan/exp:", 3200, 3400),
           (D + "scan/state_write/dynamic_update_slice:", 3400, 3500),
           (D + "ssm_out/dot_general:", 3500, 3600),
           (D + "mlp/dot_general:", 3600, 3900)]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 3000, 4000), ("jit_poke", 5000, 5010)]
    Span = program_trace.Span
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=3000, bucket=4096,
            queue_wait_us=1)),
        Span("serve.engine.emit", 2010, 2020, dict(rid=7, kind="first")),
        Span("serve.engine.decode_dispatch", 2900, 2950, dict(
            useful=16, capacity=32, active=15, live_kv_tokens=30000))]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    # program_trace's own vocabulary charges the mixer's scopes to `layers`
    assert t.scope_ms("jit_decode")["layers"] == pytest.approx(600 / 1e6)
    per = ssm_trace.by_scope(t, t.whole_modules("jit_decode"))
    assert per == [{"ssm_in": 100, "conv": 20, "ssm_params": 80, "scan": 300,
                    "ssm_out": 100, "mlp": 300, "": 100}]
    m = dict(JAMBA_PUBLISHED, arch="jamba",
             dtypes={"params": "bfloat16", "activations": "bfloat16"},
             deployment={"engine": {"decode_chunk": 2}})
    run = {"config": m, "cell": "x", "seed": 0,
           "device": {"kind": "TPU v5 lite"}}
    assert _reader("decode_ssm_ms")(run) == pytest.approx(600 / 1e6 / 2)
    assert _reader("prefill_ssm_ms_per_ktok")(run) == \
        pytest.approx(700 / 1e6 / 3.0)
    counts = models.adapter("jamba").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")
    ops_, byts = counts.selective_scan_ops_bytes(m, 3000, 2)
    assert byts / b > ops_ / f         # no vector peak: the bytes bound it
    assert _reader("scan_roofline_pct")(run) == \
        pytest.approx(100 * 26 * (byts / b) / (400 / 1e9))
    moved = counts.decode_state_bytes(m, 15 * 2, 2)
    assert moved == 2 * 30 * 26 * 5120 * (16 * 4 + 3 * 2)
    assert _reader("decode_state_roofline_pct")(run) == \
        pytest.approx(100 * (moved / b) / (300 / 1e9))
    # a program without the scopes (the parent; any other model)
    deeper = re.compile("/(ssm_in|conv|ssm_params|scan|ssm_out)")
    plain = program_trace.ProgramTrace(
        spans, modules, [(deeper.sub("", p), s, e) for p, s, e in ops])
    monkeypatch.setattr(program_trace, "load", lambda run: plain)
    for name in ("decode_ssm_ms", "prefill_ssm_ms_per_ktok",
                 "scan_roofline_pct", "decode_state_roofline_pct"):
        assert _reader(name)(run) is None, name
    # and a model whose counts know no state, whatever the trace holds
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    other = dict(run, config=dict(OLMOE_PUBLISHED, arch="olmoe",
                                  dtypes=m["dtypes"],
                                  deployment=m["deployment"]))
    for name in ("scan_roofline_pct", "decode_state_roofline_pct"):
        assert _reader(name)(other) is None, name


# -- arch `dots`: the manifest's entries, and the latent-attention readers ----

def test_dots_manifest_entries_are_the_catalogs_row_cut_to_a_share():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]     # PR 39's; later PRs after
                 if c["name"] == "dots.vlm1.inst-serve")
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] and cfg["arch"] == "dots"
    assert entry["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    eng = cfg["deployment"]["engine"]
    assert (eng["n_slots"], eng["max_seq"], eng["decode_chunk"]) == (32, 4096, 8)
    assert eng["kv_pages"] == 1 + eng["n_slots"] * eng["max_seq"] // eng["page_size"]
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "serve-batch-dots-vlm1")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots.vlm1.inst-serve", "batch-summarize-dots-vlm1", 1)
    mine = cases.load(cases.BENCH, "traffic", "batch-summarize-dots-vlm1.json")
    theirs = cases.load(cases.BENCH, "traffic", "batch-summarize-jamba2.json")
    assert {k for k in mine if mine[k] != theirs.get(k)} == {
        "what", "arrivals", "shape_seed", "check"}
    assert mine["arrivals"] == dict(theirs["arrivals"], clients=64)
    assert len(mine["check"]["prompt_lengths"]) * mine["check"]["tokens"] == 1024
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    new = ["prefill_mla_ms_per_ktok", "decode_mla_ms",
           "latent_decode_roofline_pct", "latent_prefill_attn_roofline_pct",
           "moe_share_experts_roofline_pct", "local_assignment_share_pct"]
    at = list(lists).index(new[0])      # appended by PR 39; later PRs after
    assert list(lists)[at:at + 6] == new
    assert all(lists[n][0] == "serve-batch-dots-vlm1" for n in new)
    # no share of a roofline that counts work this chip does not do
    for name in ("moe_experts_roofline_pct", "decode_attn_roofline_pct"):
        assert "serve-batch-dots-vlm1" not in lists[name]
    for name in ("prefill_ms_per_ktok", "kv_pages_peak_pct", "decode_moe_ms",
                 "prefill_moe_ms_per_ktok", "expert_load_max_over_mean",
                 "engine_slot_refill_ms", "prefill_stall_pct"):
        assert "serve-batch-dots-vlm1" in lists[name]


def test_latent_attention_readers_on_a_synthetic_trace(monkeypatch):
    """The six readers of PR 39 on a trace built by hand: a prefill of 2,000
    prompt tokens and one decode chunk of 2 steps, their scopes, the prompt
    kernel's event, the counters on the spans. A program without the scopes
    (the parent, every other model) reads None and raises nothing."""
    from benchmark import latent_trace, peaks
    Span = program_trace.Span
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=2000, bucket=2048,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.emit", 2100, 2110, dict(rid=7, kind="first")),
        Span("serve.engine.prefill_experts", 2120, 2120, dict(
            rid=7, touched=8, local=1000, routed=16000)),
        Span("serve.engine.decode_dispatch", 2200, 2210, dict(
            useful=16, capacity=16, active=2, live_kv_tokens=4000,
            experts_touched=6, expert_tokens="1:2", local_assignments=4,
            routed_assignments=32)),
        Span("serve.engine.decode_dispatch", 3200, 3210, dict(
            useful=16, capacity=16, active=2, live_kv_tokens=4000,
            experts_touched=6, expert_tokens="3:6", local_assignments=4,
            routed_assignments=32)),
    ]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 2300, 3000), ("jit_poke", 4000, 4010)]
    pre = "jit(prefill)/layers/while/body/"
    dec = "jit(decode)/while/body/layers/while/body/"
    ops = [(pre + "qkv/q_latent/dot_general:", 1000, 1200),
           (pre + "qkv/kv_up/dot_general:", 1200, 1300),
           (pre + "attn/latent_flash_fwd/pallas_call:", 1300, 1700),
           (pre + "mlp/experts/ragged_dot:", 1700, 1800),
           (pre + "mlp/shared_expert/dot_general:", 1800, 2000),
           (dec + "qkv/absorb/dot_general:", 2300, 2400),
           (dec + "attn/paged_latent_decode/pallas_call:", 2400, 2700),
           (dec + "attn_out/absorb/dot_general:", 2700, 2800),
           (dec + "mlp/experts/ragged_dot:", 2800, 3000)]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    flash = ("%latent_flash_fwd.1 = bf16[128,2048,128]{2,1,0} custom-call("
             "bf16[128,2048,128]{2,1,0} %a, bf16[128,2048,64]{2,1,0} %b, "
             "bf16[128,2048,128]{2,1,0} %c, bf16[1,2048,64]{2,1,0} %d, "
             "bf16[128,2048,128]{2,1,0} %e), "
             'custom_call_target="tpu_custom_call"')
    other = ("%flash_fwd.1 = bf16[32,2048,128]{2,1,0} custom-call("
             "bf16[32,2048,128]{2,1,0} %a, bf16[32,2048,128]{2,1,0} %b, "
             "bf16[32,2048,128]{2,1,0} %c), "
             'custom_call_target="tpu_custom_call"')
    kernel = _device_view([(flash, 1300.0, 1700.0), (other, 1.0, 2.0)])
    m = cases.load(ROOT, "benchmark/configs/dots.vlm1.inst-serve.json")
    m["deployment"]["engine"]["decode_chunk"] = 2
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": kernel,
           "device": {"kind": "TPU v5 lite"}}
    assert latent_trace.latent_flash_calls(run) == [
        (128, 2048, 128, 64, 128, pytest.approx(400e-9))]
    assert _reader("prefill_mla_ms_per_ktok")(run) == \
        pytest.approx(700 / 1e6 / 2.0)
    assert _reader("decode_mla_ms")(run) == pytest.approx(500 / 1e6 / 2)
    assert _reader("local_assignment_share_pct")(run) == \
        pytest.approx(100 * 1008 / 16064)
    counts = models.adapter("dots").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")
    ops_, byts = counts.latent_decode_ops_bytes(m, [2000, 2000], 2)
    assert _reader("latent_decode_roofline_pct")(run) == pytest.approx(
        100 * 2 * 5 * max(ops_ / f, byts / b) / 300e-9)
    ops_, byts = counts.latent_flash_call_ops_bytes(128, 2048, 128, 64, 128, 2)
    assert _reader("latent_prefill_attn_roofline_pct")(run) == pytest.approx(
        100 * max(ops_ / f, byts / b) / 400e-9)

    def least(local, touched):
        o, y = counts.experts_ops_bytes(m, local, touched, 2, 2)
        return max(o / f, y / b)

    want = (4 * least(250, 2) + 8 * least(0.5, 0.75)) / (300 / 1e9)
    assert _reader("moe_share_experts_roofline_pct")(run) == \
        pytest.approx(100 * want)
    # a program without the scopes or counters: no metric, no error
    bare = program_trace.ProgramTrace(
        [Span(s.name, s.start, s.end, {
            k: v for k, v in s.args.items()
            if k not in ("local", "routed", "local_assignments",
                         "routed_assignments")}) for s in spans],
        modules, [(re.sub("/(q_latent|kv_up|absorb)", "", p), s, e)
                  for p, s, e in ops])
    monkeypatch.setattr(program_trace, "load", lambda run: bare)
    for name in ("prefill_mla_ms_per_ktok", "decode_mla_ms",
                 "latent_decode_roofline_pct",
                 "latent_prefill_attn_roofline_pct",
                 "moe_share_experts_roofline_pct",
                 "local_assignment_share_pct"):
        assert _reader(name)(dict(run, trace_data=_device_view([]))) is None, \
            name


# -- arch `mimo`: the manifest's entries, and the window readers -------------

MIMO_CELL = "serve-longdoc-mimo-v2"
MIMO_READERS = ["prefill_window_attn_ms_per_ktok",
                "prefill_full_attn_ms_per_ktok", "decode_window_attn_ms",
                "decode_full_attn_ms", "window_prefill_roofline_pct",
                "window_decode_roofline_pct",
                "full_prefill_attn_roofline_pct",
                "full_decode_attn_roofline_pct", "window_kv_share_pct"]


def test_mimo_manifest_entries_are_the_catalogs_row_and_the_issues_traffic():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "mimo-v2-flash-serve")
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] and cfg["arch"] == "mimo"
    assert entry["reduced"] == list(cfg["reduced"])
    cell = next(w for w in manifest["workloads"] if w["name"] == MIMO_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2-flash-serve", "longdoc-qa-mimo-v2", 1)
    assert "29%" in cell["why"] and "19%" in cell["why"]
    mix = cases.load(cases.BENCH, "traffic", "longdoc-qa-mimo-v2.json")
    keye = cases.load(cases.BENCH, "traffic", "longdoc-qa-keye.json")
    assert mix["kind"] == "serve_closed_checked"
    assert mix["arrivals"] == dict(keye["arrivals"], clients=64)
    assert mix["output_tokens"] == keye["output_tokens"]
    assert mix["prompt_tokens"]["dist"] == "uniform"
    assert mix["shape_seed"] == 4201 and mix["trace"]["seconds"] == 4
    chk = mix["check"]
    assert len(chk["prompt_lengths"]) * chk["tokens"] == 2048
    # every checked stream crosses the window and wraps the ring
    assert min(chk["prompt_lengths"]) > cfg["sliding_window"]
    assert max(chk["prompt_lengths"]) <= mix["prompt_tokens"]["max"]
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    at = list(lists).index(MIMO_READERS[0])
    assert list(lists)[at:at + 9] == MIMO_READERS
    # (PR 62's cell, the other mixed stack, reads them too)
    assert all(lists[n] == [MIMO_CELL, "serve-longdoc-laguna"]
               for n in MIMO_READERS)
    for name in ("prefill_ms_per_ktok", "kv_pages_peak_pct", "decode_moe_ms",
                 "prefill_moe_ms_per_ktok", "expert_load_max_over_mean",
                 "engine_slot_refill_ms", "prefill_stall_pct",
                 "moe_share_experts_roofline_pct",
                 "local_assignment_share_pct"):
        assert MIMO_CELL in lists[name]
    # no share that multiplies by one layer count and one head width, or
    # counts experts this chip does not hold
    for name in ("decode_attn_roofline_pct", "moe_experts_roofline_pct",
                 "decode_rider_share_pct", "latent_decode_roofline_pct"):
        assert MIMO_CELL not in lists[name]


def test_window_readers_on_a_synthetic_trace(monkeypatch):
    """The nine readers of PR 42 on a trace built by hand: a prefill of 7,000
    prompt tokens and one decode chunk of 2 steps, their scopes, the
    counters on the spans. A program without the scopes (the parent, every
    other model) reads None and raises nothing."""
    from benchmark import peaks, window_trace
    Span = program_trace.Span
    dispatch = dict(useful=16, capacity=16, active=2, live_kv_tokens=14000)
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=7000, bucket=7168,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.emit", 2100, 2110, dict(rid=7, kind="first")),
        Span("serve.engine.decode_dispatch", 2200, 2210,
             dict(dispatch, window_kv_tokens=512)),
        Span("serve.engine.decode_dispatch", 3200, 3210,
             dict(dispatch, window_kv_tokens=512)),
    ]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 2300, 3000), ("jit_poke", 4000, 4010)]
    pre = "jit(prefill)/layers/while/body/"
    dec = "jit(decode)/while/body/layers/while/body/"
    ops = [(pre + "qkv/dot_general:", 1000, 1200),
           (pre + "attn/window_attn/pallas_call:", 1200, 1300),
           (pre + "attn/full_attn/pallas_call:", 1300, 1700),
           (pre + "attn/mul:", 1700, 1710),
           (pre + "mlp/experts/ragged_dot:", 1710, 2000),
           (dec + "window_write/select_n:", 2300, 2340),
           (dec + "attn/window_attn/dot_general:", 2340, 2400),
           (dec + "kv_write/scatter:", 2400, 2450),
           (dec + "attn/full_attn/pallas_call:", 2450, 2700),
           (dec + "attn/mul:", 2700, 2710),
           (dec + "mlp/experts/ragged_dot:", 2710, 3000)]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    m = cases.load(ROOT, "benchmark/configs/mimo-v2-flash-serve.json")
    m["deployment"]["engine"]["decode_chunk"] = 2
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": None,
           "device": {"kind": "TPU v5 lite"}}
    got = {name: _reader(name)(run) for name in MIMO_READERS}
    assert got["prefill_window_attn_ms_per_ktok"] == \
        pytest.approx(100 / 1e6 / 7.0)
    assert got["prefill_full_attn_ms_per_ktok"] == \
        pytest.approx(400 / 1e6 / 7.0)
    assert got["decode_window_attn_ms"] == pytest.approx(100 / 1e6 / 2)
    assert got["decode_full_attn_ms"] == pytest.approx(300 / 1e6 / 2)
    assert got["window_kv_share_pct"] == pytest.approx(
        100 * 1024 / (28000 * 2))
    counts = models.adapter("mimo").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")
    for window, layers, ns in ((True, 5, 100e-9), (False, 2, 400e-9)):
        ops_, byts = counts.prefill_attn_ops_bytes(m, 7000, window, 2)
        name = "window_prefill_roofline_pct" if window \
            else "full_prefill_attn_roofline_pct"
        assert got[name] == pytest.approx(
            100 * layers * max(ops_ / f, byts / b) / ns)
    assert got["window_decode_roofline_pct"] == pytest.approx(
        100 * 5 * 512 * 8 * 320 * 2 / b / 60e-9)
    assert got["full_decode_attn_roofline_pct"] == pytest.approx(
        100 * 2 * 14000 * 2 * 4 * 320 * 2 / b / 250e-9)
    # a trace without this stack's scopes or counters: every reader is silent
    bare = program_trace.ProgramTrace(
        [Span(s.name, s.start, s.end,
              {k: v for k, v in s.args.items() if k != "window_kv_tokens"})
         for s in spans], modules,
        [(p.replace("window_attn/", "").replace("full_attn/", "")
           .replace("window_write/", "kv_write/"), s, e) for p, s, e in ops])
    monkeypatch.setattr(program_trace, "load", lambda run: bare)
    assert [_reader(name)(run) for name in MIMO_READERS] == [None] * 9
    monkeypatch.setattr(program_trace, "load", lambda run: None)
    assert [_reader(name)(run) for name in MIMO_READERS] == [None] * 9
    assert window_trace.deepest_scope(
        dec + "attn/window_attn/dot_general:") == "window_attn"


def test_the_engines_spans_carry_what_the_window_readers_read():
    """The names `benchmark/window_trace.py` and the readers look for are the
    ones the program emits: the scopes in the lowered programs of a mixed
    stack, the span argument and the counters in the engine."""
    import jax
    import jax.numpy as jnp

    from benchmark import window_trace
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models import serving
    from ray_tpu.models.serving import build_programs
    from ray_tpu.serve import engine as engine_mod

    adapter = models.adapter("mimo")
    m = cases.load(ROOT, "benchmark/configs/mimo-v2-flash-serve.json")
    model = dict(m, **adapter.REHEARSE)
    cfg = adapter.build_config(model, {"params": "float32",
                                       "activations": "float32"}, 128)
    built = build_programs(cfg, 2, 2, 16, 17)
    params = jax.eval_shape(lambda: fuse_qkv(
        init_params(cfg, jax.random.PRNGKey(0)), cfg))
    caches = jax.eval_shape(built.empty)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = built.decode.lower(
        params, caches, arg((2, 8), jnp.int32), arg((2,), jnp.int32),
        arg((2,), jnp.int32), arg((2,), jnp.bool_), arg((2,), jnp.float32),
        arg((2,), jnp.int32), arg((2, 2), jnp.uint32)
        ).as_text(debug_info=True)
    for scope in window_trace.SCOPES + ("kv_write",):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
    text = built.prefill.lower(
        params, caches, arg((8,), jnp.int32), arg((1, 64), jnp.int32), 1,
        0.0, 0, arg((2,), jnp.uint32), 0).as_text(debug_info=True)
    for scope in window_trace.SCOPES:
        assert f"{scope}/" in text, scope
    src = open(engine_mod.__file__).read() + open(serving.__file__).read()
    for name in ("window_kv_tokens", "window_cache_bytes",
                 "full_cache_bytes"):
        assert f'"{name}"' in src or f"{name}=" in src, name


# ---------------------------------------------------------------------------
# lfm2: gated short-convolution layers beside attention on heads of 64 (PR 46)
# ---------------------------------------------------------------------------

LFM2_CELL = "serve-generate-lfm2"
LFM2_READERS = ["prefill_conv_ms_per_ktok", "decode_conv_ms",
                "hybrid_experts_roofline_pct",
                "head64_prefill_attn_roofline_pct",
                "head64_decode_attn_roofline_pct", "decode_mfu_pct"]


def test_lfm2_manifest_entries_are_the_catalogs_row_and_the_issues_cell():
    manifest = cases.load(ROOT, "BENCHMARK.json")
    # (by name: later PRs' entries come after these)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "lfm2-24b-a2b-serve")
    cfg = cases.load(ROOT, entry["file"])
    assert entry["source"] == cfg["source_url"] and cfg["arch"] == "lfm2"
    assert entry["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    cell = next(w for w in manifest["workloads"] if w["name"] == LFM2_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-serve", "generate-long-lfm2", 1)
    assert "22%" in cell["why"] and "25%" in cell["why"] \
        and len(cell["why"]) <= 200
    mix = cases.load(cases.BENCH, "traffic", "generate-long-lfm2.json")
    assert mix["kind"] == "serve_closed_checked"
    chk = mix["check"]
    assert len(chk["prompt_lengths"]) * chk["tokens"] == 1024
    # a prompt for each rung of the ladder the mix uses (512, 1024, and the
    # 256 its shortest prompts fall in is under the 300's 512: 100 -> 128)
    assert max(chk["prompt_lengths"]) <= mix["prompt_tokens"]["max"]
    assert chk["logit_tolerance"] > chk["mean_logit_tolerance"] > 0
    lists = {p["name"]: p.get("workloads", []) for p in manifest["per_layer"]}
    e2e = {p["name"]: p.get("workloads", []) for p in manifest["end_to_end"]}
    # its readers in the order PR 46 added them, PR 47's after them
    names = list(lists)
    at = names.index(LFM2_READERS[0])
    assert names[at:at + 7] == LFM2_READERS + ["decode_sample_ms"]
    assert lists["decode_sample_ms"] == e2e["batch_tokens_per_s"]
    # (`decode_mfu_pct` reads the stack of PR 49 too)
    assert all(lists[n][0] == LFM2_CELL for n in LFM2_READERS)
    assert all(lists[n] == [LFM2_CELL] for n in LFM2_READERS[:-1])
    moved = {p["name"]: p["moves"] for p in manifest["per_layer"]}
    assert {moved[n] for n in LFM2_READERS} == {"batch_tokens_per_s"}
    for name in ("prefill_ms_per_ktok", "kv_pages_peak_pct", "decode_moe_ms",
                 "prefill_moe_ms_per_ktok", "expert_load_max_over_mean",
                 "engine_slot_refill_ms", "prefill_stall_pct"):
        assert LFM2_CELL in lists[name]
    assert LFM2_CELL in e2e["batch_tokens_per_s"]
    # no share that multiplies by `num_hidden_layers` where two layers of
    # nine have attention and eight have experts
    for name in ("decode_attn_roofline_pct", "moe_experts_roofline_pct",
                 "decode_rider_share_pct", "moe_share_experts_roofline_pct"):
        assert LFM2_CELL not in lists[name]


def test_lfm2_counts_against_a_hand_count():
    m = cases.load(ROOT, "benchmark/configs/lfm2-24b-a2b-serve.json")
    counts = models.adapter("lfm2").counts
    d, e, f, v = 2048, 3 * 2048 * 1536, 3 * 2048 * 11776, 65536 * 2048
    conv = 4 * d * d + 3 * d                    # W_in, W_out, the taps
    attn = 2 * d * 64 * (32 + 8)
    sparse = 64 * e + (d + 1) * 64 + 2 * d
    assert counts.total_params(m) == (
        conv + f + 2 * d                        # the dense conv layer
        + 2 * (attn + 2 * 64 + sparse) + 6 * (conv + sparse)
        + v + d) == 5_177_950_976
    assert counts.layers(m) == (1, 8)
    # one decode step of 2 slots at 100 and 28 cached positions, 60 experts
    # touched a sparse layer
    ops, byts = counts.decode_step_ops_bytes(m, [100, 28], 2, 2,
                                             experts_touched=60)
    matmuls = 2.0 * (2 * attn + 7 * 4 * d * d + f + 8 * (d * 64 + 4 * e) + v)
    assert ops == 2 * (matmuls + 7 * 8.0 * d) + 2 * 4.0 * 32 * 64 * 128
    weights = (2 * (attn + 128) + 7 * conv + f + 8 * ((d + 1) * 64 + 60 * e)
               + 9 * 2 * d + v + d)
    assert byts == 2.0 * weights + 2 * 128 * 2048 + 2.0 * 2 * 7 * 2 * d * 2
    with pytest.raises(TypeError):
        counts.decode_step_ops_bytes(m, [100], 2, 2)
    # a prompt's attention in ONE layer: the lower triangle, q k v o once
    ops, byts = counts.prefill_attn_ops_bytes(m, 1000, 2)
    assert ops == 4.0 * 32 * 64 * 1000 * 1001 / 2
    assert byts == 1000 * (2 * 32 + 2 * 8) * 64 * 2
    ops, byts = counts.experts_ops_bytes(m, 256, 62.9, 2, 2)
    assert ops == 2.0 * e * 256 and byts == 62.9 * e * 2 + 2.0 * 256 * d * 2
    ops, byts = counts.conv_ops_bytes(m, 10, 2)
    assert ops == 8.0 * 10 * d and byts == 4 * 10 * d * 2 + 3 * d * 2


def test_conv_stack_readers_on_a_synthetic_trace(monkeypatch):
    """The six readers of PR 46 on a trace built by hand: a prefill of 1,000
    prompt tokens and one decode chunk of 2 steps, their scopes, the counters
    on the spans. A program without the scopes (the parent, every other
    model) reads None and raises nothing."""
    from benchmark import conv_trace, peaks
    Span = program_trace.Span
    dispatch = dict(useful=128, capacity=128, active=64,
                    live_kv_tokens=64000, experts_touched=2 * 8 * 62)
    spans = [
        Span("serve.engine.admit", 900, 950, dict(
            rid=7, kind="prefill", prompt_tokens=1000, bucket=1024,
            queue_wait_us=1, decoding=0, slot_idle_us=0)),
        Span("serve.engine.prefill_experts", 2050, 2060,
             dict(rid=7, touched=8 * 64)),
        Span("serve.engine.emit", 2100, 2110, dict(rid=7, kind="first")),
        Span("serve.engine.decode_dispatch", 2200, 2210, dispatch),
        Span("serve.engine.decode_dispatch", 3200, 3210, dispatch),
    ]
    modules = [("jit_poke", 0, 10), ("jit_prefill", 1000, 2000),
               ("jit_decode", 2300, 3000), ("jit_poke", 4000, 4010)]
    pre = "jit(prefill)/layers/while/body/"
    dec = "jit(decode)/while/body/layers/while/body/"
    ops = [(pre + "conv_in/dot_general:", 1000, 1100),
           (pre + "conv/mul:", 1100, 1150),
           (pre + "conv_out/dot_general:", 1150, 1200),
           (pre + "attn/pallas_call:", 1200, 1500),
           (pre + "mlp/experts/pallas_call:", 1500, 2000),
           (dec + "conv_in/dot_general:", 2300, 2340),
           (dec + "conv/select_n:", 2340, 2360),
           (dec + "conv_out/dot_general:", 2360, 2400),
           (dec + "kv_write/scatter:", 2400, 2450),
           (dec + "attn/pallas_call:", 2450, 2550),
           (dec + "mlp/experts/pallas_call:", 2550, 3000)]
    t = program_trace.ProgramTrace(spans, modules, ops)
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    m = cases.load(ROOT, "benchmark/configs/lfm2-24b-a2b-serve.json")
    m["deployment"]["engine"]["decode_chunk"] = 2
    run = {"config": m, "cell": "x", "seed": 0, "trace_data": None,
           "device": {"kind": "TPU v5 lite"}}
    got = {name: _reader(name)(run) for name in LFM2_READERS}
    assert got["prefill_conv_ms_per_ktok"] == pytest.approx(200 / 1e6 / 1.0)
    assert got["decode_conv_ms"] == pytest.approx(100 / 1e6 / 2)
    counts = models.adapter("lfm2").counts
    f, b = peaks.peak("TPU v5 lite", "bf16_flops_per_s"), \
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s")

    def least(ops_bytes):
        return max(ops_bytes[0] / f, ops_bytes[1] / b)

    assert got["head64_prefill_attn_roofline_pct"] == pytest.approx(
        100 * 2 * least(counts.prefill_attn_ops_bytes(m, 1000, 2)) / 300e-9)
    assert got["head64_decode_attn_roofline_pct"] == pytest.approx(
        100 * 2 * 64000 * 2 * 2048 / b / 100e-9)
    # the experts: 8 sparse layers, not num_hidden_layers 9
    want = 8 * least(counts.experts_ops_bytes(m, 4000, 64, 2, 2)) \
        + 2 * 8 * least(counts.experts_ops_bytes(m, 256, 62, 2, 2))
    assert got["hybrid_experts_roofline_pct"] == pytest.approx(
        100 * want / 950e-9)
    step = counts.decode_step_ops_bytes(m, [1000.0] * 64, 2, 2,
                                        experts_touched=62.0)
    assert got["decode_mfu_pct"] == pytest.approx(
        100 * least(step) / (700e-9 / 2))
    # a program without the scopes, and a run without a trace
    bare = program_trace.ProgramTrace(spans, modules, [
        (p.replace("conv_in/", "qkv/").replace("conv_out/", "attn_out/")
         .replace("conv/", "attn/"), s, e) for p, s, e in ops])
    monkeypatch.setattr(program_trace, "load", lambda run: bare)
    assert [_reader(name)(run) for name in LFM2_READERS] == [None] * 6
    monkeypatch.setattr(program_trace, "load", lambda run: None)
    assert [_reader(name)(run) for name in LFM2_READERS] == [None] * 6
    assert conv_trace.deepest_scope(dec + "conv_in/mul:") == "conv_in"
    assert conv_trace.deepest_scope(dec + "mlp/experts/x:") == "experts"


def test_the_engines_spans_carry_what_the_conv_stack_readers_read():
    """The names `benchmark/conv_trace.py` and the readers look for are the
    ones the program emits: the scopes in the lowered programs of a stack
    with conv layers, the span arguments and the counter in the engine."""
    import jax
    import jax.numpy as jnp

    from benchmark import conv_trace, moe_trace
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models import serving
    from ray_tpu.models.serving import build_programs
    from ray_tpu.serve import engine as engine_mod

    adapter = models.adapter("lfm2")
    m = cases.load(ROOT, "benchmark/configs/lfm2-24b-a2b-serve.json")
    cfg = adapter.build_config(dict(m, **adapter.REHEARSE), {
        "params": "float32", "activations": "float32"}, 128)
    built = build_programs(cfg, 2, 2, 16, 17)
    params = jax.eval_shape(lambda: fuse_qkv(
        init_params(cfg, jax.random.PRNGKey(0)), cfg))
    caches = jax.eval_shape(built.empty)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    scopes = conv_trace.SCOPES + moe_trace.MOE_SCOPES + (
        "qkv", "qk_norm", "rope", "attn", "attn_out")
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
    for name in ("conv_state_bytes", "live_kv_tokens", "experts_touched",
                 "touched"):
        assert f'"{name}"' in src or f"{name}=" in src, name

"""Tests of the benchmark's own yardstick. Run them with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The repo's `pytest tests/` does not collect this directory.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

import benchmark  # noqa: E402
from benchmark import flops, models, peaks, trace, traffic  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "tinymoe")
CONTRACT = ("check_supported", "build_config", "init_params", "reference",
            "loss_fn", "CHECK_LEAVES", "counts", "REHEARSE")
COUNTS = ("train_flops_per_token", "prefill_flops", "decode_step_ops_bytes",
          "total_params")

MISTRAL = dict(hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
               intermediate_size=14336, vocab_size=32768, num_hidden_layers=2)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# -- traffic ---------------------------------------------------------------

@pytest.mark.parametrize("name", ["chat", "batch-summarize"])
def test_traffic_is_the_seed_and_the_same_work_for_every_seed(name):
    mix = load(BENCH, "traffic", name + ".json")
    a = traffic.requests(mix, 20.0, 2147483999, 32768)
    b = traffic.requests(mix, 20.0, 2147483999, 32768)
    c = traffic.requests(mix, 20.0, 7, 32768)
    assert [(r.t_sched, r.prompt, r.max_tokens) for r in a] == \
        [(r.t_sched, r.prompt, r.max_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # Another seed is another order of the same sizes and the same gaps.
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    if mix["arrivals"]["process"] == "closed":   # and in the same order
        assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in c)
    assert len(a) == len(c) and a[-1].t_sched == pytest.approx(c[-1].t_sched)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)


def test_bounded_pareto_matches_its_closed_form_median():
    d = {"dist": "bounded_pareto", "min": 128, "max": 2048, "alpha": 1.3}
    x = traffic._draw(d, 200_000, np.random.default_rng(0))
    median = 128 / (1 - 0.5 * (1 - (128 / 2048) ** 1.3)) ** (1 / 1.3)
    assert abs(np.median(x) - median) < 3


def test_train_rows_are_the_seed():
    mix = load(BENCH, "traffic", "pretrain-4k.json")
    a, b = traffic.rows(mix, 4, 9, 32768), traffic.rows(mix, 4, 9, 32768)
    assert a.shape == (4, 4096) and a.dtype == np.int32 and (a == b).all()
    assert (a != traffic.rows(mix, 4, 10, 32768)).any()


# -- trace reduction -------------------------------------------------------

def test_interval_arithmetic():
    assert trace.merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.total([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]


def test_self_time_takes_children_from_their_parent():
    chip = trace.Chip("c", [("%while.1 = x", 0, 100), ("%a = x", 10, 30),
                            ("%b = x", 40, 90)], [], [])
    t = chip.self_times()
    assert t["%while.1 = x"] == pytest.approx(30e-9)
    assert t["%a = x"] == pytest.approx(20e-9)
    assert trace.total(chip.busy()) == 100


def test_recorded_tpu_trace_reduces_to_known_numbers():
    """tiny.xplane.pb: recorded on a TPU v5 lite in PR 23 (three train steps
    of a 2-layer d_model 256 model, then two engine requests)."""
    t = trace.load(os.path.join(HERE, "tiny.xplane.pb"))
    assert len(t.chips) == 1
    assert len(t.module_durations("jit_step_fn")) == 3
    assert len(t.module_durations("jit_decode")) == 2
    assert len(t.module_durations("jit_prefill")) == 2
    assert sum(t.module_durations("jit_step_fn")) == pytest.approx(545.895e-6)
    assert t.busy_s == pytest.approx(951.383e-6, rel=1e-6)
    assert t.window_s == pytest.approx(0.03861323, rel=1e-6)
    assert 0.0 < t.busy_s / t.window_s < 0.05      # the probe mostly slept
    names = {n for n, _, _ in t.host_spans}
    assert {"bench.next_feed", "bench.step_dispatch", "bench.step_wait",
            "bench.request"} <= names
    gaps = t.idle_gaps(3)
    assert gaps[0][0] == "bench.request" and gaps[0][1] > gaps[1][1]
    assert t.collective_exposed_s() == 0.0
    ops = t.top_ops(5)
    assert len(ops) == 5 and ops[0][1] >= ops[1][1] > 0


def test_flash_kernel_reader_on_the_recorded_trace():
    from benchmark.run import load_reader
    read = load_reader(BENCH, "layer_metrics", "attn_kernel_roofline")
    run = {"trace_data": trace.load(os.path.join(HERE, "tiny.xplane.pb")),
           "device": {"kind": "TPU v5 lite"}}
    share = read(run)
    assert 0.0 < share < 100.0


# -- operation counts ------------------------------------------------------

def test_flops_against_hand_counts_for_one_mistral_layer():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, three 4096x14336.
    weights = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256
    assert weights == 218_103_808
    assert flops.layer_params(MISTRAL) == weights + 2 * 4096
    assert flops.head_params(MISTRAL) == 134_217_728
    assert flops.matmul_flops_per_token(MISTRAL) == \
        2.0 * (2 * weights + 134_217_728)
    # Causal attention over 4096: 4096*4097/2 pairs, 4*32*128 ops a pair.
    assert flops.attention_flops(MISTRAL, 4096, 4096, True) == \
        4.0 * 32 * 128 * 4096 * 4097 / 2
    assert flops.attention_flops(MISTRAL, 1, 1000, False) == 4.0 * 32 * 128 * 1000
    per_tok = flops.train_flops_per_token(MISTRAL, 4096)
    assert per_tok == pytest.approx(3 * (2 * (2 * weights + 134_217_728)
                                         + 2 * 4 * 32 * 128 * 4097 / 2))
    f, b = flops.flash_call_ops_bytes(1, 32, 4096, 4096, 128, True, 2, False)
    assert f == 4.0 * 32 * 128 * 4096 * 4097 / 2
    assert b == 4 * 32 * 4096 * 128 * 2 + 32 * 4096 * 4
    assert flops.flash_call_ops_bytes(1, 32, 4096, 4096, 128, True, 2, True)[0] \
        == 2.5 * f


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9", "bf16_flops_per_s")


# -- the adapter contract: reference against program, counts ---------------

@pytest.fixture
def tinymoe_on_path():
    """The fixture architecture's files, importable under the names they
    would have had they been added to benchmark/ (as the copy test adds them)."""
    added = [(benchmark.__path__, FIXTURE),
             (models.__path__, os.path.join(FIXTURE, "models"))]
    for path, d in added:
        path.append(d)
    yield
    for path, d in added:
        path.remove(d)
    for name in [n for n in sys.modules if "tinymoe" in n]:
        del sys.modules[name]


def _tiny(arch):
    adapter = models.adapter(arch)
    model = dict(adapter.REHEARSE, rope_theta=1e6, rms_norm_eps=1e-5)
    cfg = adapter.build_config(model, {"params": "float32",
                                       "activations": "float32"}, 128)
    return adapter, model, cfg, adapter.init_params(cfg, 3)


@pytest.mark.parametrize("arch", ["llama", "tinymoe"])
def test_adapter_exposes_the_whole_contract(arch, tinymoe_on_path):
    adapter = models.adapter(arch)
    assert not [n for n in CONTRACT if not hasattr(adapter, n)]
    assert not [n for n in COUNTS if not callable(getattr(adapter.counts, n))]
    ref = adapter.reference()
    assert callable(ref.served_token_gaps) and callable(ref.loss_and_check_grads)
    assert {"hidden_size", "num_hidden_layers", "vocab_size"} <= set(adapter.REHEARSE)
    for path in adapter.CHECK_LEAVES.values():
        assert all(isinstance(k, str) for k in path)


@pytest.mark.parametrize("arch", ["llama", "tinymoe"])
def test_reference_agrees_with_the_program_at_tiny_widths(arch, tinymoe_on_path):
    """Through the contract alone: the serve check's gaps and the train
    check's comparison (benchmark/train_loop.py), program against reference
    on the same float32 weights."""
    import jax.numpy as jnp

    from benchmark.train_loop import _check_against_reference
    from ray_tpu.models import llama

    adapter, model, cfg, params = _tiny(arch)
    if arch == "llama":
        assert (cfg.n_kv_heads, cfg.d_ff, cfg.rope_theta) == (2, 128, 1e6)
    else:
        assert (cfg.n_experts, cfg.top_k_experts, cfg.d_ff) == (4, 2, 64)
    toks = np.random.default_rng(0).integers(0, 256, (2, 96), dtype=np.int32)
    # One row, as the serve check sees it: a sparse block's capacity, and so
    # which assignments overflow, is counted over all the tokens of a call.
    want = np.asarray(llama.forward(params, jnp.asarray(toks[:1]), cfg))[0]
    ref = adapter.reference()
    # A served token's gap is the reference's largest logit less its logit of
    # that token: the program's own logits say what it must be. Token 5 is
    # arbitrary; the argmax must read 0.
    prompt, tail = [int(t) for t in toks[0, :80]], [int(t) for t in toks[0, 80:]]
    gaps = ref.served_token_gaps(params, model, prompt, tail + [5])
    rows = want[79:]
    served = np.asarray(tail + [5])
    assert np.allclose(gaps, rows.max(-1) - rows[np.arange(17), served],
                       atol=2e-4)
    assert ref.served_token_gaps(params, model, prompt + tail,
                                 [int(want[-1].argmax())]) == \
        [pytest.approx(0.0, abs=1e-4)]
    chk = _check_against_reference(adapter, params, jnp.asarray(toks), cfg,
                                   None, model, 64)
    assert chk["loss_rel_err"] < 1e-5 and chk["param_dtypes"] == ["float32"]
    names = {"final_norm", "last_attn_norm", "last_mlp_norm"}
    assert set(chk["grad_rel_err"]) == (names | {"last_router"}
                                        if arch == "tinymoe" else names)
    assert max(chk["grad_rel_err"].values()) < 1e-4, chk


def test_dense_reference_cannot_pass_a_sparse_block(tinymoe_on_path):
    """`tinymoe` pointed at llama's reference (what the harness did for every
    `arch` before it asked the adapter) raises or disagrees; it never passes."""
    import jax.numpy as jnp

    from benchmark.train_loop import _check_against_reference

    _, model, cfg, params = _tiny("tinymoe")
    wrong = models.adapter("tinymoe_llamaref")
    assert wrong.reference() is models.adapter("llama").reference()
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (1, 64), dtype=np.int32))
    try:
        chk = _check_against_reference(wrong, params, toks, cfg, None, model, 64)
    except (TypeError, ValueError):
        return
    assert chk["loss_rel_err"] > 1e-3 or \
        max(chk["grad_rel_err"].values()) > 0.08


def test_sparse_counts_against_a_hand_count(tinymoe_on_path):
    counts = models.adapter("tinymoe").counts
    m = dict(models.adapter("tinymoe").REHEARSE)
    # a token multiplies: q 64x64, k and v 64x32 each, o 64x64, the router
    # 64x4, and 2 of 4 experts of three 64x64 matrices; the head 64x256.
    active = 4096 + 2 * 2048 + 4096 + 256 + 2 * 3 * 4096
    assert active == 37_120
    attn = 2 * 4.0 * 4 * 16 * (128 * 129 / 2) / 128      # 2 layers, causal
    assert counts.train_flops_per_token(m, 128) == \
        3 * (2 * (2 * active + 16_384) + attn) == 642_816
    assert counts.total_params(m) == \
        2 * (active + 2 * 3 * 4096 + 2 * 64) + 2 * 16_384 + 64
    assert counts.prefill_flops(m, 128) == \
        2.0 * 2 * active * 128 + attn * 128 + 2.0 * 16_384
    ops, byts = counts.decode_step_ops_bytes(m, [100, 28], 2, 2)
    assert ops == 2 * 2 * (2 * active + 16_384) + 2 * 4.0 * 4 * 16 * 128
    assert byts == 2 * (counts.total_params(m) - 16_384) \
        + 2 * (2 * 2 * 16 * 2) * 128
    # and the dense block's count of the same keys is another number
    assert flops.train_flops_per_token(m, 128) != 642_816


def test_dense_counts_are_reached_through_the_adapter():
    counts = models.adapter("llama").counts
    assert counts.train_flops_per_token(MISTRAL, 4096) == \
        flops.train_flops_per_token(MISTRAL, 4096)
    assert counts.total_params(MISTRAL) == 2 * (218_103_808 + 8192) \
        + 2 * 134_217_728 + 4096


# -- the manifest ----------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# Widths may never be reduced: hidden, intermediate, latent, state and
# projection sizes, head sizes, expansion factors, experts per token, windows.
WIDTH = re.compile(r"(hidden_size|intermediate_size|head_dim|_dim\b|_rank\b"
                   r"|num_experts_per_tok|sliding_window|state_size|d_state"
                   r"|d_conv|conv_kernel|expand|projection)")
# What each source publishes (the keys that decide the shapes of its blocks).
PUBLISHED = {
    "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json":
        dict(hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
             intermediate_size=14336, vocab_size=32768, rope_theta=1e6,
             num_hidden_layers=32),
}


def test_manifest_is_consistent_with_the_files():
    m = load(ROOT, "BENCHMARK.json")
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        mix = load(BENCH, "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(BENCH, "drivers", mix["kind"] + ".py"))
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(cells)
    for c in m["configs"]:
        assert any(w["config"] == c["name"] for w in m["workloads"])
        cfg = load(ROOT, c["file"])
        assert os.path.exists(os.path.join(BENCH, "models", cfg["arch"] + ".py"))
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["assumed"] and cfg["deployment"]["chips"] in (1, 4)
        assert not WIDTH.search(" ".join(c["reduced"])), c["reduced"]
        for key, value in PUBLISHED.get(c["source"], {}).items():
            assert cfg[key] == value or key in c["reduced"], (c["name"], key)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        for e in m[group]:
            assert NAME.match(e["name"]) and UNIT.match(e["unit"])
            assert e["better"] in ("lower", "higher")
            assert os.path.exists(os.path.join(BENCH, folder, e["name"] + ".py"))
            for w in e.get("workloads", []):
                assert w in cells
    for e in m["end_to_end"]:
        assert 0 < e["bound"] <= 0.1
    for p in m["per_layer"]:
        target = e2e[p["moves"]]
        for w in p.get("workloads", cells):
            assert "workloads" not in target or w in target["workloads"], \
                (p["name"], w)
    for w in cells:   # setup_s, one more end-to-end metric, one per-layer
        assert sum(w in e.get("workloads", cells) for e in m["end_to_end"]) >= 2
        assert any(w in p.get("workloads", cells) for p in m["per_layer"])


# -- a later PR adds only files and entries --------------------------------

def _run_rehearsal(root, cell, trace_flag, seconds="3"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483999", "--seconds", seconds,
         "--trace", str(trace_flag), "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600)


def _rehearse(root, cell, trace_flag, seconds="3"):
    p = _run_rehearsal(root, cell, trace_flag, seconds)
    assert p.returncode == 3, p.stderr[-3000:]   # a rehearsal is not a result
    assert p.stdout.strip() == ""
    return json.loads(p.stderr.strip().splitlines()[-1])


def test_new_cell_config_and_metric_are_files_and_entries(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, a cell and a
    per-layer metric as NEW files plus NEW entries, edit nothing, and run the
    new cell and an old one end to end at rehearsal size."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    m = load(ROOT, "BENCHMARK.json")
    cfg = load(BENCH, "configs", "mistral-7b-v0.3-serve.json")
    cfg["num_hidden_layers"] = 8
    with open(os.path.join(root, "benchmark/configs/other.json"), "w") as f:
        json.dump(cfg, f)
    mix = load(BENCH, "traffic", "chat.json")
    mix["arrivals"]["rate_per_s"] = 9.0
    with open(os.path.join(root, "benchmark/traffic/chat-fast.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark/layer_metrics/requests_seen.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run['replica']['stamps'])\n")
    m["configs"].append({"name": "other", "source": m["configs"][0]["source"],
                         "file": "benchmark/configs/other.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({"name": "other.chat-fast", "config": "other",
                           "traffic": "chat-fast", "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            e["workloads"].append("other.chat-fast")
    m["per_layer"].append({"name": "requests_seen", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "service", "moves": "ttft_p95_ms",
                           "workloads": ["other.chat-fast"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    e2e = _rehearse(root, "other.chat-fast", 0, "4")
    assert e2e["correct"] and e2e["failed"] == 0 and e2e["attempted"] > 10
    assert set(e2e["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert e2e["device"]["platform"] == "cpu"
    layer = _rehearse(root, "other.chat-fast", 1, "4")
    assert layer["metrics"]["requests_seen"]["value"] == e2e["attempted"]
    assert "decode_step_ms" not in layer["metrics"]   # no device on the CPU
    old = _rehearse(root, "train-1chip", 0)
    assert old["correct"] and old["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0


def _only_additions(src, dst):
    """Every file of `src` is in `dst`, byte for byte; returns what `dst` adds."""
    import filecmp
    added = []

    def walk(cmp, rel):
        assert not cmp.left_only and not cmp.diff_files and not cmp.funny_files, \
            (rel, cmp.left_only, cmp.diff_files)
        added.extend(os.path.join(rel, n) for n in cmp.right_only)
        for name, sub in cmp.subdirs.items():
            walk(sub, os.path.join(rel, name))

    walk(filecmp.dircmp(src, dst, ignore=["out", "__pycache__"]), "")
    return sorted(added)


def test_new_architecture_is_files_and_entries(tmp_path):
    """Copy the benchmark and add an ARCHITECTURE that is not `llama` (adapter,
    reference, counts, rehearsal widths, configuration, a reader) as new
    files plus entries. Its train cell rehearses correct against its OWN
    reference, a reader sees its OWN count, and the same block held to the
    dense reference is not correct."""
    root = str(tmp_path)
    copy = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, copy,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copytree(FIXTURE, copy, dirs_exist_ok=True)
    cfg = load(FIXTURE, "configs", "tinymoe-train.json")
    with open(os.path.join(copy, "configs/tinymoe-llamaref-train.json"),
              "w") as f:
        json.dump(dict(cfg, arch="tinymoe_llamaref"), f)
    added = _only_additions(BENCH, copy)
    assert added == ["configs/tinymoe-llamaref-train.json",
                     "configs/tinymoe-train.json", "flops_tinymoe.py",
                     "layer_metrics/train_flops_per_token.py",
                     "models/tinymoe.py", "models/tinymoe_llamaref.py",
                     "reference_tinymoe.py"]
    m = load(ROOT, "BENCHMARK.json")
    cells = []
    for name in ("tinymoe", "tinymoe-llamaref"):
        m["configs"].append({"name": name, "source": "test fixture",
                             "file": f"benchmark/configs/{name}-train.json",
                             "reduced": [], "why": "test"})
        m["workloads"].append({"name": name + ".pretrain-4k", "config": name,
                               "traffic": "pretrain-4k", "chips": 1,
                               "why": "test"})
        cells.append(name + ".pretrain-4k")
    for e in m["end_to_end"]:
        if e["name"] == "train_tokens_per_s_per_chip":
            e["workloads"] += cells
    m["per_layer"].append({"name": "train_flops_per_token", "unit": "ops/token",
                           "better": "lower", "source": "program_counter",
                           "layer": "device (train)",
                           "moves": "train_tokens_per_s_per_chip",
                           "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    e2e = _rehearse(root, cells[0], 0)
    assert e2e["correct"] and e2e["failed"] == 0
    assert e2e["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    with open(os.path.join(copy, "out", cells[0], "2147483999",
                           "run-trace0.json")) as f:
        chk = json.load(f)["check"]
    assert set(chk["grad_rel_err"]) == {"final_norm", "last_attn_norm",
                                        "last_mlp_norm", "last_router"}
    layer = _rehearse(root, cells[0], 1)
    # the fixture's own count at its rehearsal widths (the hand count of
    # test_sparse_counts_against_a_hand_count), not the dense block's
    assert layer["metrics"]["train_flops_per_token"]["value"] == 642_816
    wrong = _run_rehearsal(root, cells[1], 0)
    assert wrong.stdout.strip() == ""
    assert wrong.returncode == 1 or (
        wrong.returncode == 3 and not json.loads(
            wrong.stderr.strip().splitlines()[-1])["correct"]), wrong.stderr[-3000:]

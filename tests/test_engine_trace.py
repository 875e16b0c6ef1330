"""benchmark/engine_trace.py and the four readers of PR 37 on traces built
here by hand (no profiler, no device, no clock), and the guard that keeps the
program's span names, PERF.md's list of them and the readers' literals one
vocabulary."""

import glob
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import engine_trace, program_trace, trace  # noqa: E402
from benchmark.program_trace import ProgramTrace, Span  # noqa: E402

IDLE, BLOCK, ADMIT, DISPATCH = engine_trace.STATES
MS = 1_000_000      # ns


def _reader(name):
    from benchmark.run import HERE, load_reader
    return load_reader(HERE, "layer_metrics", name)


def _admit(rid, start, end, **args):
    return Span(ADMIT, start, end, dict(
        rid=rid, kind="prefill", prompt_tokens=1000, bucket=1024,
        queue_wait_us=1, **args))


def _serve_trace():
    """A window of 10,000 ns on a 4-slot engine. The chip runs 3,000-4,500
    (a prefill, a chunk), 5,300-6,800 (the same) and 7,000-8,000 (a chunk);
    its four gaps are the ones the tests below split by hand."""
    spans = [
        Span(IDLE, 0, 2920, {}),                    # ends with its admission
        _admit(1, 2500, 2900, decoding=0, slot_idle_us=0),
        Span(DISPATCH, 2930, 2990, dict(useful=4, capacity=16, active=1)),
        Span(BLOCK, 2990, 5200, {}),                # admits while it stands
        _admit(2, 5000, 5100, decoding=1, slot_idle_us=1500),
        Span("serve.engine.emit", 4010, 4020, dict(rid=1, kind="first")),
        Span("serve.engine.emit", 4400, 5150, dict(kind="chunk")),  # emitter
        Span("serve.engine.emit", 6310, 6320, dict(rid=2, kind="first")),
        Span(BLOCK, 7900, 8100, {}),                # the last loop span
        _admit(3, 7950, 7970, decoding=2, slot_idle_us=2500),
        _admit(4, 7970, 7990, decoding=3, slot_idle_us=5000),
    ]
    modules = [("jit_prefill", 3000, 4000), ("jit_decode", 4000, 4500),
               ("jit_prefill", 5300, 6300), ("jit_decode", 6300, 6800),
               ("jit_decode", 7000, 8000)]
    ops = [("jit(prefill)/mlp/dot_general:", 3000, 4000),
           ("jit(decode)/while", 4000, 4500),
           ("jit(decode)/while/body/mlp/dot_general:", 4100, 4400),
           ("jit(prefill)/mlp/dot_general:", 5300, 6300),
           ("jit(decode)/while", 6300, 6800), ("jit(decode)/while", 7000, 8000)]
    return ProgramTrace(sorted(spans, key=lambda s: s.start), modules, ops)


def _without_pr37(t):
    """The same trace as the parent would have written it."""
    return ProgramTrace(
        [Span(s.name, s.start, s.end, {k: v for k, v in s.args.items()
                                       if k not in ("decoding", "slot_idle_us")})
         for s in t.spans if s.name != IDLE], t.modules, t.ops)


def _run(window_ns=10_000.0):
    return {"cell": "x", "seed": 0,
            "config": {"deployment": {"engine": {"n_slots": 4}}},
            "device": {"window_s": window_ns / 1e9},
            "trace_data": trace.Trace([], [], 0.0, window_ns)}


def test_idle_gaps_split_each_gap_by_the_loop_state_that_covers_it():
    t = _serve_trace()
    assert engine_trace.self_intervals(t, IDLE) == [(0, 2500), (2900, 2920)]
    assert engine_trace.self_intervals(t, BLOCK) == [
        (2990, 5000), (5100, 5200), (7900, 7950), (7990, 8100)]
    assert engine_trace.self_intervals(t, ADMIT) == [
        (2500, 2900), (5000, 5100), (7950, 7990)]
    # the stand the trace ended in left no event: the last loop span is an
    # `emit_block`, so what follows it is idle; the first is no bare admit
    assert engine_trace.edge_idles(t, (0, 10_000)) == [(8100, 10_000)]
    pieces = engine_trace.idle_gaps(t, (0, 10_000))
    assert pieces == [
        (IDLE, 0, 2500), (ADMIT, 2500, 2900), (IDLE, 2900, 2920),
        (engine_trace.NO_SPAN, 2920, 2930), (DISPATCH, 2930, 2990),
        (BLOCK, 2990, 3000),
        # under `emit_block`, less the admit nested in it; the emitter's
        # `serve.engine.emit` span over the same time is not subtracted
        (BLOCK, 4500, 5000), (ADMIT, 5000, 5100), (BLOCK, 5100, 5200),
        (engine_trace.NO_SPAN, 5200, 5300),
        (engine_trace.NO_SPAN, 6800, 7000),         # under nothing
        (BLOCK, 8000, 8100), (IDLE, 8100, 10_000)]
    assert sum(e - s for _, s, e in pieces) == 10_000 - 4_000   # the idle time
    per = engine_trace.shares(t, _run()["trace_data"])
    assert per == pytest.approx({IDLE: 44.2, BLOCK: 7.1, ADMIT: 5.0,
                                 DISPATCH: 0.6, engine_trace.NO_SPAN: 3.1})
    assert engine_trace.with_work_pct(per) == pytest.approx(60.0 - 44.2)
    gaps = engine_trace.longest(pieces, k=2)
    assert [round(g * 1e9) for g, _ in gaps] == [3000, 2000]
    assert [name for name, _ in gaps[0][1]] == [
        IDLE, ADMIT, DISPATCH, engine_trace.NO_SPAN, BLOCK]
    # without `trace.py`'s view, the window is first to last of what it holds
    assert engine_trace.window_of(t) == (0, 8100)
    assert sum(e - s for _, s, e in engine_trace.idle_gaps(t)) == 8100 - 4000


def test_a_trace_that_began_in_a_stand_gives_its_head_to_idle():
    """No idle span was recorded: the one the trace began in ended with the
    first admission, which found no slot decoding."""
    t = _serve_trace()
    t = ProgramTrace([s for s in t.spans if s.name != IDLE], t.modules, t.ops)
    assert engine_trace.edge_idles(t, (0, 10_000)) == [(0, 2500),
                                                       (8100, 10_000)]
    per = engine_trace.shares(t, _run()["trace_data"])
    assert per[IDLE] == pytest.approx(44.0)     # all but 2900-2920
    busy = ProgramTrace([s for s in t.spans if s.args.get("rid") != 1],
                        t.modules, t.ops)       # first loop span: a dispatch
    assert engine_trace.edge_idles(busy, (0, 10_000)) == [(8100, 10_000)]


@pytest.mark.parametrize("name,want", [
    # mean(1.5, 2.5, 5.0), not their median: rid 1 reads 0, a first tenant
    ("engine_slot_refill_ms", 3.0),
    # rid 1's prefill ran 1,000 ns with 0 slots decoding, rid 2's 1,000 with
    # 1; the others' never ran: 1,000 slot-ns of 10,000 ns x 4 slots
    ("prefill_stall_pct", 2.5),
    ("idle_with_work_pct", 15.8),
])
def test_serve_readers_on_a_hand_built_trace(monkeypatch, name, want):
    t = _serve_trace()
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    assert _reader(name)(_run()) == pytest.approx(want)
    old = _without_pr37(t)
    monkeypatch.setattr(program_trace, "load", lambda run: old)
    assert _reader(name)(_run()) is None
    monkeypatch.setattr(program_trace, "load", lambda run: None)
    assert _reader(name)(_run()) is None
    # a rehearsal's trace: host spans, no device plane
    cpu = ProgramTrace(t.spans, [], [])
    monkeypatch.setattr(program_trace, "load", lambda run: cpu)
    run = dict(_run(), device={}, trace_data=None)
    assert _reader(name)(run) == (want if name == "engine_slot_refill_ms"
                                  else None)


def test_ingest_next_ref_reader_on_a_hand_built_trace(monkeypatch):
    def step(n, at):
        return Span("train.step", at * MS, (at + 10) * MS, dict(step_num=n))

    spans = [step(0, 0), Span("data.iter.next_ref", 20 * MS, 22 * MS, {}),
             Span("data.iter.get_block", 22 * MS, 30 * MS, {}),
             step(1, 100), Span("data.iter.next_ref", 120 * MS, 123 * MS, {}),
             Span("data.iter.next_ref", 125 * MS, 126 * MS, {}),
             step(2, 200), Span("data.iter.next_ref", 220 * MS, 229 * MS, {}),
             step(3, 300)]
    t = ProgramTrace(spans, [], [])
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    assert _reader("ingest_next_ref_ms")({}) == pytest.approx(4.0)   # 2, 4, 9
    assert _reader("ingest_get_ms")({}) == pytest.approx(0.0)   # 8, 0, 0
    bare = ProgramTrace([s for s in spans if "next_ref" not in s.name], [], [])
    monkeypatch.setattr(program_trace, "load", lambda run: bare)
    assert _reader("ingest_next_ref_ms")({}) is None


def test_manifest_entries_of_the_four_readers():
    from benchmark.tests.test_benchmark import load
    per_layer = {p["name"]: p for p in load(ROOT, "BENCHMARK.json")["per_layer"]}
    batch = ["serve-batch", "serve-batch-olmoe", "serve-longdoc-keye",
             "serve-batch-jamba2"]
    want = {
        "engine_slot_refill_ms": ("scheduler (serve)", "ms",
                                  "batch_tokens_per_s", batch),
        "prefill_stall_pct": ("scheduler (serve)", "%",
                              "batch_tokens_per_s", batch),
        "idle_with_work_pct": ("device", "%", "ttft_p95_ms", ["serve-chat"]),
        "ingest_next_ref_ms": ("scheduler (train)", "ms",
                               "train_tokens_per_s_per_chip", ["train-1chip"]),
    }
    # appended by PR 37, in this order; later PRs append after them, and
    # append their cells to the lists
    names = list(per_layer)
    at = names.index("engine_slot_refill_ms")
    assert names[at:at + 4] == list(want)
    for name, (layer, unit, moves, cells) in want.items():
        p = per_layer[name]
        assert (p["layer"], p["unit"], p["moves"],
                p["workloads"][:len(cells)], p["better"], p["source"]) == (
            layer, unit, moves, cells, "lower", "program_span")


# -- one vocabulary: the program's spans, PERF.md's list, the readers --------

_SPAN_CALL = re.compile(
    r"tracing\.(?:span|compile_span)\(\s*\"([^\"]+)\"")
_READ_CALL = re.compile(r"\b(?:named|per_step_ms)\(")
_SPAN_NAME = re.compile(r"\"((?:serve|train|data)\.[a-z_.]+)\"")
_ARG_READ = re.compile(r"args(?:\.get\(|\[)\"([a-z_]+)\"")


def _emitted():
    names = set()
    for path in glob.glob(os.path.join(ROOT, "ray_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            names.update(_SPAN_CALL.findall(f.read()))
    return sorted(names)


def _timeline_readers():
    """benchmark/timeline_record.py and the readers over it (PR 51)."""
    shared = os.path.join(ROOT, "benchmark", "timeline_record.py")
    files = [shared]
    for path in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       "*.py")):
        with open(path) as f:
            if "timeline_record" in f.read():
                files.append(path)
    return files


def _timeline_reads():
    """(span names, span arguments) that `timeline_record.py` and its readers
    name as literals: its constants (`ADMIT = "serve.engine.admit"`) and
    every `args["x"]` / `args.get("x"`."""
    names, args = set(), set()
    for path in _timeline_readers():
        with open(path) as f:
            text = f.read()
        names.update(_SPAN_NAME.findall(text))
        args.update(_ARG_READ.findall(text))
    return sorted(names), sorted(args)


def _read():
    """Span names in the arguments of every `named(` / `per_step_ms(` call of
    the readers and of the modules they share, `engine_trace`'s states, and
    the names `timeline_record.py` holds as constants."""
    names = set(engine_trace.STATES) | set(_timeline_reads()[0])
    for path in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       "*.py")) + \
            glob.glob(os.path.join(ROOT, "benchmark", "*.py")):
        with open(path) as f:
            text = f.read()
        for call in _READ_CALL.finditer(text):
            depth, i = 1, call.end()
            while depth and i < len(text):
                depth += {"(": 1, ")": -1}.get(text[i], 0)
                i += 1
            names.update(_SPAN_NAME.findall(text[call.end():i]))
    return sorted(names)


def _perf_md_names():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    start = text.index("**Every name the program emits")
    return text[start:text.index("\n## 4.", start)]


@pytest.mark.parametrize("kind,name",
                         [("emitted", n) for n in _emitted()]
                         + [("read", n) for n in _read()])
def test_span_names_are_one_vocabulary(kind, name):
    """Every span the program opens is in PERF.md section 3's paragraph of
    names with what reads it, and every span name a reader asks a trace for
    is one the program opens: no span 'read by no metric' unknown to the
    record, no reader of a span that was renamed."""
    if kind == "emitted":
        assert f"`{name}`" in _perf_md_names(), \
            f"{name} is opened under ray_tpu/ and PERF.md section 3 lacks it"
    else:
        assert name in _emitted(), f"a reader asks for {name}: nothing opens it"


@pytest.mark.parametrize("arg", _timeline_reads()[1])
def test_span_arguments_the_timeline_readers_read_are_set_under_ray_tpu(arg):
    """An argument `timeline_record.py` or a reader over it takes from a span
    is one the program sets, as a keyword (`queue_wait_us=`) or as a key
    (`"first_chunk_us"`), in a file that opens spans or on the span's way to
    the timeline, and PERF.md section 3's paragraph names it."""
    texts = []
    for path in glob.glob(os.path.join(ROOT, "ray_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            text = f.read()
        if "tracing." in text or path.endswith(("tracing.py",
                                                "controller.py")):
            texts.append(text)
    assert any(re.search(rf"\b{arg}=|\"{arg}\"", t) for t in texts), \
        f"a timeline reader takes `{arg}` from a span: nothing sets it"
    assert f"`{arg}`" in _perf_md_names(), \
        f"PERF.md section 3 does not name the span argument `{arg}`"


def test_the_guard_sees_the_names_it_is_for():
    assert {"serve.engine.idle", "serve.engine.admit", "data.iter.next_ref",
            "train.compile", "serve.engine.warm"} <= set(_emitted())
    assert {"serve.engine.admit", "serve.engine.decode_dispatch",
            "data.iter.next_ref", "data.iter.format", "train.step",
            "serve.engine.emit_block", "serve.engine.idle",
            "serve.proxy.request", "serve.replica.call", "serve.engine.warm",
            "train.compile"} <= set(_read())
    assert len(_timeline_readers()) == 12       # the module and its eleven
    assert {"queue_wait_us", "slot_idle_us", "first_chunk_us", "compile_s",
            "useful", "capacity", "riders", "prompt_tokens", "trace_id",
            "rid", "kind"} <= set(_timeline_reads()[1])


# -- the layering: serve/ -> models/ -> ops/, and a scheduler that builds no
# -- program and knows no architecture (PR 45) -------------------------------

import ast  # noqa: E402


def _tree(*path):
    with open(os.path.join(ROOT, "ray_tpu", *path)) as f:
        return ast.parse(f.read())


def _imported(tree):
    """Every module a file imports, at any depth of its functions."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""
            yield from (f"{node.module}.{a.name}" for a in node.names)


def _named(tree):
    """Every identifier a file reads or binds: names, attributes, arguments,
    imported names. Strings (a counter's key, a refusal's message) are not
    identifiers."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            yield node.id
        elif isinstance(node, ast.Attribute):
            yield node.attr
        elif isinstance(node, ast.arg):
            yield node.arg
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            yield from (a.asname or a.name.split(".")[-1]
                        for a in node.names)


_LOWER = sorted(os.path.relpath(p, os.path.join(ROOT, "ray_tpu"))
                for d in ("models", "ops")
                for p in glob.glob(os.path.join(ROOT, "ray_tpu", d, "*.py")))


@pytest.mark.parametrize("path", _LOWER)
def test_models_and_ops_import_nothing_from_serve(path):
    assert not [m for m in _imported(_tree(path))
                if m.startswith("ray_tpu.serve")]


@pytest.mark.parametrize("name", [
    "named_scope", "lax", "hybrid", "latent", "mixed", "_hybrid", "_latent",
    "_mixed", "_by_slot", "_third", "conv", "_conv", "conv_layers",
    # what a model counts, the layout of its routing counts and of its
    # weights (PR 57: `models/serving.py::Books`, `serving_params`)
    "index_topk", "_index_topk", "window", "_window", "n_experts", "n_held",
    "_sparse", "shares", "block_forwards", "cache_bytes", "fuse_qkv",
    "split_qkv", "param_dtype"])
def test_the_scheduler_builds_no_program_and_names_no_architecture(name):
    assert name not in set(_named(_tree("serve", "engine.py")))


def test_the_scheduler_reads_max_seq_alone_of_the_configuration():
    """Every attribute `serve/engine.py` reads off the configuration, under
    any name it gives it (`mcfg`, `self.mcfg`, an alias of either)."""
    tree = _tree("serve", "engine.py")

    def is_cfg(node):
        return (isinstance(node, ast.Name) and node.id in held) or (
            isinstance(node, ast.Attribute) and node.attr == "mcfg")

    held = {"mcfg"}
    for node in ast.walk(tree):         # `m = self.mcfg`, `a, m = b, mcfg`
        if isinstance(node, ast.Assign):
            for to, what in zip(*(
                    n.elts if isinstance(n, ast.Tuple) else [n]
                    for n in (node.targets[0], node.value))):
                if isinstance(to, ast.Name) and is_cfg(what):
                    held.add(to.id)
    assert held == {"mcfg"}
    assert {n.attr for n in ast.walk(tree)
            if isinstance(n, ast.Attribute) and is_cfg(n.value)} == {"max_seq"}


def test_the_scheduler_imports_the_parameter_layout_and_the_programs_alone():
    """Nothing of `ray_tpu.ops` nor of `models.block`: of `models.serving`
    the programs, the books and the layout of the weights. And it is
    importable without jax (jax is imported inside functions)."""
    tree = _tree("serve", "engine.py")
    models = {m for m in _imported(tree)
              if m.startswith(("ray_tpu.models.", "ray_tpu.ops"))}
    assert models == {"ray_tpu.models.serving"}
    top = {m for node in tree.body
           if isinstance(node, (ast.Import, ast.ImportFrom))
           for m in _imported(ast.Module([node], []))}
    assert not [m for m in top if m.split(".")[0] in ("jax", "numpy")]


def test_the_prefill_pool_asks_adopts_and_names_no_architectures_field():
    named = set(_named(_tree("serve", "llm.py")))
    assert "adopts" in named
    assert not named & {"index_topk", "ssm_state", "latent", "mixed",
                        "kv_lora_rank", "attn_pattern", "attn_layers",
                        "conv", "conv_layers"}


def _own(fn):
    """The nodes of a function's body, less those of functions nested in it."""
    todo = list(fn.body)
    while todo:
        node = todo.pop()
        if not isinstance(node, (ast.FunctionDef, ast.Lambda)):
            yield node
            todo.extend(ast.iter_child_nodes(node))


def test_one_walk_for_a_prompt_and_one_for_a_decode_step():
    """Two functions of `models/serving.py` walk the segments, one for a
    prompt and one for a decode step, both as `_segments` lists them (since
    PR 61: each segment with the layer of its cache its first ordinal
    writes, reckoned once for both), and none of the three names an
    architecture."""
    walks = [fn for fn in ast.walk(_tree("models", "serving.py"))
             if isinstance(fn, ast.FunctionDef) and any(
                 isinstance(n, ast.For) and "segments" in ast.dump(n.iter)
                 for n in _own(fn))]
    assert sorted(fn.name for fn in walks) == ["_segments", "_step", "walk"]
    for fn in walks:
        assert not set(_named(fn)) & {"hybrid", "latent", "mixed",
                                      "ssm_state", "kv_lora_rank",
                                      "attn_pattern"}

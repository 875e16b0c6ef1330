"""benchmark/program_trace.py and the readers over it (PR 24): on traces built
here event by event (the pairing's edge cases), on the trace recorded on the
chip with the program's spans and scopes (`tiny24.xplane.pb`), and on PR 23's
recorded trace, which has neither."""

import os

import pytest

from benchmark import program_trace as pt
from benchmark.run import load_reader

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MS = 1_000_000      # ns

NEW_READERS = [
    "engine_queue_wait_ms", "engine_pipeline_wait_ms",
    "engine_prefill_emit_ms", "prefill_padding_pct", "decode_occupancy_pct",
    "decode_layers_carry_ms", "decode_kv_ms", "decode_attn_ms",
    "decode_mlp_ms", "ingest_get_ms", "ingest_device_put_ms",
    "train_optimizer_ms", "train_head_loss_ms", "prefill_ms_per_ktok"]


def build(spans=(), modules=(), ops=()) -> pt.ProgramTrace:
    """A trace as the profiler would write it, from (name, start_ms, end_ms,
    args) host spans, (name, start_ms, end_ms) programs and (scope path,
    start_ms, end_ms) instructions; serialised and parsed back."""
    space = pt._xspace_class()()

    def plane(name):
        p = space.planes.add(name=name)
        ids = {"stat": {}, "event": {}}

        def stat_id(key):
            if key not in ids["stat"]:
                ids["stat"][key] = len(ids["stat"]) + 1
                e = p.stat_metadata.add(key=ids["stat"][key])
                e.value.id, e.value.name = ids["stat"][key], key
            return ids["stat"][key]

        def event_id(key, tf_op=None):
            if key not in ids["event"]:
                ids["event"][key] = len(ids["event"]) + 1
                e = p.event_metadata.add(key=ids["event"][key])
                e.value.id, e.value.name = ids["event"][key], key
                if tf_op is not None:
                    e.value.stats.add(metadata_id=stat_id("tf_op"),
                                      str_value=tf_op)
            return ids["event"][key]

        return p, stat_id, event_id

    def add(line, meta_id, start_ms, end_ms):
        return line.events.add(metadata_id=meta_id,
                               offset_ps=int(start_ms * 1e9),
                               duration_ps=int((end_ms - start_ms) * 1e9))

    host, stat_id, event_id = plane("/host:CPU")
    line = host.lines.add(name="llm-engine")
    for name, s, e, args in spans:
        ev = add(line, event_id(name), s, e)
        for k, v in args.items():
            st = ev.stats.add(metadata_id=stat_id(k))
            if isinstance(v, str):
                st.str_value = v
            else:
                st.int64_value = v
    chip, stat_id, event_id = plane("/device:TPU:0")
    line = chip.lines.add(name="XLA Modules")
    for name, s, e in modules:
        add(line, event_id(name + "(123)"), s, e)
    line = chip.lines.add(name="XLA Ops")
    for i, (path, s, e) in enumerate(ops):
        add(line, event_id(f"%fusion.{i} = f32[] fusion()", tf_op=path), s, e)
    return pt.parse(space.SerializeToString())


def admit(rid, start, kind="prefill", prompt=100, bucket=128, wait=50):
    return ("serve.engine.admit", start, start + 1,
            {"rid": rid, "kind": kind, "prompt_tokens": prompt,
             "bucket": bucket, "queue_wait_us": wait})


def first(rid, start, end):
    return ("serve.engine.emit", start, end, {"rid": rid, "kind": "first"})


# -- parsing -----------------------------------------------------------------

def test_a_built_trace_parses_back_with_args_programs_and_scopes():
    t = build(spans=[admit(7, 10), ("bench.request", 0, 99, {}),
                     ("data.iter.format", 3, 4, {"rows": 2})],
              modules=[("jit_decode", 5, 9)],
              ops=[("jit(decode)/while/body/layers/while/body/attn/exp:", 6, 7)])
    assert [s.name for s in t.spans] == ["data.iter.format",
                                         "serve.engine.admit"]
    assert t.spans[1].args == {"rid": 7, "kind": "prefill",
                               "prompt_tokens": 100, "bucket": 128,
                               "queue_wait_us": 50}
    assert t.spans[1].start == 10 * MS and t.spans[1].end == 11 * MS
    assert t.modules == [("jit_decode", 5 * MS, 9 * MS)]
    assert t.ops == [("jit(decode)/while/body/layers/while/body/attn/exp:",
                      6 * MS, 7 * MS)]
    assert t.named("serve.engine.admit", kind="adopt") == []


@pytest.mark.parametrize("path, scope", [
    ("jit(decode)/while/body/closed_call/layers/while/body/closed_call/"
     "attn/nkgd,nskd->nkgs/dot_general:", "attn"),
    ("jit(decode)/while/body/closed_call/layers/while/body/"
     "dynamic_update_slice:", "layers"),
    ("jit(step_fn)/transpose(jvp(layers))/while/body/checkpoint/"
     "rematted_computation/mlp/dot_general:", "mlp"),
    ("jit(step_fn)/jvp(head)/dot_general:", "head"),
    ("jit(step_fn)/optimizer/mul:", "optimizer"),
    ("jit(step_fn)/jvp(jit(_take))/gather:", ""),
    ("jit(decode)/while/body/attn:", ""),          # a primitive, not a scope
    ("kv_gather/gather", "kv_gather"),             # relative, outlined body
    ("", ""),
])
def test_deepest_scope(path, scope):
    assert pt.deepest_scope(path) == scope


def test_scope_ms_is_self_time_by_scope_and_sums_to_the_program():
    # Four executions of 10 ms; the first and the last are the window's edges.
    modules = [("jit_decode", 100 * i, 100 * i + 10) for i in range(4)]
    ops = []
    for i in range(4):
        b = 100 * i
        ops += [("jit(decode)/while:", b, b + 9),                # 1 own
                ("jit(decode)/while/body/layers/while:", b + 1, b + 8),  # 2
                ("jit(decode)/while/body/layers/while/body/attn/exp:",
                 b + 2, b + 5),
                ("jit(decode)/while/body/layers/while/body/kv_gather/gather:",
                 b + 5, b + 7),
                ("jit(decode)/while/body/head/dot_general:", b + 8, b + 9)]
    t = build(modules=modules + [("jit_prefill", 50, 51)], ops=ops)
    assert len(t.whole_modules("jit_decode")) == 2
    per = t.scope_ms("jit_decode")
    assert per == pytest.approx({"": 1.0, "layers": 2.0, "attn": 3.0,
                                 "kv_gather": 2.0, "head": 1.0,
                                 "(idle)": 1.0})
    assert sum(per.values()) == pytest.approx(10.0)
    assert t.scope_ms("jit_step_fn") is None


def test_scope_ms_is_not_moved_by_an_execution_the_profiler_lost():
    """Seen on the chip at a trace's end: one long `jit_decode` event with
    few instructions under it. The median over executions ignores it."""
    modules = [("jit_decode", 100 * i, 100 * i + 10) for i in range(5)]
    modules += [("jit_decode", 500, 540), ("jit_decode", 541, 542)]
    ops = [("jit(decode)/layers/while/body/attn/exp:", 100 * i + 2,
            100 * i + 8) for i in range(5)]
    per = build(modules=modules, ops=ops).scope_ms("jit_decode")
    assert per == pytest.approx({"attn": 6.0, "(idle)": 4.0})


def test_per_step_ms_takes_what_ended_since_the_step_before():
    spans = []
    for i in range(4):
        b = 100 * i
        spans += [("data.iter.get_block", b, b + 2 + i, {}),
                  ("data.iter.format", b + 10, b + 11, {"rows": 1}),
                  ("data.iter.device_put", b + 20, b + 20.5, {"rows": 1}),
                  ("train.step", b + 30, b + 31, {"step_num": i})]
    t = build(spans=spans)
    # Steps 1..3 (the first has no step before it): 4, 5, 6 ms.
    assert t.per_step_ms(("data.iter.get_block",
                          "data.iter.format")) == pytest.approx(5.0)
    assert t.per_step_ms(("data.iter.device_put",)) == pytest.approx(0.5)
    assert build(spans=spans[:4]).per_step_ms(("data.iter.format",)) is None


# -- the pairing of admits, prefills and first emits -------------------------

def pairs(t):
    return [(a.args["rid"], round((p[1] - a.start) / MS, 3),
             round((e.end - p[1]) / MS, 3) if e else None)
            for a, p, e in t.prefills()]


def test_prefills_pair_in_order_even_when_admits_run_ahead():
    # Two admits before the first prefill starts (queued behind a chunk).
    t = build(spans=[admit(3, 10), admit(4, 20), first(3, 400, 450),
                     first(4, 451, 520)],
              modules=[("jit_decode", 0, 350), ("jit_prefill", 350, 440),
                       ("jit_prefill", 440, 510), ("jit_decode", 510, 860)])
    assert pairs(t) == [(3, 340.0, 100.0), (4, 420.0, 80.0)]


def test_prefills_skip_a_request_admitted_before_the_trace_began():
    # rid 2 was admitted before the window: its prefill and its first emit are
    # inside, and its prefill even starts after rid 3's admit.
    t = build(spans=[admit(3, 10), first(2, 300, 380), first(3, 380, 470)],
              modules=[("jit_decode", 0, 300), ("jit_prefill", 300, 370),
                       ("jit_prefill", 370, 460)])
    assert pairs(t) == [(3, 360.0, 100.0)]


def test_prefills_drop_an_admit_whose_prefill_ran_after_the_trace_ended():
    t = build(spans=[admit(3, 10), admit(4, 500), first(3, 100, 200)],
              modules=[("jit_prefill", 50, 190)])
    assert pairs(t) == [(3, 40.0, 150.0)]


def test_prefills_keep_a_pair_without_its_emit_and_ignore_adoptions():
    t = build(spans=[admit(3, 10, kind="adopt"), admit(4, 20)],
              modules=[("jit_adopt", 11, 12), ("jit_prefill", 30, 90)])
    assert pairs(t) == [(4, 10.0, None)]


def test_prefills_drop_a_pair_that_is_out_of_order_in_time():
    # A prefill that started before its admit cannot be its prefill.
    t = build(spans=[admit(3, 100), first(3, 150, 160)],
              modules=[("jit_prefill", 50, 90)])
    assert pairs(t) == []


# -- the readers ---------------------------------------------------------------

def read(name, trace, monkeypatch):
    monkeypatch.setattr(pt, "load", lambda run: trace)
    run = {"cell": "x", "seed": 1, "config": {"deployment": {"engine": {
        "decode_chunk": 8}}}}
    return load_reader(BENCH, "layer_metrics", name)(run)


@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_gives_none_without_a_trace(name, monkeypatch):
    assert read(name, None, monkeypatch) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_gives_none_on_the_parents_trace(name, monkeypatch):
    """PR 23's recorded trace: a device plane, `bench.*` spans, and a program
    with no span and no scope of its own."""
    parent = pt.load_path(os.path.join(HERE, "tiny.xplane.pb"))
    assert parent.spans == [] and len(parent.modules) > 4 and parent.ops
    assert read(name, parent, monkeypatch) is None


def test_serve_readers_on_a_built_trace(monkeypatch):
    chunk = ("serve.engine.decode_dispatch", 0, 1,
             {"useful": 24, "capacity": 128, "active": 5})
    t = build(spans=[admit(3, 10, prompt=100, bucket=128, wait=400),
                     admit(4, 20, prompt=300, bucket=512, wait=600),
                     admit(5, 30, prompt=40, bucket=64, wait=2000),
                     first(3, 400, 450), first(4, 451, 520), chunk,
                     chunk[:3] + ({"useful": 40, "capacity": 128,
                                   "active": 5},)],
              modules=[("jit_decode", 0, 350), ("jit_prefill", 350, 440),
                       ("jit_prefill", 440, 510), ("jit_decode", 510, 860)])
    assert read("engine_queue_wait_ms", t, monkeypatch) == pytest.approx(0.6)
    assert read("engine_pipeline_wait_ms", t, monkeypatch) == \
        pytest.approx((340 + 420) / 2)
    assert read("engine_prefill_emit_ms", t, monkeypatch) == \
        pytest.approx((100 + 80) / 2)
    assert read("prefill_padding_pct", t, monkeypatch) == \
        pytest.approx(100.0 * (28 + 212 + 24) / (128 + 512 + 64))
    assert read("decode_occupancy_pct", t, monkeypatch) == \
        pytest.approx(100.0 * 64 / 256)
    # the two paired prefills: 90 + 70 ms for 100 + 300 prompt tokens; the
    # third admit's prefill ran after the trace and counts on neither side
    assert read("prefill_ms_per_ktok", t, monkeypatch) == \
        pytest.approx(160 / 0.4)


RECORDED = os.path.join(HERE, "tiny24.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """Recorded on a TPU v5 lite in PR 24 (scratch probe): four train steps
    of a 2-layer d_model 256 model fed through `iter_jax_batches_from_refs`,
    then three engine requests (129, 39 and 69 prompt tokens)."""
    return pt.load_path(RECORDED)


def test_recorded_trace_has_the_programs_spans_and_scopes(recorded):
    names = {s.name for s in recorded.spans}
    assert {"serve.engine.admit", "serve.engine.decode_dispatch",
            "serve.engine.emit", "serve.engine.emit_block", "train.step",
            "data.iter.get_block", "data.iter.format",
            "data.iter.device_put"} <= names
    admits = recorded.named("serve.engine.admit")
    assert [a.args["prompt_tokens"] for a in admits] == [129, 39, 69]
    assert [a.args["bucket"] for a in admits] == [256, 64, 128]
    scopes = {pt.deepest_scope(p) for p, _, _ in recorded.ops}
    assert set(pt.SCOPES) <= scopes
    assert any("transpose(jvp(" in p for p, _, _ in recorded.ops)
    got = [(a.args["rid"], e is not None) for a, _, e in recorded.prefills()]
    assert got == [(a.args["rid"], True) for a in admits]


@pytest.mark.parametrize("name, value", [
    ("engine_queue_wait_ms", 2.789),              # of 199, 2789, 5271 us
    ("engine_pipeline_wait_ms", 0.834883),        # of 0.835, 0.740, 0.976
    ("engine_prefill_emit_ms", 5.876371),         # of 8.008, 5.876, 3.485
    ("prefill_padding_pct", 100.0 * (127 + 25 + 59) / (256 + 64 + 128)),
    ("decode_occupancy_pct", 100.0 * (16 + 8 + 24) / (3 * 32)),
    ("decode_layers_carry_ms", 0.008878866 / 8),
    ("decode_kv_ms", (0.017767616 + 0.010692696) / 8),
    ("decode_attn_ms", 0.012114492 / 8),
    ("decode_mlp_ms", (0.016275624 + 0.005192890 + 0.007488164) / 8),
    ("ingest_get_ms", 0.02019),
    ("ingest_device_put_ms", 0.27829),
    ("train_optimizer_ms", 0.015676718),
    ("train_head_loss_ms", 0.005868594 + 0.007951484),
    # 0.040479 + 0.027980 + 0.032665 ms for 129 + 39 + 69 prompt tokens
    ("prefill_ms_per_ktok", 0.101123672 / 0.237),
])
def test_every_new_reader_on_the_recorded_trace(recorded, name, value,
                                                monkeypatch):
    assert read(name, recorded, monkeypatch) == pytest.approx(value, rel=1e-5)


def test_scopes_of_the_recorded_programs_sum_to_their_durations(recorded):
    from benchmark.stats import median
    for program in ("jit_decode", "jit_step_fn"):
        per = recorded.scope_ms(program)
        whole = median([(e - s) / 1e6
                        for _, s, e in recorded.whole_modules(program)])
        assert sum(per.values()) == pytest.approx(whole, rel=0.02)
        assert per[""] < 0.3 * whole       # most of the time has a name


def test_the_older_reduction_still_reads_the_recorded_trace():
    """trace.py (PR 23) on a program with named kernels and scopes: programs,
    busy time, and the flash kernels found by their custom-call target."""
    from benchmark import trace
    t = trace.load(RECORDED)
    assert len(t.module_durations("jit_step_fn")) == 4
    assert len(t.module_durations("jit_decode")) == 3
    assert 0.0 < t.busy_s < t.window_s
    share = load_reader(BENCH, "layer_metrics", "attn_kernel_roofline")(
        {"trace_data": t, "device": {"kind": "TPU v5 lite"}})
    assert 0.0 < share < 100.0

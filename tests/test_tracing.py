"""ray_tpu.utils.tracing: span() and its two sinks, the engine's spans and
counters, the scope names in the lowered programs, the iterator's spans, and
a span under an actor task on the timeline. CPU only."""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.utils import tracing

SCOPES_DECODE = ("embed", "layers", "attn_norm", "qkv", "rope", "kv_write",
                 "attn", "attn_out", "mlp_norm", "mlp", "head", "sample")
SCOPES_PREFILL = ("embed", "layers", "attn_norm", "qkv", "rope", "attn",
                  "attn_out", "mlp_norm", "mlp", "head", "kv_write", "sample")
SCOPES_TRAIN = ("embed", "layers", "attn_norm", "qkv", "rope", "attn",
                "attn_out", "mlp_norm", "mlp", "head", "loss", "optimizer")


class _Profiled:
    """`with _Profiled(dir) as p:` traces the body; `p.events(prefix)` are
    the host events (name, start_ns, end_ns, stats) it left."""

    def __init__(self, directory):
        self.dir = str(directory)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def events(self, prefix):
        (path,) = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                            recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        return sorted(
            (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix))


def test_span_with_no_profiler_and_no_worker_is_cheap_and_records_nothing(
        monkeypatch):
    import ray_tpu.api
    monkeypatch.setattr(ray_tpu.api, "_core_worker", None)
    assert tracing._worker() is None and tracing.context() is None
    t0 = time.perf_counter()
    for i in range(10_000):
        with tracing.span("test.noop", i=i) as args:
            pass
    assert time.perf_counter() - t0 < 0.2
    assert args == {"i": 9_999}      # the body's copy, handed nowhere


def test_compile_span_counts_what_jax_compiled():
    @jax.jit
    def f(x):
        return x * 3 + 1

    with tracing.compile_span("test.compile", program="f") as first:
        f(jnp.ones(7)).block_until_ready()
    with tracing.compile_span("test.compile", program="f") as again:
        f(jnp.ones(7)).block_until_ready()
    assert first["compiles"] >= 1 and first["compile_s"] > 0
    assert again["compiles"] == 0 and again["program"] == "f"


@pytest.fixture(scope="module")
def engine():
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.engine import Engine
    cfg = LlamaConfig.tiny()
    eng = Engine(init_params(cfg, jax.random.PRNGKey(0)), cfg, n_slots=4,
                 decode_chunk=4, page_size=16)
    while sorted(eng._warm) != sorted(eng.buckets):
        assert not eng.warm_error, eng.warm_error
        time.sleep(0.05)
    yield eng
    eng.stop()


def test_engine_spans_land_in_the_profile_and_agree_with_counters(
        engine, tmp_path):
    asks = [(5, 7), (40, 12), (17, 3)]      # (prompt tokens, max_tokens)
    before = engine.counters()
    with _Profiled(tmp_path) as prof:
        streams = [engine.submit(list(range(1, 1 + n)), m) for n, m in asks]
        chunks = []
        for q in streams:
            got = []
            while (toks := q.get()) is not None:
                got.append(toks)
            chunks.append(got)
    after = engine.counters()
    delta = {k: after[k] - before[k] for k in after
             if k not in ("peak_pages_used", "n_slots", "chunk")}

    admits = [s for n, _, _, s in prof.events("serve.engine.admit")]
    assert [a["rid"] for a in admits] == sorted(a["rid"] for a in admits)
    assert [a["prompt_tokens"] for a in admits] == [n for n, _ in asks]
    assert all(a["kind"] == "prefill" and a["queue_wait_us"] >= 0
               and a["bucket"] in engine.buckets
               and a["bucket"] >= a["prompt_tokens"] for a in admits)
    assert admits[0]["pending"] >= admits[-1]["pending"] == 0
    padded = sum(a["bucket"] - a["prompt_tokens"] for a in admits)
    assert delta["admitted"] == 3
    assert delta["prefill_tokens"] == sum(n for n, _ in asks)
    assert delta["prefill_padded_tokens"] == padded == 27 + 24 + 15
    assert delta["queue_wait_s_sum"] * 1e6 >= \
        sum(a["queue_wait_us"] for a in admits) - 3

    chunks_d = [s for n, _, _, s in
                prof.events("serve.engine.decode_dispatch")]
    streamed_after_first = sum(len(c) for got in chunks for c in got[1:])
    assert streamed_after_first == sum(m - 1 for _, m in asks)
    # A token after a request's first is a decode chunk's or, since PR 41, a
    # riding step's inside a later admission's prefill (the 64 rung of this
    # `max_seq` of 128 rides: `engine.rung_rides`), counted on its admit span.
    rode = sum(a.get("riders", 0) for a in admits)
    assert [("riders" in a) for a in admits] == [
        a["bucket"] == 64 for a in admits]
    assert rode == delta["rider_tokens"] >= delta["rider_steps"]
    assert sum(c["useful"] for c in chunks_d) \
        == delta["decode_useful_tokens"] == streamed_after_first - rode
    assert len(chunks_d) == delta["decode_chunks"]
    assert all(c["capacity"] == 16 and 1 <= c["active"] <= 3
               and c["useful"] <= c["active"] * 4 for c in chunks_d)

    emits = prof.events("serve.engine.emit")
    firsts = [s for n, _, _, s in emits
              if n == "serve.engine.emit" and s["kind"] == "first"]
    assert sorted(f["rid"] for f in firsts) == [a["rid"] for a in admits]
    assert sum(1 for n, _, _, s in emits if n == "serve.engine.emit"
               and s["kind"] == "chunk") == len(chunks_d)
    assert sum(1 for n, _, _, _ in emits
               if n == "serve.engine.emit_block") == len(chunks_d)


def test_decode_dispatch_carries_the_live_kv_tokens_the_host_holds(
        engine, tmp_path):
    """`live_kv_tokens` on a `serve.engine.decode_dispatch` span is the sum
    over the active slots of the position each held when the chunk was
    dispatched: what decode attention reads a layer at the chunk's first
    step. One request at a time, so the host's own sum is known here: a
    prompt of n tokens is dispatched at n, n + chunk, ..."""
    asks = [(5, 7), (40, 12)]               # (prompt tokens, max_tokens)
    before = engine.counters()["live_kv_tokens"]
    with _Profiled(tmp_path) as prof:
        for n, m in asks:
            q = engine.submit(list(range(1, 1 + n)), m)
            while q.get() is not None:
                pass
    spans = [s for _, _, _, s in prof.events("serve.engine.decode_dispatch")]
    want = [n + engine.chunk * i for n, m in asks
            for i in range(-(-(m - 1) // engine.chunk))]
    assert [s["live_kv_tokens"] for s in spans] == want == [5, 9, 40, 44, 48]
    assert all(s["active"] == 1 for s in spans)
    assert engine.counters()["live_kv_tokens"] - before == sum(want)
    # beside it, what a whole-table gather would have moved
    assert sum(want) < len(spans) * engine.n_slots * engine.mcfg.max_seq


@pytest.mark.parametrize("program", ["decode", "prefill", "train_step"])
def test_scope_names_are_in_the_lowered_program(engine, program):
    def shape_of(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    if program == "train_step":
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.parallel import MeshConfig, ParallelContext
        from ray_tpu.train.spmd import make_train_fns
        cfg = LlamaConfig.tiny()
        init, step = make_train_fns(
            cfg, ParallelContext.create(MeshConfig(), jax.devices()[:1]))
        state = jax.eval_shape(init._jitted, jax.random.PRNGKey(0))
        lowered = step.lower(state, jax.ShapeDtypeStruct((2, 32), jnp.int32))
        wanted = SCOPES_TRAIN
    else:
        e = engine
        if program == "decode":
            lowered = e._programs.decode.lower(*e.decode_shapes())
            wanted = SCOPES_DECODE
        else:
            lowered = e._programs.prefill.lower(*e.prefill_shapes(32))
            wanted = SCOPES_PREFILL
    # `loc("jit(f)/attn/dot_general"`; relative to an outlined scan body,
    # `loc("attn/dot_general"`; differentiated, `jvp(attn)`.
    text = lowered.as_text(debug_info=True)
    missing = [s for s in wanted
               if not re.search(rf'[/"(]{s}[/)]', text)]
    assert not missing, missing


def test_flash_kernels_are_named_in_the_tpu_lowering():
    from ray_tpu.ops import attention as A
    x = jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, 2, 256), jnp.float32)
    fwd = jax.jit(lambda q, k, v: A._flash_fwd_pallas(
        q, k, v, causal=True, sm_scale=0.1)).trace(x, x, x).lower(
            lowering_platforms=("tpu",)).as_text()
    bwd = jax.jit(lambda q, k, v, o, l, do: A._flash_bwd_pallas(
        q, k, v, o, l, do, causal=True, sm_scale=0.1)).trace(
            x, x, x, x, lse, x).lower(lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "flash_fwd"' in fwd
    assert 'kernel_name = "flash_bwd_dkv"' in bwd
    assert 'kernel_name = "flash_bwd_dq"' in bwd


def test_train_step_wrapper_keeps_the_jitted_functions_surface():
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel import MeshConfig, ParallelContext
    from ray_tpu.train.spmd import make_train_fns
    cfg = LlamaConfig.tiny(n_layers=1)
    init, step = make_train_fns(
        cfg, ParallelContext.create(MeshConfig(), jax.devices()[:1]))
    assert callable(step.lower) and callable(init.lower)
    state = init(jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 32), jnp.int32)
    state, m0 = step(state, toks)      # train.compile
    state, m1 = step(state, toks)      # train.step 1
    assert int(state["step"]) == 2 and float(m1["loss"]) < float(m0["loss"])


# -- with a runtime: the iterator's spans, and the timeline sink -------------

@pytest.fixture(scope="module")
def cluster():
    from ray_tpu.core.cluster_utils import Cluster
    c = Cluster(num_nodes=1, resources={"CPU": 4})
    c.connect()
    yield c
    c.shutdown()


def test_iter_jax_batches_same_arrays_and_its_spans_a_block(
        cluster, tmp_path):
    import ray_tpu
    from ray_tpu.data.iterator import iter_jax_batches_from_refs
    blocks = [{"tokens": np.arange(i * 32, (i + 1) * 32,
                                   dtype=np.int32).reshape(4, 8)}
              for i in range(3)]
    refs = [ray_tpu.put(b) for b in blocks]
    with _Profiled(tmp_path) as prof:
        got = list(iter_jax_batches_from_refs(iter(refs), batch_size=4))
    assert len(got) == 3
    for batch, block in zip(got, blocks):
        assert isinstance(batch["tokens"], jax.Array)
        np.testing.assert_array_equal(np.asarray(batch["tokens"]),
                                      block["tokens"])
    names = [n for n, _, _, _ in prof.events("data.iter.")]
    made = (["data.iter.get_block", "data.iter.format",
             "data.iter.device_put"] * 3
            + ["data.iter.next_ref"] * 4)   # the last finds the source dry
    # The default makes the next batch ahead: the consumer's four takes (the
    # last is told of the end) beside the producer's spans.
    assert sorted(names) == sorted(made + ["data.iter.take"] * 4)
    puts = [s for n, _, _, s in prof.events("data.iter.device_put")]
    assert all(p["rows"] == 4 and p["bytes"] == 4 * 8 * 4 for p in puts)
    assert all(s["ready"] in (0, 1)
               for _, _, _, s in prof.events("data.iter.take"))
    # Inline, the parent's spans exactly.
    with _Profiled(tmp_path / "inline") as prof:
        again = list(iter_jax_batches_from_refs(iter(refs), batch_size=4,
                                                prefetch_batches=0))
    for batch, block in zip(again, blocks):
        np.testing.assert_array_equal(np.asarray(batch["tokens"]),
                                      block["tokens"])
    assert sorted(n for n, _, _, _ in prof.events("data.iter.")) \
        == sorted(made)


class _Sink:
    """What `tracing.span` asks of a core worker, with the timeline's buffer
    kept here (the worker's own is shipped away every 2 s)."""

    def __init__(self):
        from ray_tpu.core._native import graftscope
        self._asm, self._scope_spans = graftscope.SpanAssembler("test"), []

    def _scope_asm(self):
        return self._asm

    def _lost_spans(self, spans):
        raise AssertionError("the buffer's bound was passed")


def test_producers_spans_carry_the_consumers_trace_and_take_says_ready(
        cluster, monkeypatch):
    import threading

    import ray_tpu
    from ray_tpu.data.iterator import (PRODUCER_THREAD,
                                       iter_jax_batches_from_refs)
    refs = [ray_tpu.put({"tokens": np.full((4, 8), i, np.int32)})
            for i in range(3)]
    sink = _Sink()
    monkeypatch.setattr(tracing, "_worker", lambda: sink)
    trace_id = bytes(range(16))
    with tracing.root(trace_id):
        it = iter_jax_batches_from_refs(iter(refs), batch_size=4)
        first = next(it)
        # Wait until all is made: one batch in the queue, one in the
        # producer's hands.
        deadline = time.time() + 10
        while time.time() < deadline and sum(
                s["name"] == "data.iter.device_put"
                for s in sink._scope_spans) < 3:
            time.sleep(0.005)
        rest = list(it)
    assert int(first["tokens"][0, 0]) == 0 and len(rest) == 2
    spans = list(sink._scope_spans)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert {n: len(v) for n, v in by_name.items()} == {
        "data.iter.next_ref": 4, "data.iter.get_block": 3,
        "data.iter.format": 3, "data.iter.device_put": 3,
        "data.iter.take": 4}
    me = threading.current_thread().name
    for s in spans:
        assert s["trace_id"] == trace_id.hex() and s["cat"] == "program"
        assert s["tid"] == (me if s["name"] == "data.iter.take"
                            else PRODUCER_THREAD)
    takes = by_name["data.iter.take"]
    assert all(0 <= t["args"]["waited_us"] <= t["dur"] + 1 for t in takes)
    # The second take's batch lay waiting; the first races the producer's
    # start, the later ones its `put` of what it held.
    assert takes[1]["args"]["ready"] == 1
    assert all(t["args"]["ready"] in (0, 1) for t in takes)


def test_span_inside_an_actor_task_is_on_the_timeline_under_its_trace(
        cluster):
    import ray_tpu
    from ray_tpu import state
    from ray_tpu.core._native import graftscope
    if not (graftscope.available() and graftscope.enabled()):
        pytest.skip("graftscope recorder unavailable")

    @ray_tpu.remote
    class Worker:
        def work(self, n):
            import threading

            from ray_tpu.utils import tracing
            with tracing.span("test.inside_task", n=n):
                ctx = tracing.context()
            # A thread acting for the task carries the context explicitly.
            def other():
                with tracing.span("test.other_thread", ctx=ctx, n=n):
                    pass
            t = threading.Thread(target=other)
            t.start()
            t.join()
            return ctx[0].hex()

    w = Worker.remote()
    # The path to the timeline is best-effort (a flush the controller misses
    # is dropped), so ask again until one call's spans are all there.
    deadline = time.time() + 40     # the worker's flusher ticks every 2 s
    mine, track = [], None
    while time.time() < deadline and (len(mine) < 2 or track is None):
        trace_id = ray_tpu.get(w.work.remote(3))
        time.sleep(3.0)
        events = state.timeline(native=True)
        mine = [e for e in events if e["name"].startswith("test.")
                and e["args"].get("trace_id") == trace_id]
        track = next(((e["pid"], e["tid"]) for e in events
                      if e.get("cat") == "task"
                      and e["args"]["trace_id"] == trace_id), None)
    assert {e["name"] for e in mine} == {"test.inside_task",
                                         "test.other_thread"}
    for e in mine:
        assert e["cat"] == "program" and e["args"]["n"] == 3
        assert e["args"]["mono_ns"] > 0 and e["dur"] >= 0
        assert (e["pid"], e["tid"]) == track     # nested under the task

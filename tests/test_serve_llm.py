"""LLM serving preset: deploy a tiny Llama, stream completions via handle
and HTTP, non-streaming OpenAI-shaped response.

Mirrors the reference's LLM-serve smoke coverage (reference:
python/ray/llm/tests/serve/ deployment tests) on a CPU-sized model.
"""

import functools
import json
import urllib.request

import pytest

import ray_tpu
import ray_tpu.serve as serve
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.serve.llm import LLMConfig, build_llm_app


@pytest.fixture(scope="module")
def llm_handle():
    c = Cluster(num_nodes=1, resources={"CPU": 6})
    c.connect()
    serve.start(http=True)
    cfg = LLMConfig(vocab_size=512, d_model=128, n_layers=2, max_seq=64,
                    num_tpus=0, decode_chunk=4,
                    detokenizer=lambda ids: "".join(f"<{t}>" for t in ids))
    handle = serve.run(build_llm_app(cfg), name="llm")
    yield handle
    serve.shutdown()
    c.shutdown()


def test_streaming_completion_via_handle(llm_handle):
    chunks = list(llm_handle.stream(
        {"prompt": [1, 2, 3], "max_tokens": 6}))
    text = "".join(chunks)
    assert text.count("<") == 6  # six generated token markers
    # Greedy decode is deterministic: same prompt, same output.
    again = "".join(llm_handle.stream(
        {"prompt": [1, 2, 3], "max_tokens": 6}))
    assert again == text


def test_nonstreaming_openai_shape(llm_handle):
    resp = llm_handle.options(method_name="complete").remote(
        {"prompt": [4, 5], "max_tokens": 4}).result(timeout=120)
    assert resp["object"] == "text_completion"
    assert resp["choices"][0]["text"].count("<") == 4


def test_http_streaming_completion(llm_handle):
    port = serve.get_proxy().port
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llm",
        data=json.dumps({"prompt": [7, 8, 9],
                         "max_tokens": 5}).encode(),
        headers={"x-serve-stream": "1"})
    with urllib.request.urlopen(req, timeout=120) as r:
        body = r.read().decode()
    assert body.count("<") == 5


def test_continuous_batching_concurrent_streams(llm_handle):
    """Concurrent requests share the replica's decode loop: all finish,
    and greedy outputs are identical to their solo runs (slot isolation).
    Reference behavior: vllm continuous batching under concurrency."""
    import threading

    prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7], [11]]
    solo = ["".join(llm_handle.stream({"prompt": p, "max_tokens": 6}))
            for p in prompts]

    results = [None] * len(prompts)

    def run(i):
        results[i] = "".join(llm_handle.stream(
            {"prompt": prompts[i], "max_tokens": 6}))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert results == solo, (results, solo)


def test_continuous_batching_oversubscribed(llm_handle):
    """More requests than KV slots: queueing admits them as slots free."""
    import threading

    n = 12  # > max_ongoing_requests slots
    results = [None] * n

    def run(i):
        results[i] = "".join(llm_handle.stream(
            {"prompt": [3, 1, 4], "max_tokens": 4}))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert all(r is not None and r.count("<") == 4 for r in results), results
    assert len(set(results)) == 1  # deterministic greedy


def test_prefill_buckets_cross_boundary():
    """Bucketed prefill: prompts on either side of a bucket boundary
    produce the same tokens as each other's greedy continuation — the
    bucket width is a shape choice, never a semantics change. Engine
    buckets double up to 1024 (tests/test_prefill_ladder.py has the rungs
    above it) and end at max_seq."""
    import jax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.engine import Engine

    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
                      n_kv_heads=2, d_ff=64, max_seq=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, n_slots=2, decode_chunk=2)
    try:
        assert eng.buckets == [32, 64, 128]

        def gen(prompt, n):
            q = eng.submit(prompt, n)
            out = []
            while True:
                item = q.get(timeout=60)
                if item is None:
                    return out
                out.extend(item)

        short = gen([1, 2, 3], 4)                      # bucket 32
        long_p = gen(list(range(1, 41)), 4)            # bucket 64
        assert len(short) == 4 and len(long_p) == 4
        # Determinism within a bucket AND the engine stays healthy
        # across bucket switches (32 -> 64 -> 32).
        assert gen([1, 2, 3], 4) == short
        assert gen(list(range(1, 41)), 4) == long_p
    finally:
        eng.stop()


def test_prefill_decode_disaggregation():
    """PD disaggregation (reference: prefill_decode_disagg.py
    build_pd_openai_app): prompt -> prefill pool -> DeviceRef KV handoff
    -> decode pool, streamed through the ingress. Greedy output must
    match the monolithic engine exactly (same init seed)."""
    c = Cluster(num_nodes=1, resources={"CPU": 8})
    c.connect()
    try:
        serve.start()
        from ray_tpu.serve.llm import run_pd_llm_app

        cfg = LLMConfig(vocab_size=512, d_model=128, n_layers=2,
                        max_seq=64, num_tpus=0, decode_chunk=2,
                        max_ongoing_requests=4,
                        detokenizer=lambda ids: "".join(
                            f"<{t}>" for t in ids))
        pd = run_pd_llm_app(cfg, name="pd")

        # Monolithic reference output (identical params: PRNGKey(0)).
        mono = serve.run(build_llm_app(cfg), name="mono")
        prompt = {"prompt": [1, 2, 3, 4], "max_tokens": 8}
        want = "".join(mono.stream(dict(prompt)))

        got = "".join(pd.stream(dict(prompt)))
        assert got == want, (got, want)
        assert got.count("<") == 8

        # Concurrent PD streams (continuous batching on the decode pool).
        import threading
        outs = [None] * 4

        def run_one(i):
            outs[i] = "".join(pd.stream(dict(prompt)))

        ts = [threading.Thread(target=run_one, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert all(o == want for o in outs), outs

        # max_tokens=1: the prefill token alone completes the request.
        one = "".join(pd.stream({"prompt": [1, 2, 3, 4], "max_tokens": 1}))
        assert one == want[: len(one)] and one.count("<") == 1

        # SAMPLED parity: the same (seed, position) key derivation on
        # both topologies — PD output matches monolithic exactly,
        # including the prefill-side-sampled FIRST token.
        sampled_req = {"prompt": [1, 2, 3, 4], "max_tokens": 6,
                       "temperature": 1.0, "seed": 77}
        mono_s = "".join(mono.stream(dict(sampled_req)))
        pd_s = "".join(pd.stream(dict(sampled_req)))
        assert pd_s == mono_s, (pd_s, mono_s)
    finally:
        serve.shutdown()
        c.shutdown()


def test_paged_engine_matches_naive_greedy():
    """The paged-KV engine's output must EXACTLY match a naive greedy
    loop that recomputes full attention every step — the strongest
    correctness check on block-table paging (reference: vLLM paged
    attention parity tests). Covers prompts inside one page, spanning
    pages, and crossing prefill buckets."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, init_params, forward
    from ray_tpu.serve.engine import Engine

    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
                      n_kv_heads=2, d_ff=64, max_seq=64, dtype=np.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    fwd = jax.jit(lambda p, t: forward(p, t, cfg, None))

    def naive_greedy(prompt, n):
        ids = list(prompt)
        out = []
        for _ in range(n):
            toks = jnp.asarray(np.array(ids, np.int32)[None])
            out.append(int(jnp.argmax(fwd(params, toks)[0, len(ids) - 1])))
            ids.append(out[-1])
        return out

    # A copy: the engine takes its tree's q/k/v stacks over, `naive` reads them.
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=3,
                 decode_chunk=4, page_size=16)
    try:
        def gen(prompt, n):
            q = eng.submit(prompt, n)
            out = []
            while True:
                item = q.get(timeout=60)
                if item is None:
                    return out
                out.extend(item)

        for prompt in ([1, 2, 3], [7] * 20, list(range(1, 34))):
            assert gen(prompt, 8) == naive_greedy(prompt, 8)
    finally:
        eng.stop()


def test_paged_engine_oversubscription_bounded_pages():
    """More concurrent streams than FULL-LENGTH sequences would fit: 10
    short requests run in a pool sized for ~3 max_seq sequences. All
    complete with correct (deterministic) output, and the peak physical
    page usage stays under the pool size — the density win paging buys
    over per-slot max_seq strips."""
    import threading

    import jax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.engine import Engine

    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
                      n_kv_heads=2, d_ff=64, max_seq=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    # maxp = 128/16 = 8 pages/full seq; pool of 25 pages ~ 3 full seqs,
    # but 12 slots: only short requests can reach full occupancy.
    eng = Engine(params, cfg, n_slots=12, decode_chunk=4, page_size=16,
                 n_pages=26)
    try:
        def gen(prompt, n):
            q = eng.submit(prompt, n)
            out = []
            while True:
                item = q.get(timeout=120)
                if item is None:
                    return out
                out.extend(item)

        solo = gen([5, 6, 7], 6)
        outs = [None] * 10
        def run(i):
            outs[i] = gen([5, 6, 7], 6)
        ts = [threading.Thread(target=run, args=(i,)) for i in range(10)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        assert all(o == solo for o in outs), outs
        # 10 requests x ceil((3+6)/16)=1 page each: density 10 streams in
        # 10 pages, where max_seq strips would need 80.
        assert eng.peak_pages_used <= 25
        assert eng.pages_in_use() == 0  # all returned
    finally:
        eng.stop()


def test_sampling_temperature_topk_seed():
    """Sampling controls (reference: vLLM SamplingParams): temperature 0
    and top_k=1 reproduce greedy exactly; a fixed seed reproduces the
    same stream (slot-independent); different seeds diverge."""
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.engine import Engine

    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
                      n_kv_heads=2, d_ff=64, max_seq=64,
                      dtype=np.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, n_slots=3, decode_chunk=4, page_size=16)
    try:
        def gen(prompt, n, **kw):
            q = eng.submit(prompt, n, **kw)
            out = []
            while True:
                item = q.get(timeout=60)
                if item is None:
                    return out
                out.extend(item)

        greedy = gen([1, 2, 3], 8)
        assert gen([1, 2, 3], 8, temperature=0.0) == greedy
        assert gen([1, 2, 3], 8, temperature=1.0, top_k=1,
                   seed=9) == greedy
        s1 = gen([1, 2, 3], 8, temperature=1.0, seed=42)
        s2 = gen([1, 2, 3], 8, temperature=1.0, seed=42)
        s3 = gen([1, 2, 3], 8, temperature=1.0, seed=43)
        assert s1 == s2
        assert s3 != s1 or s1 != greedy
        # Concurrent sampled + greedy streams keep slot isolation.
        import threading
        outs = [None] * 3
        kws = [{}, {"temperature": 1.0, "seed": 42},
               {"temperature": 1.0, "seed": 43}]

        def run(i):
            outs[i] = gen([1, 2, 3], 8, **kws[i])

        ts = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert outs[0] == greedy and outs[1] == s1 and outs[2] == s3
    finally:
        eng.stop()


# ----------------------------------------------------------------------
# The sampler takes its top-k only where a row asks for a sample (PR 47)
# ----------------------------------------------------------------------
def parents_sample_tokens(logits, temp, topk, keys, pos, cap=64):
    """`models/serving.py::sample_tokens` as PR 47's parent (7f64f96) had it,
    letter for letter but for the names it imports: the top-`cap`, the draws
    and the gather for every row of every call, thrown away where `temp` is
    0. The reference the sampler is held to, token for token; and, put on
    `serving.sample_tokens`, what the pinned programs of tests/test_dots.py
    and tests/test_prefill_riders.py are lowered with."""
    import jax
    import jax.numpy as jnp

    cap = min(cap, logits.shape[-1])

    def one_gumbel(key, p):
        return jax.random.gumbel(jax.random.fold_in(key, p), (cap,))

    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        vals, idxs = jax.lax.top_k(logits.astype(jnp.float32), cap)
        k_eff = jnp.where(topk > 0, jnp.minimum(topk, cap), cap)
        mask = jnp.arange(cap)[None, :] < k_eff[:, None]
        scaled = jnp.where(mask, vals / jnp.maximum(temp, 1e-6)[:, None],
                           -1e30)
        g = jax.vmap(one_gumbel)(keys, pos)
        pick = jnp.argmax(scaled + g, axis=-1)
        sampled = jnp.take_along_axis(idxs, pick[:, None], axis=1)[:, 0]
        return jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)


# Eight rows a call: (temperature, top_k) a row; top_k 0 is "the cap", 100 is
# over it.
ROWS = {
    "greedy": [(0.0, 0), (0.0, 5), (0.0, 100), (0.0, 1)] * 2,
    "sampling": [(0.7, 0), (1.0, 5), (1.3, 100), (0.2, 1), (5.0, 0),
                 (1.0, 2), (0.7, 64), (3.0, 63)],
    "mixed": [(0.0, 0), (0.7, 0), (0.0, 5), (1.0, 5), (1.3, 100), (0.0, 100),
              (0.0, 1), (5.0, 3)],
    "one_sampling_slot": [(0.0, 0)] * 5 + [(0.9, 40)] + [(0.0, 0)] * 2,
}


@functools.lru_cache(maxsize=None)
def _samplers():
    import jax

    from ray_tpu.models.serving import sample_tokens
    return jax.jit(sample_tokens), jax.jit(parents_sample_tokens)


@pytest.mark.fast
@pytest.mark.parametrize("vocab", [300, 40])     # over and under TOPK_CAP
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_sample_tokens_is_the_parents_token_for_token(rows, dtype, vocab):
    """Whatever the rows ask for, together: the tokens are the parent's, to
    the bit, at several positions of several keys. bfloat16 logits hold
    ties (300 draws on a grid of 2**-6 near 1), which argmax and top_k must
    go on breaking the same way."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.engine import _seed_key

    new, was = _samplers()
    temp = jnp.asarray([t for t, _ in ROWS[rows]], jnp.float32)
    topk = jnp.asarray([k for _, k in ROWS[rows]], jnp.int32)
    n = len(ROWS[rows])
    keys = jnp.asarray(np.stack([_seed_key(1000 + i) for i in range(n)]))
    differs = False
    for draw in range(4):
        logits = (2.0 * jax.random.normal(jax.random.PRNGKey(draw),
                                          (n, vocab))).astype(dtype)
        pos = jnp.arange(n, dtype=jnp.int32) * 7 + draw
        got = np.asarray(new(logits, temp, topk, keys, pos))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(
            got, np.asarray(was(logits, temp, topk, keys, pos)))
        greedy = np.asarray(jnp.argmax(logits, axis=-1))
        still = np.asarray(temp) == 0
        np.testing.assert_array_equal(got[still], greedy[still])
        differs |= bool((got[~still] != greedy[~still]).any())
    assert differs is (rows != "greedy")  # a sample is not always the argmax


@pytest.mark.fast
def test_top_k_is_only_inside_the_samplers_branch():
    """A decode program holds ONE `top_k`, inside one branch of the one
    conditional under `sample`; the other branch holds no instruction at
    all, and nothing outside the conditional takes a top-k: a chunk whose
    slots are all greedy runs the argmax alone. Read on the jaxpr and on the
    lowered text, so any backend shows it."""
    import jax

    decode, args, _ = _tiny_decode(n_layers=2)

    def top_ks(jaxpr):
        return sum(e.primitive.name == "top_k" for e in _all_eqns(jaxpr))

    whole = jax.make_jaxpr(decode)(*args).jaxpr
    (cond,) = [e for e in _all_eqns(whole) if e.primitive.name == "cond"]
    assert "sample" in str(cond.source_info.name_stack)
    greedy, draw = (b.jaxpr for b in cond.params["branches"])
    assert not greedy.eqns and greedy.outvars == greedy.invars[-1:]
    assert top_ks(whole) == 1 == top_ks(draw)
    text = decode.lower(*args).as_text()
    assert text.count("chlo.top_k") == 1 and text.count("stablehlo.case") == 1


@pytest.mark.fast
def test_a_sampling_stream_between_greedy_ones_is_counted_and_unmoved(
        tmp_path):
    """`decode_chunks_sampling` and the dispatch span's `sampling` say how
    often the sampler's branch engages: 0 on an all-greedy run, and where a
    stream at temperature 0.8 decodes between two greedy ones, the chunks it
    is live in (its 9 tokens: one from the prefill, two chunks of 4) and no
    other; its tokens are the same request's alone, and the greedy streams'
    are theirs."""
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.engine import Engine
    from test_tracing import _Profiled

    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
                      n_kv_heads=2, d_ff=64, max_seq=64, dtype=np.float32)
    eng = Engine(init_params(cfg, jax.random.PRNGKey(0)), cfg, n_slots=3,
                 decode_chunk=4, page_size=16)

    def gen(prompt, n, **kw):
        q = eng.submit(prompt, n, **kw)
        out = []
        while (item := q.get(timeout=60)) is not None:
            out.extend(item)
        return out

    def run(asks, directory):
        before = eng.counters()
        with _Profiled(directory) as prof:
            outs = _together(gen, asks)
        after = eng.counters()
        spans = [s for _, _, _, s in
                 prof.events("serve.engine.decode_dispatch")]
        assert len(spans) == after["decode_chunks"] - before["decode_chunks"]
        return outs, [s["sampling"] for s in spans], (
            after["decode_chunks_sampling"] - before["decode_chunks_sampling"])

    sampled = ([1, 2, 3], 9, {"temperature": 0.8, "top_k": 5, "seed": 42})
    quiet = [([4, 5, 6, 7], 17, {}), ([9, 8], 14, {"temperature": 0.0})]
    try:
        assert eng.counters()["decode_chunks_sampling"] == 0
        greedy, sampling, chunks = run(quiet, tmp_path / "greedy")
        assert chunks == 0 and set(sampling) == {0}
        (alone,), sampling, chunks = run([sampled], tmp_path / "alone")
        assert chunks == 2 and sampling == [1, 1]
        assert len(alone) == 9 and alone != gen(*sampled[:2])
        outs, sampling, chunks = run([quiet[0], sampled, quiet[1]],
                                     tmp_path / "mixed")
        assert outs == [greedy[0], alone, greedy[1]]
        assert chunks == 2 == sum(sampling) and set(sampling) == {0, 1}
    finally:
        eng.stop()


# ----------------------------------------------------------------------
# The decode program updates the KV arena in place (PR 25)
# ----------------------------------------------------------------------
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else (v,)):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _all_eqns(sub)


def _tiny_decode(n_layers=3):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.models.serving import build_programs

    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=n_layers,
                      n_heads=4, n_kv_heads=2, d_ff=64, max_seq=64,
                      dtype=np.float32)
    ns, chunk, page, n_pages = 3, 4, 16, 9
    built = build_programs(cfg, ns, chunk, page, n_pages)
    decode = built.decode
    args = (fuse_qkv(init_params(cfg, jax.random.PRNGKey(0))), built.empty(),
            jnp.zeros((ns, cfg.max_seq // page), jnp.int32),
            jnp.zeros(ns, jnp.int32), jnp.zeros(ns, jnp.int32),
            jnp.zeros(ns, bool), jnp.zeros(ns, jnp.float32),
            jnp.zeros(ns, jnp.int32), jnp.zeros((ns, 2), jnp.uint32))
    return decode, args, chunk


@pytest.mark.parametrize("path", ["reference", "kernel"])
def test_decode_arena_is_a_scan_carry_only_written_and_attended(
        path, request):
    """The arena must ride the layer scan's CARRY and be touched, for each
    of K and V, by the page write (a gather and a scatter of the slots'
    current pages) and by decode attention: one `pallas_call` handed the
    WHOLE arena on the kernel's path, the reference path's gather
    otherwise — nothing else. As an xs/ys of the scan it is sliced out a
    layer at a time and restacked into a second arena every step (55% of a
    decode step on the chip before PR 25); a kernel handed `kc[l]` is
    handed a copy. Read on the jaxpr, so any backend shows it."""
    import jax

    if path == "kernel":
        request.getfixturevalue("kernel_in_interpret_mode")
    decode, args, chunk = _tiny_decode(n_layers=3)
    arena = args[1].kc.shape
    slab = arena[1:]
    scans = [e for e in _all_eqns(jax.make_jaxpr(decode)(*args).jaxpr)
             if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [chunk, arena[0]]
    layers = scans[1]
    n_fixed = layers.params["num_consts"] + layers.params["num_carry"]
    carry = layers.invars[layers.params["num_consts"]:n_fixed]
    xs, ys = layers.invars[n_fixed:], layers.outvars[
        layers.params["num_carry"]:]
    assert [v.aval.shape for v in carry].count(arena) == 2
    assert not [v.aval.shape for v in list(xs) + list(ys)
                if v.aval.shape in (arena, slab)]
    # Inside the layer: no slab exists, and the arena (as it came in, or
    # as the write left it) is consumed by those ops alone. (What is
    # inside the kernel's own jaxpr is its business: it sees references.)
    # Since PR 41 the write and the attention are one jit of their own
    # (`_token_step`, which the riders in a prefill share): the layer hands
    # the arena to it and to nothing else, and inside it the rule holds.
    body = layers.params["jaxpr"].jaxpr

    def touching(eqns):
        for eqn in eqns:
            shapes = [getattr(v.aval, "shape", None)
                      for v in list(eqn.invars) + list(eqn.outvars)]
            assert slab not in shapes, eqn
            if arena in shapes[:len(eqn.invars)]:
                yield eqn

    step, = touching(body.eqns)
    assert step.primitive.name == "jit" and step.params["name"] == "_token_step"
    consumers = [eqn.primitive.name
                 for eqn in touching(step.params["jaxpr"].jaxpr.eqns)]
    write = ["gather", "scatter"] * 2
    attend = ["pallas_call"] if path == "kernel" else ["gather"] * 2
    assert sorted(consumers) == sorted(write + attend)


def _drain(q):
    out = []
    while (item := q.get(timeout=120)) is not None:
        out.extend(item)
    return out


def _prefill_adopt_and_two_chunks(cfg, params):
    """Greedy tokens of three requests through a new 2-slot engine: one
    prefilled here that decodes over three chunks and a page boundary, one
    ADOPTED (its KV prefilled outside, as a PrefillServer hands it over)
    that joins beside it and leaves first, and a third that can only join
    once a slot is free."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.serving import prefill_core
    from ray_tpu.serve.engine import Engine

    # A copy: the engine takes its tree's q/k/v stacks over.
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                 decode_chunk=4, page_size=16, adopts=True)
    try:
        a = eng.submit(list(range(3, 17)), 11)     # positions 14..24
        prompt = [5] * 20
        first, ks, vs, _, _ = jax.jit(prefill_core(cfg))(
            fuse_qkv(params), jnp.asarray([prompt + [0] * 12], jnp.int32),
            len(prompt))
        b = eng.submit_prefilled(ks, vs, len(prompt), int(first), 6)
        c = eng.submit([9, 8, 7], 9)
        return [_drain(q) for q in (a, b, c)], eng.counters()
    finally:
        eng.stop()


def test_engine_tokens_with_the_kernel_equal_the_reference_paths(
        request):
    """The Pallas decode kernel (interpret mode: its own code, on this
    CPU) inside the whole engine, against the XLA reference path in the
    same engine: the same greedy tokens through a prefill, an adopt and
    chunks in which slots join and leave."""
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.ops.attention import attention_path_counts

    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=3, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq=64, dtype=np.float32)
    params = init_params(cfg, jax.random.PRNGKey(1))
    want, counters = _prefill_adopt_and_two_chunks(cfg, params)
    assert [len(t) for t in want] == [11, 5, 9]    # the adopt's first is out
    before = attention_path_counts().get("decode_pallas", 0)
    request.getfixturevalue("kernel_in_interpret_mode")
    got, counters_k = _prefill_adopt_and_two_chunks(cfg, params)
    assert attention_path_counts().get("decode_pallas", 0) > before
    assert got == want
    assert counters_k["decode_useful_tokens"] == \
        counters["decode_useful_tokens"] == 10 + 5 + 8


def test_decode_call_donates_the_arena():
    """In place across calls too: the arena handed to decode_jit is
    consumed (deleted), not copied, wherever the backend donates."""
    decode, args, _ = _tiny_decode(n_layers=2)
    (kc, vc, _, _), last, pos = args[1], args[3], args[4]
    out = decode(*args)
    out[0].kc.block_until_ready()
    if not last.is_deleted():
        pytest.skip("this backend does not donate buffers")
    assert kc.is_deleted() and vc.is_deleted() and pos.is_deleted()
    assert out[0].kc.shape == kc.shape and out[0].vc.shape == vc.shape


@pytest.fixture(scope="module")
def paged3():
    """A 3-layer float32 engine over 4 usable pages of 16 tokens and 3
    slots, with the naive reference: a full causal forward pass a token,
    sampled by the engine's own (seed, position) rule."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, forward, init_params
    from ray_tpu.models.serving import sample_tokens
    from ray_tpu.serve.engine import Engine, _seed_key

    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=3, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq=64, dtype=np.float32)
    params = init_params(cfg, jax.random.PRNGKey(1))
    fwd = jax.jit(lambda p, t: forward(p, t, cfg, None))

    def naive(prompt, n, temperature=0.0, top_k=0, seed=0):
        ids, out = list(prompt), []
        key = jnp.asarray(_seed_key(seed))[None]
        for _ in range(n):
            # One shape for every length: causal, so the zero tail is
            # never attended by the row that is read.
            toks = np.zeros((1, cfg.max_seq), np.int32)
            toks[0, :len(ids)] = ids
            row = fwd(params, jnp.asarray(toks))[0, len(ids) - 1]
            out.append(int(sample_tokens(
                row[None], jnp.asarray([temperature], jnp.float32),
                jnp.asarray([top_k], jnp.int32), key,
                jnp.asarray([len(ids) - 1], jnp.int32))[0]))
            ids.append(out[-1])
        return out

    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=3,
                 decode_chunk=4, page_size=16, n_pages=5)

    def gen(prompt, n, **kw):
        q = eng.submit(prompt, n, **kw)
        out = []
        while True:
            item = q.get(timeout=60)
            if item is None:
                return out
            out.extend(item)

    yield eng, gen, naive
    eng.stop()


def _together(gen, asks):
    import threading
    outs = [None] * len(asks)

    def run(i):
        prompt, n, kw = asks[i]
        outs[i] = gen(prompt, n, **kw)

    ts = [threading.Thread(target=run, args=(i,)) for i in range(len(asks))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    return outs


SAMPLING = {"greedy": {},
            "sampled": {"temperature": 0.8, "top_k": 5, "seed": 1234}}


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
@pytest.mark.parametrize("scenario", ["page_boundary_mid_chunk",
                                      "slots_go_inactive",
                                      "freed_pages_reused"])
def test_paged_engine_matches_naive_cases(paged3, scenario, sampling):
    """test_paged_engine_matches_naive_greedy's check, on the paths an
    in-place arena could break: a write that crosses into the slot's next
    page in the middle of a chunk, slots that are (or fall) inactive while
    another decodes (their rows land in null page 0), and a request that
    is handed the pages a finished one left its rows in. Tokens equal the
    naive reference exactly, greedy and seeded."""
    eng, gen, naive = paged3
    kw = SAMPLING[sampling]
    if scenario == "page_boundary_mid_chunk":
        # Positions 14..23: the first chunk of 4 writes 14, 15 | 16, 17.
        prompt = list(range(3, 17))
        want = naive(prompt, 10, **kw)
        assert gen(prompt, 10, **kw) == want
        assert (want == naive(prompt, 10)) == (not kw)  # sampling samples
    elif scenario == "slots_go_inactive":
        # One slot never used, one finishing after 3 tokens while the
        # third keeps decoding across a page boundary.
        asks = [([9, 8, 7], 3, kw), (list(range(20, 34)), 12, kw)]
        assert _together(gen, asks) == [naive(*a[:2], **kw) for a in asks]
    else:
        # 3 of the 4 usable pages each: the second request is handed at
        # least two pages holding the first one's rows.
        first, second = list(range(1, 34)), [5] * 30 + [6, 7, 8]
        assert gen(first, 9, **kw) == naive(first, 9, **kw)
        assert eng.pages_in_use() == 0
        assert gen(second, 9, **kw) == naive(second, 9, **kw)
        assert eng.peak_pages_used <= 4
    assert eng.error is None

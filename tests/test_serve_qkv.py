"""The q/k/v projection in serving: ONE matmul over one fused weight stack
(`models.block.fuse_qkv`), made when a server is built, so that the TPU
compiler reads a layer's projection weights in place (tests/
test_tpu_compile.py holds the compiled program to that; PERF.md, PR 30).
Here, at tiny float32 sizes on the CPU, dense and sparse: the values. The
fused form is the three matmuls bit for bit, the engine serves the tokens
the three-matrix formulation chooses, `Engine.params` still answers with
the published tree, the prefill pool's hand-off is adopted, and training
keeps a matrix each.
"""

import pytest

QKV_KINDS = ["dense", "sparse"]


def _drain(q):
    out = []
    while (item := q.get(timeout=120)) is not None:
        out.extend(item)
    return out


def _qkv_model(kind):
    """A 3-layer float32 model and its PUBLISHED parameters: dense with
    GQA (4 heads on 2), or sparse with MHA and the q/k norm over the whole
    projection (off one, so that it shows), as the two serve
    configurations of BENCHMARK.json are."""
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params

    sparse = dict(n_kv_heads=4, n_experts=4, top_k_experts=2, d_ff=16,
                  qk_norm=True, norm_topk_prob=False)
    cfg = LlamaConfig(**dict(dict(
        vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=64, dtype=np.float32),
        **(sparse if kind == "sparse" else {})))
    params = init_params(cfg, jax.random.PRNGKey(1))
    if kind == "sparse":
        lay = dict(params["layers"])
        for i, name in enumerate(("q_norm", "k_norm")):
            lay[name] = 1.0 + 0.2 * jax.random.normal(
                jax.random.PRNGKey(7 + i), lay[name].shape)
        lay["router"] = lay["router"] * 40.0       # a router that decides
        params = dict(params, layers=lay)
    return cfg, params


def _greedy_by_forward(cfg, params, prompt, n):
    """`n` greedy tokens by a full causal `llama.forward` a token: the
    train path, which projects through `wq`, `wk`, `wv`, a matmul each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import forward

    ids = list(prompt)
    for _ in range(n):
        toks = np.zeros((1, cfg.max_seq), np.int32)
        toks[0, :len(ids)] = ids
        row = forward(params, jnp.asarray(toks), cfg, None)[0, len(ids) - 1]
        ids.append(int(jax.numpy.argmax(row)))
    return ids[len(prompt):]


@pytest.mark.parametrize("kind", QKV_KINDS)
def test_fused_projection_gives_the_three_matrices_q_k_v(kind):
    """One matmul over `fuse_qkv`'s stack and a split of its columns IS the
    three matmuls: `attention_inputs` returns the same q, k and v for a
    prefill's `[1, S, D]` and a decode step's `[slots, D]` (to float32
    rounding: this CPU's matmul sums a row in an order that depends on how
    many columns it was given); `split_qkv` gives the three stacks back bit
    for bit, and a serving program handed the published tree says what it
    takes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.block import attention_inputs, fuse_qkv, split_qkv
    from ray_tpu.models.serving import prefill_core

    cfg, params = _qkv_model(kind)
    fused = fuse_qkv(params)
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    assert fused["layers"]["wqkv"].shape == (3, 32, (H + 2 * KVH) * hd)
    assert not {"wq", "wk", "wv"} & set(fused["layers"])
    back = split_qkv(fused, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for shape in ((1, 24, 32), (5, 32)):
        x = jax.random.normal(jax.random.PRNGKey(3), shape)
        got, want = (attention_inputs(
            jax.tree.map(lambda w: w[1], tree["layers"]), x, cfg,
            lambda t: t * 1.5) for tree in (fused, params))
        for a, b in zip(got, want):
            assert a.shape == b.shape and a.shape[-1] == hd
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="fuse_qkv"):
        jax.jit(prefill_core(cfg))(
            params, jnp.zeros((1, 32), jnp.int32), 3)


@pytest.mark.parametrize("kind", QKV_KINDS)
def test_engine_on_the_fused_stack_serves_the_three_matrix_tokens(kind):
    """The engine's greedy tokens are the ones a full forward pass through
    `wq`, `wk`, `wv` chooses, the formulation every serving program had
    before PR 30: a prefill that decodes over three chunks and a page
    boundary (positions 14..24, pages of 16), an ADOPTED request (its KV
    from the shared prefill core, as a prefill pool hands it over) that
    joins beside it, and a third that waits for a slot."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.serving import prefill_core
    from ray_tpu.serve.engine import Engine

    cfg, params = _qkv_model(kind)
    prompts = [list(range(3, 17)), [5] * 20, [9, 8, 7]]
    # A copy: the engine takes its tree's q/k/v stacks over.
    eng = Engine(jax.tree.map(jnp.copy, params), cfg, n_slots=2,
                 decode_chunk=4, page_size=16, adopts=True)
    try:
        a = eng.submit(prompts[0], 11)
        first, ks, vs, _, _ = jax.jit(prefill_core(cfg))(
            fuse_qkv(params), jnp.asarray([prompts[1] + [0] * 12], jnp.int32),
            len(prompts[1]))
        b = eng.submit_prefilled(ks, vs, len(prompts[1]), int(first), 6)
        c = eng.submit(prompts[2], 9)
        served = [_drain(q) for q in (a, b, c)]
    finally:
        eng.stop()
    want = [_greedy_by_forward(cfg, params, p, n)
            for p, n in zip(prompts, (11, 6, 9))]
    assert [int(first)] + served[1] == want[1]
    assert [served[0], served[2]] == [want[0], want[2]]


def test_prefill_pool_hand_off_is_adopted_by_the_decode_pool():
    """`PrefillServer` holds the fused layout too (it shares the engine's
    prefill core) and its KV, adopted by a `DecodeServer`'s engine, goes on
    to the tokens a monolithic `LLMServer` serves. In process: the
    DeviceRef transfer between the pools is tests/test_serve_llm.py's."""
    import cloudpickle
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm import (DecodeServer, LLMConfig, LLMServer,
                                   PrefillServer)

    blob = cloudpickle.dumps(LLMConfig(
        vocab_size=512, d_model=128, n_layers=2, max_seq=64, num_tpus=0,
        decode_chunk=2, max_ongoing_requests=2))
    prompt = [1, 2, 3, 4]
    mono = LLMServer(blob)
    try:
        want = _drain(mono.engine.submit(prompt, 8))
    finally:
        mono.engine.stop()
    pool = PrefillServer(blob)
    assert "wqkv" in pool.params["layers"]
    toks = np.zeros((1, 32), np.int32)
    toks[0, :len(prompt)] = prompt
    first, ks, vs, _, _ = pool._core(pool.params, jnp.asarray(toks),
                                     len(prompt))
    decode = DecodeServer(blob)
    try:
        rest = _drain(decode.engine.submit_prefilled(
            ks, vs, len(prompt), int(first), 8))
    finally:
        decode.engine.stop()
    assert len(want) == 8 and [int(first)] + rest == want


@pytest.mark.parametrize("kind", QKV_KINDS)
def test_engine_params_are_the_published_tree_and_no_second_copy(kind):
    """`Engine.params` answers with the tree the engine was built from
    (what `benchmark/serve_app.py::bench_check` hands the plain reference):
    `wq`, `wk`, `wv` bit for bit, every other leaf the engine's own array.
    It is split from the fused stack anew when asked, so the engine holds
    one copy of the projections; and the three stacks it was built from are
    taken over (deleted), so that a caller still holding its tree while the
    engine warms up does not hold them a second time."""
    import jax
    import numpy as np

    from ray_tpu.serve.engine import Engine

    cfg, params = _qkv_model(kind)
    want = {k: np.array(params["layers"][k]) for k in ("wq", "wk", "wv")}
    eng = Engine(params, cfg, n_slots=2, decode_chunk=2, page_size=16)
    try:
        assert all(params["layers"][k].is_deleted() for k in want)
        assert not params["layers"]["wo"].is_deleted()
        held = eng._params["layers"]
        assert "wqkv" in held and not set(want) & set(held)
        got = eng.params
        assert jax.tree.structure(got) == jax.tree.structure(params)
        for k, w in want.items():
            np.testing.assert_array_equal(np.asarray(got["layers"][k]), w)
        assert all(got["layers"][k] is held[k]
                   for k in held if k != "wqkv")
        assert got["embed"] is params["embed"]
        assert eng.params["layers"]["wq"] is not got["layers"]["wq"]
    finally:
        eng.stop()


def test_a_stack_with_a_name_of_its_own_is_left_no_projection(monkeypatch):
    """`serving.serving_params` finds what to take over as the leaves the
    fused tree no longer holds, not in a list of stack names: a tree whose
    attention stack is called something of its own (and a layout that fuses
    it) is left holding no `wq`, `wk`, `wv`, and every leaf the programs
    still read stays, the caller's own array."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import block, serving

    cfg, params = _qkv_model("dense")
    tree = {"towers" if k == "layers" else k: v for k, v in params.items()}

    def fuse(params, cfg):
        towers = dict(params["towers"])
        towers["wqkv"] = jnp.concatenate(
            [towers.pop(k) for k in ("wq", "wk", "wv")], axis=-1)
        return dict(params, towers=towers)

    monkeypatch.setattr(block, "fuse_qkv", fuse)
    fused = serving.serving_params(tree, cfg)
    assert all(tree["towers"][k].is_deleted() for k in ("wq", "wk", "wv"))
    assert {k for k, w in tree["towers"].items() if not w.is_deleted()} \
        == set(fused["towers"]) - {"wqkv"}
    assert all(fused["towers"][k] is tree["towers"][k]
               for k in fused["towers"] if k != "wqkv")
    assert not any(w.is_deleted() for w in jax.tree.leaves(fused))


def test_train_step_keeps_a_projection_matrix_each():
    """Training's parameters, gradients and optimizer state stay by matrix
    (`tp` shards `wq` over heads and `wk`, `wv` over kv heads; checkpoints
    name them), and its step projects through each: no fused stack is made
    or multiplied. (The lowered text of the step was the parent's byte for
    byte when the fused form entered serving: CHANGES.md, PR 30.)"""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel import MeshConfig, ParallelContext
    from ray_tpu.train.spmd import make_train_fns

    # d_model 64, 4 heads on 2 of 16: q is 64 wide, k and v 32, fused 128
    cfg = LlamaConfig.tiny(d_ff=96)
    init, step = make_train_fns(
        cfg, ParallelContext.create(MeshConfig(), jax.devices()[:1]))
    state = jax.eval_shape(init._jitted, jax.random.PRNGKey(0))
    layers = state["params"]["layers"]
    assert {"wq", "wk", "wv"} <= set(layers) and "wqkv" not in layers
    text = step.lower(
        state, jax.ShapeDtypeStruct((2, 32), jnp.int32)).as_text()
    widths = set(re.findall(r"tensor<(?:\d+x)?64x(\d+)xf32>", text))
    assert {"64", "32"} <= widths and "128" not in widths

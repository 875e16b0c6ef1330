"""LFM2-24B-A2B's stack through ONE engine (two slots, the attention layers'
decode kernel interpreted), at small float32 widths on the CPU: the window a
slot keeps behind a padded bucket, the program through its pages and its
windows against `benchmark/reference_lfm2.py`, the routing counts, the paths
and what the stack refuses. tests/test_lfm2.py holds the model's description,
the conv operator, the router, the kernels, the engine in bfloat16, the
adapter and the configuration, and says what the tolerances are; two files
so that neither is the suite's longest (`--dist loadfile` keeps a file on one
worker).
"""

import os
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import models, reference_lfm2
from ray_tpu.models import llama, serving
from ray_tpu.models.block import fuse_qkv
from ray_tpu.ops import attention
from engine_pins import Spans, pinned
from test_lfm2 import LOGIT_TOL, ROOT, _drain, _engine, _tokens, tiny


@pytest.fixture(scope="module")
def engine(tiny):
    _, _, cfg, params = tiny
    eng = _engine(cfg, params)
    yield eng
    eng.stop()


def _reference_windows(params, model, prompt):
    """The last two rows of z = B * X of every conv layer, in the order the
    layers run, zeros where the prompt has no such row: [conv layers, 2, D],
    by the reference's own layers."""
    ref = reference_lfm2
    out = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(prompt)].astype(jnp.float32)
        for name, i, conv in ref.stack_order(model):
            sparse = "router" in params[name]
            lp = {k: v if sparse and k in ref._EXPERTS else v[i]
                  for k, v in params[name].items()}
            if conv:
                u = ref._rms_norm(x, lp["norm"], model["norm_eps"])
                b, _, xx = jnp.split(u @ lp["in_proj"], 3, axis=-1)
                out.append(jnp.pad(b * xx, ((2, 0), (0, 0)))[-2:])
            x = ref.operator_half(x, lp, model, conv)
            x, _ = ref.feed_forward_half(x, lp, model, i if sparse else None)
    return np.asarray(jnp.stack(out))


@pytest.mark.parametrize("n", [1, 2, 3, 31, 50])
def test_a_slots_window_is_written_behind_a_padded_bucket_and_overwritten_whole(
        tiny, engine, n):
    """Prompts of 1, 2 and 3 tokens, one short of a rung (31 in 32) and one
    with dead rows behind it (50 in 64), one after the other into the SAME
    slot: after admission the slot's window of every conv layer is rows
    `n - 2, n - 1` of that layer's z (zeros for the rows a prompt of 1 or 2
    does not have: nothing of the last tenant's), the other slot's untouched.
    A request for ONE token decodes nothing, so the window is the
    prefill's."""
    _, model, cfg, params = tiny
    prompt = _tokens(n, 100 + n)
    assert len(_drain(engine.submit(prompt, 1))) == 1
    ssm, window = engine._caches.state
    assert ssm is None and window.shape == (4, 2, 2, cfg.d_model)
    want = _reference_windows(params, model, prompt)
    got = np.asarray(window[:, :, 0])
    assert np.abs(got - want).max() < 1e-3 * max(1.0, np.abs(want).max())
    if n < 3:
        assert not got[:, :2 - n].any()     # rows before position 0: zeros
    assert not np.asarray(window[:, :, 1]).any()    # nobody's slot


# ---------------------------------------------------------------------------
# Through the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bucket", [(10, 32), (31, 32), (100, 128),
                                      (7, 32)],
                         ids=["short", "one-short-of-a-rung", "a-long-one",
                              "a-shorter-one-after"])
def test_prefill_then_decode_through_the_caches_is_the_reference(
        tiny, engine, n, bucket):
    """A prompt's prefill, then 40 tokens decoded through the attention
    layers' pages (pages of 16, two heads to a row, the kernel interpreted)
    and the conv layers' windows, across ten chunks of 4 and two or three
    page boundaries: the prefill's logits are the reference's at the
    prompt's last position, and every served token is the reference's
    largest logit to float32 rounding."""
    adapter, model, cfg, params = tiny
    prompt = _tokens(n, n)
    ref = adapter.reference()
    _, ks, vs, logits, experts, state = jax.jit(serving.prefill_core(cfg))(
        fuse_qkv(params, cfg),
        jnp.asarray([prompt + [0] * (bucket - n)], jnp.int32), n)
    want = np.asarray(ref.logits_last(params, model, prompt, 1))[0]
    assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
    assert ks.shape == vs.shape == (2, bucket, 2, 64)
    assert state[0] is None and state[1].shape == (4, 2, cfg.d_model)
    # the routing counts of a prompt: the reference's, the padding left out
    assert experts.shape == (cfg.n_experts + 1,)
    np.testing.assert_array_equal(
        np.asarray(experts[:-1]),
        np.asarray(ref.expert_counts(params, model, prompt, slice(0, n))))
    assert int(experts[:-1].sum()) == n * cfg.top_k_experts * 5     # sparse
    served = _drain(engine.submit(prompt, 40))
    assert len(served) == 40
    gaps = ref.served_token_gaps(params, model, prompt, served)
    assert max(gaps) < LOGIT_TOL, gaps
    # teeth: against the prompt less its last token the same tokens are
    # another row's
    short = ref.served_token_gaps(params, model, prompt[:-1], served)
    assert max(short) > 100 * LOGIT_TOL
    assert engine._slot_req == [None, None]


def test_a_burst_rides_the_prefills_through_the_kernel_and_is_the_riderless_engines(
        tiny, engine):
    """Three requests AT ONCE on the two slots (their rungs, 128, ride; their
    pages, 6 + 7 then 5 of the pool's 16, fit): the second's prefill carries
    the first's slot a step, the third waits for the second's slot and
    carries the first's again; the riders' `_token_step` runs
    the `paged_decode` kernel (interpreted) over pages of two heads of 64 to
    a row, their windows move in the prefill's carry. Every stream is token
    for token what the same engine serves with nobody riding, and the plain
    reference's greedy continuation; the admit spans' `riders` add up to the
    counters."""
    _, model, _, params = tiny
    asks = [(70, 20), (100, 8), (66, 6)]
    prompts = [_tokens(n, 40 + n) for n, _ in asks]

    def serve():
        streams = [engine.submit(p, m) for p, (_, m) in zip(prompts, asks)]
        return [_drain(q) for q in streams]

    before = engine.counters()
    with Spans() as spans:
        riding = serve()
    after = engine.counters()
    with mock.patch.object(engine, "_ride_plan", lambda free_rows: []):
        plain = serve()
    assert engine.counters()["rider_tokens"] == after["rider_tokens"]
    assert [len(s) for s in riding] == [m for _, m in asks]
    assert riding == plain
    rode = [a["riders"] for a in spans.named("serve.engine.admit")]
    assert rode == [0, 1, 1]
    assert sum(rode) == after["rider_tokens"] - before["rider_tokens"]
    assert sum(map(bool, rode)) == after["rider_steps"] - before["rider_steps"]
    for prompt, served in zip(prompts, riding):
        gaps = reference_lfm2.served_token_gaps(params, model, prompt, served)
        assert max(gaps) < LOGIT_TOL, gaps
    assert engine._slot_req == [None, None]


def test_a_chunks_routing_counts_are_the_references(tiny):
    """The decode program's `experts` of one chunk of 4 steps over one live
    slot of two: tokens per expert of the 4 rows the steps fed, summed over
    the sparse layers, as the reference routes the same sequence; the idle
    slot counts nothing; the last entry the distinct experts touched, summed
    over steps and layers."""
    _, model, cfg, params = tiny
    n, chunk = 21, 4
    prompt = _tokens(n, 7)
    progs = serving.build_programs(cfg, 2, chunk, 16, 17)
    fused = fuse_qkv(params, cfg)
    zero_key = jnp.zeros(2, jnp.uint32)
    pages = jnp.zeros(16, jnp.int32).at[:3].set(jnp.asarray([4, 2, 9]))
    caches, first, _ = progs.prefill(
        fused, progs.empty(), pages,
        jnp.asarray([prompt + [0] * (32 - n)], jnp.int32), jnp.int32(n),
        jnp.float32(0), jnp.int32(0), zero_key, jnp.int32(1))
    bt = jnp.zeros((2, 16), jnp.int32).at[1, :3].set(jnp.asarray([4, 2, 9]))
    caches, last, pos, out, experts = progs.decode(
        fused, caches, bt, jnp.zeros(2, jnp.int32).at[1].set(first),
        jnp.zeros(2, jnp.int32).at[1].set(n), jnp.asarray([False, True]),
        jnp.zeros(2), jnp.zeros(2, jnp.int32), jnp.zeros((2, 2), jnp.uint32))
    fed = [int(first)] + [int(t) for t in out[1, :chunk - 1]]
    want = reference_lfm2.expert_counts(params, model, prompt + fed,
                                        slice(n, n + chunk))
    np.testing.assert_array_equal(np.asarray(experts[:-1]), np.asarray(want))
    assert int(experts[:-1].sum()) == chunk * cfg.top_k_experts * 5
    assert chunk * 5 * 1 <= int(experts[-1]) <= chunk * 5 * cfg.top_k_experts
    assert [int(p) for p in pos] == [0, n + chunk]


def test_the_engine_took_the_paths_and_keeps_two_shapes_of_cache(tiny,
                                                                 engine):
    _, _, cfg, _ = tiny
    assert len(_drain(engine.submit(_tokens(20, 1), 8))) == 8
    counts = attention.attention_path_counts()
    assert counts["decode_pallas"] >= 1             # interpreted, in decode
    assert counts["fwd_reference"] >= 1             # the CPU's prefill path
    kc, vc, ic, (ssm, window) = engine._caches
    # pages: the 2 attention layers alone, 2 kv heads of 64 in ONE row
    assert kc.shape == vc.shape == (2, engine.n_pages, 1, 16, 128)
    assert ic is None and ssm is None
    # windows: the 4 conv layers (the dense one first), 2 rows, 2 slots
    assert window.shape == (4, 2, 2, cfg.d_model)
    c = engine.counters()
    assert c["conv_state_bytes"] == window.nbytes == 4 * 2 * 2 * 256 * 4
    assert "state_bytes" not in c and "state_writes" not in c
    assert len(c["expert_tokens"]) == cfg.n_experts
    assert sum(c["expert_tokens"]) > 0 and c["decode_experts_touched"] > 0
    assert engine.pool.pages_for(100, 40) == 9      # positions, not layers
    assert engine._programs.takes_riders and engine._programs.by_slot
    assert [w for w in engine.buckets if engine._rides(w)] == [128, 256]


def test_the_cast_of_the_experts_asks_the_stacks_and_never_an_array(tiny):
    """`serving._experts_in_compute_dtype` walks whatever stacks hold a
    `router` (it names none), and asks a top-level ARRAY nothing: `"router"
    in array` is an element-wise compare of the whole embedding, seconds of
    every sparse model's start on the chip (my chip runs, PR 46)."""
    _, _, cfg, params = tiny

    class Leaf:
        dtype = cfg.dtype

        def __iter__(self):
            raise AssertionError("a leaf was searched for a stack's key")

    tree = dict(params, embed=Leaf(), final_norm=Leaf())
    assert serving._experts_in_compute_dtype(tree, cfg) is tree
    import dataclasses
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    cast = serving._experts_in_compute_dtype(tree, half)
    for stack in ("conv", "layers"):
        assert cast[stack]["w_up"].dtype == jnp.bfloat16
        assert cast[stack]["router"].dtype == jnp.float32
    assert cast["dense"] is tree["dense"]       # no router: not the experts'


def test_a_pd_handoff_and_the_training_forward_refuse_the_stack_by_name(
        tiny, engine):
    _, _, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="conv_layers"):
        engine.submit_prefilled(None, None, 4, 1, 4)
    assert not serving.adopts(cfg)          # what `PrefillServer` asks
    with open(os.path.join(ROOT, "ray_tpu", "serve", "llm.py")) as f:
        assert "conv_layers" in f.read()    # ... and says, refusing
    with pytest.raises(NotImplementedError, match="short-convolution"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="serves only"):
        reference_lfm2.loss_and_check_grads(params, {}, None)
    with pytest.raises(NotImplementedError, match="short-convolution"):
        models.adapter("lfm2").loss_fn(params, jnp.zeros((1, 8), jnp.int32),
                                       cfg, None)


def test_what_the_engine_counts_is_what_the_parent_counted(engine):
    """The keys of `Engine.counters()`: tests/engine_pins.py's row."""
    assert pinned(engine, "lfm2")

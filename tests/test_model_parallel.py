"""Parity + training tests for the Llama model under every parallelism config.

The single-device forward is ground truth; each mesh config must produce the
same loss (within fp32 reduction tolerance) and a decreasing loss over steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (LlamaConfig, forward, init_params, loss_fn,
                                  param_count)
from ray_tpu.parallel import MeshConfig, ParallelContext
from ray_tpu.train.spmd import make_train_fns

TINY = LlamaConfig.tiny(max_seq=64, n_layers=4, n_heads=4, n_kv_heads=2)
TINY_MOE = LlamaConfig.tiny(max_seq=64, n_layers=4, n_heads=4, n_kv_heads=2,
                            n_experts=4)

CONFIGS = [
    ("dp8", MeshConfig(dp=8), TINY),
    ("fsdp8", MeshConfig(fsdp=8), TINY),
    ("tp4_dp2", MeshConfig(dp=2, tp=4), TINY),
    ("sp4_dp2", MeshConfig(dp=2, sp=4), TINY),
    ("pp2_dp2_fsdp2", MeshConfig(pp=2, dp=2, fsdp=2), TINY),
    ("ep2_dp2_tp2", MeshConfig(dp=2, ep=2, tp=2), TINY_MOE),
    ("pp2_ep2_sp2", MeshConfig(pp=2, ep=2, sp=2), TINY_MOE),
    # Two virtual slices: dp crosses DCN outermost, tp stays inside a slice.
    ("dcn2_dp4_tp2", MeshConfig(dp=4, tp=2, dcn_dp=2), TINY),
]


def _tokens(cfg, bs=4, seq=64, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32)


@pytest.mark.parametrize("name,mcfg,lcfg", CONFIGS,
                         ids=[c[0] for c in CONFIGS])
def test_loss_parity_vs_single_device(devices8, name, mcfg, lcfg):
    # Note pp+MoE: gpipe carries the aux loss per microbatch (averaged),
    # vs the reference's full-batch aux — a nonlinear statistic, so the
    # values differ slightly; rtol below absorbs it.
    params = init_params(lcfg, jax.random.PRNGKey(0))
    toks = _tokens(lcfg)
    ref_loss, _ = jax.jit(
        lambda p, t: loss_fn(p, t, lcfg, None))(params, toks)
    ctx = ParallelContext.create(mcfg)
    sharded_loss, _ = jax.jit(
        lambda p, t: loss_fn(p, t, lcfg, ctx))(params, jnp.asarray(toks))
    np.testing.assert_allclose(float(sharded_loss), float(ref_loss),
                               rtol=2e-3)


@pytest.mark.parametrize("name,mcfg,lcfg", CONFIGS[:5],
                         ids=[c[0] for c in CONFIGS[:5]])
def test_train_step_decreases_loss(devices8, name, mcfg, lcfg):
    ctx = ParallelContext.create(mcfg)
    init, step = make_train_fns(lcfg, ctx)
    state = init(jax.random.PRNGKey(0))
    toks = jax.device_put(_tokens(lcfg, bs=8), ctx.batch_sharding())
    losses = []
    for _ in range(3):
        state, m = step(state, toks)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_param_count_matches_formula():
    cfg = TINY
    n = param_count(cfg)
    d, f, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    hd = cfg.head_dim
    per_layer = (2 * d  # norms
                 + d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
                 + cfg.n_heads * hd * d + 3 * d * f)
    expected = 2 * V * d + d + L * per_layer
    assert n == expected


def test_params_are_sharded(devices8):
    ctx = ParallelContext.create(MeshConfig(fsdp=4, tp=2))
    init, _ = make_train_fns(TINY, ctx)
    state = init(jax.random.PRNGKey(0))
    wq = state["params"]["layers"]["wq"]
    # d_model dim sharded over fsdp=4, heads dim over tp=2
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[1] == wq.shape[1] // 4
    assert shard_shape[2] == wq.shape[2] // 2

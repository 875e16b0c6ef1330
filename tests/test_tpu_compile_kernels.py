"""The kernels of the main path, each alone, compiled for a described
`v5e:2x2` at the cells' shapes (tests/compile_for_v5e.py says why): flash
and its mixed kinds, sparse attention, the selective scan, the state step,
the experts' grouped matmul and a share's local combine; and the train step
over four described chips."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from compile_for_v5e import moved_stacks, shapes_on
from ray_tpu.ops import attention

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


FLASH_SHAPES = [(2, 32, 2048, 128),   # Llama-2-7B attention at batch 2
                (1, 8, 256, 128),     # one 256-token prefill bucket
                (1, 32, 3584, 128)]   # a rung between 2048 and 4096: blocks
#                                       of 512 (serve/engine.py::prefill_widths)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", FLASH_SHAPES,
                         ids=["x".join(map(str, s)) for s in FLASH_SHAPES])
def test_flash_kernels_compile_for_v5e(topo, shape, direction):
    b, h, s, d = shape
    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32, sharding=one_chip)
    if direction == "fwd":
        fn = jax.jit(lambda q, k, v: attention._flash_fwd_pallas(
            q, k, v, causal=True, sm_scale=d ** -0.5))
        args = (x, x, x)
    else:
        fn = jax.jit(lambda q, k, v, o, l, do: attention._flash_bwd_pallas(
            q, k, v, o, l, do, causal=True, sm_scale=d ** -0.5))
        args = (x, x, x, x, lse, x)
    lowered = fn.lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    assert lowered.compile().memory_analysis().temp_size_in_bytes >= 0


def _tiny_step(topo, mesh_cfg, monkeypatch):
    """The whole train step, lowered for the described chips. The model
    asks jax.devices() which attention path to take and sees this
    sandbox's CPU, so the test — not the program — steers it."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel import ParallelContext
    from ray_tpu.train.spmd import (default_optimizer, make_train_fns,
                                    state_shardings)

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = LlamaConfig(vocab_size=1024, d_model=512, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=1024, max_seq=256)
    ctx = ParallelContext.create(mesh_cfg, devices=list(topo.devices))
    init, step = make_train_fns(cfg, ctx)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, state_shardings(cfg, ctx, default_optimizer()))
    toks = jax.ShapeDtypeStruct((4, 256), jnp.int32,
                                sharding=ctx.batch_sharding())
    return step.lower(state, toks)


def test_dp4_train_step_compiles_for_v5e_2x2(topo, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: on a four-chip mesh the
    model must wrap the call in a shard_map, or this fails to lower."""
    from ray_tpu.parallel import MeshConfig

    lowered = _tiny_step(topo, MeshConfig(dp=4), monkeypatch)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert "all-reduce" in compiled.as_text()  # the gradient reduction


def test_pp_with_kernel_fails_clearly(topo, monkeypatch):
    """Under pp the kernel would need a nested shard_map, which jax 0.9
    cannot differentiate; the model says so instead of a verifier dump."""
    from ray_tpu.parallel import MeshConfig

    with pytest.raises(NotImplementedError, match="pipeline"):
        _tiny_step(topo, MeshConfig(pp=2, dp=2), monkeypatch)


# ---------------------------------------------------------------------------
# Sparse attention (ops/sparse_attention.py) at Keye-VL-2.0's widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [8192, 7168, 5120])
@pytest.mark.parametrize("kernel", ["index_select", "masked_flash"])
def test_sparse_attention_kernels_compile_for_v5e(topo, kernel, S):
    """Prefill's two kernels in the 8,192 bucket and in two of the rungs under
    it (`serve/engine.py::prefill_widths`; multiples of 1,024, so the key
    blocks stay 1,024 wide): 16 indexer heads of 64, top-k 2,048; GQA 32/4
    heads of 128 under the selection's mask."""
    from ray_tpu.ops import sparse_attention as sa
    sds = shapes_on(topo.devices[0])

    if kernel == "index_select":
        lowered = jax.jit(
            lambda qi, ki, w: sa._index_select_pallas(qi, ki, w, 2048)).lower(
            sds((S, 16, 64), jnp.bfloat16), sds((S, 64), jnp.bfloat16),
            sds((S, 16), jnp.float32))
    else:
        kv = sds((4, S, 128), jnp.bfloat16)
        lowered = jax.jit(lambda q, k, v, m: sa._masked_flash_pallas(
            q, k, v, m, sm_scale=128 ** -0.5)).lower(
            sds((4, 8, S, 128), jnp.bfloat16), kv, kv, sds((S, S), jnp.int8))
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("max_pages", [128, 256])
def test_sparse_paged_decode_compiles_for_v5e(topo, max_pages):
    """Decode's streaming kernel at the cell's sizes: 16 slots, 128 pages of
    64 a slot (and the widest table that still streams at top-k 2,048), 4 kv
    heads of 128 by token, bfloat16, the selection an int8 row a slot. One
    Mosaic kernel, the arenas its operands as they come, nothing set aside."""
    import re

    from ray_tpu.ops import sparse_attention as sa
    sds = shapes_on(topo.devices[0])

    ns, page = 16, 64
    assert sa._streams(max_pages * page, 2048)
    arena = sds((4, ns * max_pages + 1, page, 4 * 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, m, kc, vc, layer, bt, n: sa._sparse_paged_decode(
            q, m, kc, vc, layer, bt, n, sm_scale=128 ** -0.5)).lower(
        sds((ns, 32, 128), jnp.bfloat16), sds((ns, max_pages * page), jnp.int8),
        arena, arena, sds((), jnp.int32), sds((ns, max_pages), jnp.int32),
        sds((ns,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.search(r"bf16\[4,%d,64,512\]\S* (copy|transpose)\("
                         % (ns * max_pages + 1), text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# ---------------------------------------------------------------------------
# State-space layers (ops/ssm.py) at AI21-Jamba2-3B's widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [4096, 3584, 2560])
def test_selective_scan_kernel_compiles_for_v5e(topo, S):
    """4,096 rows (and two narrower rungs of the prefill ladder, each a
    multiple of the kernel's 512-row block) of 5,120 channels and 16 states,
    bfloat16 rows and a float32 time step, gated: one Mosaic kernel, and
    nothing of size rows x channels x states beside it (that would be 1.3
    GB)."""
    from ray_tpu.ops import ssm
    sds = shapes_on(topo.devices[0])

    Di, N = 5120, 16
    rows, maps = sds((S, Di), jnp.bfloat16), sds((S, N), jnp.bfloat16)
    lowered = jax.jit(lambda x, dt, a, b, c, d, s0, z, n: ssm._scan_pallas(
        x, dt, a, b, c, d, s0, n, z, interpret=False,
        block_channels=ssm._BLOCK_CHANNELS, block_rows=ssm._BLOCK_ROWS)
    ).lower(rows, sds((S, Di), jnp.float32), sds((N, Di), jnp.float32), maps,
            maps, sds((Di,), jnp.float32), sds((N, Di), jnp.float32), rows,
            sds((), jnp.int32))
    assert lowered.as_text().count("tpu_custom_call") == 1
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < 4 * S * N * 4 + (1 << 20)  # B, C re-laid


def test_ssd_state_step_kernel_compiles_for_v5e(topo):
    """A Mamba-2 decode step's update of one layer at the Granite cell's
    widths (9 layers x 64 slots x 128 states x 8,192 channels of float32,
    2.42 GB; bfloat16 rows, a float32 time step a head): one Mosaic kernel
    handed the WHOLE state, which it aliases; nothing the size of a layer's
    state (268 MB) is set aside, only the slots' vectors."""
    from ray_tpu.ops import ssm
    sds = shapes_on(topo.devices[0])

    L, ns, N, Di, H = 9, 64, 128, 8192, 128
    rows, maps = sds((ns, Di), jnp.bfloat16), sds((ns, N), jnp.bfloat16)
    head = sds((H,), jnp.float32)
    lowered = jax.jit(ssm.ssd_state_step, donate_argnums=0).lower(
        sds((L, ns, N, Di), jnp.float32), sds((), jnp.int32),
        sds((ns,), jnp.bool_), rows, sds((ns, H), jnp.float32), head, maps,
        maps, head)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1 and "ssd_state_step" in text
    mem = lowered.compile().memory_analysis()
    assert mem.alias_size_in_bytes == L * ns * N * Di * 4
    assert mem.temp_size_in_bytes < 16 << 20


# ---------------------------------------------------------------------------
# A stack of window and full attention layers (PR 42) at the cell's sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,kvh", [(128, 8), (0, 4)],
                         ids=["window", "full"])
@pytest.mark.parametrize("S", [7168, 512])
def test_mixed_flash_kernels_compile_for_v5e(topo, window, kvh, S):
    """`window_flash_fwd` (8 query heads of a kv head a grid step, two key
    blocks of 128) and `full_flash_fwd` (keys in two parts a kv head, values
    of 128) at MiMo-V2's widths, at a rung that is no power of two and at the
    narrowest a kernel takes."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(1, 64, S, 128), sds(1, 64, S, 64), sds(1, kvh, S, 128),
            sds(1, kvh, S, 64), sds(1, kvh, S, 128))
    if window:
        fn = jax.jit(lambda *a: attention._window_flash_pallas(
            *a, sm_scale=192 ** -0.5, window=window, interpret=False))
        args += (sds(64, dtype=jnp.float32),)
    else:
        fn = jax.jit(lambda *a: attention._latent_flash_pallas(
            *a, sm_scale=192 ** -0.5, interpret=False,
            name="full_flash_fwd"))
    lowered = fn.lower(*args)
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    assert ("window_flash_fwd" if window else "full_flash_fwd") in text
    assert lowered.compile().memory_analysis().temp_size_in_bytes >= 0


# ---------------------------------------------------------------------------
# The experts' grouped matmul (ops/moe.py::grouped_matmul)
# ---------------------------------------------------------------------------


# (rows, groups stacked, K, N): each sparse configuration's widest prefill
GROUPED_SHAPES = {"dots-4096": (8192, 64, 7168, 2048),
                  "mimo-8192": (16384, 96, 4096, 2048),
                  "olmoe-4096": (32768, 512, 2048, 1024),
                  "keye-8192": (65536, 512, 2048, 768)}


@pytest.mark.parametrize("matrix", ["gate-up", "down"])
@pytest.mark.parametrize("shape", sorted(GROUPED_SHAPES))
def test_grouped_matmul_compiles_for_v5e(topo, shape, matrix, monkeypatch):
    from ray_tpu.ops import moe

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    sds = shapes_on(topo.devices[0])
    m, g, k, n = GROUPED_SHAPES[shape]
    if matrix == "down":
        k, n = n, k

    before = attention.attention_path_counts().get("experts_grouped_pallas", 0)
    lowered = jax.jit(moe.grouped_matmul).lower(
        sds((m, k), jnp.bfloat16), sds((g, k, n), jnp.bfloat16),
        sds((g,), jnp.int32))
    assert attention.attention_path_counts()["experts_grouped_pallas"] \
        == before + 1
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text
    compiled = lowered.compile()
    assert not moved_stacks(compiled.as_text(), [(1, g, k, n)])
    # the visit lists and nothing else: no second result, no copy of a stack
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# (tokens, rows of a share's block, d): the two share cells' widest prefill
# and their decode step
COMBINE_SHAPES = {"dots-4096": (4096, 8192, 7168),
                  "mimo-8192": (8192, 16384, 4096),
                  "dots-decode": (32, 64, 7168),
                  "mimo-decode": (32, 64, 4096)}


@pytest.mark.parametrize("shape", sorted(COMBINE_SHAPES))
def test_local_combine_compiles_for_v5e(topo, shape, monkeypatch):
    """A share's combine at the cells' shapes is the Pallas kernel, its
    float32 result in the buffer it was handed, and beside it the run tables
    alone: nothing of `tokens x k` rows, nothing row-sized at all."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    sds = shapes_on(topo.devices[0])
    tokens, rows, d = COMBINE_SHAPES[shape]

    before = attention.attention_path_counts().get("share_combine_local", 0)
    lowered = jax.jit(moe.local_combine, donate_argnums=(0,)).lower(
        sds((tokens, d), jnp.float32), sds((), jnp.bool_),
        sds((rows, d), jnp.bfloat16), sds((rows,), jnp.int32),
        sds((tokens, 8), jnp.float32), sds((16,), jnp.int32))
    assert attention.attention_path_counts()["share_combine_local"] \
        == before + 1
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "local_combine" in text
    mem = lowered.compile().memory_analysis()
    assert mem.alias_size_in_bytes == tokens * d * 4
    assert mem.temp_size_in_bytes < 1 << 20


"""Compiles for a TPU that is described, not attached, plus the env that
pins worker processes to chips.

The TPU compiler is installed wherever libtpu is, so the kernels of the
main path are compiled here for `v5e:2x2` at their real shapes: what the
chip's compiler would refuse (a slice off the tiling, too much VMEM, a
Mosaic call GSPMD cannot partition) fails in tier-1 and costs no chip
time. A compile is not a run — tests/test_ops.py checks values (on the
reference path) and chip_smoke.py checks them on the chip.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu import accelerators  # noqa: E402
from ray_tpu.ops import attention  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it cannot describe v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """An executable compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip; keep the
    cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


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


# (vocab, d_model, heads, kv heads, d_ff, pages): the attention widths of
# the two serve configurations of BENCHMARK.json, 4 of their layers. OLMoE's
# feed-forward is dense here: it is the attention (MHA, 16 kv heads of 128,
# one query head a kv head) that the decode kernel has to adapt to.
DECODE_WIDTHS = {"mistral-7b": (32768, 4096, 32, 8, 14336, 929),
                 "olmoe-attn": (50304, 2048, 16, 16, 1024, 1025)}


@pytest.mark.parametrize("widths", sorted(DECODE_WIDTHS))
def test_decode_program_reads_and_updates_the_arena_in_place_on_v5e(
        topo, widths, monkeypatch):
    """The serving decode chunk, compiled for the chip: the KV arena must
    alias through the layer loop, the chunk loop and the donated entry
    buffers. As a scan xs/ys it was sliced out a layer at a time and
    restacked into a second arena every step: 24 of a 44 ms step on the
    chip (PERF.md, PR 25). And attention must read it where it lies: one
    Mosaic kernel a layer, handed the whole arena, and no gathered copy of
    every slot's whole block table (10 of a 21 ms step, 43 of 54 under MHA
    with its float32 copy; PERF.md, PR 28). The jaxpr test in
    tests/test_serve_llm.py holds the program's shape; this one holds what
    the TPU compiler makes of it."""
    import re

    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.models.serving import Caches, build_programs

    # The engine asks jax.devices() which attention path to take and sees
    # this sandbox's CPU, so the test, not the program, steers it.
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    vocab, d_model, H, KVH, d_ff, n_pages = DECODE_WIDTHS[widths]
    cfg = LlamaConfig(vocab_size=vocab, d_model=d_model, n_layers=4,
                      n_heads=H, n_kv_heads=KVH, d_ff=d_ff, max_seq=4096,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    ns, chunk, page, hd = 16, 8, 64, cfg.head_dim
    maxp = cfg.max_seq // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode = build_programs(cfg, ns, chunk, page, n_pages).decode
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)))))
    slab = (n_pages, KVH, page, hd)
    arena = sds((cfg.n_layers,) + slab, jnp.bfloat16)
    compiled = decode.lower(
        params, Caches(arena, arena), sds((ns, maxp), jnp.int32),
        sds((ns,), jnp.int32), sds((ns,), jnp.int32), sds((ns,), jnp.bool_),
        sds((ns,), jnp.float32), sds((ns,), jnp.int32),
        sds((ns, 2), jnp.uint32)).compile()
    text = compiled.as_text()
    # (name, dtype, dims, op) of every instruction with an array result.
    results = [(name, dtype, tuple(int(d) for d in dims.split(",")), op)
               for name, dtype, dims, op in re.findall(
                   r"%(\S+) = (\w+)\[([\d,]+)\]\S* ([\w-]+)\(", text)]
    # Every op whose result is arena- or slab-shaped: none may be a copy, a
    # slice or an update-slice, bare or fused (a fusion carries the op in
    # its name: `bitcast_dynamic-update-slice_fusion`).
    on_arena = [(name, op) for name, _, dims, op in results
                if dims in (slab, (cfg.n_layers,) + slab)]
    assert "scatter" in {op for _, op in on_arena}  # the pattern still reads
    moved = [name for name, op in on_arena
             if op == "copy" or "dynamic-" in op + name]
    assert not moved, moved
    # No instruction makes the gathered history, in any order of its
    # dimensions, with slots and pages merged or apart, in any dtype.
    gathered = [sorted(d) for d in ((ns * maxp, page, KVH, hd),
                                    (ns, maxp, page, KVH, hd),
                                    (ns, maxp * page, KVH, hd))]
    made = [(name, dtype, dims) for name, dtype, dims, _ in results
            if sorted(dims) in gathered]
    assert not made, made
    # One Mosaic kernel in the program, the layer body's attention, and its
    # operands are the two whole arenas.
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    dims = ",".join(map(str, (cfg.n_layers,) + slab))
    assert calls[0].count(f"bf16[{dims}]") == 2, calls[0]
    # The weights are read where they lie, a layer at a time, inside the
    # matmul that uses them: nothing the program MATERIALISES (an instruction
    # outside the fusions' bodies) is a stack of all the layers of a weight
    # matrix, or one layer's matrix, made by a copy, a slice or either half
    # of an asynchronous one, bare or fused by name. Handed
    # `wq`, `wk`, `wv` a stack each, the compiler re-laid all three at entry
    # (`copy.18-20`), sliced a layer's out as copies
    # (`constant_dynamic-slice_fusion.6-8`) and moved the whole `wk` stack out
    # of and into fast memory every layer of every step (`copy-done.1`,
    # `slice-done` x 4): 28% of a decode step on the chip (PERF.md, PR 30).
    bodies = set(re.findall(r" fusion\(.*calls=%([\w.-]+)", text))
    materialised = [
        (name, tuple(int(d) for d in dims.split(",")), op)
        for comp, block in re.findall(
            r"^(?:ENTRY )?%(\S+) \(.*?\{$(.*?)^\}", text, re.M | re.S)
        if comp not in bodies
        for name, dims, op in re.findall(
            r"%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(", block)]
    assert any("fusion" in op for _, _, op in materialised)  # still reads
    stacks = {tuple(x.shape) for x in jax.tree.leaves(params["layers"])
              if len(x.shape) == 3}
    stacks |= {(cfg.n_layers, d_model, n * hd) for n in (H, KVH)}
    weights = stacks | {(1,) + w[1:] for w in stacks} | {
        w[1:] for w in stacks}
    moved = [(name, dims) for name, dims, op in materialised
             if dims in weights and re.search("copy|slice", op + name)]
    assert not moved, moved
    # Temporaries: nothing set aside, and far under one layer's slab (the
    # gathered K and V alone were 1.1 slabs each, their float32 copies twice
    # that; the re-laid projection stacks 577 MiB at 12 Mistral layers,
    # against 1 MiB now).
    one_slab = n_pages * page * KVH * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20 < one_slab


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
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

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
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

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


def test_sparse_decode_leaves_the_arenas_where_they_lie_on_v5e(
        topo, monkeypatch):
    """The decode chunk of a model with an indexer, at Keye's widths and the
    cell's engine sizes (2 layers): K, V and the indexer keys ride the loops
    as carries, and the gather of the selected positions reads the K/V arena
    (by token: `[L, pages, page, KVH * hd]`) as rows. Indexed through its
    dimensions instead, XLA re-lays the whole arena and copies it (1 GiB at
    4 layers) to and from every page write, every layer of every step."""
    import json
    import re

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/keye-vl-2.0-30b-a3b-serve.json")) as f:
        config = json.load(f)
    config["num_hidden_layers"] = 2
    eng = config["deployment"]["engine"]
    cfg = models.adapter("keye").build_config(config, config["dtypes"],
                                              eng["max_seq"])
    ns, page = eng["n_slots"], eng["page_size"]
    maxp = cfg.max_seq // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    built = build_programs(cfg, ns, eng["decode_chunk"], page,
                           eng["kv_pages"])
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)))))
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(built.empty))
    kc = caches.kc
    compiled = built.decode.lower(
        params, caches, sds((ns, maxp), jnp.int32), sds((ns,), jnp.int32),
        sds((ns,), jnp.int32), sds((ns,), jnp.bool_), sds((ns,), jnp.float32),
        sds((ns,), jnp.int32), sds((ns, 2), jnp.uint32)).compile()
    # (The indexer keys' arena, 64 wide under 128 lanes, is re-tiled once at
    # the chunk's entry and exit: 2 x 67 MB a chunk of 8 steps, not a layer.)
    shapes = {tuple(kc.shape), tuple(kc.shape[1:])}
    moved = [(name, op) for name, dims, op in re.findall(
        r"%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(", compiled.as_text())
        if tuple(int(d) for d in dims.split(",")) in shapes
        and (op in ("copy", "transpose") or "dynamic-" in op + name)]
    assert not moved, moved
    assert kc.shape == (2, eng["kv_pages"], page, 4 * 128)
    one_slab = kc.shape[1] * page * kc.shape[3] * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_slab
    # The table is 4 x top-k wide, so the step streams (`sa._streams`): the
    # kernel is in the program and no gathered `[ns * topk, KVH * hd]` rows
    # (2 x 32 MiB a layer, until PR 44) are left in it.
    text = compiled.as_text()
    assert "sparse_paged_decode" in text
    assert not re.search(r"\[%d,%d\]" % (ns * cfg.index_topk, kc.shape[3]),
                         text)


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
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

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
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

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


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_jamba_programs_keep_arena_and_state_in_place_on_v5e(
        topo, program, monkeypatch):
    """The hybrid stack's decode chunk and its 4,096-bucket prefill at the
    cell's sizes (benchmark/configs/jamba2-3b-serve.json): the K/V arena of
    the 2 attention layers and the recurrent state of the 26 state-space
    layers are donated and alias the outputs; decode's attention is the
    `paged_decode` kernel at MQA `groups` 20, prefill's scan the
    `selective_scan` kernel; and no program sets a layer's weights aside
    (the stacks are read by index inside the segment's loop, the attention
    layers' by a constant one)."""
    import json

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "jamba2-3b-serve.json")) as f:
        model = json.load(f)
    eng = model["deployment"]["engine"]
    cfg = models.adapter("jamba").build_config(model, model["dtypes"],
                                               eng["max_seq"])
    ns, page = eng["n_slots"], eng["page_size"]
    maxp = eng["max_seq"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    built = build_programs(cfg, ns, eng["decode_chunk"], page,
                           eng["kv_pages"])
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)))))
    assert "lm_head" not in params
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(built.empty))
    kc, vc, _, state = caches
    assert kc.shape == (2, eng["kv_pages"], 1, page, 128)
    assert [tuple(x.shape) for x in state] == [(26, ns, 16, 5120),
                                               (26, 3, ns, 5120)]
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = built.decode.lower(
            params, caches, sds((ns, maxp), jnp.int32), sds((ns,), jnp.int32),
            sds((ns,), jnp.int32), sds((ns,), jnp.bool_),
            sds((ns,), jnp.float32), sds((ns,), jnp.int32),
            sds((ns, 2), jnp.uint32))
        kernel, path = "paged_decode", "decode_pallas"
    else:
        lowered = built.prefill.lower(
            params, caches, sds((maxp,), jnp.int32), sds((1, 4096), jnp.int32),
            1, 0.0, 0, sds((2,), jnp.uint32), 0)
        kernel, path = "selective_scan", "scan_pallas"
    text = lowered.as_text()
    assert "tpu_custom_call" in text and kernel in text
    counts = attention.attention_path_counts()
    assert counts[path] > before.get(path, 0)
    mem = lowered.compile().memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (kc, vc) + tuple(state))
    assert mem.alias_size_in_bytes >= held
    # A layer's weights set aside would be 0.2 GB (a Mamba layer), a
    # segment's 1.4; the prefill's own temporaries are its activations.
    assert mem.temp_size_in_bytes < ((16 << 20) if program == "decode"
                                     else (256 << 20))


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


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_mimo_programs_keep_both_caches_in_place_on_v5e(
        topo, program, monkeypatch):
    """The mixed stack's decode chunk and its 2,048-bucket prefill (the
    8,192-wide one compiles as well, 1.71 GB of temporaries, in 24 s of every
    core: a builder's compile, PERF.md section 4) at the cell's sizes (benchmark/configs/mimo-v2-flash-serve.json): the pages of
    the 2 full layers (keys in 256 lanes, values in 128) and the rings of the
    5 window layers are donated and alias the outputs; decode's full layers
    run the `paged_decode` kernel at those widths, prefill the two flash
    kernels; and serving fits the chip beside the widest prefill's
    temporaries."""
    import json

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "mimo-v2-flash-serve.json")) as f:
        model = json.load(f)
    eng = model["deployment"]["engine"]
    cfg = models.adapter("mimo").build_config(model, model["dtypes"],
                                              eng["max_seq"])
    ns, page = eng["n_slots"], eng["page_size"]
    maxp = eng["max_seq"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    built = build_programs(cfg, ns, eng["decode_chunk"], page,
                           eng["kv_pages"])
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)), cfg)))
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(built.empty))
    kc, vc, _, state = caches
    assert kc.shape == (2, eng["kv_pages"], 4, page, 256)
    assert vc.shape == (2, eng["kv_pages"], 4, page, 128)
    assert [tuple(x.shape) for x in state] == [(5, ns, 8, 128, 256),
                                               (5, ns, 8, 128, 128)]
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = built.decode.lower(
            params, caches, sds((ns, maxp), jnp.int32), sds((ns,), jnp.int32),
            sds((ns,), jnp.int32), sds((ns,), jnp.bool_),
            sds((ns,), jnp.float32), sds((ns,), jnp.int32),
            sds((ns, 2), jnp.uint32))
        kernels, paths = ["paged_decode"], ["decode_pallas",
                                            "window_decode_reference"]
    else:
        lowered = built.prefill.lower(
            params, caches, sds((maxp,), jnp.int32), sds((1, 2048), jnp.int32),
            1, 0.0, 0, sds((2,), jnp.uint32), 0)
        kernels, paths = ["window_flash_fwd", "full_flash_fwd"], [
            "window_fwd_pallas", "full_fwd_pallas"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    mem = lowered.compile().memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (kc, vc) + tuple(state))
    assert held == 1_736_835_072
    assert mem.alias_size_in_bytes >= held
    # decode sets nothing aside; a prefill's temporaries are its activations
    # (1.71 GB at 8,192 rows), and arguments + temporaries fit the chip's 15.75
    assert mem.temp_size_in_bytes < ((64 << 20) if program == "decode"
                                     else (1 << 30))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12 << 30


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_lfm2_programs_keep_pages_and_windows_in_place_on_v5e(
        topo, program, monkeypatch):
    """The stack of short-convolution layers beside attention on heads of 64:
    its decode chunk of 64 slots and its widest prefill (2,048 rows) at the
    cell's sizes (benchmark/configs/lfm2-24b-a2b-serve.json). The pages of
    the 2 attention layers hold two kv heads to a 128-lane row (2,048 B a
    token a layer, no padded lane) and the 7 conv layers' windows are 2 x
    2,048 numbers a slot; both are donated and alias the outputs. Decode's
    attention is the `paged_decode` kernel over that arena, a prompt's the
    `flash_fwd` kernel at a head of half a tile, the experts the grouped
    matmul with no copy of a stack; and the bytes are PERF.md section 4's
    row: 10.90 GB of arguments, temporaries of 4.6 MB (decode) and 63.5 MB
    (the widest prefill)."""
    import json

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "lfm2-24b-a2b-serve.json")) as f:
        model = json.load(f)
    eng = model["deployment"]["engine"]
    cfg = models.adapter("lfm2").build_config(model, model["dtypes"],
                                              eng["max_seq"])
    ns, page = eng["n_slots"], eng["page_size"]
    maxp = eng["max_seq"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    built = build_programs(cfg, ns, eng["decode_chunk"], page,
                           eng["kv_pages"])
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)), cfg)))
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(built.empty))
    kc, vc, ic, (ssm, window) = caches
    assert kc.shape == vc.shape == (2, eng["kv_pages"], 4, page, 128)
    assert ic is None and ssm is None and window.shape == (7, 2, ns, 2048)
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = built.decode.lower(
            params, caches, sds((ns, maxp), jnp.int32), sds((ns,), jnp.int32),
            sds((ns,), jnp.int32), sds((ns,), jnp.bool_),
            sds((ns,), jnp.float32), sds((ns,), jnp.int32),
            sds((ns, 2), jnp.uint32))
        kernels, paths = ["paged_decode", "grouped_matmul"], [
            "decode_pallas", "experts_grouped_pallas"]
    else:
        lowered = built.prefill.lower(
            params, caches, sds((maxp,), jnp.int32), sds((1, 2048), jnp.int32),
            1, 0.0, 0, sds((2,), jnp.uint32), 0)
        kernels, paths = ["flash_fwd", "grouped_matmul"], [
            "fwd_pallas", "experts_grouped_pallas"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    assert counts.get("experts_ragged_dot", 0) == before.get(
        "experts_ragged_dot", 0)
    compiled = lowered.compile()
    stacks = [tuple(params[stack][w].shape) for stack in ("conv", "layers")
              for w in ("w_gate", "w_up", "w_down")]
    assert not _moved_stacks(compiled.as_text(), stacks)
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (kc, vc, window))
    assert held == 2 * 2 * eng["kv_pages"] * 4 * page * 128 * 2 \
        + 7 * 2 * ns * 2048 * 2 == 540_803_072
    assert mem.alias_size_in_bytes >= held
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert weights == 2 * 5_177_950_976
    # arguments: the weights, the caches and a step's few vectors
    assert 0 <= mem.argument_size_in_bytes - weights - held < 1 << 20
    assert mem.temp_size_in_bytes < ((8 << 20) if program == "decode"
                                     else (96 << 20))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_granite_programs_keep_pages_and_state_in_place_on_v5e(
        topo, program, monkeypatch):
    """The Mamba-2 hybrid over a share of the experts: its decode chunk of 64
    slots and its 1,024-row prefill at the cell's sizes
    (benchmark/configs/granite-4.0-h-small-serve.json). The recurrent state
    is 2.42 GB (9 layers x 64 slots x 128 x 8,192 float32) and rides the
    decode loop's carry: it, its windows over 8,448 channels and the pages of
    the ONE attention layer are donated and alias the outputs, so no second
    copy of the state is made (a copy would show as 2.4 GB of temporaries).
    Decode's attention is the `paged_decode` kernel, its state's update the
    `ssd_state_step` kernel handed the whole state (one layer's copy would be
    268 MB of temporaries), a prompt's attention `flash_fwd`, the recurrence
    over a prompt the chunked dual form in plain XLA, the experts the grouped
    matmul with no copy of a stack and the share's combine the local kernel;
    and the bytes are PERF.md section 4's row."""
    import json

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "granite-4.0-h-small-serve.json")) as f:
        model = json.load(f)
    eng = model["deployment"]["engine"]
    cfg = models.adapter("granitemoehybrid").build_config(
        model, model["dtypes"], eng["max_seq"])
    ns, page = eng["n_slots"], eng["page_size"]
    maxp = eng["max_seq"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    built = build_programs(cfg, ns, eng["decode_chunk"], page,
                           eng["kv_pages"])
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)), cfg)))
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(built.empty))
    kc, vc, ic, (ssm, window) = caches
    assert kc.shape == vc.shape == (1, eng["kv_pages"], 8, page, 128)
    assert ic is None and ssm.shape == (9, ns, 128, 8192) \
        and ssm.dtype == jnp.float32 and window.shape == (9, 3, ns, 8448)
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = built.decode.lower(
            params, caches, sds((ns, maxp), jnp.int32), sds((ns,), jnp.int32),
            sds((ns,), jnp.int32), sds((ns,), jnp.bool_),
            sds((ns,), jnp.float32), sds((ns,), jnp.int32),
            sds((ns, 2), jnp.uint32))
        kernels, paths = ["paged_decode", "grouped_matmul", "local_combine",
                          "ssd_state_step"], [
            "decode_pallas", "experts_grouped_pallas", "share_combine_local",
            "ssd_step_pallas"]
    else:
        lowered = built.prefill.lower(
            params, caches, sds((maxp,), jnp.int32), sds((1, 1024), jnp.int32),
            1, 0.0, 0, sds((2,), jnp.uint32), 0)
        kernels, paths = ["flash_fwd", "grouped_matmul", "local_combine"], [
            "fwd_pallas", "experts_grouped_pallas", "share_combine_local",
            "ssd_chunked"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    assert counts.get("experts_ragged_dot", 0) == before.get(
        "experts_ragged_dot", 0)
    compiled = lowered.compile()
    stacks = [tuple(params[stack][w].shape) for stack in ("mamba", "layers")
              for w in ("w_gate", "w_up", "w_down")]
    assert not _moved_stacks(compiled.as_text(), stacks)
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (kc, vc, ssm, window))
    assert held == 2 * eng["kv_pages"] * 8 * page * 128 * 2 \
        + 9 * ns * 128 * 8192 * 4 + 9 * 3 * ns * 8448 * 2 == 2_982_248_448
    assert mem.alias_size_in_bytes >= held
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert weights == 2 * 4_757_211_776
    # arguments: the weights, the caches and a step's few vectors
    assert 0 <= mem.argument_size_in_bytes - weights - held < 1 << 20
    print(program, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < ((64 << 20) if program == "decode"
                                     else (1 << 30))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_sdar_block_programs_keep_the_pages_in_place_on_v5e(
        topo, program, monkeypatch):
    """Generation by blocks at the cell's sizes (benchmark/configs/
    sdar-30b-a3b-chat-serve.json: 6 layers of the published widths, every
    expert, 64 slots, a chunk of two blocks of 4): the decode program, four
    forwards (two of 512 rows, the pending block beside the open one, and two
    of 256) with the pages in the loops' carry, and the
    1,024-row prefill under the block mask. Decode's attention is the
    `paged_decode` kernel at 8 and at 4 rows a slot (counted
    `block_decode_pallas`; at 8 the first 4 lag a block: 128 rows a kv head), a
    prompt's the flash kernel with the block comparison in its diagonal tiles
    (`block_flash_fwd`, counted `block_fwd_pallas`), the experts the grouped
    matmul with no copy of a stack; the arena is donated and aliases the
    output, and a block's write moves pages, not the arena."""
    import json

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "sdar-30b-a3b-chat-serve.json")) as f:
        model = json.load(f)
    eng = model["deployment"]["engine"]
    cfg = models.adapter("sdar").build_config(model, model["dtypes"],
                                              eng["max_seq"])
    ns, page, B = eng["n_slots"], eng["page_size"], model["block_length"]
    maxp = eng["max_seq"] // page
    layers = model["num_hidden_layers"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    built = build_programs(cfg, ns, eng["decode_chunk"], page,
                           eng["kv_pages"])
    assert (built.block, built.block_forwards) == (B, 2) \
        and not built.takes_riders and not built.adopts
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)), cfg)))
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(built.empty))
    kc, vc = caches.kc, caches.vc
    assert kc.shape == vc.shape == (layers, eng["kv_pages"], 4, page, 128)
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = built.decode.lower(
            params, caches, sds((ns, maxp), jnp.int32),
            sds((ns, 2 * B), jnp.int32), sds((ns,), jnp.int32),
            sds((ns,), jnp.bool_), sds((ns,), jnp.float32),
            sds((ns,), jnp.int32), sds((ns, 2), jnp.uint32))
        kernels, paths = ["paged_decode", "grouped_matmul"], [
            "block_decode_pallas", "experts_grouped_pallas"]
    else:
        lowered = built.prefill.lower(
            params, caches, sds((maxp,), jnp.int32), sds((1, 1024), jnp.int32),
            1, 0.0, 0, sds((2,), jnp.uint32), None)
        kernels, paths = ["block_flash_fwd", "grouped_matmul"], [
            "block_fwd_pallas", "experts_grouped_pallas"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    for other in ("fwd_pallas", "decode_pallas"):   # the causal paths: unused
        assert counts.get(other, 0) == before.get(other, 0)
    compiled = lowered.compile()
    stacks = [tuple(params["layers"][w].shape)
              for w in ("w_gate", "w_up", "w_down")]
    assert not _moved_stacks(compiled.as_text(), stacks)
    mem = compiled.memory_analysis()
    held = 2 * kc.size * kc.dtype.itemsize
    assert held == 2 * layers * eng["kv_pages"] * 4 * page * 128 * 2
    assert mem.alias_size_in_bytes >= held
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 2 * models.adapter("sdar").counts.total_params(model)
    print(program, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
    if program == "prefill":
        # a prefill yields no token: it computes no head, and never reads it
        head = params["lm_head"]
        weights -= head.size * head.dtype.itemsize
    assert 0 <= mem.argument_size_in_bytes - weights - held < 1 << 20
    assert mem.temp_size_in_bytes < ((512 << 20) if program == "decode"
                                     else (1 << 30))


# ---------------------------------------------------------------------------
# The experts' grouped matmul (ops/moe.py::grouped_matmul)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("program", ["decode", "prefill1024", "prefill2048",
                                     "prefill2560", "prefill3072",
                                     "prefill3584"])
def test_nemotron_programs_keep_pages_and_state_in_place_on_v5e(
        topo, program, monkeypatch):
    """The stack of one-part layers at the cell's sizes
    (benchmark/configs/nemotron-3-nano-30b-a3b-serve.json): its decode chunk
    of 32 slots and its prefill at every bucket the mix's prompts of
    1,024-3,584 land in. The recurrent state of the 7 mixers is 0.47 GB (7 x
    32 slots x 128 x 4,096 float32) and rides the decode loop's carry: it,
    its windows over 6,144 channels (x and 8 groups' B and C) and the pages of
    the TWO attention layers are donated and alias the outputs. Decode's
    attention is the `paged_decode` kernel at 16 query heads a kv head, its
    state's update the `ssd_state_step` kernel with groups handed the whole
    state, a prompt's attention `flash_fwd`, the recurrence over a prompt
    the chunked dual form in plain XLA, the experts' TWO grouped matmuls the
    Pallas kernel at 2688 -> 1856 -> 2688 with no copy of a stack, and the
    share's combine the local kernel; and the bytes are PERF.md section 4's
    row."""
    import json

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs",
                           "nemotron-3-nano-30b-a3b-serve.json")) as f:
        model = json.load(f)
    eng = model["deployment"]["engine"]
    cfg = models.adapter("nemotron_h").build_config(
        model, model["dtypes"], eng["max_seq"])
    ns, page = eng["n_slots"], eng["page_size"]
    maxp = eng["max_seq"] // page

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    built = build_programs(cfg, ns, eng["decode_chunk"], page,
                           eng["kv_pages"])
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)), cfg)))
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(built.empty))
    kc, vc, ic, (ssm, window) = caches
    assert kc.shape == vc.shape == (2, eng["kv_pages"], 2, page, 128)
    assert ic is None and ssm.shape == (7, ns, 128, 4096) \
        and ssm.dtype == jnp.float32 and window.shape == (7, 3, ns, 6144)
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = built.decode.lower(
            params, caches, sds((ns, maxp), jnp.int32), sds((ns,), jnp.int32),
            sds((ns,), jnp.int32), sds((ns,), jnp.bool_),
            sds((ns,), jnp.float32), sds((ns,), jnp.int32),
            sds((ns, 2), jnp.uint32))
        kernels, paths = ["paged_decode", "grouped_matmul", "local_combine",
                          "ssd_state_step"], [
            "decode_pallas", "experts_grouped_pallas", "share_combine_local",
            "ssd_step_pallas"]
    else:
        lowered = built.prefill.lower(
            params, caches, sds((maxp,), jnp.int32),
            sds((1, int(program[7:])), jnp.int32), 1, 0.0, 0,
            sds((2,), jnp.uint32), 0)
        kernels, paths = ["flash_fwd", "grouped_matmul", "local_combine"], [
            "fwd_pallas", "experts_grouped_pallas", "share_combine_local",
            "ssd_chunked"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    assert counts.get("experts_ragged_dot", 0) == before.get(
        "experts_ragged_dot", 0)
    compiled = lowered.compile()
    stacks = [tuple(params["experts"][w].shape) for w in ("w_up", "w_down")]
    assert not _moved_stacks(compiled.as_text(), stacks)
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (kc, vc, ssm, window))
    assert held == 2 * 2 * eng["kv_pages"] * 2 * page * 128 * 2 \
        + 7 * ns * 128 * 4096 * 4 + 7 * 3 * ns * 6144 * 2 == 746_586_112
    assert mem.alias_size_in_bytes >= held
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert weights == 2 * 5_282_534_208
    # arguments: the weights, the caches and a step's few vectors
    assert 0 <= mem.argument_size_in_bytes - weights - held < 1 << 20
    print(program, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < ((64 << 20) if program == "decode"
                                     else (2 << 30))


def _moved_stacks(hlo, stacks):
    """Names of the compiled program's instructions whose result has the
    shape of an expert stack, of one layer of one or of one expert's matrix
    and is a copy, a slice or an update-slice, bare or fused by name (the
    decode program's test has the pattern): a kernel handed one layer of a
    stack is first given a copy of it (PERF.md, PR 27)."""
    import re
    shapes = set()
    for s in stacks:
        shapes |= {s, s[1:], s[2:], (s[0] * s[1],) + s[2:]}
    return [name for name, dims, op in re.findall(
        r"%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(", hlo)
        if tuple(int(d) for d in dims.split(",")) in shapes
        and (op == "copy" or "dynamic-" in op + name or "slice" in op + name)]


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
    one_chip = SingleDeviceSharding(topo.devices[0])
    m, g, k, n = GROUPED_SHAPES[shape]
    if matrix == "down":
        k, n = n, k

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    before = attention.attention_path_counts().get("experts_grouped_pallas", 0)
    lowered = jax.jit(moe.grouped_matmul).lower(
        sds((m, k), jnp.bfloat16), sds((g, k, n), jnp.bfloat16),
        sds((g,), jnp.int32))
    assert attention.attention_path_counts()["experts_grouped_pallas"] \
        == before + 1
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "grouped_matmul" in text
    compiled = lowered.compile()
    assert not _moved_stacks(compiled.as_text(), [(1, g, k, n)])
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
    one_chip = SingleDeviceSharding(topo.devices[0])
    tokens, rows, d = COMBINE_SHAPES[shape]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

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


@pytest.mark.parametrize("config", ["dots.vlm1.inst-serve",
                                    "mimo-v2-flash-serve"])
def test_a_shares_prefill_reads_the_expert_stacks_where_they_lie_on_v5e(
        topo, config, monkeypatch):
    """The 2,048-wide prefill of the two configurations that hold a SHARE of
    their experts, at the cells' sizes: every sparse layer's three grouped
    matmuls are the Pallas kernel, handed the stacks of all layers, and the
    compiled program holds no copy or slice of a stack, of a layer of one or
    of an expert's matrix."""
    import json

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", config + ".json")) as f:
        model = json.load(f)
    eng = model["deployment"]["engine"]
    cfg = models.adapter(model["arch"]).build_config(model, model["dtypes"],
                                                     eng["max_seq"])
    maxp = eng["max_seq"] // eng["page_size"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    built = build_programs(cfg, eng["n_slots"], eng["decode_chunk"],
                           eng["page_size"], eng["kv_pages"])
    params = shaped(jax.eval_shape(
        lambda: fuse_qkv(init_params(cfg, jax.random.PRNGKey(0)), cfg)))
    before = attention.attention_path_counts()
    lowered = built.prefill.lower(
        params, shaped(jax.eval_shape(built.empty)), sds((maxp,), jnp.int32),
        sds((1, 2048), jnp.int32), 1, 0.0, 0, sds((2,), jnp.uint32),
        0 if built.by_slot else None)
    counts = attention.attention_path_counts()
    assert counts["experts_grouped_pallas"] > before.get(
        "experts_grouped_pallas", 0)
    assert counts.get("experts_ragged_dot", 0) == before.get(
        "experts_ragged_dot", 0)
    # and every sparse segment's combine the local kernel, none the gather
    assert counts["share_combine_local"] > before.get(
        "share_combine_local", 0)
    assert counts.get("share_combine_gather", 0) == before.get(
        "share_combine_gather", 0)
    assert "grouped_matmul" in lowered.as_text() \
        and "local_combine" in lowered.as_text()
    stacks = [tuple(params[stack][w].shape)
              for stack in ("layers", "window") if stack in params
              for w in ("w_gate", "w_up", "w_down")
              if "router" in params[stack]]
    assert stacks and all(len(s) == 4 for s in stacks)
    hlo = lowered.compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 4
    assert not _moved_stacks(hlo, stacks)


# ---------------------------------------------------------------------------
# A riding rung's prefill (serve/engine.py::rung_rides) at the cells' sizes
# ---------------------------------------------------------------------------

RIDING_RUNGS = [("mistral-7b-v0.3-serve", 4096), ("olmoe-1b-7b-serve", 4096),
                ("olmoe-1b-7b-serve", 2048)]


@pytest.mark.parametrize("config,width", RIDING_RUNGS,
                         ids=[f"{c}-{w}" for c, w in RIDING_RUNGS])
def test_a_riding_prefill_updates_the_arena_in_place_and_fits_on_v5e(
        topo, config, width, monkeypatch):
    """The prefill program of a riding rung, compiled for the chip at the two
    riding cells' sizes, beside the same width's program with nobody to take
    (the parent's text): the arena rides the layer scan's carry through the
    riders' page writes and the `paged_decode` kernel and still aliases the
    donated entry buffers, nothing arena- or slab-shaped is copied, sliced
    out or re-laid, and the step's page rows and 17 rows of logits stay
    within 5% + 16 MiB of the riderless program's temporaries (OLMoE serves
    within 0.9 GB of the chip's memory: PERF.md section 4)."""
    import json
    import re

    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.serving import build_programs
    from ray_tpu.serve.engine import Engine, rung_rides

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", config + ".json")) as f:
        model = json.load(f)
    eng = model["deployment"]["engine"]
    adapter = models.adapter(model["arch"])
    cfg = adapter.build_config(model, model["dtypes"], eng["max_seq"])
    ns, page = eng["n_slots"], eng["page_size"]
    maxp = eng["max_seq"] // page
    assert rung_rides(eng["max_seq"], ns, width)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    built = build_programs(cfg, ns, eng["decode_chunk"], page,
                           eng["kv_pages"])
    assert built.takes_riders
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: fuse_qkv(Engine._experts_in_compute_dtype(
                adapter.init_params(cfg, 0), cfg), cfg)))
    caches = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(built.empty))
    kc, vc = caches.kc, caches.vc
    slots = sds((ns,), jnp.int32)
    riders = (sds((ns, maxp), jnp.int32), sds((ns,), jnp.bool_),
              sds((ns,), jnp.float32), slots, sds((ns, 2), jnp.uint32))

    def compiled(*more):
        lowered = built.prefill.lower(
            params, caches, sds((maxp,), jnp.int32),
            sds((1, width), jnp.int32), 1, 0.0, 0, sds((2,), jnp.uint32),
            None, *more)
        return lowered.as_text(), lowered.compile()

    plain_text, plain = compiled(None, None, None)
    text, riding = compiled(slots, slots, riders)
    assert "paged_decode" in text and "paged_decode" not in plain_text
    hlo = riding.as_text()
    calls = [kind.count('custom_call_target="tpu_custom_call"')
             for kind in (plain.as_text(), hlo)]
    assert calls[1] == calls[0] + 1, calls
    # Nothing whose result is arena- or slab-shaped is a copy, a slice or an
    # update-slice, bare or fused by name (see the decode program's test).
    arena, slab = tuple(kc.shape), tuple(kc.shape[1:])
    on_arena = [(name, op) for name, dims, op in re.findall(
        r"%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(", hlo)
        if tuple(int(d) for d in dims.split(",")) in (arena, slab)]
    assert "scatter" in {op for _, op in on_arena}  # the pattern still reads
    moved = [name for name, op in on_arena
             if op == "copy" or "dynamic-" in op + name]
    assert not moved, moved
    mem, was = riding.memory_analysis(), plain.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (kc, vc))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes <= 1.05 * was.temp_size_in_bytes + (16 << 20)


# ---------------------------------------------------------------------------
# Chip pinning env (no compiler needed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chips,bounds", [
    ([2], "1,1,1"),          # one chip of a 2x2 host
    ([0, 1], "1,2,1"),       # two
    ([0, 1, 2, 3], None),    # the whole host: nothing overridden
], ids=["1-chip", "2-chips", "4-chips"])
def test_worker_env_for_chips_on_2x2_host(chips, bounds):
    env = accelerators.worker_env_for_chips(chips, host_chips=4)
    if bounds is None:
        # Not "1,4,1", which describes no 2x2 host: libtpu's own view.
        assert env == {}
        return
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"  # an island
    assert env["ALLOW_MULTIPLE_LIBTPU_LOAD"] == "1"  # others share the host


def test_attached_device_files_outrank_the_host_env(monkeypatch):
    """Found on the chip: a VM passed one chip of a 2x2 host (as
    /dev/vfio/2) still carries TPU_CHIPS_PER_HOST_BOUNDS=2,2,1."""
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setattr(
        accelerators.glob, "glob",
        lambda pat: ["/dev/vfio/2"] if pat == "/dev/vfio/[0-9]*" else [])
    assert accelerators.num_tpu_chips() == 1
    monkeypatch.setattr(accelerators.glob, "glob", lambda pat: [])
    assert accelerators.num_tpu_chips() == 4  # no files: the env is all


def test_worker_env_rejects_a_group_libtpu_cannot_describe():
    with pytest.raises(ValueError, match="3 TPU chips"):
        accelerators.worker_env_for_chips([0, 1, 2], host_chips=4)


def test_gang_env_joins_one_chip_workers_into_a_2x2():
    ports = [8476, 8477, 8478, 8479]
    envs = [accelerators.gang_env(r, 4, 1, ports, host="10.0.0.1")
            for r in range(4)]
    for rank, env in enumerate(envs):
        assert env["TPU_VISIBLE_CHIPS"] == str(rank)
        assert env["CLOUD_TPU_TASK_ID"] == str(rank)
        assert env["TPU_PROCESS_PORT"] == str(ports[rank])
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "2,2,1"  # one topology, not 4
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    assert envs[0]["TPU_PROCESS_ADDRESSES"].split(",")[3] == "10.0.0.1:8479"
    with pytest.raises(ValueError, match="cannot join"):
        accelerators.gang_env(0, 3, 1, [1, 2, 3])

"""The serving programs that hold a K/V arena alone, compiled for a described
`v5e:2x2` at the cells' sizes (tests/compile_for_v5e.py says why): the dense
and the sparse decode chunk, the indexed one's, and the riding rungs'
prefill."""

import re

import jax
import jax.numpy as jnp
import pytest

from compile_for_v5e import described_cell, shapes_on
from ray_tpu.ops import attention

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


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
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.models.serving import Caches, build_programs

    # The engine asks jax.devices() which attention path to take and sees
    # this sandbox's CPU, so the test, not the program, steers it.
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    sds = shapes_on(topo.devices[0])
    vocab, d_model, H, KVH, d_ff, n_pages = DECODE_WIDTHS[widths]
    cfg = LlamaConfig(vocab_size=vocab, d_model=d_model, n_layers=4,
                      n_heads=H, n_kv_heads=KVH, d_ff=d_ff, max_seq=4096,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    ns, chunk, page, hd = 16, 8, 64, cfg.head_dim
    maxp = cfg.max_seq // page

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


def test_sparse_decode_leaves_the_arenas_where_they_lie_on_v5e(
        topo, monkeypatch):
    """The decode chunk of a model with an indexer, at Keye's widths and the
    cell's engine sizes (2 layers): K, V and the indexer keys ride the loops
    as carries, and the gather of the selected positions reads the K/V arena
    (by token: `[L, pages, page, KVH * hd]`) as rows. Indexed through its
    dimensions instead, XLA re-lays the whole arena and copies it (1 GiB at
    4 layers) to and from every page write, every layer of every step."""
    cell = described_cell(topo, monkeypatch, "keye-vl-2.0-30b-a3b-serve",
                          layers=2)
    eng, cfg, ns, page = cell.eng, cell.cfg, cell.ns, cell.page
    kc = cell.caches.kc
    compiled = cell.lower_decode().compile()
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
# A riding rung's prefill (serve/engine.py::rung_rides) at the cells' sizes
# ---------------------------------------------------------------------------

RIDING_RUNGS = [("mistral-7b-v0.3-serve", 4096), ("olmoe-1b-7b-serve", 4096),
                ("olmoe-1b-7b-serve", 2048)]


@pytest.mark.timeout(240)
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
    from ray_tpu.models import serving
    from ray_tpu.serve.engine import rung_rides

    cell = described_cell(
        topo, monkeypatch, config,
        init=lambda adapter, cfg: serving._experts_in_compute_dtype(
            adapter.init_params(cfg, 0), cfg))
    ns = cell.ns
    assert rung_rides(cell.eng["max_seq"], ns, width)
    assert cell.built.takes_riders
    kc, vc = cell.caches.kc, cell.caches.vc

    def compiled(*more):
        lowered = cell.lower_prefill(width, None, *more)
        return lowered.as_text(), lowered.compile()

    plain_text, plain = compiled(None, None, None)
    text, riding = compiled(*cell.riding())
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

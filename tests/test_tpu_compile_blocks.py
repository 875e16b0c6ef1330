"""LFM2's short-convolution stack and SDAR's generation by blocks, compiled
for a described `v5e:2x2` at the cells' sizes (tests/compile_for_v5e.py says
why)."""

import jax
import pytest

from compile_for_v5e import copies_of, described_cell, moved_stacks
from ray_tpu.ops import attention
from ray_tpu.serve.engine import rung_rides

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


@pytest.mark.parametrize("program", ["decode", "prefill", "riding"])
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
    (the widest prefill). `riding` (PR 60) is the 1,024 rung as the engine
    calls it, where 0.58 of the cell's prompts land with room: the 64 slots'
    decode step in its last 64 rows, `paged_decode` beside `flash_fwd` in the
    2 attention layers, each conv layer's windows read and written where they
    lie in the segments' carry and the riders' rows put into the product
    `C * c` by an update of 64 rows; no copy of the arena, ONE of the windows
    (3.7 MB: XLA lays them out anew for the prompt's `write_state`, which
    reads one slot's rows of every layer, as it does a hybrid's); temporaries
    37.0 MB where the same rung with nobody to take, the parent's program
    (0ed315d), holds 34.6."""
    cell = described_cell(topo, monkeypatch, "lfm2-24b-a2b-serve")
    eng, params, caches, ns, page = (cell.eng, cell.params, cell.caches,
                                      cell.ns, cell.page)
    kc, vc, ic, (ssm, window) = caches
    assert kc.shape == vc.shape == (2, eng["kv_pages"], 4, page, 128)
    assert ic is None and ssm is None and window.shape == (7, 2, ns, 2048)
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode()
        kernels, paths = ["paged_decode", "grouped_matmul"], [
            "decode_pallas", "experts_grouped_pallas"]
    elif program == "prefill":
        lowered = cell.lower_prefill(2048, 0)
        kernels, paths = ["flash_fwd", "grouped_matmul"], [
            "fwd_pallas", "experts_grouped_pallas"]
    else:
        assert rung_rides(eng["max_seq"], ns, 1024) and cell.built.takes_riders
        lowered = cell.lower_prefill(1024, 0, *cell.riding())
        kernels, paths = ["flash_fwd", "grouped_matmul", "paged_decode"], [
            "fwd_pallas", "experts_grouped_pallas", "decode_pallas"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    assert ("paged_decode" in text) == (program != "prefill")
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    assert counts.get("experts_ragged_dot", 0) == before.get(
        "experts_ragged_dot", 0)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    stacks = [tuple(params[stack][w].shape) for stack in ("conv", "layers")
              for w in ("w_gate", "w_up", "w_down")]
    assert not moved_stacks(hlo, stacks)
    assert not copies_of(hlo, kc)
    assert len(copies_of(hlo, window)) <= (program == "riding")
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
    assert mem.temp_size_in_bytes < {"decode": 8 << 20, "prefill": 96 << 20,
                                     "riding": 48 << 20}[program]


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
    cell = described_cell(topo, monkeypatch, "sdar-30b-a3b-chat-serve")
    model, eng, built, params, caches = (cell.model, cell.eng, cell.built,
                                         cell.params, cell.caches)
    page, B = cell.page, model["block_length"]
    layers = model["num_hidden_layers"]
    assert built.block == B and not built.takes_riders and not built.adopts
    assert built.books(caches).dispatch(     # a block is two forwards
        [0], [False], B, [], False)["forwards"] == 2
    kc, vc = caches.kc, caches.vc
    assert kc.shape == vc.shape == (layers, eng["kv_pages"], 4, page, 128)
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode(2 * B)
        kernels, paths = ["paged_decode", "grouped_matmul"], [
            "block_decode_pallas", "experts_grouped_pallas"]
    else:
        lowered = cell.lower_prefill(1024, None)
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
    assert not moved_stacks(compiled.as_text(), stacks)
    mem = compiled.memory_analysis()
    held = 2 * kc.size * kc.dtype.itemsize
    assert held == 2 * layers * eng["kv_pages"] * 4 * page * 128 * 2
    assert mem.alias_size_in_bytes >= held
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 2 * cell.adapter.counts.total_params(model)
    print(program, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
    if program == "prefill":
        # a prefill yields no token: it computes no head, and never reads it
        head = params["lm_head"]
        weights -= head.size * head.dtype.itemsize
    assert 0 <= mem.argument_size_in_bytes - weights - held < 1 << 20
    assert mem.temp_size_in_bytes < ((512 << 20) if program == "decode"
                                     else (1 << 30))

"""The programs of the two configurations that hold a SHARE of their experts,
compiled for a described `v5e:2x2` at the cells' sizes
(tests/compile_for_v5e.py says why): MiMo-V2's mixed stack, both shares'
prefill over the expert stacks, and dots' riding rung against its arena of
latent rows."""

import pytest

from compile_for_v5e import (copies_of, described_cell, mixed_riding_rung,
                             moved_stacks)
from ray_tpu.ops import attention
from ray_tpu.serve.engine import rung_rides

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


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
    cell = described_cell(topo, monkeypatch, "mimo-v2-flash-serve")
    eng, caches, ns, page = cell.eng, cell.caches, cell.ns, cell.page
    kc, vc, _, state = caches
    assert kc.shape == (2, eng["kv_pages"], 4, page, 256)
    assert vc.shape == (2, eng["kv_pages"], 4, page, 128)
    assert [tuple(x.shape) for x in state] == [(5, ns, 8, 128, 256),
                                               (5, ns, 8, 128, 128)]
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode()
        kernels, paths = ["paged_decode"], ["decode_pallas",
                                            "window_decode_reference"]
    else:
        lowered = cell.lower_prefill(2048, 0)
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


@pytest.mark.timeout(300)
def test_mimos_widest_riding_prefill_steps_the_slots_in_both_caches_on_v5e(
        topo, monkeypatch):
    """The 8,192-wide prefill of MiMo-V2's mixed stack at the cell's sizes
    (32 slots, `max_seq` 8,192) as the riding rung's program, beside the same
    width's with nobody to take (`compile_for_v5e.mixed_riding_rung`): the
    riders' step (keys of 192 in 256 lanes, a sink, a ring of 128) adds 0.43
    GB of temporaries to the riderless program's 1.03, and arguments and
    temporaries stay inside the 12 GiB the cell's other programs are held
    to on a chip of 15.75."""
    was, mem = mixed_riding_rung(
        described_cell(topo, monkeypatch, "mimo-v2-flash-serve"), 8192)
    assert mem.temp_size_in_bytes <= was.temp_size_in_bytes + (512 << 20)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12 << 30


@pytest.mark.parametrize("config", ["dots.vlm1.inst-serve",
                                    "mimo-v2-flash-serve"])
def test_a_shares_prefill_reads_the_expert_stacks_where_they_lie_on_v5e(
        topo, config, monkeypatch):
    """The 2,048-wide prefill of the two configurations that hold a SHARE of
    their experts, at the cells' sizes: every sparse layer's three grouped
    matmuls are the Pallas kernel, handed the stacks of all layers, and the
    compiled program holds no copy or slice of a stack, of a layer of one or
    of an expert's matrix."""
    cell = described_cell(topo, monkeypatch, config)
    params = cell.params
    before = attention.attention_path_counts()
    lowered = cell.lower_prefill(2048, 0 if cell.built.by_slot else None)
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
    assert not moved_stacks(hlo, stacks)


@pytest.mark.timeout(240)
def test_dots_riding_prefill_steps_the_slots_in_the_latent_arena_in_place_on_v5e(
        topo, monkeypatch):
    """The 2,048-wide prefill of the latent stack at the cell's sizes
    (benchmark/configs/dots.vlm1.inst-serve.json: 32 slots, `max_seq` 4,096,
    so the rung rides), beside the same width's program with nobody to take:
    the riding one holds the `latent_decode` kernel once a segment (the dense
    layer's, the sparse layers' scan's), the 0.84 GB arena of latent rows
    rides both scans' carry through the riders' page writes and the kernel
    and still aliases the donated entry buffer, nothing arena- or
    layer-shaped is copied, no expert stack is copied or sliced (the riders'
    assignments go through the prompt's grouped matmuls), and the step's rows
    stay within 5% + 16 MiB of the riderless program's temporaries."""
    cell = described_cell(topo, monkeypatch, "dots.vlm1.inst-serve")
    kc = cell.caches.kc
    assert rung_rides(cell.eng["max_seq"], cell.ns, 2048)
    assert cell.built.takes_riders and cell.caches.vc is None
    assert kc.shape == (5, cell.eng["kv_pages"], cell.page, 640)
    before = attention.attention_path_counts().get("latent_decode_pallas", 0)

    def compiled(*more):
        lowered = cell.lower_prefill(2048, None, *more)
        return lowered.as_text(), lowered.compile()

    plain_text, plain = compiled(None, None, None)
    assert attention.attention_path_counts().get(
        "latent_decode_pallas", 0) == before
    text, riding = compiled(*cell.riding())
    assert attention.attention_path_counts()["latent_decode_pallas"] > before
    assert "latent_decode" in text and "latent_decode" not in plain_text
    assert "latent_flash_fwd" in text and "latent_flash_fwd" in plain_text
    hlo = riding.as_text()
    calls = [kind.count('custom_call_target="tpu_custom_call"')
             for kind in (plain.as_text(), hlo)]
    assert calls[1] == calls[0] + 2, calls
    assert not copies_of(hlo, kc)
    assert not moved_stacks(hlo, [
        tuple(cell.params["layers"][w].shape)
        for w in ("w_gate", "w_up", "w_down")])
    mem, was = riding.memory_analysis(), plain.memory_analysis()
    assert mem.alias_size_in_bytes >= kc.size * kc.dtype.itemsize
    assert mem.temp_size_in_bytes <= 1.05 * was.temp_size_in_bytes + (16 << 20)

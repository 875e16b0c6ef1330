"""Laguna-S-2.1's serving programs compiled for a described `v5e:2x2` at the
cell's sizes (tests/compile_for_v5e.py says why): a mixed stack whose kinds
of attention differ in their query heads, a window of four blocks, a key of
one tile, a gate a head, and a share of the experts beside a shared one."""

import pytest

from compile_for_v5e import copies_of, described_cell, mixed_riding_rung
from ray_tpu.ops import attention

pytestmark = pytest.mark.usefixtures("_no_compile_cache")

CONFIG = "laguna-s-2.1-serve"


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_laguna_programs_run_their_kernels_and_fit_on_v5e(
        topo, program, monkeypatch):
    """The decode chunk and the 2,048-bucket prefill at the cell's sizes
    (benchmark/configs/laguna-s-2.1-serve.json; the 8,192-wide prefill is a
    builder's compile, PERF.md section 4): the pages of the 2 full layers
    and the rings of 512 positions of the 6 window layers, keys and values
    128 wide, are donated and alias the outputs; decode's full layers run the
    `paged_decode` kernel at 48 heads on 8, prefill `window_blocks_fwd` at 72
    heads on 8 and `full_flash_fwd` on a key of one part, and no attention
    falls to XLA's reference; serving fits the chip beside the prefill's
    temporaries."""
    cell = described_cell(topo, monkeypatch, CONFIG)
    eng, caches, ns, page = cell.eng, cell.caches, cell.ns, cell.page
    kc, vc, _, state = caches
    assert kc.shape == vc.shape == (2, eng["kv_pages"], 8, page, 128)
    assert [tuple(x.shape) for x in state] == [(6, ns, 8, 512, 128)] * 2
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode()
        kernels, paths = ["paged_decode"], ["decode_pallas",
                                            "window_decode_reference"]
    else:
        lowered = cell.lower_prefill(2048, 0)
        kernels, paths = ["window_blocks_fwd", "full_flash_fwd"], [
            "window_fwd_pallas", "full_fwd_pallas"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    assert all(counts.get(p, 0) == before.get(p, 0)
               for p in ("window_fwd_reference", "full_fwd_reference",
                         "decode_reference"))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (kc, vc) + tuple(state))
    assert held == 2 * 2 * 4097 * 8 * 64 * 128 * 2 + 2 * 6 * 32 * 8 * 512 \
        * 128 * 2 == 2_550_661_120
    assert mem.alias_size_in_bytes >= held
    assert not copies_of(compiled.as_text(), kc, vc, *state)
    # the weights, 5.69 GB, and the caches are arguments; a prefill's
    # temporaries are its activations; together inside the chip's 15.75 GB
    assert mem.temp_size_in_bytes < ((64 << 20) if program == "decode"
                                     else (1 << 30))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11 << 30


@pytest.mark.timeout(300)
def test_lagunas_widest_riding_prefill_steps_the_slots_in_both_caches_on_v5e(
        topo, monkeypatch):
    """The 8,192-wide prefill at the cell's sizes (32 slots, `max_seq`
    8,192: the rung every prompt over 7,168 tokens lands in) as the riding
    rung's program, beside the same width's with nobody to take
    (`compile_for_v5e.mixed_riding_rung`): the riders' step costs no
    temporaries beyond the riderless program's (1.21 GB against 1.45: the
    gated transpose goes in bfloat16 once the riders' rows are put into the
    kernel's output), and arguments and temporaries stay inside the 11 GiB
    that leave the cell's pages (`kv_pages_peak_pct` 90.8) their room on a
    chip of 15.75."""
    was, mem = mixed_riding_rung(described_cell(topo, monkeypatch, CONFIG),
                                 8192)
    assert mem.temp_size_in_bytes <= was.temp_size_in_bytes + (64 << 20)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11 << 30

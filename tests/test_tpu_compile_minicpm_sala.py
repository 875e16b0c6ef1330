"""MiniCPM-SALA's serving programs compiled for a described `v5e:2x2` at the
cell's sizes (tests/compile_for_v5e.py says why): a stack of one block-sparse
layer and three linear ones, pages of one kv head a layer of the arena,
pooled keys and a linear state a slot, a prompt of 12,288 rows in one
program."""

import pytest

from compile_for_v5e import copies_of, described_cell
from ray_tpu.ops import attention

pytestmark = pytest.mark.usefixtures("_no_compile_cache")

CONFIG = "minicpm-sala-serve"
REFERENCES = ("linear_reference", "block_sparse_reference",
              "decode_reference", "fwd_reference")


@pytest.mark.timeout(480)
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_sala_programs_run_their_kernels_and_fit_on_v5e(topo, program,
                                                        monkeypatch):
    """The decode chunk and the 12,288-rung prefill at the cell's sizes
    (benchmark/configs/minicpm-sala-serve.json): the pages of the ONE sparse
    layer (2 kv heads, each a layer of the arena), its pooled keys and the
    three linear layers' state are donated and alias the outputs, and nothing
    shaped like the state or a layer of it is copied; decode runs
    `linear_step` and `paged_decode` (a kv head's selected pages by a table
    of its own), the prefill `linear_chunk` and `block_flash` under the mask
    by blocks; nothing falls to a reference path; serving fits the chip
    beside the prefill's temporaries."""
    cell = described_cell(topo, monkeypatch, CONFIG)
    eng, caches, ns, page = cell.eng, cell.caches, cell.ns, cell.page
    kc, vc, (pooled, sums), (state,) = caches
    assert (eng["max_seq"], ns, page, eng["kv_pages"]) == (12288, 32, 64, 6145)
    assert kc.shape == vc.shape == (2, 6145, 1, 64, 128)
    assert pooled.shape == (1, ns, 768, 256) and sums.shape == (1, ns, 2, 256)
    assert state.shape == (3, ns, 32, 128, 128) and state.dtype == "float32"
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode()
        kernels, paths = ["linear_step", "paged_decode"], [
            "linear_pallas", "decode_pallas"]
    else:
        lowered = cell.lower_prefill(12288, 0)
        kernels, paths = ["linear_chunk", "block_flash"], [
            "linear_pallas", "block_sparse_pallas"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    assert all(counts.get(p, 0) == before.get(p, 0) for p in REFERENCES)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in (kc, vc, pooled, sums, state))
    assert held == 2 * 2 * 6145 * 64 * 128 * 2 + 32 * 770 * 256 * 2 \
        + 32 * 2 * 256 * 2 + 3 * 32 * 32 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= held
    assert not copies_of(compiled.as_text(), state)
    # the weights, 3.42 GB, and the caches are arguments; a prefill's
    # temporaries are its activations; together inside the chip's 15.75 GB
    assert mem.temp_size_in_bytes < ((96 << 20) if program == "decode"
                                     else (3 << 30))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 9 << 30

"""Nemotron-3-Nano's stack of one-part layers, compiled for a described
`v5e:2x2` at the cell's sizes (tests/compile_for_v5e.py says why): the decode
chunk and the prefill buckets up to 2,048 that the mix lands in; the wider
ones, the same check, are tests/test_tpu_compile_nemotron_wide.py (a compile
is about 50 s of a loaded worker, and six are more than a file may take). A
bucket from 2,048 up is a RIDING rung's (`engine.rung_rides`), lowered as the
engine calls it, the 32 slots' decode step in its tail rows (PR 58)."""

import jax
import jax.numpy as jnp
import pytest

from compile_for_v5e import copies_of, described_cell, moved_stacks
from ray_tpu.serve.engine import rung_rides
from ray_tpu.ops import attention

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


def nemotron_program_keeps_pages_and_state_in_place(topo, program,
                                                      monkeypatch):
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
    row. A riding rung's prefill also holds the slots' step in its last 32
    rows: `paged_decode` on their pages and the SAME `ssd_state_step` kernel
    on the whole state where it lies in the segments' carry, and neither the
    arena nor the state is copied."""
    cell = described_cell(topo, monkeypatch, "nemotron-3-nano-30b-a3b-serve")
    eng, params, caches, ns, page = (cell.eng, cell.params, cell.caches,
                                      cell.ns, cell.page)
    kc, vc, ic, (ssm, window) = caches
    assert kc.shape == vc.shape == (2, eng["kv_pages"], 2, page, 128)
    assert ic is None and ssm.shape == (7, ns, 128, 4096) \
        and ssm.dtype == jnp.float32 and window.shape == (7, 3, ns, 6144)
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode()
        kernels, paths = ["paged_decode", "grouped_matmul", "local_combine",
                          "ssd_state_step"], [
            "decode_pallas", "experts_grouped_pallas", "share_combine_local",
            "ssd_step_pallas"]
    else:
        width = int(program[7:])
        rides = rung_rides(eng["max_seq"], ns, width)
        assert rides is (width >= 2048) and cell.built.takes_riders
        lowered = cell.lower_prefill(width, 0,
                                     *(cell.riding() if rides else ()))
        kernels, paths = ["flash_fwd", "grouped_matmul", "local_combine"], [
            "fwd_pallas", "experts_grouped_pallas", "share_combine_local",
            "ssd_chunked"]
        if rides:
            kernels += ["paged_decode", "ssd_state_step"]
            paths += ["decode_pallas", "ssd_step_pallas"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    assert counts.get("experts_ragged_dot", 0) == before.get(
        "experts_ragged_dot", 0)
    compiled = lowered.compile()
    stacks = [tuple(params["experts"][w].shape) for w in ("w_up", "w_down")]
    assert not moved_stacks(compiled.as_text(), stacks)
    assert not copies_of(compiled.as_text(), kc, ssm)
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


@pytest.mark.timeout(300)
@pytest.mark.parametrize("program", ["decode", "prefill1024", "prefill2048"])
def test_nemotron_programs_keep_pages_and_state_in_place_on_v5e(
        topo, program, monkeypatch):
    nemotron_program_keeps_pages_and_state_in_place(topo, program,
                                                     monkeypatch)

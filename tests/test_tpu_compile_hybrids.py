"""The state-space hybrids' serving programs, compiled for a described
`v5e:2x2` at the cells' sizes (tests/compile_for_v5e.py says why): Jamba's
selective scan beside attention, Granite's Mamba-2 over a share of the
experts. Each cell's prefill is a RIDING rung's (`engine.rung_rides`), lowered
as the engine calls it, the live slots' decode step in its tail rows (PR
58)."""

import jax
import jax.numpy as jnp
import pytest

from compile_for_v5e import copies_of, described_cell, moved_stacks
from ray_tpu.serve.engine import rung_rides
from ray_tpu.ops import attention

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


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
    layers' by a constant one). The prefill is the riding rung's: the 16
    slots' step in its last 16 rows (`paged_decode` beside `flash_fwd`, the
    slots' state read and written where it lies in the segments' carry), and
    neither the arena nor the state is copied (the WINDOWS are, once a
    program, 12.8 MB: XLA lays them out anew for the prompt's `write_state`,
    which reads one slot's rows of every layer)."""
    cell = described_cell(topo, monkeypatch, "jamba2-3b-serve")
    eng, caches, ns, page = cell.eng, cell.caches, cell.ns, cell.page
    assert "lm_head" not in cell.params
    kc, vc, _, state = caches
    assert kc.shape == (2, eng["kv_pages"], 1, page, 128)
    assert [tuple(x.shape) for x in state] == [(26, ns, 16, 5120),
                                               (26, 3, ns, 5120)]
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode()
        kernel, path = "paged_decode", "decode_pallas"
    else:
        assert rung_rides(eng["max_seq"], ns, 4096) and cell.built.takes_riders
        lowered = cell.lower_prefill(4096, 0, *cell.riding())
        kernel, path = "selective_scan", "scan_pallas"
    text = lowered.as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert "paged_decode" in text
    counts = attention.attention_path_counts()
    assert counts[path] > before.get(path, 0)
    assert counts["decode_pallas"] > before.get("decode_pallas", 0)
    compiled = lowered.compile()
    assert not copies_of(compiled.as_text(), kc, state[0])
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (kc, vc) + tuple(state))
    assert mem.alias_size_in_bytes >= held
    # A layer's weights set aside would be 0.2 GB (a Mamba layer), a
    # segment's 1.4; the prefill's own temporaries are its activations (146
    # MB with nobody to take, 234 MB with the riders' rows selected into the
    # convolution's and the scan's outputs).
    assert mem.temp_size_in_bytes < ((16 << 20) if program == "decode"
                                     else (256 << 20))


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
    and the bytes are PERF.md section 4's row. The 1,024-row prefill is a
    riding rung's (`max_seq` 2,048): the 64 slots' step in its last 64 rows,
    their state through the SAME `ssd_state_step` kernel on the whole state
    in the segments' carry, their pages through `paged_decode`; no copy of
    the arena or of the 2.4 GB state."""
    cell = described_cell(topo, monkeypatch, "granite-4.0-h-small-serve")
    eng, params, caches, ns, page = (cell.eng, cell.params, cell.caches,
                                      cell.ns, cell.page)
    kc, vc, ic, (ssm, window) = caches
    assert kc.shape == vc.shape == (1, eng["kv_pages"], 8, page, 128)
    assert ic is None and ssm.shape == (9, ns, 128, 8192) \
        and ssm.dtype == jnp.float32 and window.shape == (9, 3, ns, 8448)
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode()
        kernels, paths = ["paged_decode", "grouped_matmul", "local_combine",
                          "ssd_state_step"], [
            "decode_pallas", "experts_grouped_pallas", "share_combine_local",
            "ssd_step_pallas"]
    else:
        assert rung_rides(eng["max_seq"], ns, 1024) and cell.built.takes_riders
        lowered = cell.lower_prefill(1024, 0, *cell.riding())
        kernels, paths = ["flash_fwd", "grouped_matmul", "local_combine",
                          "paged_decode", "ssd_state_step"], [
            "fwd_pallas", "experts_grouped_pallas", "share_combine_local",
            "ssd_chunked", "decode_pallas", "ssd_step_pallas"]
    text = lowered.as_text()
    assert all(k in text for k in kernels)
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0) for p in paths)
    assert counts.get("experts_ragged_dot", 0) == before.get(
        "experts_ragged_dot", 0)
    compiled = lowered.compile()
    stacks = [tuple(params[stack][w].shape) for stack in ("mamba", "layers")
              for w in ("w_gate", "w_up", "w_down")]
    assert not moved_stacks(compiled.as_text(), stacks)
    assert not copies_of(compiled.as_text(), kc, ssm)
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

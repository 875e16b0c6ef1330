"""Brumby-14B-Base's stack of power-retention layers, compiled for a
described `v5e:2x2` at the cell's sizes (tests/compile_for_v5e.py says why):
the decode chunk and one prefill bucket; and the two kernels alone."""

import jax
import jax.numpy as jnp
import pytest

from compile_for_v5e import copies_of, described_cell, results, shapes_on
from ray_tpu.ops import attention, retention

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


@pytest.mark.timeout(400)
@pytest.mark.parametrize("program", ["decode", "prefill1024"])
def test_brumby_programs_keep_the_state_in_place_on_v5e(topo, program,
                                                        monkeypatch):
    """The stack at the cell's sizes
    (benchmark/configs/brumby-14b-base-serve.json): NO arena (nothing is
    paged: `kc` and `vc` are None), the state of 4 layers x 48 slots, 6.60 GB
    float32 in the program's layout of 65 blocks of 128 lanes, which rides
    the decode loop's carry, is donated and aliases the output with no copy
    of it or of a layer of it. Decode's operator is the kernel
    `retention_state_step` handed the whole state; a prompt's final state the
    kernel `retention_state`; no attention kernel is in either program. The
    expansion `phi` is never an array in HBM: no instruction of the compiled
    program has a result with an axis of 65 blocks (or of 8,256 or 8,320
    rows) but the state's own tiles."""
    cell = described_cell(topo, monkeypatch, "brumby-14b-base-serve")
    params, caches, ns = cell.params, cell.caches, cell.ns
    kc, vc, ic, (S, z) = caches
    assert kc is None and vc is None and ic is None
    assert S.shape == (4, ns, 8, 65, 128, 128) and S.dtype == jnp.float32
    assert z.shape == (4, ns, 8, 72, 128)
    assert not cell.built.paged and not cell.built.takes_riders \
        and not cell.built.adopts and cell.built.by_slot
    before = dict(attention.attention_path_counts())
    if program == "decode":
        lowered = cell.lower_decode()
        kernel, path = "retention_state_step", "retention_step_pallas"
    else:
        lowered = cell.lower_prefill(int(program[7:]), 0)
        kernel, path = "retention_state", "retention_state_pallas"
    text = lowered.as_text()
    assert kernel in text
    assert not any(k in text for k in ("paged_decode", "flash_fwd"))
    counts = attention.attention_path_counts()
    assert counts[path] > before.get(path, 0)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert not copies_of(hlo, S, z)
    state_tiles = {S.shape[i:] for i in range(4)} | {
        z.shape[i:] for i in range(4)}
    wide = [(name, shape, op) for name, shape, op in results(hlo)
            if {65, 8256, 8320} & set(shape) and shape not in state_tiles
            and shape[-3:] != (65, 128, 128)]
    assert not wide, wide[:5]
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in (S, z))
    assert held == 4 * ns * 8 * (65 * 128 + 72) * 128 * 4 == 6_599_737_344
    assert mem.alias_size_in_bytes >= held
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    # (`bg`, 8 constants a layer in float32, is no weight of the count)
    assert weights == 2 * 2_877_241_344 + 4 * 8 * 4
    assert 0 <= mem.argument_size_in_bytes - weights - held < 1 << 20
    print(program, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
    # beside 12.35 GB of arguments, inside the chip's 15.75 GiB
    assert mem.temp_size_in_bytes < ((64 << 20) if program == "decode"
                                     else (2 << 30))


@pytest.mark.parametrize("kernel", ["step", "state2048"])
def test_retention_kernels_compile_for_v5e(topo, kernel):
    """The two kernels alone at the cell's shapes: the step on the whole
    state of 48 slots, the state's build at the widest bucket."""
    sds = shapes_on(topo.devices[0])
    L, ns, KVH, H, d = 4, 48, 8, 40, 128
    Ss, zs = retention.state_shapes(L, ns, KVH, d)
    bf, f32 = jnp.bfloat16, jnp.float32
    if kernel == "step":
        fn = jax.jit(lambda S, z, l, a, q, k, v, g: retention._step_pallas(
            S, z, l, a, q, k, v, g, interpret=False),
            donate_argnums=(0, 1))
        lowered = fn.lower(
            sds(Ss, f32), sds(zs, f32), sds((), jnp.int32),
            sds((ns,), jnp.bool_), sds((ns, H, d), bf), sds((ns, KVH, d), bf),
            sds((ns, KVH, d), bf), sds((ns, KVH), f32))
    else:
        W = int(kernel[5:])
        fn = jax.jit(lambda k, v, w: retention._state_pallas(
            k, v, w, interpret=False))
        lowered = fn.lower(sds((KVH, W, d), bf), sds((KVH, W, d), bf),
                           sds((KVH, W), f32))
    assert "tpu_custom_call" in lowered.as_text()
    assert lowered.compile().memory_analysis().temp_size_in_bytes >= 0

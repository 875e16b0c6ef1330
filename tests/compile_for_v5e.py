"""What the tests/test_tpu_compile_*.py files share: a cell's serving programs
laid out for a TPU that is described, not attached.

The TPU compiler is installed wherever libtpu is, so the kernels of the
main path are compiled here for `v5e:2x2` at their real shapes: what the
chip's compiler would refuse (a slice off the tiling, too much VMEM, a
Mosaic call GSPMD cannot partition) fails in tier-1 and costs no chip
time. A compile is not a run — tests/test_ops.py checks values (on the
reference path) and chip_smoke.py checks them on the chip.

The fixtures `topo` and `_no_compile_cache` are tests/conftest.py's. A file
of this family is a unit of scheduling (`--dist loadfile` keeps it on one
worker): it stays under ~230 s alone, so a new model's programs go into the
file of their family only while that holds, and into a new file otherwise.
Each worker that is handed such a file loads libtpu to describe the chip, so
several do at once: the driver's command sets `ALLOW_MULTIPLE_LIBTPU_LOAD=1`
(`/root/TESTS_LAST_RUN.json`); under xdist without it, all but the first
worker's files SKIP (`topo` cannot take libtpu's lock), which a run's count of
skips shows (one, `tests/test_native_store.py`'s, is the tree's).
"""

import dataclasses
import json
import os
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import attention

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "benchmark", "configs")


def shapes_on(device):
    """`sds(shape, dtype)`: a ShapeDtypeStruct on that one chip."""
    one_chip = SingleDeviceSharding(device)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    return sds


@dataclasses.dataclass
class Described:
    """A cell's configuration, its programs at the cell's engine sizes and
    the shapes of what they take, on the first chip of the topology."""
    model: dict
    eng: dict
    adapter: Any
    cfg: Any
    built: Any
    params: Any
    caches: Any
    sds: Callable

    @property
    def ns(self):
        return self.eng["n_slots"]

    @property
    def page(self):
        return self.eng["page_size"]

    @property
    def maxp(self):
        return self.eng["max_seq"] // self.page

    def lower_decode(self, last_width=None):
        """The decode chunk, lowered; `last_width` where a slot's last
        tokens are a row of that many (a block's) and not one."""
        ns, sds = self.ns, self.sds
        last = (ns,) if last_width is None else (ns, last_width)
        return self.built.decode.lower(
            self.params, self.caches, sds((ns, self.maxp), jnp.int32),
            sds(last, jnp.int32), sds((ns,), jnp.int32), sds((ns,), jnp.bool_),
            sds((ns,), jnp.float32), sds((ns,), jnp.int32),
            sds((ns, 2), jnp.uint32))

    def riding(self):
        """A riding rung's last three arguments as `Engine._place` passes
        them: the slots' `last` and `pos`, and the riders (the block table,
        who rides, the slots' temperatures, top-ks and keys)."""
        ns, sds = self.ns, self.sds
        slots = sds((ns,), jnp.int32)
        return slots, slots, (
            sds((ns, self.maxp), jnp.int32), sds((ns,), jnp.bool_),
            sds((ns,), jnp.float32), slots, sds((ns, 2), jnp.uint32))

    def lower_prefill(self, width, slot, *riding):
        """The prefill of one prompt `width` wide into `slot` (None where
        the model keeps nothing by slot), lowered; `riding`: `self.riding()`
        for a riding rung's program as the engine calls it."""
        sds = self.sds
        return self.built.prefill.lower(
            self.params, self.caches, sds((self.maxp,), jnp.int32),
            sds((1, width), jnp.int32), 1, 0.0, 0, sds((2,), jnp.uint32),
            slot, *riding)


def mixed_riding_rung(cell, width):
    """A mixed stack's prefill of `width` rows with nobody to take and as the
    riding rung's program, both compiled -> (memory of the first, of the
    second): the riding one holds the `paged_decode` kernel once a stack of
    full layers (the dense layer's, the sparse ones' scan's) more, the pages
    and the rings ride the scans' carry through the riders' writes and reads
    and still alias the donated entry buffers, and nothing shaped like a
    cache or a layer of one is copied (a prompt's ring write after riders is
    an update in place: `slot_state.write_window_prompt(in_bounds=True)`)."""
    from ray_tpu.serve.engine import rung_rides
    assert rung_rides(cell.eng["max_seq"], cell.ns, width)
    assert cell.built.takes_riders
    kc, vc, _, state = cell.caches
    before = attention.attention_path_counts()

    def compiled(*more):
        lowered = cell.lower_prefill(width, 0, *more)
        return lowered.as_text(), lowered.compile()

    plain_text, plain = compiled(None, None, None)
    counts = attention.attention_path_counts()
    assert all(counts.get(p, 0) == before.get(p, 0)
               for p in ("decode_pallas", "window_decode_reference"))
    text, riding = compiled(*cell.riding())
    counts = attention.attention_path_counts()
    assert all(counts[p] > before.get(p, 0)
               for p in ("decode_pallas", "window_decode_reference"))
    assert "paged_decode" in text and "paged_decode" not in plain_text
    hlo = riding.as_text()
    calls = [kind.count('custom_call_target="tpu_custom_call"')
             for kind in (plain.as_text(), hlo)]
    assert calls[1] == calls[0] + 2, calls
    assert not copies_of(hlo, kc, vc, *state)
    mem, was = riding.memory_analysis(), plain.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in (kc, vc) + tuple(state))
    return was, mem


def described_cell(topo, monkeypatch, config, layers=None, init=None):
    """`benchmark/configs/<config>.json` (cut to `layers` layers where given)
    as the benchmark builds it: the adapter's config at the cell's `max_seq`,
    `build_programs` at the cell's engine sizes, the fused parameters
    (`init(adapter, cfg)` where given, `llama.init_params` otherwise: shapes
    alone, nothing is allocated) and the empty caches."""
    from benchmark import models
    from ray_tpu.models.block import fuse_qkv
    from ray_tpu.models.llama import init_params
    from ray_tpu.models.serving import build_programs

    # The engine asks jax.devices() which attention path to take and sees
    # this sandbox's CPU, so the test, not the program, steers it.
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        model = json.load(f)
    if layers is not None:
        model["num_hidden_layers"] = layers
    eng = model["deployment"]["engine"]
    adapter = models.adapter(model["arch"])
    cfg = adapter.build_config(model, model["dtypes"], eng["max_seq"])
    if init is None:
        def init(adapter, cfg):
            return init_params(cfg, jax.random.PRNGKey(0))
    sds = shapes_on(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    built = build_programs(cfg, eng["n_slots"], eng["decode_chunk"],
                           eng["page_size"], eng["kv_pages"])
    return Described(
        model, eng, adapter, cfg, built,
        shaped(jax.eval_shape(lambda: fuse_qkv(init(adapter, cfg), cfg))),
        shaped(jax.eval_shape(built.empty)), sds)


def results(hlo):
    """(name, shape, op) of every instruction of a compiled program's text
    whose result is one array."""
    return [(name, tuple(int(d) for d in dims.split(",")), op)
            for name, dims, op in re.findall(
                r"%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(", hlo)]


def copies_of(hlo, *arrays):
    """Names of the compiled program's `copy` instructions whose result has
    the shape of one of `arrays` or of one layer of it: a cache that rides a
    loop's carry and aliases the donated buffers has none."""
    shapes = {tuple(a.shape[i:]) for a in arrays for i in (0, 1)}
    return [name for name, shape, op in results(hlo)
            if op == "copy" and shape in shapes]


def moved_stacks(hlo, stacks):
    """Names of the compiled program's instructions whose result has the
    shape of an expert stack, of one layer of one or of one expert's matrix
    and is a copy, a slice or an update-slice, bare or fused by name (the
    decode program's test has the pattern): a kernel handed one layer of a
    stack is first given a copy of it (PERF.md, PR 27)."""
    shapes = set()
    for s in stacks:
        shapes |= {s, s[1:], s[2:], (s[0] * s[1],) + s[2:]}
    return [name for name, shape, op in results(hlo) if shape in shapes
            and (op == "copy" or "dynamic-" in op + name
                 or "slice" in op + name)]

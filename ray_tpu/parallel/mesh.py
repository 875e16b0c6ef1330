"""Device-mesh construction for ray_tpu.

TPU-first replacement for the reference's process-group world (torch DDP/NCCL
groups created by Ray Train, reference: python/ray/train/torch/config.py and
python/ray/util/collective/collective.py:166). Instead of rank-indexed process
groups, parallelism is expressed as named axes of a `jax.sharding.Mesh`;
XLA/GSPMD inserts the collectives over ICI/DCN.

Axis vocabulary (all six are always present; unused axes have size 1):

  pp   pipeline parallel — p2p activation transfer, lowest bandwidth need,
       outermost (maps to DCN across slices in multi-slice deployments)
  dp   pure data parallel — gradient allreduce per step
  fsdp sharded data parallel (ZeRO-3/GSPMD param sharding) — allgather/reducescatter
  ep   expert parallel — all-to-all dispatch for MoE layers
  sp   sequence/context parallel — ring attention K/V rotation (ppermute)
  tp   tensor parallel — per-layer allreduce, highest bandwidth, innermost so it
       lands on the tightest ICI ring
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXIS_NAMES = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# Axes over which the global batch is split.
BATCH_AXES = ("dp", "fsdp")
# Axes over which model parameters are sharded (fsdp dimension-sharding + tp).
PARAM_AXES = ("fsdp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    pp: int = 1
    dp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    # Cross-slice (DCN) factors: how much of pp/dp/fsdp spans SLICES
    # rather than ICI (SURVEY §5.8; the scaling-book recipe: only the
    # lowest-bandwidth axes — dp, fsdp-reduce, pp activations — may ride
    # DCN; tp/sp/ep stay strictly intra-slice, enforced by construction
    # since they have no DCN factor). The slice-crossing factor of each
    # axis is OUTERMOST within that axis, so GSPMD's per-axis collectives
    # decompose into intra-slice ICI ops + a small cross-slice phase.
    dcn_pp: int = 1
    dcn_dp: int = 1
    dcn_fsdp: int = 1

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.pp, self.dp, self.fsdp, self.ep, self.sp, self.tp)

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    @property
    def num_slices(self) -> int:
        return self.dcn_pp * self.dcn_dp * self.dcn_fsdp

    @property
    def dcn_shape(self) -> tuple[int, ...]:
        return (self.dcn_pp, self.dcn_dp, self.dcn_fsdp, 1, 1, 1)

    @property
    def ici_shape(self) -> tuple[int, ...]:
        """Per-slice factor of each axis."""
        out = []
        for name, total, dcn in zip(AXIS_NAMES, self.shape, self.dcn_shape):
            if total % dcn:
                raise ValueError(
                    f"axis {name}={total} not divisible by its DCN factor "
                    f"{dcn} (the slice-crossing factor must divide the "
                    f"axis)")
            out.append(total // dcn)
        return tuple(out)

    def with_axes(self, **kw) -> "MeshConfig":
        return dataclasses.replace(self, **kw)

    @staticmethod
    def for_devices(n: int) -> "MeshConfig":
        """Reasonable default factorization: all-FSDP (ZeRO-style) over n chips."""
        return MeshConfig(fsdp=n)


def _slice_groups(devices: list, num_slices: int,
                  per: Optional[int] = None) -> list:
    """Partition devices into per-slice groups. Real multi-slice TPUs
    expose `device.slice_index`; virtual/CPU meshes fall back to
    contiguous equal chunks (the driver's 2-virtual-slice dry run).

    `per` (group size) defaults to len(devices)//num_slices; pass it
    explicitly when `devices` is a superset to draw from (so a mesh
    needing 6 of each 8-device physical slice isn't rejected by a
    pre-truncated list)."""
    if per is None:
        if len(devices) % num_slices:
            raise ValueError(f"{len(devices)} devices do not split into "
                             f"{num_slices} equal slices")
        per = len(devices) // num_slices
    if per < 1 or len(devices) < num_slices * per:
        raise ValueError(f"need {num_slices} slices of {per} devices, "
                         f"have {len(devices)} devices")
    by_slice: dict = {}
    n_with = sum(1 for d in devices
                 if getattr(d, "slice_index", None) is not None)
    if n_with and n_with != len(devices):
        raise ValueError(
            f"mixed device list: {n_with}/{len(devices)} devices report a "
            f"slice_index — cannot infer slice topology")
    if n_with:
        for d in devices:
            by_slice.setdefault(d.slice_index, []).append(d)
    if by_slice:
        # Real slice topology present: no group may STRADDLE a physical
        # slice boundary — a straddling "ICI" submesh is a topology lie.
        # Subdividing is fine: one physical slice with >= k*per devices
        # yields k virtual slices (this is how the driver's
        # jax.distributed multi-process CPU dryrun presents itself —
        # every device reports slice_index=0). Two separate concerns:
        #  SELECT round-robin across physical slices (depth-first would
        #  pack every virtual slice into the lowest-indexed physical
        #  slice and leave the others' devices out of the mesh);
        #  ORDER the selection physical-slice-major, so the OUTERMOST
        #  nontrivial DCN axis (np.unravel_index varies the last
        #  coordinate fastest) is the one that truly crosses physical
        #  slices — matching the axis doc above: pp outermost on DCN.
        per_slice_groups = []  # [(phys_key, [groups...])] in index order
        for k in sorted(by_slice):
            ds = by_slice[k]
            per_slice_groups.append(
                (k, [ds[i * per:(i + 1) * per]
                     for i in range(len(ds) // per)]))
        selected: list = []  # (phys_order, depth, group)
        depth = 0
        while len(selected) < num_slices:
            layer = [(order, depth, gs[depth])
                     for order, (_, gs) in enumerate(per_slice_groups)
                     if depth < len(gs)]
            if not layer:
                raise ValueError(
                    f"cannot form {num_slices} slices of {per} devices "
                    f"from physical slices "
                    f"{ {k: len(v) for k, v in by_slice.items()} } "
                    f"without straddling a slice boundary — pick DCN "
                    f"factors matching the real slice topology")
            selected.extend(layer)
            depth += 1
        selected = selected[:num_slices]
        selected.sort(key=lambda t: (t[0], t[1]))
        return [g for _, _, g in selected]
    # No slice identity (CPU / virtual mesh): contiguous equal chunks.
    return [devices[i * per:(i + 1) * per] for i in range(num_slices)]


def _merge_hybrid(groups: list, config: "MeshConfig") -> Mesh:
    """Compose per-slice ICI submeshes into the hybrid mesh: each axis's
    slice-crossing (DCN) factor is OUTERMOST within the axis — the layout
    mesh_utils.create_hybrid_device_mesh produces, built manually so
    virtual CPU slices work identically for the multi-chip dry run."""
    ici_shape = config.ici_shape
    dcn_shape = config.dcn_shape
    slice_arrays = [_ici_mesh(ici_shape, g) for g in groups]
    arr = np.empty(dcn_shape + ici_shape, dtype=object)
    for si, sa in enumerate(slice_arrays):
        arr[np.unravel_index(si, dcn_shape)] = sa
    # Interleave (dcn_0, ici_0, dcn_1, ici_1, ...) then merge each pair:
    # axis k of the final mesh = dcn_k (outer) x ici_k (inner).
    k = len(AXIS_NAMES)
    arr = arr.transpose([ax for i in range(k) for ax in (i, k + i)])
    return Mesh(arr.reshape(config.shape), AXIS_NAMES)


def _ici_mesh(shape: tuple, devices: list) -> np.ndarray:
    """Device array for one slice. create_device_mesh lays the axes onto
    the physical torus for devices that have coordinates and is a plain
    reshape for those that do not (CPU), so it is never second-guessed:
    a topology it cannot map raises, instead of silently becoming a
    naive reshape that puts the wrong collectives on the wrong links."""
    return mesh_utils.create_device_mesh(
        shape, devices=devices, allow_split_physical_axes=True)


def _select_single_slice(devices: list, n: int) -> list:
    """Pick n devices for a single-slice (all-ICI) mesh. When the devices
    carry real slice topology, prefer a single physical slice — a
    truncation that straddles slices would label DCN hops as ICI. If no
    one slice holds n devices, the mesh genuinely spans slices: warn
    (collectives on every axis will ride DCN; set dcn_* factors to split
    the low-bandwidth axes deliberately) and fall back to the first n."""
    if getattr(devices[0], "slice_index", None) is None:
        return devices[:n]
    by_slice: dict = {}
    for d in devices:
        si = getattr(d, "slice_index", None)
        if si is None:
            return devices[:n]  # mixed: no usable topology signal
        by_slice.setdefault(si, []).append(d)
    for k in sorted(by_slice):
        if len(by_slice[k]) >= n:
            return by_slice[k][:n]
    from ray_tpu.utils import get_logger
    get_logger("mesh").warning(
        "single-slice mesh of %d devices spans %d physical slices — every "
        "axis's collectives will cross DCN; set MeshConfig dcn_* factors "
        "to place only low-bandwidth axes (dp/fsdp/pp) across slices",
        n, len(by_slice))
    return devices[:n]


def build_mesh(config: MeshConfig,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    n = config.num_devices
    if n > len(devices):
        raise ValueError(
            f"MeshConfig {config} needs {n} devices but only {len(devices)} available")
    devices = list(devices)
    if n < len(devices) and devices[0].platform != "cpu":
        from ray_tpu.utils import get_logger
        get_logger("mesh").warning(
            "MeshConfig %s uses %d of the %d %s devices this process "
            "holds; the rest stay idle", config, n, len(devices),
            devices[0].platform)
    if config.num_slices == 1:
        devices = _select_single_slice(devices, n)
        return Mesh(_ici_mesh(config.shape, devices), AXIS_NAMES)

    # Multi-slice (DCN) mesh. Validate axis/DCN divisibility up front
    # (ici_shape raises the precise error; per = prod(ici_shape) >= 1
    # follows), then group from the FULL device list (not a [:n]
    # truncation) so a mesh needing, say, 6 devices from each of two
    # 8-device physical slices is satisfiable.
    per = math.prod(config.ici_shape)
    groups = _slice_groups(devices, config.num_slices, per=per)
    return _merge_hybrid(groups, config)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.devices()[0]
    return Mesh(np.array([device]).reshape((1,) * len(AXIS_NAMES)), AXIS_NAMES)

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import (AXIS_NAMES, MeshConfig, build_mesh, spec_for,
                              tree_specs)
from ray_tpu.parallel.sharding import DEFAULT_RULES
from ray_tpu.utils.config import GlobalConfig


def test_mesh_axis_names(devices8):
    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    assert mesh.axis_names == AXIS_NAMES
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4


def test_mesh_too_many_devices(devices8):
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(dp=16))


def test_for_devices_default():
    cfg = MeshConfig.for_devices(8)
    assert cfg.num_devices == 8 and cfg.fsdp == 8


def test_spec_for_rules():
    assert spec_for(("embed", "heads")) == P("fsdp", "tp")
    assert spec_for((None, "expert")) == P(None, "ep")
    assert spec_for(("layers", "embed")) == P(None, "fsdp")


def test_tree_specs():
    tree = {"a": ("embed", "mlp"), "b": {"c": ("vocab", "embed")}}
    specs = tree_specs(tree)
    assert specs["a"] == P("fsdp", "tp")
    assert specs["b"]["c"] == P("tp", "fsdp")


def test_hybrid_mesh_slice_layout(devices8):
    """Multi-slice mesh: the DCN factor of dp is OUTERMOST within the dp
    axis, and each slice's devices stay contiguous within their dp block
    (tp never crosses a slice) — SURVEY §5.8 layout."""
    mesh = build_mesh(MeshConfig(dp=4, tp=2, dcn_dp=2))
    assert mesh.axis_names == AXIS_NAMES
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    devs = jax.devices()[:8]
    arr = mesh.devices  # shape (1, 4, 1, 1, 1, 2)
    # dp rows 0-1 hold virtual slice 0 (devices 0-3); rows 2-3 slice 1.
    assert set(arr[0, :2, 0, 0, 0, :].flat) == set(devs[:4])
    assert set(arr[0, 2:, 0, 0, 0, :].flat) == set(devs[4:])
    # Every tp row lies entirely inside one slice.
    for dp_i in range(4):
        row = set(arr[0, dp_i, 0, 0, 0, :].flat)
        assert row <= set(devs[:4]) or row <= set(devs[4:])


def test_hybrid_mesh_spmd_parity(devices8):
    """A dp-over-DCN mesh computes the same result as the flat mesh
    (GSPMD lowers the same program; only collective decomposition
    differs)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    def f(x):
        return jax.lax.psum(jnp.sum(x, axis=tuple(range(1, x.ndim))),
                            axis_name="dp")

    x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
    outs = []
    for cfg in (MeshConfig(dp=4, tp=2), MeshConfig(dp=4, tp=2, dcn_dp=2)):
        mesh = build_mesh(cfg)
        xs = jax.device_put(x, NamedSharding(mesh, P("dp")))
        y = jax.jit(jax.shard_map(f, mesh=mesh,
                                  in_specs=P("dp"), out_specs=P()))(xs)
        outs.append(np.asarray(y))
    np.testing.assert_allclose(outs[0], outs[1])


def test_hybrid_mesh_validation(devices8):
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(dp=3, dcn_dp=2))  # 3 % 2 != 0
    cfg = MeshConfig(dp=4, tp=2, dcn_dp=2)
    assert cfg.num_slices == 2
    assert cfg.ici_shape == (1, 2, 1, 1, 1, 2)


class _FakeDev:
    """Stand-in for a TPU device with a slice_index (CPU devices in the
    single-process fixture have none, so the by_slice path was untested
    before round 5 — the round-4 review's weak point 2)."""

    # No coordinates: create_device_mesh lays these out like CPU devices.
    platform = "cpu"
    device_kind = "cpu"

    def __init__(self, i, slice_index):
        self.id = i
        self.slice_index = slice_index

    def __repr__(self):
        return f"FakeDev({self.id}, slice={self.slice_index})"


def test_slice_groups_subdivides_single_physical_slice():
    """The driver's jax.distributed multi-process CPU dryrun presents ALL
    devices with slice_index=0; one physical slice must subdivide into
    virtual slices (refuse only straddling)."""
    from ray_tpu.parallel.mesh import _slice_groups

    devs = [_FakeDev(i, 0) for i in range(8)]
    groups = _slice_groups(devs, 2)
    assert len(groups) == 2
    assert [d.id for d in groups[0]] == [0, 1, 2, 3]
    assert [d.id for d in groups[1]] == [4, 5, 6, 7]


def test_slice_groups_real_multislice():
    from ray_tpu.parallel.mesh import _slice_groups

    devs = [_FakeDev(i, i // 4) for i in range(8)]
    groups = _slice_groups(devs, 2)
    assert {d.slice_index for d in groups[0]} == {0}
    assert {d.slice_index for d in groups[1]} == {1}


def test_slice_groups_refuses_straddling():
    """3 physical slices of 2 devices cannot form 2 groups of 3 without a
    group straddling a slice boundary."""
    from ray_tpu.parallel.mesh import _slice_groups

    devs = [_FakeDev(i, i // 2) for i in range(6)]
    with pytest.raises(ValueError, match="straddl"):
        _slice_groups(devs, 2)


def test_slice_groups_subdivide_plus_whole():
    """One big slice (4 devs) + one exact slice (2 devs) -> 3 groups of 2:
    two carved from slice 0, one whole slice 1."""
    from ray_tpu.parallel.mesh import _slice_groups

    devs = [_FakeDev(i, 0) for i in range(4)] + \
           [_FakeDev(i, 1) for i in range(4, 6)]
    groups = _slice_groups(devs, 3)
    # Selection is round-robin (both physical slices used); final order
    # is physical-slice-major.
    assert [[d.id for d in g] for g in groups] == [[0, 1], [2, 3], [4, 5]]
    for g in groups:
        assert len({d.slice_index for d in g}) == 1


def test_build_mesh_with_slice_index_devices():
    """END-TO-END hybrid build over slice_index-bearing devices (the path
    the dryrun exercises: every jax.distributed CPU device reports slice
    0). Mesh accepts the fake device objects, so the full
    by_slice-grouping -> _merge_hybrid composition is covered."""
    devs = [_FakeDev(i, 0) for i in range(8)]
    mesh = build_mesh(MeshConfig(dp=4, tp=2, dcn_dp=2), devices=devs)
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    arr = mesh.devices
    # dp rows 0-1 = virtual slice 0 (ids 0-3); rows 2-3 = slice 1.
    assert sorted(d.id for d in arr[0, :2, 0, 0, 0, :].flat) == [0, 1, 2, 3]
    assert sorted(d.id for d in arr[0, 2:, 0, 0, 0, :].flat) == [4, 5, 6, 7]


def test_build_mesh_round_robin_across_physical_slices():
    """With 2 real physical slices and num_slices=2, each virtual slice
    must land on a DIFFERENT physical slice (a depth-first carve would
    pack both into slice 0 and leave slice 1 out of the mesh)."""
    devs = [_FakeDev(i, i // 8) for i in range(16)]
    mesh = build_mesh(MeshConfig(dp=4, tp=2, dcn_dp=2), devices=devs)
    arr = mesh.devices
    assert {d.slice_index for d in arr[0, :2, 0, 0, 0, :].flat} == {0}
    assert {d.slice_index for d in arr[0, 2:, 0, 0, 0, :].flat} == {1}


def test_slice_groups_uneven_superset():
    """Drawing 6-of-8 from each physical slice: the group size comes from
    the mesh, not a pre-truncated device list."""
    from ray_tpu.parallel.mesh import _slice_groups

    devs = [_FakeDev(i, i // 8) for i in range(16)]
    groups = _slice_groups(devs, 2, per=6)
    assert [len(g) for g in groups] == [6, 6]
    assert {d.slice_index for d in groups[0]} == {0}
    assert {d.slice_index for d in groups[1]} == {1}


def test_multi_axis_dcn_outermost_crosses_physical():
    """When virtual slices outnumber physical slices under TWO nontrivial
    DCN factors, the OUTERMOST DCN axis (pp) must be the one crossing
    physical slices; the inner one (dp) rides intra-slice ICI — the
    bandwidth ordering the module doc promises."""
    devs = [_FakeDev(i, i // 8) for i in range(16)]
    mesh = build_mesh(MeshConfig(pp=2, dp=2, dcn_pp=2, dcn_dp=2),
                      devices=devs)
    arr = mesh.devices  # shape (2, 2, 1, 1, 1, 1)
    # Across pp (outermost DCN axis): physical slice CHANGES.
    for dp_i in range(2):
        assert (arr[0, dp_i, 0, 0, 0, 0].slice_index !=
                arr[1, dp_i, 0, 0, 0, 0].slice_index)
    # Across dp (inner DCN axis): physical slice is the SAME (ICI hop).
    for pp_i in range(2):
        assert (arr[pp_i, 0, 0, 0, 0, 0].slice_index ==
                arr[pp_i, 1, 0, 0, 0, 0].slice_index)


def test_single_slice_mesh_prefers_one_physical_slice():
    """num_slices==1 with real slice topology: select from ONE physical
    slice instead of a [:n] truncation that straddles (DCN mislabeled as
    ICI). Slice 0 has only 4 devices, so an 8-device mesh must come
    entirely from slice 1."""
    devs = [_FakeDev(i, 0) for i in range(4)] + \
           [_FakeDev(i, 1) for i in range(4, 12)]
    mesh = build_mesh(MeshConfig(dp=8), devices=devs)
    assert {d.slice_index for d in mesh.devices.flat} == {1}


def test_slice_groups_mixed_devices_rejected():
    from ray_tpu.parallel.mesh import _slice_groups

    devs = [_FakeDev(0, 0), _FakeDev(1, 0), object(), object()]
    with pytest.raises(ValueError, match="mixed"):
        _slice_groups(devs, 2)


def test_build_mesh_indivisible_dcn_clear_error():
    """num_slices > axis factor must raise the divisibility ValueError,
    not ZeroDivisionError, on both slice_index and plain devices."""
    devs = [_FakeDev(i, 0) for i in range(8)]
    with pytest.raises(ValueError, match="divisible"):
        build_mesh(MeshConfig(dp=2, dcn_dp=4), devices=devs)


def test_config_env_override(monkeypatch):
    monkeypatch.setenv("RAY_TPU_SCHEDULER_SPREAD_THRESHOLD", "0.75")
    from ray_tpu.utils.config import Config
    c = Config()
    assert c.scheduler_spread_threshold == 0.75
    assert c.health_check_period_ms == 1000


def test_config_unknown_flag():
    with pytest.raises(AttributeError):
        GlobalConfig.no_such_flag

"""The env that pins worker processes to chips (no compiler needed)."""

import pytest

from ray_tpu import accelerators


@pytest.mark.parametrize("chips,bounds", [
    ([2], "1,1,1"),          # one chip of a 2x2 host
    ([0, 1], "1,2,1"),       # two
    ([0, 1, 2, 3], None),    # the whole host: nothing overridden
], ids=["1-chip", "2-chips", "4-chips"])
def test_worker_env_for_chips_on_2x2_host(chips, bounds):
    env = accelerators.worker_env_for_chips(chips, host_chips=4)
    if bounds is None:
        # Not "1,4,1", which describes no 2x2 host: libtpu's own view.
        assert env == {}
        return
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"  # an island
    assert env["ALLOW_MULTIPLE_LIBTPU_LOAD"] == "1"  # others share the host


def test_attached_device_files_outrank_the_host_env(monkeypatch):
    """Found on the chip: a VM passed one chip of a 2x2 host (as
    /dev/vfio/2) still carries TPU_CHIPS_PER_HOST_BOUNDS=2,2,1."""
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setattr(
        accelerators.glob, "glob",
        lambda pat: ["/dev/vfio/2"] if pat == "/dev/vfio/[0-9]*" else [])
    assert accelerators.num_tpu_chips() == 1
    monkeypatch.setattr(accelerators.glob, "glob", lambda pat: [])
    assert accelerators.num_tpu_chips() == 4  # no files: the env is all


def test_worker_env_rejects_a_group_libtpu_cannot_describe():
    with pytest.raises(ValueError, match="3 TPU chips"):
        accelerators.worker_env_for_chips([0, 1, 2], host_chips=4)


def test_gang_env_joins_one_chip_workers_into_a_2x2():
    ports = [8476, 8477, 8478, 8479]
    envs = [accelerators.gang_env(r, 4, 1, ports, host="10.0.0.1")
            for r in range(4)]
    for rank, env in enumerate(envs):
        assert env["TPU_VISIBLE_CHIPS"] == str(rank)
        assert env["CLOUD_TPU_TASK_ID"] == str(rank)
        assert env["TPU_PROCESS_PORT"] == str(ports[rank])
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "2,2,1"  # one topology, not 4
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    assert envs[0]["TPU_PROCESS_ADDRESSES"].split(",")[3] == "10.0.0.1:8479"
    with pytest.raises(ValueError, match="cannot join"):
        accelerators.gang_env(0, 3, 1, [1, 2, 3])

"""Device plane: transfer-server pulls, DeviceRef ownership, channels,
DAG tensor transport + in-DAG allreduce.

Mirrors the reference's accelerator-channel and GPU-object coverage
(reference: python/ray/tests/test_gpu_objects_gloo.py,
python/ray/dag/tests/experimental/test_torch_tensor_dag.py) on the
TPU-native transfer plane (CPU backend in CI; DMA on real slices).
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.cluster_utils import Cluster
from ray_tpu.dag import InputNode, MultiOutputNode, allreduce
from ray_tpu.device_objects import device_get, device_put_ref
from ray_tpu.experimental.channel import DeviceChannel

CPU_ENV = {"env_vars": {"JAX_PLATFORMS": "cpu"}}


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(num_nodes=1, resources={"CPU": 8})
    c.connect()
    yield c
    c.shutdown()


@ray_tpu.remote
class TensorActor:
    """Holds/creates jax arrays; reports device-plane stats."""

    def make_ref(self, scale):
        import jax.numpy as jnp
        return device_put_ref(jnp.arange(8.0) * scale)

    def make_array(self, scale):
        import jax.numpy as jnp
        return jnp.arange(8.0) * scale

    def consume(self, arr):
        return float(arr.sum())

    def table_size(self):
        from ray_tpu.core.ref import get_core_worker
        return len(get_core_worker()._device_objects)

    def plane_stats(self):
        from ray_tpu.experimental.device_plane import DevicePlane
        p = DevicePlane.maybe()
        return {"staged": p.staged if p else 0,
                "pulls": p.pulls if p else 0}

    def read_channel(self, ch, timeout=30.0):
        arr = ch.read(timeout=timeout)
        return np.asarray(arr).tolist()


def _actor():
    return TensorActor.options(runtime_env=CPU_ENV).remote()


# ----------------------------------------------------------------------
# DeviceRef: transfer-plane pulls + ownership integration
# ----------------------------------------------------------------------

def test_device_get_pulls_over_transfer_plane(cluster):
    a = _actor()
    ref = ray_tpu.get(a.make_ref.remote(3.0))
    arr = device_get(ref, timeout=60.0)
    assert np.allclose(np.asarray(arr), np.arange(8.0) * 3.0)
    # The producer staged on ITS transfer server (no host-bytes fallback).
    stats = ray_tpu.get(a.plane_stats.remote())
    assert stats["staged"] >= 1
    # And this process pulled through its own plane.
    from ray_tpu.experimental.device_plane import DevicePlane
    assert DevicePlane.get().pulls >= 1


def test_device_ref_autofree_on_last_drop(cluster):
    a = _actor()
    ref = ray_tpu.get(a.make_ref.remote(1.0))
    assert ray_tpu.get(a.table_size.remote()) >= 1
    del ref
    import gc
    gc.collect()
    deadline = time.time() + 30
    while time.time() < deadline:
        if ray_tpu.get(a.table_size.remote()) == 0:
            break
        time.sleep(0.2)
    assert ray_tpu.get(a.table_size.remote()) == 0, \
        "HBM array not freed after last DeviceRef dropped"


def test_device_ref_local_roundtrip(cluster):
    import jax.numpy as jnp
    ref = device_put_ref(jnp.ones(4))
    out = device_get(ref)
    assert np.allclose(np.asarray(out), 1.0)


def test_cross_slice_device_get_host_relays(cluster):
    """A DeviceRef owned on a DIFFERENT slice must route through the
    host-relay (object-plane/DCN) path, not the intra-slice transfer
    plane (SURVEY §5.8; cross_slice_device_dma defaults off)."""
    other_env = {"env_vars": {"JAX_PLATFORMS": "cpu",
                              "TPU_NAME": "slice-B"}}
    a = TensorActor.options(runtime_env=other_env).remote()
    ref = ray_tpu.get(a.make_ref.remote(5.0))
    assert ref.slice == "slice-B"
    before = ray_tpu.get(a.plane_stats.remote())
    arr = device_get(ref, timeout=60.0)
    assert np.allclose(np.asarray(arr), np.arange(8.0) * 5.0)
    # The owner must NOT have staged a transfer-plane ticket: the pull
    # rode the host-bytes relay.
    after = ray_tpu.get(a.plane_stats.remote())
    assert after["staged"] == before["staged"], \
        "cross-slice device_get used the intra-slice transfer plane"


# ----------------------------------------------------------------------
# Device channels: acquire/release + backpressure
# ----------------------------------------------------------------------

def test_channel_driver_to_actor(cluster):
    import jax.numpy as jnp
    a = _actor()
    ch = DeviceChannel.create([a], capacity=2)
    ch.write(jnp.full(4, 5.0))
    got = ray_tpu.get(a.read_channel.remote(ch))
    assert got == [5.0] * 4
    ch.write(jnp.full(4, 7.0))
    got = ray_tpu.get(a.read_channel.remote(ch))
    assert got == [7.0] * 4
    ch.close()


def test_channel_backpressure(cluster):
    import jax.numpy as jnp
    a = _actor()
    ch = DeviceChannel.create([a], capacity=1)
    ch.write(jnp.zeros(2))
    # Ring full: the second write must block until the reader releases.
    with pytest.raises(Exception):
        ch.write(jnp.ones(2), timeout=1.5)
    got = ray_tpu.get(a.read_channel.remote(ch))  # releases slot 1
    assert got == [0.0, 0.0]
    ch.write(jnp.ones(2), timeout=30.0)  # now succeeds
    got = ray_tpu.get(a.read_channel.remote(ch))
    assert got == [1.0, 1.0]
    ch.close()


# ----------------------------------------------------------------------
# DAG tensor transport + in-DAG allreduce
# ----------------------------------------------------------------------

def test_dag_tensor_transport_no_host_roundtrip(cluster):
    producer = _actor()
    consumer = _actor()
    with InputNode() as inp:
        t = producer.make_array.bind(inp).with_tensor_transport()
        out = consumer.consume.bind(t)
    compiled = out.experimental_compile()
    val = ray_tpu.get(compiled.execute(2.0), timeout=120)
    assert val == float(np.arange(8.0).sum() * 2.0)
    # Tensor moved producer-device -> consumer-device via the plane.
    assert ray_tpu.get(producer.plane_stats.remote())["staged"] >= 1
    assert ray_tpu.get(consumer.plane_stats.remote())["pulls"] >= 1
    # Replay (compiled plans are reusable).
    val = ray_tpu.get(compiled.execute(3.0), timeout=120)
    assert val == float(np.arange(8.0).sum() * 3.0)


def test_dag_allreduce(cluster):
    actors = [_actor() for _ in range(3)]
    with InputNode() as inp:
        parts = [a.make_array.bind(inp) for a in actors]
        outs = allreduce(parts, op="sum")
        dag = MultiOutputNode(outs)
    compiled = dag.experimental_compile()
    refs = compiled.execute(1.0)
    device_refs = ray_tpu.get(refs, timeout=120)
    expect = np.arange(8.0) * 3.0  # three identical inputs, summed
    for dref in device_refs:
        arr = device_get(dref, timeout=60.0)
        assert np.allclose(np.asarray(arr), expect)


def test_dag_allreduce_mean_feeds_consumer(cluster):
    actors = [_actor() for _ in range(2)]
    consumer = _actor()
    with InputNode() as inp:
        parts = [a.make_array.bind(inp) for a in actors]
        outs = allreduce(parts, op="mean")
        final = consumer.consume.bind(outs[0])
    compiled = final.experimental_compile()
    val = ray_tpu.get(compiled.execute(4.0), timeout=120)
    assert val == float((np.arange(8.0) * 4.0).sum())

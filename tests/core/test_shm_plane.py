"""Shared-memory object plane: zero-copy cross-process objects.

Verifies that the C++ store is wired into the runtime:
large task outputs and puts travel through the native shm segment
(`src/object_store/store.cc`), and readers on the same host get numpy
views over shared memory — no pickle of the payload on the RPC plane.
"""

import numpy as np
import pytest


@pytest.fixture
def shm_cluster():
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 1},
                      shm_capacity=512 * 2**20)
    if cluster.shm_plane is None:
        cluster.shutdown()
        pytest.skip("shm store unavailable")
    yield cluster
    cluster.shutdown()
    ray_tpu.shutdown()


def test_large_put_lands_in_shm(shm_cluster):
    import ray_tpu

    arr = np.arange(1_000_000, dtype=np.float64)  # 8 MB
    ref = ray_tpu.put(arr)
    stats = shm_cluster.shm_plane.stats()
    assert stats["num_sealed"] >= 1
    assert shm_cluster.shm_plane.contains(ref.id)
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(out, arr)


def test_small_put_stays_on_heap(shm_cluster):
    import ray_tpu

    before = shm_cluster.shm_plane.stats()["num_sealed"]
    ref = ray_tpu.put({"tiny": 1})
    assert shm_cluster.shm_plane.stats()["num_sealed"] == before
    assert ray_tpu.get(ref) == {"tiny": 1}


def test_remote_large_output_read_zero_copy(shm_cluster):
    """A 100MB array produced on a worker node is read by the driver as a
    zero-copy view over the shared segment."""
    import ray_tpu

    shm_cluster.add_node(num_cpus=2)

    @ray_tpu.remote(num_cpus=2)
    def produce():
        # 100 MB; deterministic content for verification.
        return np.arange(13_107_200, dtype=np.float64)

    ref = produce.remote()
    out = ray_tpu.get(ref)
    assert out.nbytes == 104_857_600
    assert out[0] == 0 and out[-1] == 13_107_199
    # Zero-copy: the array does not own its data; it views the mapped
    # shm segment, so no pickle of the payload happened on the driver.
    assert not out.flags["OWNDATA"]
    assert not out.flags["WRITEABLE"]
    assert shm_cluster.shm_plane.contains(ref.id)


def test_driver_large_arg_readable_on_node(shm_cluster):
    """Driver-side put travels to the node through shm, not pickle RPC."""
    import ray_tpu

    shm_cluster.add_node(num_cpus=2)
    arr = np.full(2_000_000, 7.5)  # 16 MB
    ref = ray_tpu.put(arr)

    @ray_tpu.remote(num_cpus=2)
    def consume(x):
        return float(x.sum()), bool(x.flags["OWNDATA"])

    total, owns = ray_tpu.get(consume.remote(ref))
    assert total == 7.5 * 2_000_000
    assert not owns, "node received a heap copy, not a shm view"


def test_transfer_plane_cross_segment(shm_cluster):
    """A node simulating a remote host (own shm segment) produces a
    large object; the driver pulls it through the native chunked
    transfer server (C++ plane), not pickle RPC."""
    import ray_tpu

    shm_cluster.add_node(num_cpus=2, simulate_remote_host=True)

    @ray_tpu.remote(num_cpus=2)
    def produce():
        return np.arange(4_000_000, dtype=np.float64)  # 32 MB

    ref = produce.remote()
    out = ray_tpu.get(ref)
    assert out[0] == 0 and out[-1] == 3_999_999
    assert not out.flags["OWNDATA"], "expected zero-copy view after pull"
    # The object was pulled into the driver's own segment.
    assert shm_cluster.shm_plane.contains(ref.id)


def test_composite_value_with_arrays(shm_cluster):
    import ray_tpu

    shm_cluster.add_node(num_cpus=2)
    payload = {"w": np.ones((512, 512)), "step": 3,
               "names": ["a", "b"]}
    ref = ray_tpu.put(payload)

    @ray_tpu.remote(num_cpus=2)
    def check(d):
        return float(d["w"].sum()), d["step"], d["names"]

    s, step, names = ray_tpu.get(check.remote(ref))
    assert s == 512 * 512 and step == 3 and names == ["a", "b"]

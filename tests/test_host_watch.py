"""The process's own pauses on the flight-recorder ring (util/events.py
``start_host_watch``): ``host.pause`` with its owner, ``host.watch`` once a
second, ``gc.pause``; and ``train.report``'s ``period_s``.
"""

import gc
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu import config
from ray_tpu.air import session as air_session
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.core import api as core_api
from ray_tpu.core.runtime_cluster import ClusterRuntime, ring_timeline
from ray_tpu.state import api as state
from ray_tpu.util import events

ME = f"pid:{os.getpid()}"


def _mine(kind):
    """This process's records of ``kind`` that are still in its ring or
    were shipped: (ts, value, attrs), oldest first."""
    found = {}
    for ev in events.snapshot():
        if ev[1] == kind and ev[2] == ME:
            found[ev[4]["span"]] = (ev[0], ev[3], ev[4])
    return sorted(found.values(), key=lambda r: r[0])


def _overlapping(kind, lo, hi):
    return [r for r in _mine(kind) if r[0] < hi and r[0] + r[1] > lo]


# ----------------------------------------------------------------------
# the ticker's books, driven by hand (no thread, no cluster)
# ----------------------------------------------------------------------
@pytest.fixture
def ring():
    events.reset_for_tests()
    assert events.enabled()
    yield
    events.reset_for_tests()


def _records(kind):
    return [ev for ev in events.snapshot() if ev[1] == kind]


def test_the_limit_of_twenty_a_second_sums_what_it_skips(ring):
    """30 late wakes inside one second: 20 records, the other 10 summed
    into the first record of the next second."""
    watch = events._HostWatch(100.0)
    now = 100.0
    for _ in range(30):
        watch.woke(now, now + 0.025)        # 25 ms late
        now += 0.030
    pauses = _records("host.pause")
    assert len(pauses) == events.PAUSES_A_SECOND == 20
    assert all("skipped" not in p[4] for p in pauses)
    assert all(p[3] == pytest.approx(0.025) for p in pauses)
    assert not _records("host.watch")       # 0.9 s so far
    watch.woke(now, now + 0.2)              # past the second's end
    assert len(_records("host.pause")) == 20        # this one is the 31st
    seen = _records("host.watch")
    assert len(seen) == 1
    assert seen[0][4]["ticks"] == 31 and seen[0][4]["late"] == 31
    assert seen[0][4]["pause_max_s"] == pytest.approx(0.2)
    now += 0.25
    watch.woke(now, now + 0.040)
    last = _records("host.pause")[-1]
    assert len(_records("host.pause")) == 21
    assert last[4]["skipped"] == 11
    assert last[4]["skipped_s"] == pytest.approx(10 * 0.025 + 0.2)
    watch.woke(now + 0.05, now + 0.05 + 0.030)
    assert "skipped" not in _records("host.pause")[-1][4]


def test_a_punctual_tick_records_nothing_and_a_pause_is_stamped_by_the_wall(
        ring):
    watch = events._HostWatch(5.0)
    for i in range(10):
        watch.woke(5.0 + 0.01 * i, 5.0 + 0.01 * i + 0.004)
    assert not _records("host.pause")
    before = time.time()
    watch.woke(5.2, 5.5)
    (pause,) = _records("host.pause")
    assert pause[2] == ME and pause[4]["parent"] is None
    assert pause[3] == pytest.approx(0.3)
    # ts = the wake it asked for, on time.time()'s clock
    assert before - 0.3 <= pause[0] <= time.time() - 0.3
    assert {"cpu_s", "gc_s", "majflt", "nivcsw"} <= set(pause[4])
    assert ("runq_s" in pause[4]) == os.path.exists(
        "/proc/thread-self/schedstat")


def test_a_kernel_without_schedstat_leaves_runq_s_out(ring, monkeypatch):
    """Not 0: a run-queue delay that was not read is not one of none."""
    monkeypatch.setattr(events, "_thread_runq_s", lambda: None)
    watch = events._HostWatch(5.0)
    watch.woke(5.2, 5.5)
    (pause,) = _records("host.pause")
    assert "runq_s" not in pause[4] and "cpu_s" in pause[4]


def test_a_collection_under_the_rings_lock_does_not_wait_for_it(ring):
    """The collector runs in whichever thread reaches its threshold, also
    one inside ``drain()`` or ``snapshot()`` with ``_lock`` held (it is not
    reentrant): the hook takes no lock, and the record arrives with the
    ticker's next wake."""
    done = []

    def collect_with_the_lock_held():
        with events._lock:
            events._on_gc("start", {"generation": 2})
            events._on_gc("stop", {"generation": 2, "collected": 7})
            events._on_gc("start", {"generation": 0})
            events._on_gc("stop", {"generation": 0, "collected": 1})
        done.append(True)
    t = threading.Thread(target=collect_with_the_lock_held, daemon=True)
    before = time.time()
    t.start()
    t.join(5.0)
    assert done, "the hook waited for the lock its own thread holds"
    assert not _records("gc.pause")             # not from inside the hook
    events._record_collections()                # the ticker's next wake
    (found,) = _records("gc.pause")             # generation 0, short: none
    assert found[2] == ME and found[4]["parent"] is None
    assert found[4]["generation"] == 2 and found[4]["collected"] == 7
    assert before - 0.01 <= found[0] <= time.time() and 0 <= found[3] < 1.0
    assert not events._gc_found


def test_the_time_woke_takes_is_no_lateness_of_the_next_wake(
        ring, monkeypatch):
    """``woke`` held up 50 ms (its own system calls, a wait for ``_lock``
    behind a long drain): the thread slept on time, so no ``host.pause``
    names the process as owner of a pause its own watcher made."""
    slow = events._HostWatch.woke
    calls = []

    def held_up(self, asked, now):
        calls.append(now - asked)
        slow(self, asked, now)
        time.sleep(0.05)
        if len(calls) == 5:
            events._flush_stop.set()
    monkeypatch.setattr(events._HostWatch, "woke", held_up)
    events._flush_stop.clear()
    events._host_watch_loop()
    assert len(calls) == 5
    if max(calls) < events.PAUSE_S:     # no pause of the host's beside it
        assert not _records("host.pause")
    assert sorted(calls)[2] < 0.03      # each asked from after ``woke``


def test_no_thread_with_events_disabled():
    events.reset_for_tests()
    config.set_override("events_enabled", False)
    try:
        events.configure("ab" * 16, "127.0.0.1:1")
        events.start_host_watch()
        assert not [t for t in threading.enumerate()
                    if t.name == "events-host-watch"]
        assert events._on_gc not in gc.callbacks
    finally:
        config.clear_override("events_enabled")
        events.reset_for_tests()


def test_no_thread_in_a_process_without_a_flusher(ring):
    events.start_host_watch()
    assert not [t for t in threading.enumerate()
                if t.name == "events-host-watch"]


# ----------------------------------------------------------------------
# the thread, in a driver (a watched process)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cluster():
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 2,
                                "object_store_bytes": 64 << 20})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()
    assert not [t for t in threading.enumerate()
                if t.name == "events-host-watch" and t.is_alive()
                and not t.join(2.0) and t.is_alive()]
    assert events._on_gc not in gc.callbacks


def _hold_the_interpreter(seconds):
    """One C call that never lets the interpreter go: ``sum`` over a range,
    sized from a short one."""
    n = 200_000
    t0 = time.perf_counter()
    sum(range(n))
    per = (time.perf_counter() - t0) / n
    n = int(seconds / per)
    t0, w0 = time.perf_counter(), time.time()
    sum(range(n))
    return w0, time.perf_counter() - t0


def test_a_pause_by_a_thread_that_holds_the_interpreter_is_this_processes(
        cluster):
    assert [t for t in threading.enumerate() if t.name == "events-host-watch"]
    for _ in range(3):      # a loaded host may stop us beside the plant
        out = []
        holder = threading.Thread(
            target=lambda: out.append(_hold_the_interpreter(0.25)))
        holder.start()
        holder.join()
        began, held = out[0]
        time.sleep(0.05)
        found = _overlapping("host.pause", began, began + held)
        assert found, "no host.pause over a held interpreter"
        ts, value, attrs = max(found, key=lambda r: r[1])
        if value >= 0.6 * held and attrs["cpu_s"] >= 0.7 * value:
            break
    assert value >= 0.6 * held
    assert attrs["cpu_s"] >= 0.7 * value        # it ran all along
    assert began - 0.03 <= ts <= began + held


def test_a_stopped_process_reads_no_cpu(cluster):
    helper = ("import os, signal, sys, time\n"
              "pid = int(sys.argv[1])\n"
              "time.sleep(0.2)\n"
              "a = time.time(); os.kill(pid, signal.SIGSTOP)\n"
              "time.sleep(0.2)\n"
              "os.kill(pid, signal.SIGCONT); b = time.time()\n"
              "print(a, b)\n")
    for _ in range(3):
        proc = subprocess.Popen([sys.executable, "-c", helper,
                                 str(os.getpid())], stdout=subprocess.PIPE)
        try:
            # sleeping, so that the pause is the stop's and nothing else's
            stopped, continued = map(float, proc.communicate(
                timeout=30)[0].split())
        finally:
            os.kill(os.getpid(), signal.SIGCONT)
        time.sleep(0.05)
        found = _overlapping("host.pause", stopped, continued)
        assert found, "no host.pause over a stopped process"
        ts, value, attrs = max(found, key=lambda r: r[1])
        if attrs["cpu_s"] <= 0.25 * value:
            break
    assert 0.15 <= value <= continued - stopped + 0.1
    assert attrs["cpu_s"] <= 0.25 * value       # it did not run
    assert stopped - 0.03 <= ts <= stopped + 0.03
    assert abs(ts + value - continued) <= 0.05


def test_host_watch_once_a_second_without_device_attrs_and_gc_pause(cluster):
    n0 = len(_mine("host.watch"))
    t0 = time.time()
    gc.collect()                                # generation 2
    collected_at = time.time()
    time.sleep(2.3)
    deadline = time.time() + 10.0               # a starved host wakes late
    while len(_mine("host.watch")) < n0 + 2 and time.time() < deadline:
        time.sleep(0.1)
    watches = _mine("host.watch")[n0:]
    assert 2 <= len(watches) <= 3
    for ts, value, attrs in watches:
        # a second, and how late the wake that stored it was
        assert 0.95 <= value <= 1.1 + attrs["pause_max_s"]
        assert 20 <= attrs["ticks"] <= 101
        assert attrs["late"] >= 0 and attrs["pause_max_s"] >= 0.0
        assert 0.0 <= attrs["own_cpu_s"] < 0.2 * value
    forced = _overlapping("gc.pause", t0 - 0.01, collected_at + 0.01)
    assert any(a["generation"] == 2 and a["collected"] >= 0
               for _, _, a in forced)
    # they ship: state.list_spans() returns them, rt.timeline() draws them
    events.flush_now()
    spans = [s for s in state.list_spans(ident=ME)]
    assert {"host.watch", "gc.pause"} <= {s["kind"] for s in spans}
    drawn = ring_timeline(spans)
    assert any(e["name"] == "gc.pause" and e["ph"] == "X" for e in drawn)
    assert any(e["name"] == "host.watch" and e["ph"] == "C"
               and "span" not in e["args"] for e in drawn)
    assert all("ts" in e and "dur" in e for e in drawn)


def test_host_watch_carries_no_device_attrs_where_jax_was_never_imported():
    code = (
        "import sys, time\n"
        "from ray_tpu.util import events\n"
        "watch = events._HostWatch(0.0)\n"
        "watch.woke(1.01, 1.012)\n"
        "(w,) = [e for e in events.snapshot() if e[1] == 'host.watch']\n"
        "assert 'jax' not in sys.modules, 'the watch imported jax'\n"
        "assert sorted(w[4]) == ['late', 'own_cpu_s', 'parent', "
        "'pause_max_s', 'span', 'ticks'], w[4]\n"
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "watch.woke(2.02, 2.022)\n"
        "w = [e for e in events.snapshot() if e[1] == 'host.watch'][-1]\n"
        "assert 'bytes_in_use' not in w[4], 'it brought a backend up'\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "jax.devices()\n"
        "watch.woke(3.03, 3.032)    # a CPU device has no memory_stats\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr


# ----------------------------------------------------------------------
# a step's period, where it happens
# ----------------------------------------------------------------------
def test_period_s_on_the_second_report_and_not_the_first(ring):
    s = air_session._Session(world_rank=0, world_size=1, local_rank=0)
    s.report({"loss": 1.0})
    time.sleep(0.05)
    s.report({"loss": 0.9})
    time.sleep(0.02)
    s.report({"loss": 0.8})
    first, second, third = [ev[4] for ev in _records("train.report")]
    assert "period_s" not in first and first["iteration"] == 1
    assert 0.05 <= second["period_s"] < 0.2
    assert 0.02 <= third["period_s"] < second["period_s"] + 0.1
    assert len(s.reports) == 3

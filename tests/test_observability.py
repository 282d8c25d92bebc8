"""Observability: on-demand worker profiling + task-path spans.

Role parity: dashboard/modules/reporter/profile_manager.py (py-spy role)
and python/ray/util/tracing/tracing_helper.py (span export around
submit/execute with context propagation).
"""

import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util.profiler import collect


def test_profiler_collect_local():
    """The in-process sampler sees a busy function in its stacks."""
    import threading
    stop = threading.Event()

    def busy_beaver():
        while not stop.is_set():
            sum(i * i for i in range(1000))

    t = threading.Thread(target=busy_beaver, name="beaver")
    t.start()
    try:
        dump = collect(duration_s=0.5, interval_s=0.005)
    finally:
        stop.set()
        t.join()
    assert "busy_beaver" in dump
    lines = [ln for ln in dump.splitlines() if "busy_beaver" in ln]
    assert lines and int(lines[0].rsplit(" ", 1)[1]) > 5


@pytest.fixture()
def traced_rt():
    """A runtime with no flag set: the ring, and so its spans, are on by
    default."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    yield ray_tpu
    ray_tpu.shutdown()


def test_spans_cover_task_lifecycle(traced_rt):
    from ray_tpu import state
    from ray_tpu.util import events

    @ray_tpu.remote
    def traced_add(x):
        return x + 1

    with events.span("test.job") as job:
        assert ray_tpu.get(traced_add.remote(41)) == 42
    deadline = time.time() + 30
    spans = []
    while time.time() < deadline:
        events.flush_now()
        spans = state.list_spans()
        if {s["kind"] for s in spans} >= {"test.job", "task.execute"}:
            break
        time.sleep(0.25)
    kinds = {s["kind"] for s in spans}
    assert {"test.job", "task.execute"} <= kinds, kinds
    # the worker's execute span joins the submitter's span as a child and
    # shares its ident
    sub = next(s for s in spans if s["kind"] == "test.job")
    assert sub["attrs"]["span"] == job.id
    exe = next(s for s in spans if s["kind"] == "task.execute"
               and "traced_add" in s["attrs"].get("task", ""))
    assert exe["attrs"]["parent"] == job.id
    assert exe["ident"] == sub["ident"] == job.ident
    assert exe["pid"] != sub["pid"]
    assert exe["value"] >= 0
    assert sub["ts"] <= exe["ts"] <= sub["ts"] + sub["value"]
    # the ring's own instants of the same task are joined by the task id
    ring = state.list_ring_events(kind="task")
    assert any(e["kind"] == "task.submit"
               and e["ident"] == exe["attrs"]["task_id"] for e in ring)
    # filtered query narrows to one ident
    only = state.list_spans(ident=job.ident)
    assert only and all(s["ident"] == job.ident for s in only)
    assert len(only) < len(spans)     # init and the lease have their own


def test_profile_worker_via_state_api(traced_rt):
    import os as _os
    from ray_tpu import state

    @ray_tpu.remote
    class Spinner:
        def pid(self):
            return _os.getpid()

        def spin(self, seconds):
            end = time.time() + seconds
            n = 0
            while time.time() < end:
                n += sum(i for i in range(500))
            return n

    s = Spinner.remote()
    pid = ray_tpu.get(s.pid.remote())
    fut = s.spin.remote(4.0)
    dump = state.profile_worker(pid, duration_s=1.0, interval_s=0.005)
    ray_tpu.get(fut)
    assert dump.strip(), "empty profile"
    assert "spin" in dump, dump[:500]

    with pytest.raises(ValueError):
        state.profile_worker(99_999_999)


def test_dashboard_spans_and_profile_endpoints(traced_rt):
    from ray_tpu.core.api import _global_runtime
    from ray_tpu.dashboard import Dashboard

    @ray_tpu.remote
    def dash_task():
        return 1

    from ray_tpu.util import events
    with events.span("test.dash"):
        ray_tpu.get(dash_task.remote())
    rt = _global_runtime()
    dash = Dashboard(rt.conductor_address, port=0)
    try:
        import json
        spans = json.loads(urllib.request.urlopen(
            f"http://{dash.host}:{dash.port}/api/spans", timeout=10).read())
        # span records only (this process's are flushed by the endpoint)
        assert spans and all("span" in s["attrs"] for s in spans)
        assert "test.dash" in {s["kind"] for s in spans}
    finally:
        dash.stop()

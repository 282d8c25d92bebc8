"""State API + metrics + microbench smoke tests (parity:
python/ray/tests/test_state_api*.py style, util/metrics tests)."""

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.core import api as core_api
from ray_tpu.core.runtime_cluster import ClusterRuntime


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()


def test_state_lists(cluster):
    from ray_tpu import state

    @rt.remote
    def task_for_state():
        return 1

    @rt.remote
    class ActorForState:
        def ping(self):
            return "pong"

    a = ActorForState.remote()
    rt.get([task_for_state.remote(), a.ping.remote()], timeout=60)
    import time
    time.sleep(1.5)  # task-event flush period

    nodes = state.list_nodes()
    assert len(nodes) >= 1 and nodes[0]["state"] == "ALIVE"

    actors = state.list_actors()
    assert any("ActorForState" in x["class_name"] for x in actors)

    tasks = state.list_tasks()
    assert any("task_for_state" in t["name"] for t in tasks)

    summary = state.summarize_tasks()
    assert any("task_for_state" in name for name in summary)

    objects = state.list_objects()
    assert len(objects) >= 1
    rt.kill(a)


# What an operator's task views return for one execution, as they did when
# a second recorder in every worker fed them (pinned before that recorder
# was folded into the flight-recorder ring, PR 43).
LIST_TASKS_FIELDS = {"task_id", "name", "type", "state", "start_time_s",
                     "end_time_s", "duration_s", "node_id", "worker_pid",
                     "error_message"}
TIMELINE_SLICE_FIELDS = {"cat", "name", "ph", "ts", "dur", "pid", "tid",
                         "args"}


@pytest.mark.parametrize("what", ["task", "actor_task", "failed_task"])
def test_task_views_keep_their_fields(cluster, what):
    import os
    import time
    from ray_tpu import state
    from ray_tpu.core.exceptions import TaskError

    @rt.remote
    def viewed_task():
        return os.getpid()

    @rt.remote
    def viewed_failure():
        raise ValueError("viewed")

    @rt.remote
    class ViewedActor:
        def viewed_call(self):
            return os.getpid()

    actor = None
    if what == "task":
        pid, name, kind = rt.get(viewed_task.remote(), timeout=60), \
            "viewed_task", "task"
    elif what == "actor_task":
        actor = ViewedActor.remote()
        pid, name, kind = rt.get(actor.viewed_call.remote(), timeout=60), \
            "ViewedActor.viewed_call", "actor_task"
    else:
        with pytest.raises(TaskError):
            rt.get(viewed_failure.remote(), timeout=60)
        pid, name, kind = None, "viewed_failure", "task"
    deadline = time.time() + 30
    while True:
        rows = [t for t in state.list_tasks() if name in t["name"]]
        if rows or time.time() > deadline:
            break
        time.sleep(0.2)
    assert len(rows) == 1
    row = rows[0]
    assert set(row) == LIST_TASKS_FIELDS
    assert row["type"] == kind and len(row["task_id"]) == 32
    assert row["state"] == ("FAILED" if what == "failed_task"
                            else "FINISHED")
    assert ("viewed" in row["error_message"]) == (what == "failed_task")
    assert row["duration_s"] == row["end_time_s"] - row["start_time_s"] >= 0
    assert abs(row["end_time_s"] - time.time()) < 120
    assert pid is None or row["worker_pid"] == pid
    assert row["node_id"] == core_api._runtime.node_id.hex()
    assert state.summarize_tasks()[row["name"]]["count"] == 1
    slices = [e for e in rt.timeline()
              if e["ph"] == "X" and e["cat"] == kind and name in e["name"]]
    assert len(slices) == 1
    assert set(slices[0]) == TIMELINE_SLICE_FIELDS
    assert set(slices[0]["args"]) == {"error", "task_id"}
    assert slices[0]["args"]["task_id"] == row["task_id"]
    assert slices[0]["dur"] == pytest.approx(row["duration_s"] * 1e6,
                                             abs=1.0)
    flows = [e for e in rt.timeline() if e.get("cat") == "task_flow"
             and e["ph"] == "t" and e["id"] == row["task_id"]]
    assert len(flows) == (1 if kind == "task" else 0)
    if actor is not None:
        rt.kill(actor)


def test_timeline_dump(cluster, tmp_path):
    @rt.remote
    def traced():
        return 2

    rt.get(traced.remote(), timeout=30)
    import time
    time.sleep(1.5)
    out = str(tmp_path / "timeline.json")
    rt.timeline(out)
    import json
    events = json.load(open(out))
    assert isinstance(events, list) and len(events) >= 1
    assert all("ts" in e and "dur" in e for e in events)


def test_metrics_registry_and_prometheus(cluster):
    from ray_tpu.util.metrics import Counter, Gauge, Histogram, \
        prometheus_text

    c = Counter("test_requests_total", "requests", tag_keys=("route",))
    c.inc(3, tags={"route": "/a"})
    g = Gauge("test_queue_depth", "depth")
    g.set(7)
    h = Histogram("test_latency_s", "latency", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(2.0)

    text = prometheus_text()
    assert "test_requests_total" in text
    assert 'route="/a"' in text
    assert "test_queue_depth 7" in text


def test_placement_group_listing(cluster):
    from ray_tpu import state
    from ray_tpu.util import placement_group, remove_placement_group
    pg = placement_group([{"CPU": 1}], strategy="PACK", name="statepg")
    pg.ready(timeout=20)
    pgs = state.list_placement_groups()
    assert any(p["name"] == "statepg" for p in pgs)
    remove_placement_group(pg)

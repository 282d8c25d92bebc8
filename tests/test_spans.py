"""Spans on the flight-recorder ring (util/events.py): nesting, parent and
ident within a thread, across ``span_record``, across a task and an actor
call; the serve chain proxy -> handle -> replica -> batcher; the trainer
gang and the lease path; the session kept after ``rt.shutdown()``; and the
bridge to the device trace's clock (``jax.profiler``)."""

import concurrent.futures
import contextlib
import json
import os
import threading
import time
import urllib.request

import pytest

import ray_tpu as rt
from ray_tpu import config as rt_config
from ray_tpu import serve, state
from ray_tpu.core import api as core_api
from ray_tpu.util import events


def _spans(ring):
    """Ring tuples or conductor dicts -> dicts with the span fields."""
    out = []
    for e in ring:
        if isinstance(e, tuple):
            e = {"ts": e[0], "kind": e[1], "ident": e[2], "value": e[3],
                 "attrs": e[4]}
        if e["attrs"] and "span" in e["attrs"]:
            out.append(e)
    return out


def _kind(spans, kind):
    return [s for s in spans if s["kind"] == kind]


def _inside(child, parent, slack=0.005):
    """The child's interval lies inside the parent's (both on the host's
    clock; ``slack`` covers time.time() against perf_counter())."""
    return (child["ts"] >= parent["ts"] - slack and
            child["ts"] + child["value"]
            <= parent["ts"] + parent["value"] + slack)


@pytest.fixture()
def ring():
    events.reset_for_tests()
    yield
    events.reset_for_tests()


def test_nesting_parent_and_ident_within_a_thread(ring):
    with events.span("test.outer", n=1) as outer:
        assert events.current() == {"ident": outer.ident, "span": outer.id}
        with events.span("test.inner") as inner:
            time.sleep(0.01)
        with events.span("test.named", ident="req-7") as named:
            with events.span("test.leaf") as leaf:
                pass
    assert events.current() is None
    by_kind = {s["kind"]: s for s in _spans(events.snapshot())}
    assert set(by_kind) == {"test.outer", "test.inner", "test.named",
                            "test.leaf"}
    o, i = by_kind["test.outer"], by_kind["test.inner"]
    assert o["attrs"] == {"span": outer.id, "parent": None, "n": 1}
    assert o["ident"] == outer.id            # a root mints its ident
    assert i["attrs"]["parent"] == outer.id and i["ident"] == outer.ident
    assert i["value"] >= 0.01 and _inside(i, o)
    # an explicit ident holds for the span and what it encloses
    assert by_kind["test.named"]["ident"] == "req-7" == named.ident
    assert by_kind["test.leaf"]["ident"] == "req-7"
    assert by_kind["test.leaf"]["attrs"]["parent"] == named.id
    assert leaf.parent == named.id and inner.parent == outer.id
    assert len({outer.id, inner.id, named.id, leaf.id}) == 4


def test_span_records_an_error_and_set(ring):
    with pytest.raises(KeyError):
        with events.span("test.fails") as sp:
            sp.set(rows=3)
            raise KeyError("x")
    (rec,) = _spans(events.snapshot())
    assert rec["attrs"]["rows"] == 3 and "KeyError" in rec["attrs"]["error"]
    assert events.current() is None


def test_span_record_and_adopt_cross_threads(ring):
    """An interval begun on one thread and ended on another: the context is
    carried by hand (``current()`` -> ``adopt``), ``span_record`` writes the
    hop, and an id minted ahead lets a child name a span before it ends."""
    hop = {}
    with events.span("test.request") as req:
        ctx = events.current()
        queued, q0 = time.time(), time.perf_counter()
        ahead = events.new_span_id()

        def on_pool_thread():
            assert events.current() is None     # threads inherit nothing
            with events.adopt(ctx):
                hop["id"] = events.span_record(
                    "test.hop", queued, time.perf_counter() - q0,
                    ident=ctx["ident"], parent=ctx["span"])
                with events.span("test.call"):
                    pass
            with events.adopt({"ident": ctx["ident"], "span": ahead}):
                with events.span("test.early_child"):
                    pass
            assert events.current() is None

        t = threading.Thread(target=on_pool_thread)
        t.start()
        t.join(10)
        assert not t.is_alive()
        events.span_record("test.ahead", queued, 0.5, ident=req.ident,
                           parent=req.id, span=ahead)
    by_kind = {s["kind"]: s for s in _spans(events.snapshot())}
    assert by_kind["test.hop"]["attrs"] == {"span": hop["id"],
                                            "parent": req.id}
    assert by_kind["test.hop"]["ts"] == queued
    assert by_kind["test.call"]["attrs"]["parent"] == req.id
    assert by_kind["test.ahead"]["attrs"]["span"] == ahead
    assert by_kind["test.early_child"]["attrs"]["parent"] == ahead
    assert {s["ident"] for s in by_kind.values()} == {req.ident}
    # adopt(None) adopts nothing; ctx= names the parent, ROOT none at all
    with events.span("test.around") as around:
        with events.adopt(None):
            assert events.current()["span"] == around.id
        with events.span("test.tree", ctx=events.ROOT) as tree:
            assert events.current()["span"] == tree.id
        with events.span("test.given", ctx=ctx) as given:
            pass
        assert events.current()["span"] == around.id
    assert tree.parent is None and tree.ident == tree.id
    assert given.parent == req.id and given.ident == req.ident


def test_events_disabled_records_nothing(ring):
    rt_config.set_override("events_enabled", False)
    try:
        with events.span("test.off", rows=1) as sp:
            sp.set(more=2)
            assert events.current() is None
            assert events.span_record("test.off2", time.time(), 0.1) is None
        assert sp.id is None
        assert events.snapshot() == []
    finally:
        rt_config.clear_override("events_enabled")
    with events.span("test.on"):
        pass
    assert [s["kind"] for s in _spans(events.snapshot())] == ["test.on"]


def test_span_ids_differ_after_fork(ring):
    """A zygote-forked worker draws a nonce of its own."""
    before = events.new_span_id()
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:
        try:
            os.write(w, events.new_span_id().encode())
        finally:
            os._exit(0)
    os.waitpid(pid, 0)
    child = os.read(r, 64).decode()
    os.close(r), os.close(w)
    assert child[:8] != before[:8]


@contextlib.contextmanager
def _runtime(num_cpus=4):
    rt.shutdown()
    rt.init(num_cpus=num_cpus)
    try:
        yield
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        rt.shutdown()


def _cluster_spans(want, timeout=30.0, **query):
    """Span records at the conductor, once every kind in ``want`` is
    there (other processes flush every half second)."""
    deadline = time.time() + timeout
    while True:
        events.flush_now()
        spans = state.list_spans(**query)
        if want <= {s["kind"] for s in spans} or time.time() > deadline:
            return spans
        time.sleep(0.2)


def test_context_crosses_a_task_and_an_actor_call():
    with _runtime():
        @rt.remote
        def task():
            with events.span("test.in_task"):
                return os.getpid()

        @rt.remote
        class Actor:
            def call(self):
                with events.span("test.in_actor"):
                    return os.getpid()

        @rt.remote(max_concurrency=2)
        class Pooled:
            def call(self):
                with events.span("test.in_pooled"):
                    return os.getpid()

        @rt.remote
        class Async:
            async def call(self):
                with events.span("test.in_async"):
                    return os.getpid()

        actors = [Actor.remote(), Pooled.remote(), Async.remote()]
        with events.span("test.job") as job:
            pids = rt.get([task.remote()] + [a.call.remote()
                                              for a in actors])
        assert rt.get(task.remote()) > 0          # outside a span: nothing
        assert os.getpid() not in pids
        spans = _cluster_spans({"test.in_task", "test.in_actor",
                                "test.in_pooled", "test.in_async"},
                               ident=job.ident)
        by_kind = {s["kind"]: s for s in spans}
        # a plain task's execution is a span of its own under the caller's
        exe = by_kind["task.execute"]
        assert exe["attrs"]["parent"] == job.id
        assert by_kind["test.in_task"]["attrs"]["parent"] == \
            exe["attrs"]["span"]
        assert len(_kind(spans, "task.execute")) == 1
        # an actor call makes the caller's span current: sync, pooled, async
        for kind in ("test.in_actor", "test.in_pooled", "test.in_async"):
            assert by_kind[kind]["attrs"]["parent"] == job.id, kind
            assert by_kind[kind]["pid"] != os.getpid()
        # the lease path: actors created outside any span are roots, each
        # with the spawn and the boot it cost under it
        every = _cluster_spans({"worker.boot"})
        grants = {s["attrs"]["span"]: s for s in _kind(every, "lease.grant")}
        spawns = {s["attrs"]["span"]: s for s in _kind(every, "worker.spawn")}
        assert len([g for g in grants.values()
                    if "actor" in g["attrs"]]) == 3
        for boot in _kind(every, "worker.boot"):
            spawn = spawns[boot["attrs"]["parent"]]
            assert _inside(boot, spawn) and boot["pid"] != spawn["pid"]
            assert boot["ident"] == spawn["ident"]
            assert spawn["attrs"]["chips"] == 0
            grant = grants[spawn["attrs"]["parent"]]
            assert _inside(spawn, grant) and grant["attrs"]["TPU"] == 0
            assert grant["ident"] == spawn["ident"]
        for a in actors:
            rt.kill(a)


STATIONS = ("call.submit", "call.turn", "call.return", "call.get")


def _end(s):
    return s["ts"] + s["value"]


def _actor_of(how):
    if how == "sync":
        @rt.remote
        class Sync:
            def call(self, x):
                return x + 1
        return Sync
    if how == "pooled":
        @rt.remote(max_concurrency=2)
        class Pooled:
            def call(self, x):
                return x + 1
        return Pooled

    @rt.remote
    class Async:
        async def call(self, x):
            return x + 1
    return Async


@pytest.mark.parametrize("how", ["sync", "pooled", "async"])
def test_an_actor_call_records_its_four_stations(how):
    """One call under an open span: one each of call.submit (caller),
    call.turn and call.return (callee), call.get (caller), children of the
    caller's span, in order, inside [start(call.submit), end(call.get)]; a
    call outside any span records none."""
    with _runtime():
        actor = _actor_of(how).remote()
        assert rt.get(actor.call.remote(0), timeout=60) == 1   # no span
        with events.span("test.job") as job:
            ref = actor.call.remote(1)
            assert rt.get(ref, timeout=60) == 2
            assert rt.get(ref, timeout=60) == 2     # a second get: no span
        spans = _cluster_spans(set(STATIONS), ident=job.ident)
        by_kind = {k: _kind(spans, k) for k in STATIONS}
        assert {k: len(v) for k, v in by_kind.items()} == \
            dict.fromkeys(STATIONS, 1)
        submit, turn, ret, get = (by_kind[k][0] for k in STATIONS)
        for s in (submit, turn, ret, get):
            assert s["attrs"]["parent"] == job.id and s["ident"] == job.ident
        assert submit["pid"] == get["pid"] == os.getpid()
        assert turn["pid"] == ret["pid"] != os.getpid()
        # ordered (one host, one clock; the slack is time.time() against
        # perf_counter() across two processes)
        slack = 0.005
        assert submit["ts"] <= turn["ts"] + slack
        assert _end(turn) <= ret["ts"] + slack
        # the value is in the store a moment before call.return's end is
        # stamped: a getter on a busy host can have it by then
        seen = 0.05
        assert _end(ret) <= _end(get) + seen
        for s in (turn, ret):
            assert submit["ts"] - slack <= s["ts"] and \
                _end(s) <= _end(get) + seen
        # the counters: what each station says it waited for
        assert submit["attrs"]["bytes"] > 0
        assert submit["attrs"]["window_wait_s"] <= submit["value"]
        t = turn["attrs"]
        assert min(t["turn_wait_s"], t["pool_wait_s"], t["resolve_s"]) >= 0
        assert t["turn_wait_s"] + t["pool_wait_s"] + t["resolve_s"] \
            <= turn["value"] + 1e-6
        assert (t["pool_wait_s"] > 0) == (how != "sync")
        r, g = ret["attrs"], get["attrs"]
        assert r["bytes"] > 0 and r["inline"] == (1 if how == "sync" else 0)
        assert r["lock_wait_s"] <= r["seal_wait_s"] <= ret["value"]
        assert (r["seal_wait_s"] > 0) == (how != "sync")
        assert g["parked_s"] + g["lock_wait_s"] <= get["value"] + 1e-6
        assert min(g["parked_s"], g["lock_wait_s"]) >= 0
        assert get["ts"] - slack <= g["woken_ts"] <= _end(get) + slack
        assert _end(ret) <= g["woken_ts"] + seen    # woken by the return
        # nothing of the calls made outside the span
        every = _cluster_spans(set(), timeout=0)
        assert all(s["ident"] == job.ident
                   for k in STATIONS for s in _kind(every, k))
        rt.kill(actor)


def test_a_batched_get_records_a_call_get_a_traced_ref():
    with _runtime():
        actor = _actor_of("sync").remote()
        with events.span("test.job") as job:
            refs = [actor.call.remote(i) for i in range(3)]
            rt.wait(refs, num_returns=3, timeout=60)
            assert rt.get(refs, timeout=60) == [1, 2, 3]
        spans = _cluster_spans(set(STATIONS), ident=job.ident)
        assert [len(_kind(spans, k)) for k in STATIONS] == [3, 3, 3, 3]
        assert all(s["attrs"]["parent"] == job.id
                   for s in _kind(spans, "call.get"))
        rt.kill(actor)


def _largest(attrs, keys):
    return max(keys, key=lambda k: attrs[k])


def test_a_delay_before_the_turn_shows_in_turn_wait_s(ring, monkeypatch):
    """The callee's half in this process: a worker service beside the
    driver's runtime, its ``_wait_turn`` 0.2 s late."""
    from ray_tpu.cluster.worker_main import WorkerService
    from ray_tpu.core import serialization
    from ray_tpu.core.ids import TaskID
    with _runtime():
        runtime = core_api._runtime
        daemon = runtime._owned_daemon
        svc = WorkerService(runtime.conductor_address,
                            runtime.daemon_address, daemon.store_socket,
                            daemon.store_prefix, runtime.node_id)
        try:
            class Target:
                def call(self, x):
                    return x + 1
            svc.actor_id, svc.actor_instance = b"a" * 16, Target()
            svc.actor_class_name = "Target"
            wait_turn = svc._wait_turn

            def late(caller_id, seqno):
                time.sleep(0.2)
                return wait_turn(caller_id, seqno)
            monkeypatch.setattr(svc, "_wait_turn", late)
            reply = svc.rpc_push_actor_task(
                task_id=TaskID.from_random().binary(), caller_id=b"c",
                seqno=0, method_name="call",
                args_blob=serialization.dumps(([1], {})), num_returns=1,
                actor_id=svc.actor_id,
                trace_ctx={"ident": "req", "span": "parent"})
            assert reply["ok"] and len(reply["returns"]) == 1
        finally:
            svc._shutdown.set()         # its watchdog must not outlive us
        spans = _spans(events.snapshot())
        (turn,), (ret,) = _kind(spans, "call.turn"), _kind(spans,
                                                          "call.return")
        assert turn["ident"] == ret["ident"] == "req"
        assert turn["attrs"]["parent"] == ret["attrs"]["parent"] == "parent"
        t = turn["attrs"]
        assert t["turn_wait_s"] >= 0.2 and turn["value"] >= 0.2
        assert _largest(t, ("turn_wait_s", "pool_wait_s", "resolve_s")) \
            == "turn_wait_s"
        assert ret["value"] < turn["value"]


def test_a_delay_in_storing_the_returns_shows_in_call_return():
    """A fault rule sleeps 0.2 s where a return is about to ride the
    reply (``_store_returns`` -> ``_emit_return``): call.return takes it,
    not call.turn, and call.get is parked for it."""
    from ray_tpu.cluster import fault_plane
    rt.shutdown()
    fault_plane.load_plan([{"site": "task.reply.inline", "action": "delay",
                            "delay_s": 0.2}])
    try:
        with _runtime():
            actor = _actor_of("sync").remote()
            assert rt.get(actor.call.remote(0), timeout=60) == 1   # alive
            with events.span("test.job") as job:
                assert rt.get(actor.call.remote(1), timeout=60) == 2
            spans = _cluster_spans(set(STATIONS), ident=job.ident)
            by_kind = {k: _kind(spans, k)[0] for k in STATIONS}
            assert by_kind["call.return"]["value"] >= 0.2
            assert by_kind["call.get"]["attrs"]["parked_s"] >= 0.2
            assert max(("call.submit", "call.turn", "call.return"),
                       key=lambda k: by_kind[k]["value"]) == "call.return"
            rt.kill(actor)
    finally:
        fault_plane.clear_plan()


def test_a_held_store_connection_shows_in_lock_wait_s():
    """The process's one store connection held for 0.2 s while a get
    fetches a store-backed return: ``lock_wait_s`` takes it."""
    with _runtime():
        actor = _actor_of("pooled").remote()
        store = core_api._runtime.plane.store
        with events.span("test.job") as job:
            ref = actor.call.remote(1)
            done, _ = rt.wait([ref], num_returns=1, timeout=60)
            assert done                 # stored: nothing left to park for
            held, go = threading.Event(), threading.Event()

            def hold():
                with store._lock:
                    held.set()
                    go.wait(10)
                    time.sleep(0.2)
            holder = threading.Thread(target=hold)
            holder.start()
            assert held.wait(10)
            go.set()
            assert rt.get(ref, timeout=60) == 2
            holder.join(10)
        spans = _cluster_spans(set(STATIONS), ident=job.ident)
        (get,) = _kind(spans, "call.get")
        g = get["attrs"]
        assert g["lock_wait_s"] >= 0.2 - 0.01 and get["value"] >= 0.2 - 0.01
        assert _largest(g, ("parked_s", "lock_wait_s")) == "lock_wait_s"
        rt.kill(actor)


SLOW_S = 0.4


def test_serve_chain_through_proxy_and_batcher():
    """Every request through a real proxy and a ``@serve.batch``
    deployment has the whole chain, children inside parents, and each
    flush's rows are the waits that name it."""
    with _runtime(num_cpus=8):
        @serve.deployment(name="batched", route_prefix="/b",
                          max_ongoing_requests=16)
        class Batched:
            @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
            def many(self, items):
                time.sleep(0.05)
                return [i * 2 for i in items]

            def __call__(self, x):
                return {"y": self.many(x)}

        handle = serve.run(Batched.bind(), http_host="127.0.0.1")

        def post(x):
            req = urllib.request.Request(
                f"http://127.0.0.1:{handle.http_port}/b",
                data=json.dumps({"x": x}).encode())
            return json.loads(urllib.request.urlopen(req, timeout=30).read())

        n = 10
        with concurrent.futures.ThreadPoolExecutor(n) as pool:
            assert sorted(r["y"] for r in pool.map(post, range(n))) == \
                [2 * i for i in range(n)]
        chain = {"serve.request", "serve.proxy.admit",
                 "serve.proxy.thread_wait", "serve.handle.slot_wait",
                 "serve.handle.call", "serve.replica.call",
                 "serve.batch.wait"}
        deadline = time.time() + 30
        while True:
            spans = _cluster_spans(chain | {"serve.batch.flush",
                                            "serve.batch.reply"})
            if len(_kind(spans, "serve.batch.wait")) >= n or \
                    time.time() > deadline:
                break
            time.sleep(0.2)
        requests = _kind(spans, "serve.request")
        assert len(requests) == n
        assert len({r["ident"] for r in requests}) == n
        for req in requests:
            assert req["attrs"]["code"] == 200
            mine = {s["kind"]: s for s in spans if s["ident"] == req["ident"]}
            assert set(mine) == chain | set(STATIONS), sorted(mine)
            # another actor call under the request's ident (the handle
            # refreshing its routes under slot_wait) has stations too: the
            # request's own are the children of its serve.handle.call
            mine.update({s["kind"]: s for s in spans
                         if s["kind"] in STATIONS and s["attrs"]["parent"]
                         == mine["serve.handle.call"]["attrs"]["span"]})
            rid = req["attrs"]["span"]
            for kind in ("serve.proxy.admit", "serve.proxy.thread_wait",
                         "serve.handle.slot_wait", "serve.handle.call"):
                assert mine[kind]["attrs"]["parent"] == rid, kind
                assert _inside(mine[kind], req), kind
                assert mine[kind]["pid"] == req["pid"]
            call, replica = mine["serve.handle.call"], \
                mine["serve.replica.call"]
            assert replica["attrs"]["parent"] == call["attrs"]["span"]
            assert replica["pid"] != call["pid"] and _inside(replica, call)
            assert call["attrs"]["retries"] == 0
            assert 1 <= replica["attrs"]["inflight"] <= n
            # the actor call's own stations: the handle's call is their
            # parent too, the way in before the replica's method, the way
            # out after it
            for kind in STATIONS:
                assert mine[kind]["attrs"]["parent"] == \
                    call["attrs"]["span"], kind
                assert _inside(mine[kind], call), kind
            assert _end(mine["call.turn"]) <= replica["ts"] + 0.005
            assert _end(replica) <= mine["call.return"]["ts"] + 0.005
            assert mine["call.return"]["attrs"]["inline"] == 0
            wait = mine["serve.batch.wait"]
            assert wait["attrs"]["parent"] == replica["attrs"]["span"]
            assert _inside(wait, replica)
            # in order: admitted, then a thread, then a slot, then the call
            assert mine["serve.proxy.admit"]["ts"] <= \
                mine["serve.proxy.thread_wait"]["ts"] <= \
                mine["serve.handle.slot_wait"]["ts"] <= call["ts"]
        flushes = _kind(spans, "serve.batch.flush")
        waits = _kind(spans, "serve.batch.wait")
        assert sum(f["attrs"]["rows"] for f in flushes) == n
        replies = {s["attrs"]["parent"]: s
                   for s in _kind(spans, "serve.batch.reply")}
        for f in flushes:
            a = f["attrs"]
            named = [w for w in waits if w["attrs"]["flush"] == a["span"]]
            assert len(named) == a["rows"] <= a["max_batch_size"] == 4
            assert a["parent"] is None and a["window_s"] == 0.05
            assert f["value"] >= 0.05
            # a wait ends where its flush starts fn
            for w in named:
                assert abs(w["ts"] + w["value"] - f["ts"]) < 0.005
            assert a["oldest_wait_s"] == pytest.approx(
                max(w["value"] for w in named), abs=1e-6)
            reply = replies[a["span"]]
            assert reply["ts"] >= f["ts"] + f["value"] - 0.005
        # the ring's request metric still folds from the span
        assert any(s["value"] >= 0.05 for s in requests)


def test_an_admitted_call_waits_for_no_executor_thread():
    """More concurrent callers of a slow handler than the loop's default
    executor has threads, under an admission budget that covers them: an
    admitted call goes straight to the replica. No
    ``serve.proxy.thread_wait`` is as long as half a handler run, and all
    six are inside the replica at once (until PR 36 the call ran on the
    default executor: two at a time, the last after two handler runs)."""
    from ray_tpu.serve.http_proxy import HTTPProxy
    with _runtime(num_cpus=8):
        @serve.deployment(name="slow", route_prefix="/slow",
                          max_ongoing_requests=16)
        def slow(x):
            time.sleep(SLOW_S)
            return x

        serve.run(slow.bind())
        proxy = HTTPProxy("127.0.0.1", 0)     # in this process
        pool2 = concurrent.futures.ThreadPoolExecutor(2)
        proxy._loop.call_soon_threadsafe(
            proxy._loop.set_default_executor, pool2)
        try:
            def post(x):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{proxy.port()}/slow",
                    data=json.dumps({"x": x}).encode())
                return json.loads(
                    urllib.request.urlopen(req, timeout=30).read())

            assert post(0) == 0               # routes and handle warm
            with concurrent.futures.ThreadPoolExecutor(6) as pool:
                assert sorted(pool.map(post, range(6))) == list(range(6))
        finally:
            proxy.close()
            pool2.shutdown(wait=False)
        deadline = time.time() + 30
        while True:                           # the replica's are another's
            spans = _cluster_spans({"serve.replica.call"})
            inside = [s["attrs"]["inflight"]
                      for s in _kind(spans, "serve.replica.call")]
            if len(inside) >= 7 or time.time() > deadline:
                break
            time.sleep(0.2)
        waits = sorted(s["value"]
                       for s in _kind(spans, "serve.proxy.thread_wait"))
        assert len(waits) == 7 == len(inside)
        assert waits[-1] < SLOW_S / 2         # behind no other call
        assert max(inside) == 6               # all six at once
        admits = _kind(spans, "serve.proxy.admit")
        assert len(admits) == 7
        assert max(s["value"] for s in admits) < SLOW_S / 2


def test_trainer_spans_and_the_kept_session(tmp_path):
    """A toy ``JaxTrainer`` run yields ``train.fit`` and its children, one
    ``train.report`` per report and ``worker.spawn > worker.boot``; after
    ``rt.shutdown()`` the session holds them and ``rt.timeline()`` starts no
    runtime."""
    from ray_tpu.air import session
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        for i in range(config["reports"]):
            session.report({"step": i})

    rt.shutdown()
    rt.init(num_cpus=4)
    try:
        result = JaxTrainer(
            loop, train_loop_config={"reports": 3},
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(name="spans", storage_path=str(tmp_path)),
        ).fit()
        assert result.error is None and len(result.metrics_history) == 3
    finally:
        rt.shutdown()
    spans = events.last_session()
    assert spans and all("span" in s["attrs"] and "node_id" in s
                         for s in spans)
    (fit,) = _kind(spans, "train.fit")
    mine = [s for s in spans if s["ident"] == fit["ident"]]
    by_id = {s["attrs"]["span"]: s for s in mine}

    def ancestors(s):
        out = []
        while s["attrs"]["parent"] in by_id:
            s = by_id[s["attrs"]["parent"]]
            out.append(s["kind"])
        return out

    (backend,) = _kind(mine, "train.backend.start")
    (gang,) = _kind(mine, "train.gang.start")
    assert ancestors(gang) == ["train.backend.start", "train.fit"]
    assert _inside(gang, backend) and _inside(backend, fit)
    # the gang's two actors: grant > spawn > boot, under the gang's start
    boots = _kind(mine, "worker.boot")
    assert len(boots) == 2
    for boot in boots:
        assert ancestors(boot) == ["worker.spawn", "lease.grant",
                                   "train.gang.start",
                                   "train.backend.start", "train.fit"]
    loops = _kind(mine, "train.loop")
    assert sorted(s["attrs"]["rank"] for s in loops) == [0, 1]
    assert len({s["pid"] for s in loops} | {fit["pid"]}) == 3
    reports = _kind(mine, "train.report")
    assert len(reports) == 2 * 3              # one per report, per rank
    for rep in reports:
        assert ancestors(rep)[:1] == ["train.loop"]
        assert "train.fit" in ancestors(rep)
    assert sorted(r["attrs"]["iteration"] for r in reports) == \
        [1, 1, 2, 2, 3, 3]
    pumps = _kind(mine, "train.pump")
    assert [p["attrs"].get("iteration") for p in pumps] == [1, 2, 3, None]
    assert all(0 <= p["attrs"]["lag_s"] < 30 for p in pumps[:3])
    assert all(ancestors(p) == ["train.fit"] for p in pumps)
    (init,) = _kind(spans, "init")
    assert init["ts"] < fit["ts"]
    # the post-mortem: a timeline of the finished session, no new runtime
    assert not rt.is_initialized()
    timeline = rt.timeline()
    assert not rt.is_initialized() and core_api._runtime is None
    slices = [e for e in timeline if e["cat"] == "span"]
    assert len(slices) == len(spans)
    drawn = next(e for e in slices if e["name"] == "train.fit")
    assert drawn["ph"] == "X" and drawn["dur"] == pytest.approx(
        fit["value"] * 1e6)
    assert drawn["args"]["span"] == fit["attrs"]["span"]
    out = tmp_path / "job.json"
    assert rt.timeline(str(out)) is None
    assert len(json.loads(out.read_text())) == len(timeline)


def _profile_start_and_events(trace_dir, prefix):
    """(profile_start_time in ns, [(name, start_ns)]) of a jax.profiler
    trace's host planes."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = ProfileData.from_file(path)
    start, found = None, []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                found.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(prefix))
    return start, found


def test_bridge_to_the_device_traces_clock(ring, tmp_path):
    """In a process that has imported jax a span also enters
    ``TraceAnnotation("rt.<kind>")``: in a profiler trace the event's
    ``profile_start_time + start_ns`` is the ring record's ``ts``, the
    host's CLOCK_REALTIME, to within a millisecond."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(3):
            with events.span("test.bridged", i=i):
                time.sleep(0.02)
                with events.span("test.bridged.child"):
                    time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    start_ns, found = _profile_start_and_events(str(tmp_path), "rt.test.")
    assert start_ns is not None
    records = _spans(events.snapshot())
    for kind in ("test.bridged", "test.bridged.child"):
        traced = sorted(s for n, s, _ in found if n == "rt." + kind)
        ring_ts = sorted(r["ts"] for r in records if r["kind"] == kind)
        assert len(traced) == len(ring_ts) == 3
        for s, ts in zip(traced, ring_ts):
            assert abs((start_ns + s) * 1e-9 - ts) < 1e-3, \
                ((start_ns + s) * 1e-9, ts)
    # and the two clocks agree on how long a span took
    durs = sorted(d * 1e-9 for n, _, d in found if n == "rt.test.bridged")
    vals = sorted(r["value"] for r in records if r["kind"] == "test.bridged")
    assert all(abs(d - v) < 1e-3 for d, v in zip(durs, vals))

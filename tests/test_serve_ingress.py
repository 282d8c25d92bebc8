"""Serve ingress hardening tests (r14): admission control + load
shedding, request deadlines with cancellation, health-aware handle
retry, adaptive batching, graceful drain, and the chaos SLO scenario
(parity: serve's http_proxy backpressure + router failure handling +
replica draining test suites)."""

import contextlib
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import cloudpickle
import pytest

import ray_tpu as rt
from ray_tpu import config as rt_config
from ray_tpu import serve
from ray_tpu.cluster import fault_plane
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.core import api as core_api
from ray_tpu.core.runtime_cluster import ClusterRuntime


@contextlib.contextmanager
def _cluster(overrides=None, num_cpus=8):
    """Fresh cluster per test so config overrides / fault plans reach the
    controller, proxy, and replica processes (propagation happens at
    worker spawn; a shared module cluster would hand out recycled workers
    with stale env)."""
    prev_runtime = core_api._runtime
    keys = list(overrides or {})
    for k, v in (overrides or {}).items():
        rt_config.set_override(k, v)
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": num_cpus})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    try:
        yield c
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        core_api._runtime = prev_runtime
        rt_.shutdown()
        c.shutdown()
        for k in keys:
            rt_config.clear_override(k)
        fault_plane.clear_plan()


def _http(port, path, payload=None, timeout=30):
    """One request; returns (code, body_dict_or_None, retry_after)."""
    data = json.dumps(payload).encode() if payload is not None else b"{}"
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"})
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.code, json.loads(resp.read()), None
    except urllib.error.HTTPError as e:
        return e.code, None, e.headers.get("Retry-After")


def _metric_total(name):
    """Sum a counter/gauge across every process snapshot in the conductor
    metrics KV (None if no process has shipped it yet)."""
    import pickle
    conductor = core_api._global_runtime().conductor
    total, found = 0.0, False
    for key in conductor.call("kv_keys", ns="metrics"):
        blob = conductor.call("kv_get", ns="metrics", key=key)
        if blob is None:
            continue
        entry = pickle.loads(blob).get(name)
        if not entry:
            continue
        for _tags, value in entry["points"]:
            total += value
            found = True
    return total if found else None


# ---------------------------------------------------------------------------
# Satellite: stale-replica routing — evict + retry on submission failure
# ---------------------------------------------------------------------------


def test_stale_replica_retry_after_kill():
    """Kill a replica and IMMEDIATELY call .remote() while the handle's
    1s routing cache still lists it: every call must succeed (the ref
    retries on the surviving replica), and the dead replica is evicted
    from the handle's local view."""
    with _cluster():
        @serve.deployment(num_replicas=2)
        class Echo:
            def __call__(self, x):
                return x + 1

        handle = serve.run(Echo.bind())
        handle._refresh(force=True)
        assert len(handle._replicas) == 2
        victim = handle._replicas[0]
        rt.kill(victim)
        # The kill is eventually-consistent: wait until the victim
        # actually stops answering, or the calls below could all complete
        # on it before it dies and exercise nothing.
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                rt.get(victim.check_health.remote(), timeout=5)
                time.sleep(0.05)
            except Exception:
                break
        else:
            pytest.fail("killed replica kept answering for 30s")
        # The handle's routing view still lists the corpse (no refresh
        # since the kill): roughly half of these route to it and must
        # transparently retry.
        refs = [handle.remote(i) for i in range(12)]
        outs = [rt.get(r, timeout=60) for r in refs]
        assert outs == [i + 1 for i in range(12)]
        assert all(isinstance(r, serve.ServeCallRef) for r in refs)
        # The failed calls evicted the corpse, and the quarantine keeps a
        # stale routing table (controller hasn't reconciled yet) from
        # re-admitting it.
        handle._refresh(force=True)
        assert victim._rt_actor_id not in {
            r._rt_actor_id for r in handle._replicas}
        serve.delete("Echo")


def test_actor_task_cancel_before_start():
    """rt.cancel on a not-yet-started actor task stores
    TaskCancelledError instead of running user code (the serve deadline
    path relies on this to not leak replica work)."""
    from ray_tpu.core.exceptions import TaskCancelledError, TaskError
    with _cluster(num_cpus=4):
        @rt.remote
        class Slow:
            def __init__(self):
                self.ran = []

            def work(self, i, s):
                self.ran.append(i)
                time.sleep(s)
                return i

            def log(self):
                return self.ran

        a = Slow.remote()
        first = a.work.remote(1, 2.0)
        queued = a.work.remote(2, 0.0)   # serialized behind `first`
        time.sleep(0.3)                  # first is executing
        rt.cancel(queued)
        with pytest.raises(TaskError) as ei:
            rt.get(queued, timeout=30)
        assert isinstance(ei.value.cause, TaskCancelledError)
        assert rt.get(first, timeout=30) == 1
        # user code for the cancelled call never ran
        assert rt.get(a.log.remote(), timeout=30) == [1]
        # and the task views name it so
        from ray_tpu import state
        deadline = time.time() + 30
        while time.time() < deadline:
            errors = [t["error_message"] for t in state.list_tasks()
                      if t["name"].endswith("Slow.work")]
            if len(errors) == 2:
                break
            time.sleep(0.2)
        assert sorted(errors) == ["", "cancelled"]


# ---------------------------------------------------------------------------
# Tentpole: overload — bounded queue, clean sheds, accepted p99 holds
# ---------------------------------------------------------------------------


def test_overload_sheds_cleanly_and_bounds_queue():
    overrides = {"serve_max_queued_requests": 6,
                 "serve_max_ongoing_requests": 2,
                 "serve_request_timeout_s": 30.0}
    with _cluster(overrides=overrides):
        @serve.deployment(num_replicas=1, route_prefix="/slow")
        class SlowModel:
            def __call__(self, x=0):
                time.sleep(0.1)
                return {"x": x}

        handle = serve.run(SlowModel.bind(), http_host="127.0.0.1")
        port = handle.http_port

        # Unloaded latency profile first.
        unloaded = []
        for i in range(10):
            t0 = time.monotonic()
            code, body, _ = _http(port, "/slow", {"x": i})
            unloaded.append(time.monotonic() - t0)
            assert code == 200 and body == {"x": i}
        p99_unloaded = sorted(unloaded)[-1]

        # 10x offered load over capacity (budget: 2 ongoing + 6 queued).
        results = []
        res_lock = threading.Lock()
        stats_samples = []

        def one_request(i):
            t0 = time.monotonic()
            code, _, retry_after = _http(port, "/slow", {"x": i})
            with res_lock:
                results.append(
                    (code, time.monotonic() - t0, retry_after))

        threads = [threading.Thread(target=one_request, args=(i,))
                   for i in range(60)]
        for t in threads:
            t.start()
        # Sample proxy occupancy mid-burst: the queue must stay bounded.
        controller = serve.api._get_controller(create=False)
        for _ in range(6):
            time.sleep(0.05)
            stats_samples.append(
                rt.get(controller.http_stats.remote(), timeout=30))
        for t in threads:
            t.join()

        codes = [c for c, _, _ in results]
        assert len(results) == 60
        assert set(codes) <= {200, 503}, f"unexpected codes: {set(codes)}"
        shed = sum(1 for c in codes if c == 503)
        assert shed > 0, "10x overload produced no sheds"
        # every shed is clean: 503 WITH Retry-After
        assert all(ra is not None for c, _, ra in results if c == 503)
        # queue depth never exceeded the budget
        assert max(s["queued"] for s in stats_samples) <= 6
        # accepted p99 within 5x of unloaded p99 (floor guards timer noise)
        accepted = sorted(lat for c, lat, _ in results if c == 200)
        assert accepted, "overload accepted nothing"
        p99 = accepted[min(len(accepted) - 1, int(0.99 * len(accepted)))]
        assert p99 <= 5 * max(p99_unloaded, 0.15), \
            f"accepted p99 {p99:.3f}s vs unloaded {p99_unloaded:.3f}s"
        # the proxy's own ledger accounts for every rejection...
        stats = rt.get(controller.http_stats.remote(), timeout=30)
        assert stats["shed"] == shed
        assert stats["served"] == 60 + 10 - shed
        # ...and so does the flight-recorder metric, once flushed
        deadline = time.time() + 20
        while time.time() < deadline:
            if _metric_total("rt_serve_shed_total") == float(shed):
                break
            time.sleep(0.5)
        assert _metric_total("rt_serve_shed_total") == float(shed)
        serve.delete("SlowModel")


def test_request_deadline_times_out_with_504():
    # Short drain deadline too: the stuck replica (30s sleep) must not
    # hold teardown for the full default drain window.
    overrides = {"serve_request_timeout_s": 1.5,
                 "serve_drain_timeout_s": 2.0}
    with _cluster(overrides=overrides):
        @serve.deployment(num_replicas=1, route_prefix="/stuck")
        class Stuck:
            def __call__(self):
                time.sleep(30)
                return "late"

        handle = serve.run(Stuck.bind(), http_host="127.0.0.1")
        t0 = time.monotonic()
        code, _, _ = _http(handle.http_port, "/stuck", timeout=30)
        elapsed = time.monotonic() - t0
        assert code == 504
        assert elapsed < 10, f"504 took {elapsed:.1f}s (deadline 1.5s)"
        serve.delete("Stuck")


# ---------------------------------------------------------------------------
# Tentpole: graceful drain — zero lost in-flight, generation re-route
# ---------------------------------------------------------------------------


def test_graceful_drain_under_traffic():
    with _cluster():
        @serve.deployment(num_replicas=3)
        class Steady:
            def __call__(self, x):
                time.sleep(0.15)
                return x * 2

        handle = serve.run(Steady.bind())
        results, errors = [], []
        stop = threading.Event()
        lock = threading.Lock()

        def traffic():
            i = 0
            while not stop.is_set():
                try:
                    out = handle.call(i, timeout=30)
                    with lock:
                        results.append((i, out))
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(e)
                i += 1

        threads = [threading.Thread(target=traffic) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        gen_before = rt.get(
            serve.api._get_controller(create=False)
            .get_routing.remote("Steady"), timeout=30)["generation"]
        # Scale down under traffic: 2 replicas must DRAIN, not die.
        serve.run(Steady.options(num_replicas=1).bind())
        saw_draining = False
        deadline = time.time() + 30
        while time.time() < deadline:
            st = serve.status()["Steady"]
            saw_draining |= st["num_replicas_draining"] > 0
            if st["num_replicas_running"] == 1 and \
                    st["num_replicas_draining"] == 0 and saw_draining:
                break
            time.sleep(0.2)
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join()

        # zero lost in-flight requests across the scale-down
        assert not errors, f"drain lost requests: {errors[:3]}"
        assert all(out == i * 2 for i, out in results)
        assert len(results) > 20
        assert saw_draining, "scale-down never reported DRAINING replicas"
        st = serve.status()["Steady"]
        assert st["num_replicas_running"] == 1
        assert st["num_replicas_draining"] == 0
        # generation bumped => handles re-routed away from DRAINING
        routing = rt.get(
            serve.api._get_controller(create=False)
            .get_routing.remote("Steady"), timeout=30)
        assert routing["generation"] > gen_before
        assert len(routing["replicas"]) == 1
        handle._refresh(force=True)
        assert len(handle._replicas) == 1
        serve.delete("Steady")


# ---------------------------------------------------------------------------
# Tentpole headline: chaos SLO — replica killed mid-open-loop-traffic
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_slo_replica_kill_mid_traffic(chaos_seed):
    """Fault plane kills replicas mid-stream (crash on a matched
    serve.replica.call): every accepted request completes (retried to
    success on another replica), sheds are clean 503 + Retry-After, no
    request outlives the deadline, and p99 recovers after the controller
    reconverges. Seed printed by the fixture for replay."""
    overrides = {"serve_max_queued_requests": 4,
                 "serve_max_ongoing_requests": 2,
                 "serve_request_timeout_s": 15.0}
    with _cluster(overrides=overrides):
        # Loaded BEFORE serve.run: controller, proxy, and every replica
        # (replacements included) inherit the plan at spawn. Only the
        # dedicated "boom" probe crashes — regular traffic crashes with
        # it when they share a replica, and must be retried to success.
        fault_plane.load_plan(
            [{"site": "serve.replica.call", "match": {"method": "boom"},
              "action": "crash", "every": 1}], seed=chaos_seed)

        @serve.deployment(num_replicas=3, route_prefix="/model")
        class Model:
            def __call__(self, x=0):
                time.sleep(0.05)
                return {"x": x, "pid": os.getpid()}

            def boom(self):
                return "unreachable"  # crash fires before user code

        handle = serve.run(Model.bind(), http_host="127.0.0.1")
        port = handle.http_port

        results = []
        lock = threading.Lock()

        def open_loop(tid):
            for i in range(25):
                t0 = time.monotonic()
                code, body, retry_after = _http(
                    port, "/model", {"x": tid * 100 + i}, timeout=25)
                with lock:
                    results.append((code, body, retry_after,
                                    time.monotonic() - t0))
                time.sleep(0.02)

        threads = [threading.Thread(target=open_loop, args=(t,))
                   for t in range(6)]
        for t in threads:
            t.start()

        def kill_one():
            # Direct replica submission (not via the handle's retry
            # wrapper): the crash must hit exactly one LIVE replica per
            # shot — the routing table may still list the previous corpse.
            handle._refresh(force=True)
            for cand in handle._replicas:
                try:
                    rt.get(cand.check_health.remote(), timeout=5)
                except Exception:
                    continue
                cand.handle_request.remote(
                    "boom", cloudpickle.dumps(((), {})))
                return

        time.sleep(0.5)
        kill_one()
        time.sleep(1.0)
        kill_one()
        for t in threads:
            t.join()

        assert len(results) == 150
        codes = [c for c, _, _, _ in results]
        assert set(codes) <= {200, 503}, \
            f"accepted requests were dropped: {set(codes)}"
        for code, body, retry_after, lat in results:
            if code == 503:
                assert retry_after is not None  # clean shed
            else:
                assert body["x"] >= 0
            assert lat < 20.0, f"request outlived the deadline: {lat:.1f}s"
        ok = [r for r in results if r[0] == 200]
        assert len(ok) >= 75, f"only {len(ok)}/150 succeeded under chaos"
        pids = {body["pid"] for _, body, _, _ in ok}

        # -- reconvergence: back to 3 replicas, p99 recovers ------------
        deadline = time.time() + 60
        while time.time() < deadline:
            if serve.status()["Model"]["num_replicas_running"] == 3:
                break
            time.sleep(0.5)
        assert serve.status()["Model"]["num_replicas_running"] == 3
        lat = []
        for i in range(20):
            t0 = time.monotonic()
            code, body, _ = _http(port, "/model", {"x": i})
            lat.append(time.monotonic() - t0)
            assert code == 200
            pids.add(body["pid"])
        assert sorted(lat)[-1] < 5.0, f"p99 did not recover: {lat}"
        # the kills actually happened: traffic + recovery probes span more
        # worker processes than the 3 original replicas (2 were replaced)
        assert len(pids) >= 4, f"no replica was replaced (pids={pids})"
        serve.delete("Model")


# ---------------------------------------------------------------------------
# Satellite: proxy protocol edges (in-process, no cluster)
# ---------------------------------------------------------------------------


class _FakeHandle:
    """Stands in for DeploymentHandle in in-process proxy tests."""
    _replicas = [object()]
    _max_ongoing = 4
    _closed = False

    def call(self, *args, timeout=None, **kwargs):
        if args:
            return {"echo": list(args[0]) if isinstance(args[0], bytes)
                    else args[0]}
        return dict(kwargs) or {"ok": True}


@pytest.fixture
def raw_proxy(monkeypatch):
    from ray_tpu.serve import api as serve_api
    from ray_tpu.serve.http_proxy import HTTPProxy
    monkeypatch.setattr(serve_api, "_handle_for",
                        lambda name: _FakeHandle())
    p = HTTPProxy("127.0.0.1", 0)
    # Pin the routing table: no controller exists to refresh from.
    p._routes_cache = {"/echo": "echo"}
    p._routes_ts = time.monotonic() + 1e9
    yield p
    p.close()
    fault_plane.clear_plan()
    rt_config.clear_override("serve_max_queued_requests")


def _raw_request(port, payload: bytes):
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    s.sendall(payload)
    return s


def _read_response(f):
    status = f.readline().decode("latin1")
    code = int(status.split(" ")[1])
    headers = {}
    while True:
        line = f.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode("latin1").partition(":")
        headers[k.strip().lower()] = v.strip()
    body = f.read(int(headers.get("content-length", 0)))
    return code, headers, body


def _post(path, body=b"{}", extra=""):
    return (f"POST {path} HTTP/1.1\r\nHost: x\r\n{extra}"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


def test_proxy_pipelined_keepalive(raw_proxy):
    s = _raw_request(raw_proxy.port(),
                     _post("/echo", b'{"a": 1}') +
                     _post("/echo", b'{"b": 2}'))
    f = s.makefile("rb")
    c1, _, b1 = _read_response(f)
    c2, _, b2 = _read_response(f)
    assert (c1, c2) == (200, 200)
    assert json.loads(b1) == {"a": 1}
    assert json.loads(b2) == {"b": 2}  # no desync across pipelining
    s.close()


def test_proxy_connection_close(raw_proxy):
    s = _raw_request(raw_proxy.port(),
                     _post("/echo", extra="Connection: close\r\n"))
    f = s.makefile("rb")
    code, _, _ = _read_response(f)
    assert code == 200
    assert f.read(1) == b""  # server honored Connection: close
    s.close()


def test_proxy_chunked_request_501_closes_socket(raw_proxy):
    s = _raw_request(
        raw_proxy.port(),
        b"POST /echo HTTP/1.1\r\nHost: x\r\n"
        b"Transfer-Encoding: chunked\r\n\r\n5\r\nhello\r\n0\r\n\r\n")
    f = s.makefile("rb")
    code, _, _ = _read_response(f)
    assert code == 501
    # socket CLOSED: the unread chunk bytes must not desync a next request
    assert f.read(1) == b""
    s.close()


def test_proxy_bad_content_length(raw_proxy):
    s = _raw_request(
        raw_proxy.port(),
        b"POST /echo HTTP/1.1\r\nHost: x\r\nContent-Length: abc\r\n\r\n")
    code, _, _ = _read_response(s.makefile("rb"))
    assert code == 400
    s.close()


def test_proxy_eof_mid_headers(raw_proxy):
    s = socket.create_connection(("127.0.0.1", raw_proxy.port()),
                                 timeout=10)
    s.sendall(b"POST /echo HTTP/1.1\r\nHost: x\r\nContent-")
    s.close()  # aborted mid-headers: dropped silently, server survives
    time.sleep(0.1)
    s2 = _raw_request(raw_proxy.port(), _post("/echo", b'{"z": 9}'))
    code, _, body = _read_response(s2.makefile("rb"))
    assert code == 200 and json.loads(body) == {"z": 9}
    s2.close()


def test_proxy_admission_fault_and_queue_full_shed(raw_proxy):
    # fault-plane admission rejection => 503 + Retry-After
    fault_plane.load_plan(
        [{"site": "serve.proxy.admit", "action": "raise", "every": 1}])
    s = _raw_request(raw_proxy.port(), _post("/echo"))
    code, headers, _ = _read_response(s.makefile("rb"))
    assert code == 503 and headers.get("retry-after") == "1"
    s.close()
    fault_plane.clear_plan()
    # zero queue budget (applied via the live-reconfigure path the
    # controller forwards to the proxy process) => unconditional shed
    applied = raw_proxy.reconfigure({"serve_max_queued_requests": 0})
    assert applied == {"serve_max_queued_requests": 0}
    s = _raw_request(raw_proxy.port(), _post("/echo"))
    code, headers, _ = _read_response(s.makefile("rb"))
    assert code == 503 and headers.get("retry-after") == "1"
    s.close()
    # value None clears the override: admission back to the default
    applied = raw_proxy.reconfigure({"serve_max_queued_requests": None})
    assert applied["serve_max_queued_requests"] > 0
    s = _raw_request(raw_proxy.port(), _post("/echo"))
    code, _, body = _read_response(s.makefile("rb"))
    assert code == 200
    s.close()
    assert raw_proxy.stats()["shed"] == 2


def test_proxy_close_is_hygienic():
    from ray_tpu.serve import http_proxy
    p = http_proxy.HTTPProxy("127.0.0.1", 0)
    assert any(q is p for q in http_proxy._live_proxies)
    p.close()
    assert p.closed
    assert not any(q is p for q in http_proxy._live_proxies)


# ---------------------------------------------------------------------------
# Tentpole (PR 36): admission is the only limit on calls in flight. The
# proxy runs in THIS process: its admission book, its handle's in-flight
# book and its threads are the test's to read.
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _proxy_here(overrides=None):
    from ray_tpu.serve.http_proxy import HTTPProxy
    from ray_tpu.util import events
    events.reset_for_tests()    # this process's ring: no earlier test's spans
    with _cluster(overrides=overrides):
        proxy = HTTPProxy("127.0.0.1", 0)
        try:
            yield proxy
        finally:
            proxy.close()


def _deploy_sleeper(name, cap, seconds):
    @serve.deployment(name=name, route_prefix=f"/{name}",
                      max_ongoing_requests=cap)
    def sleeper(x=0):
        time.sleep(seconds)
        return {"x": x}

    serve.run(sleeper.bind())


def _burst(port, path, callers, rounds=1):
    """``callers`` closed-loop clients, ``rounds`` requests each -> codes."""
    import concurrent.futures

    def client(i):
        return [_http(port, path, {"x": i})[0] for _ in range(rounds)]

    with concurrent.futures.ThreadPoolExecutor(callers) as pool:
        return [c for codes in pool.map(client, range(callers))
                for c in codes]


def _spans_once(kind, count, timeout=30.0):
    """Span records at the conductor once ``count`` of ``kind`` are there
    (this process's tail is flushed now, a replica's every half second)."""
    from ray_tpu import state
    from ray_tpu.util import events
    deadline = time.time() + timeout
    while True:
        events.flush_now()
        spans = [s for s in state.list_spans()
                 if s["attrs"] and "span" in s["attrs"]]
        if sum(s["kind"] == kind for s in spans) >= count or \
                time.time() > deadline:
            return spans
        time.sleep(0.2)


def _values(spans, kind, attr=None):
    return [s["attrs"][attr] if attr else s["value"]
            for s in spans if s["kind"] == kind]


def test_callers_past_the_default_executor_are_all_inside():
    """A budget that covers them lets more callers in at once than the
    event loop's default executor has threads (its size was the limit)."""
    callers = min(32, (os.cpu_count() or 1) + 4) + 4
    with _proxy_here() as proxy:
        _deploy_sleeper("wide", callers, 1.0)
        assert _http(proxy.port(), "/wide")[0] == 200     # handle warm
        assert _burst(proxy.port(), "/wide", callers) == [200] * callers
        spans = _spans_once("serve.replica.call", callers + 1)
        assert max(_values(spans, "serve.replica.call", "inflight")) \
            == callers
        assert proxy.stats()["shed"] == 0


def test_closed_loop_at_the_cap_finds_its_slot(monkeypatch):
    """Budget-many closed-loop callers, three rounds: a re-sent request
    finds the slot its caller's last call gave back. The handle's book
    never reads full to ``_pick``, nothing is retried and nothing shed."""
    from ray_tpu.serve.api import DeploymentHandle
    from ray_tpu.serve.controller import ReplicaBusyError
    cap, rounds, full = 8, 3, []
    pick = DeploymentHandle._pick

    def counting_pick(self, *args, **kwargs):
        try:
            return pick(self, *args, **kwargs)
        except ReplicaBusyError:
            full.append(dict(self._inflight))
            raise

    monkeypatch.setattr(DeploymentHandle, "_pick", counting_pick)
    with _proxy_here() as proxy:
        _deploy_sleeper("loop", cap, 0.5)
        assert _http(proxy.port(), "/loop")[0] == 200
        codes = _burst(proxy.port(), "/loop", cap, rounds)
        assert codes == [200] * (cap * rounds)
        assert full == []
        spans = _spans_once("serve.replica.call", cap * rounds + 1)
        assert _values(spans, "serve.handle.call", "retries") == \
            [0] * (cap * rounds + 1)
        assert len(_values(spans, "serve.handle.slot_wait")) == \
            cap * rounds + 1
        assert max(_values(spans, "serve.replica.call", "inflight")) == cap
        stats = proxy.stats()
        assert (stats["served"], stats["shed"], stats["ongoing"]) == \
            (cap * rounds + 1, 0, 0)


def test_the_budget_still_binds():
    """Cap 4, 12 callers: four inside at once, the other eight wait in
    ``serve.proxy.admit`` (not for a thread), none shed."""
    cap, callers, run_s = 4, 12, 1.0
    with _proxy_here() as proxy:
        _deploy_sleeper("narrow", cap, run_s)
        assert _http(proxy.port(), "/narrow")[0] == 200
        assert _burst(proxy.port(), "/narrow", callers) == [200] * callers
        spans = _spans_once("serve.replica.call", callers + 1)
        assert max(_values(spans, "serve.replica.call", "inflight")) == cap
        admits = sorted(_values(spans, "serve.proxy.admit"))
        assert len(admits) == callers + 1
        # the first four (and the warm one) walked in; eight waited for
        # at least most of a run
        assert admits[cap] < run_s / 2 <= admits[cap + 1]
        assert max(_values(spans, "serve.proxy.thread_wait")) < run_s / 2
        assert proxy.stats()["shed"] == 0


def test_deadline_cancels_the_call_and_frees_both_books(monkeypatch):
    """504: the in-flight call is cancelled, and by the time the client
    has its answer the slot is back in the proxy's admission book and in
    the handle's in-flight book."""
    from ray_tpu.serve.api import _handle_for
    cancelled = []
    cancel = rt.cancel
    monkeypatch.setattr(
        rt, "cancel", lambda ref, **kw: (cancelled.append(ref),
                                         cancel(ref, **kw))[1])
    overrides = {"serve_request_timeout_s": 1.0,
                 "serve_drain_timeout_s": 2.0}
    with _proxy_here(overrides) as proxy:
        _deploy_sleeper("stuck", 4, 5.0)
        code, _, _ = _http(proxy.port(), "/stuck", timeout=30)
        assert code == 504
        assert len(cancelled) == 1
        stats = proxy.stats()
        assert (stats["timeouts"], stats["ongoing"], stats["queued"]) == \
            (1, 0, 0)
        assert set(_handle_for("stuck")._inflight.values()) == {0}
        serve.delete("stuck")


def test_call_threads_never_outnumber_the_calls_in_flight():
    """``_CallThreads`` alone, under a short switch interval: waves of
    more concurrent calls than cores, each wave submitted when the last
    one's futures resolved (as admission does). Every result is its own
    call's, a thread is reused, never more threads than one wave, and
    ``close()`` leaves none alive."""
    import sys
    from ray_tpu.serve.http_proxy import _CallThreads
    wave, waves = 4 * (os.cpu_count() or 1), 20
    calls = _CallThreads()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for w in range(waves):
            gate = threading.Event()
            futs = [calls.submit(lambda i=i: (gate.wait(10), w, i)[1:])
                    for i in range(wave)]
            gate.set()
            assert [f.result(timeout=10) for f in futs] == \
                [(w, i) for i in range(wave)]
        with pytest.raises(ZeroDivisionError):      # the awaiter's to read
            calls.submit(lambda: 1 // 0).result(timeout=10)
        assert len(calls._threads) == wave
    finally:
        sys.setswitchinterval(interval)
        calls.close()
    assert not any(t.is_alive() for t in calls._threads)
    with pytest.raises(RuntimeError):
        calls.submit(lambda: None)


def test_close_joins_every_thread_of_the_proxy():
    """``close()`` ends and joins the loop thread, the route thread and
    the threads admitted calls ran on."""
    def mine():
        return sorted(t.name for t in threading.enumerate()
                      if t.name.startswith(("serve-proxy", "serve-routes",
                                            "serve-call")))

    assert mine() == []
    with _proxy_here() as proxy:
        _deploy_sleeper("four", 4, 0.8)
        assert _burst(proxy.port(), "/four", 4, rounds=2) == [200] * 8
        names = mine()
        # one thread an admitted call, reused by the second round
        assert sum(n.startswith("serve-call") for n in names) == 4
        assert "serve-proxy" in names
        proxy.close()
        assert mine() == []
        assert proxy.closed


# ---------------------------------------------------------------------------
# Tentpole: adaptive micro-batching (in-process, no cluster)
# ---------------------------------------------------------------------------


def _drive_batch(fn, waves, wave_size, pause):
    import concurrent.futures
    outs = []
    with concurrent.futures.ThreadPoolExecutor(max_workers=wave_size) as ex:
        for w in range(waves):
            futs = [ex.submit(fn, w * wave_size + i)
                    for i in range(wave_size)]
            outs.extend(f.result(timeout=30) for f in futs)
            time.sleep(pause)
    return outs


def _batch_window(before_keys):
    from ray_tpu.serve.api import _batch_states
    new = [k for k in _batch_states if k not in before_keys]
    assert len(new) == 1
    return _batch_states[new[0]]["window"]


def test_adaptive_batch_window_grows_under_slo():
    from ray_tpu.serve.api import _batch_states
    before = set(_batch_states)

    @serve.batch(max_batch_size=64, batch_wait_timeout_s=0.01,
                 target_p99_ms=500.0)
    def fast(items):
        return [i * 2 for i in items]

    outs = _drive_batch(fast, waves=4, wave_size=6, pause=0.05)
    assert sorted(outs) == [i * 2 for i in range(24)]
    # p99 far under target: the window grew multiplicatively
    assert _batch_window(before) > 0.012


def test_adaptive_batch_window_shrinks_on_breach():
    from ray_tpu.serve.api import _batch_states
    before = set(_batch_states)

    @serve.batch(max_batch_size=64, batch_wait_timeout_s=0.02,
                 target_p99_ms=5.0)
    def slow(items):
        time.sleep(0.08)
        return list(items)

    outs = _drive_batch(slow, waves=3, wave_size=4, pause=0.05)
    assert sorted(outs) == list(range(12))
    # p99 (>80ms) breaches the 5ms target: window halved repeatedly
    assert _batch_window(before) < 0.02


def test_fixed_batch_window_unchanged_without_target():
    from ray_tpu.serve.api import _batch_states
    before = set(_batch_states)

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.03)
    def plain(items):
        time.sleep(0.05)
        return list(items)

    outs = _drive_batch(plain, waves=2, wave_size=3, pause=0.04)
    assert sorted(outs) == list(range(6))
    assert _batch_window(before) == 0.03  # no target => no adaptation

"""Public serve API: @deployment / run / handles / @batch.

Role parity: serve/api.py + handle.py:78 (DeploymentHandle -> Router) +
batching (serve/batching.py). Handle routing is queue-length-aware
power-of-two-choices over replica actors (parity: router.py:263 picks the
replica with fewest in-flight), hardened with dead-replica eviction and
one retry on a different replica (parity: router's
ActorReplicaWrapper failure handling + request retries)."""

from __future__ import annotations

import functools
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

from ray_tpu.core.refs import ChannelResolvedRef
from ray_tpu.util import events, lockcheck


def _get_controller(create: bool = True):
    import ray_tpu as rt
    from ray_tpu.serve.controller import ServeController
    try:
        return rt.get_actor(ServeController.CONTROLLER_NAME)
    except ValueError:
        if not create:
            raise
        cls = rt.remote(ServeController)
        return cls.options(name=ServeController.CONTROLLER_NAME,
                           lifetime="detached", max_concurrency=32,
                           get_if_exists=True).remote()


def _retryable(exc: BaseException) -> bool:
    """True when a failed call may be retried on ANOTHER replica: the
    replica died / its worker vanished / it shed the call at its in-flight
    cap. User exceptions (TaskError wrapping application code) are not
    retried — re-running user code on failure is an application policy."""
    from ray_tpu.core.exceptions import (
        ActorError, ObjectLostError, WorkerCrashedError)
    from ray_tpu.serve.controller import ReplicaBusyError
    kinds = (ActorError, WorkerCrashedError, ObjectLostError,
             ReplicaBusyError, ConnectionError)
    seen = 0
    while exc is not None and seen < 8:
        if isinstance(exc, kinds):
            return True
        exc = getattr(exc, "cause", None)
        seen += 1
    return False


def _emit(kind: str, ident: str, value: float = 1.0, **attrs) -> None:
    try:
        events.emit(kind, ident, value=value,
                    attrs=attrs if attrs else None)
    except Exception:
        pass


class ServeCallRef(ChannelResolvedRef):
    """Ref returned by DeploymentHandle.remote(): resolves through the
    handle so a call that died with its replica (or was shed at the
    replica's in-flight cap) is retried ONCE on a different replica,
    transparently to rt.get()/rt.wait(). Timeouts cancel the in-flight
    actor task instead of leaking it. ``_key`` is the replica whose slot
    of the handle's in-flight book the call holds now; ``tracked`` says
    who gives it back: the handle's drainer (``.remote()``) or the caller
    (``.call()``)."""

    __slots__ = ("_handle", "_inner", "_key", "_args_blob", "_method",
                 "_retried", "_tracked")

    def __init__(self, handle: "DeploymentHandle", inner, key,
                 method: str, args_blob: bytes, tracked: bool = True):
        super().__init__(inner.id)
        self._handle = handle
        self._inner = inner
        self._key = key
        self._method = method
        self._args_blob = args_blob
        self._retried = False
        self._tracked = tracked

    def _resolve(self, timeout: Optional[float] = None):
        import ray_tpu as rt
        from ray_tpu.core.exceptions import GetTimeoutError
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            remaining = None if deadline is None else \
                max(0.0, deadline - time.monotonic())
            try:
                return rt.get(self._inner, timeout=remaining)
            except GetTimeoutError:
                # Deadline: the caller gets the timeout, the replica gets
                # a cancel — the call must not keep a slot occupied (and
                # the proxy must not leak work for clients that are gone).
                try:
                    rt.cancel(self._inner)
                except Exception:
                    pass
                _emit("serve.timeout", self._handle.name)
                raise
            except Exception as e:  # noqa: BLE001
                if self._retried or not _retryable(e):
                    raise
                self._retried = True
                wait_s = 2.0 if deadline is None else \
                    max(0.0, deadline - time.monotonic())
                again = self._handle._resubmit(
                    self._key, self._method, self._args_blob,
                    wait_s=min(wait_s, 30.0), track=self._tracked)
                if again is None:
                    raise
                _emit("serve.retry", self._handle.name)
                # the failed replica's slots went with its eviction
                self._inner, self._key = again

    def _is_ready(self) -> bool:
        import ray_tpu as rt
        done, _ = rt.wait([self._inner], num_returns=1, timeout=0)
        return bool(done)


class DeploymentHandle:
    """Client-side router over a deployment's replicas."""

    def __init__(self, name: str, method: str = "__call__"):
        self.name = name
        self.method = method
        self._replicas: List[Any] = []
        self._generation = -1
        self._max_ongoing = 0
        self._ts = 0.0
        self._lock = lockcheck.named_lock("serve.handle")
        self._inflight: Dict[Any, int] = {}
        # Evicted-replica quarantine: actor_id -> routing generation at
        # eviction time. The controller's table lags a death by up to a
        # reconcile period; without this a refresh at the SAME generation
        # would re-admit the corpse and a retry could land right back on
        # it. A generation bump (the controller noticed) lifts the
        # quarantine.
        self._suspects: Dict[Any, int] = {}
        self._refreshing = False    # a background fetch of the table runs
        self._closed = False
        # Opt-in compiled fast path (serve.run(..., compile=True)): one
        # compiled one-step graph per replica; requests ride a persistent
        # shm channel instead of a task submission per call.
        self._compile = False
        self._cgraphs: Dict[Any, Any] = {}

    def options(self, method_name: str) -> "DeploymentHandle":
        return DeploymentHandle(self.name, method_name)

    def __reduce__(self):
        # Handles travel into replica __init__ args (DAG composition):
        # rebuild fresh on the receiving worker (locks/caches don't ship).
        return (DeploymentHandle, (self.name, self.method))

    def _refresh(self, force: bool = False):
        """Bring the routing table up to date. A table that is merely old
        (over a second) still routes: ONE thread fetches the next one, off
        the requests' path, and every request goes on with what the handle
        has. Only a handle with no table, one told to fetch again
        (``_ts`` 0: a replica was evicted, or the background fetch failed)
        or ``force`` waits for the controller. (Until PR 51 every request
        that found the table old made the round trip itself: after a call of
        8 s all 48 callers of a closed loop did, at once, each for up to
        0.1 s, and their requests reached the batcher too far apart for one
        flush.)"""
        with self._lock:
            if not force and time.monotonic() - self._ts < 1.0 \
                    and self._replicas:
                return
            if not force and self._ts > 0.0 and self._replicas:
                if not self._refreshing:
                    self._refreshing = True
                    threading.Thread(target=self._fetch_routing_quietly,
                                     daemon=True,
                                     name="serve-handle-refresh").start()
                return
        self._fetch_routing()

    def _fetch_routing_quietly(self) -> None:
        try:
            self._fetch_routing()
        except Exception:   # noqa: BLE001
            # not lost: the next request fetches on its own path (``_ts`` 0)
            # and raises what the controller said, as every request used to
            with self._lock:
                self._ts = 0.0
        finally:
            with self._lock:
                self._refreshing = False

    def _fetch_routing(self) -> None:
        import ray_tpu as rt
        # The routing fetch is a controller round-trip (30s timeout) and
        # must NOT run under the handle lock: concurrent requests keep
        # routing on the previous table instead of convoying behind one
        # refresher. Concurrent fetches are benign — the newest table
        # wins and the generation compare below de-dups the bookkeeping.
        controller = _get_controller(create=False)
        routing = rt.get(
            controller.get_routing.remote(self.name), timeout=30)
        with self._lock:
            gen = routing["generation"]
            self._suspects = {k: g for k, g in self._suspects.items()
                              if g == gen}
            self._replicas = [r for r in routing["replicas"]
                              if r._rt_actor_id not in self._suspects]
            self._max_ongoing = routing["max_ongoing"]
            if gen != self._generation:
                # Membership changed: drop in-flight book entries for
                # replicas that left (DRAINING/dead) so p2c never favors a
                # ghost, and tear down any compiled graph pinned to one.
                self._generation = routing["generation"]
                live = {r._rt_actor_id for r in self._replicas}
                for k in [k for k in self._inflight if k not in live]:
                    self._inflight.pop(k, None)
                dead_graphs = [self._cgraphs.pop(k) for k in
                               list(self._cgraphs) if k not in live]
            else:
                dead_graphs = []
            self._ts = time.monotonic()
        for cg in dead_graphs:
            try:
                cg.teardown()
            except Exception:
                pass

    def _evict(self, key) -> None:
        """Forget a replica that failed a submission mid-window — the
        controller will reap it on its own schedule; this handle must stop
        routing to it NOW."""
        with self._lock:
            self._replicas = [r for r in self._replicas
                              if r._rt_actor_id != key]
            self._suspects[key] = self._generation
            self._inflight.pop(key, None)
            cg = self._cgraphs.pop(key, None)
            self._ts = 0.0   # next pick re-fetches the routing table
        if cg is not None:
            try:
                cg.teardown()
            except Exception:
                pass

    def _pick(self, exclude=frozenset(), enforce_cap: bool = False):
        """Power-of-two-choices on locally tracked in-flight counts."""
        self._refresh()
        with self._lock:
            candidates = [r for r in self._replicas
                          if r._rt_actor_id not in exclude]
            if enforce_cap and self._max_ongoing > 0:
                candidates = [
                    r for r in candidates
                    if self._inflight.get(r._rt_actor_id, 0) <
                    self._max_ongoing]
        if not candidates:
            if not self._replicas:
                raise RuntimeError(
                    f"deployment {self.name!r} has no replicas")
            from ray_tpu.serve.controller import ReplicaBusyError
            raise ReplicaBusyError(
                f"all replicas of {self.name!r} at in-flight cap")
        if len(candidates) == 1:
            return candidates[0]
        a, b = random.sample(candidates, 2)
        with self._lock:
            return a if self._inflight.get(a._rt_actor_id, 0) <= \
                self._inflight.get(b._rt_actor_id, 0) else b

    def _submit(self, replica, args_blob: bytes, track: bool = True):
        """Take a slot of ``replica`` in the in-flight book and submit.
        The slot is given back once, when the request completes: with
        ``track`` by the drainer thread, which watches every outstanding
        ref of this handle (the caller of ``.remote()`` may never resolve
        its ref); without it by the caller, through ``_release``."""
        key = replica._rt_actor_id
        with self._lock:
            self._inflight[key] = self._inflight.get(key, 0) + 1
        try:
            ref = replica.handle_request.remote(self.method, args_blob)
        except BaseException:  # noqa: BLE001 - re-raised, slot returned
            self._release(key)
            raise
        if track:
            self._track(ref, key)
        return ref, key

    def _release(self, key) -> None:
        with self._lock:
            self._inflight[key] = max(0, self._inflight.get(key, 1) - 1)

    def _resubmit(self, failed_key, method: str, args_blob: bytes,
                  wait_s: float = 0.0, track: bool = True):
        """Retry path for ServeCallRef: evict the failed replica, pick a
        DIFFERENT one, submit there. The pick honors the per-replica
        in-flight cap — a retry dumped onto a saturated replica would be
        shed a second time and surface as a hard failure — waiting up to
        ``wait_s`` for a slot. -> (ref, the replica's key), or None when
        no alternative exists."""
        from ray_tpu.serve.controller import ReplicaBusyError
        if failed_key is not None:
            self._evict(failed_key)
        exclude = frozenset() if failed_key is None \
            else frozenset({failed_key})
        deadline = time.monotonic() + wait_s
        while True:
            try:
                replica = self._pick(exclude=exclude, enforce_cap=True)
                break
            except ReplicaBusyError:
                if time.monotonic() >= deadline:
                    return None
                time.sleep(0.005)
            except Exception:
                return None
        return self._submit(replica, args_blob, track=track)

    def remote(self, *args, **kwargs):
        replica = self._pick()
        args_blob = cloudpickle.dumps((args, kwargs))
        if self._compile:
            key = replica._rt_actor_id
            with self._lock:
                self._inflight[key] = self._inflight.get(key, 0) + 1
            ref = self._remote_compiled(replica, key, args_blob)
            if ref is not None:
                self._track(ref, key)
                return ref
            self._release(key)
        ref, key = self._submit(replica, args_blob)
        return ServeCallRef(self, ref, key, self.method, args_blob)

    def call(self, *args, timeout: Optional[float] = None, **kwargs):
        """Blocking call with deadline + capacity backpressure: waits for
        a replica slot (per-replica in-flight cap), submits, resolves with
        the one-retry policy. Raises ReplicaBusyError when no capacity
        frees up in time, GetTimeoutError past the deadline. This is the
        proxy's dispatch path.

        The caller gives its slot of the in-flight book back itself, the
        moment its call resolved (reply, error or deadline), so the next
        ``call`` finds the slot at once. The drainer thread, which lowers
        the book one ``rt.wait`` round a ref, is ``.remote()``'s alone: at
        a full cap it would be the wait of every re-sent request."""
        from ray_tpu import config
        from ray_tpu.serve.controller import ReplicaBusyError
        if timeout is None:
            timeout = float(config.get("serve_request_timeout_s"))
        deadline = time.monotonic() + timeout
        args_blob = cloudpickle.dumps((args, kwargs))
        with events.span("serve.handle.slot_wait"):
            while True:
                try:
                    replica = self._pick(enforce_cap=True)
                    break
                except ReplicaBusyError:
                    if time.monotonic() >= deadline:
                        raise
                    time.sleep(0.005)
        with events.span("serve.handle.call") as call:
            ref, key = self._submit(replica, args_blob, track=False)
            sref = ServeCallRef(self, ref, key, self.method, args_blob,
                                tracked=False)
            try:
                return sref._resolve(max(0.0, deadline - time.monotonic()))
            finally:
                self._release(sref._key)
                call.set(retries=int(sref._retried))

    def _remote_compiled(self, replica, key, args_blob):
        """Submit through the replica's compiled graph; None means the
        caller should fall back to the classic task path (compile failed,
        or a prior request's exception poisoned the graph — that failed
        request still raises its own error at get())."""
        try:
            with self._lock:
                cg = self._cgraphs.get(key)
            if cg is None:
                from ray_tpu.dag.compiled import compile_actor_method
                cg = compile_actor_method(
                    replica, "handle_request", const_args=(self.method,),
                    max_in_flight=8)
                with self._lock:
                    self._cgraphs[key] = cg
            return cg.execute(args_blob)
        except Exception:
            with self._lock:
                cg = self._cgraphs.pop(key, None)
            if cg is not None:
                try:
                    cg.teardown()
                except Exception:
                    pass
            return None

    def teardown_compiled(self) -> None:
        """Tear down this handle's compiled replica graphs (restores the
        replicas to classic task service; safe to call repeatedly)."""
        with self._lock:
            graphs, self._cgraphs = list(self._cgraphs.values()), {}
            self._compile = False
        for cg in graphs:
            try:
                cg.teardown()
            except Exception:
                pass

    def close(self) -> None:
        """Stop the drainer thread and drop compiled graphs. Handles are
        cheap to recreate; serve.shutdown() closes the memoized ones."""
        self.teardown_compiled()
        with self._lock:
            self._closed = True
            if hasattr(self, "_outstanding"):
                self._outstanding = []

    def _track(self, ref, key) -> None:
        with self._lock:
            if not hasattr(self, "_outstanding"):
                self._outstanding = []
                threading.Thread(target=self._drain_loop, daemon=True,
                                 name=f"serve-drain-{self.name}").start()
            self._outstanding.append((ref, key))

    def _drain_loop(self) -> None:
        import ray_tpu as rt
        while not self._closed:
            with self._lock:
                pending = list(self._outstanding)
            if not pending:
                time.sleep(0.02)
                continue
            try:
                done, _ = rt.wait([r for r, _ in pending],
                                  num_returns=1, timeout=1.0)
            except Exception:
                # Runtime gone (shutdown between wait calls): this thread
                # has nothing left to account for.
                return
            if done:
                done_set = set(done)
                with self._lock:
                    still = []
                    for r, k in self._outstanding:
                        if r in done_set:
                            self._inflight[k] = max(
                                0, self._inflight.get(k, 1) - 1)
                        else:
                            still.append((r, k))
                    self._outstanding = still


class Deployment:
    """Result of @serve.deployment: holds the target + config, bindable."""

    def __init__(self, target, name: str, num_replicas: int = 1,
                 ray_actor_options: Optional[dict] = None,
                 user_config=None, route_prefix: Optional[str] = None,
                 max_concurrent_queries: int = 100,
                 autoscaling_config: Optional[dict] = None,
                 init_grace_s: float = 120.0,
                 max_ongoing_requests: int = 0):
        self._target = target
        self.name = name
        self.num_replicas = num_replicas
        self.ray_actor_options = ray_actor_options or {}
        self.user_config = user_config
        self.route_prefix = route_prefix if route_prefix is not None \
            else f"/{name}"
        self.max_concurrent_queries = max_concurrent_queries
        self.autoscaling_config = autoscaling_config
        # How long a spawned replica may stay silent while __init__ runs
        # (model loads) before an unanswered health ping means death.
        self.init_grace_s = init_grace_s
        # Per-replica in-flight cap (0 = the serve_max_ongoing_requests
        # config default). Past the cap a replica sheds instead of queues.
        self.max_ongoing_requests = max_ongoing_requests
        self._init_args = ((), {})

    def options(self, **updates) -> "Deployment":
        d = Deployment(self._target, updates.pop("name", self.name),
                       self.num_replicas, dict(self.ray_actor_options),
                       self.user_config, self.route_prefix,
                       self.max_concurrent_queries, self.autoscaling_config,
                       self.init_grace_s, self.max_ongoing_requests)
        for k, v in updates.items():
            setattr(d, k, v)
        d._init_args = self._init_args
        return d

    def bind(self, *args, **kwargs) -> "Application":
        """Bind init args — which may include other bound Applications:
        ``Ensemble.bind(ModelA.bind(), ModelB.bind())`` builds a deployment
        GRAPH (parity: the serve DAG API, serve/api.py build/run). At
        serve.run the graph deploys bottom-up and each nested Application
        arrives in __init__ as a live DeploymentHandle."""
        d = self.options()
        d._init_args = (args, kwargs)
        return Application(d)

    def deploy(self, *init_args, **init_kwargs) -> DeploymentHandle:
        import ray_tpu as rt
        controller = _get_controller()
        rt.get(controller.deploy.remote(
            self.name, cloudpickle.dumps(self._target),
            cloudpickle.dumps((init_args, init_kwargs)),
            self.num_replicas, self.ray_actor_options, self.user_config,
            self.route_prefix, self.max_concurrent_queries,
            self.autoscaling_config, self.init_grace_s,
            self.max_ongoing_requests), timeout=300)
        return DeploymentHandle(self.name)


class Application:
    def __init__(self, deployment: Deployment):
        self.deployment = deployment


def deployment(target=None, *, name: Optional[str] = None, **config):
    """@serve.deployment decorator over a class or function."""
    def wrap(t):
        return Deployment(t, name or t.__name__, **config)
    if target is not None:
        return wrap(target)
    return wrap


def _deploy_graph(app: "Application",
                  _seen: Optional[dict] = None) -> DeploymentHandle:
    """Deploy an application graph bottom-up: nested bound Applications in
    the init args deploy first and are replaced by their handles. Shared
    nodes (diamond DAGs) deploy exactly once (memoized by identity)."""
    if _seen is None:
        _seen = {}
    if id(app) in _seen:
        return _seen[id(app)]
    d = app.deployment
    args, kwargs = d._init_args

    def resolve(v):
        if isinstance(v, Application):
            return _deploy_graph(v, _seen)
        if isinstance(v, Deployment):
            return _deploy_graph(v.bind(), _seen)
        if isinstance(v, (list, tuple)):
            return type(v)(resolve(x) for x in v)
        if isinstance(v, dict):
            return {k: resolve(x) for k, x in v.items()}
        return v

    args = tuple(resolve(a) for a in args)
    kwargs = {k: resolve(v) for k, v in kwargs.items()}
    handle = d.deploy(*args, **kwargs)
    _seen[id(app)] = handle
    return handle


def run(app, *, http_host: Optional[str] = None,
        http_port: int = 0, compile: bool = False) -> DeploymentHandle:
    """Deploy an Application (parity: serve.run), including DAGs built
    with nested ``.bind()`` calls. ``compile=True`` routes the RETURNED
    handle's requests over compiled execution graphs (dag/compiled.py):
    per-replica persistent shm channels instead of a task submission per
    request. Handles nested inside the graph stay on the classic path."""
    import ray_tpu as rt
    if isinstance(app, Deployment):
        app = app.bind()
    handle = _deploy_graph(app)
    handle._compile = bool(compile)
    if http_host is not None:
        controller = _get_controller()
        port = rt.get(controller.start_http.remote(http_host, http_port),
                      timeout=120)
        handle.http_port = port
    # wait for replicas to come up
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            handle._refresh()
            if handle._replicas:
                break
        except Exception:
            pass
        time.sleep(0.2)
    return handle


def get_deployment_handle(name: str, method: str = "__call__"
                          ) -> DeploymentHandle:
    return DeploymentHandle(name, method)


# Proxy-side handle cache: ONE handle per deployment per process. A fresh
# handle per request would spawn a drainer thread each (leak) and reset
# the in-flight book the p2c router and the capacity caps depend on.
_handles: Dict[str, DeploymentHandle] = {}
_handles_lock = threading.Lock()


def _handle_for(name: str) -> DeploymentHandle:
    with _handles_lock:
        h = _handles.get(name)
        if h is None or h._closed:
            h = _handles[name] = DeploymentHandle(name)
        return h


def status() -> Dict[str, dict]:
    import ray_tpu as rt
    return rt.get(_get_controller(create=False).status.remote(), timeout=30)


def delete(name: str) -> None:
    import ray_tpu as rt
    rt.get(_get_controller(create=False).delete_deployment.remote(name),
           timeout=60)


def shutdown() -> None:
    import ray_tpu as rt
    with _handles_lock:
        stale = list(_handles.values())
        _handles.clear()
    for h in stale:
        try:
            h.close()
        except Exception:
            pass
    try:
        controller = _get_controller(create=False)
    except ValueError:
        return
    try:
        rt.get(controller.graceful_shutdown.remote(), timeout=60)
    except Exception:
        pass
    try:
        rt.kill(controller)
    except Exception:
        pass


# Per-process batching state, keyed by a decoration-time uuid so the
# wrapper stays picklable (locks/queues never enter the closure — a
# deployment class containing a @batch method is cloudpickled to replicas).
_batch_states: Dict[str, dict] = {}
_batch_states_lock = threading.Lock()


def _batch_state(key: str, window_s: float) -> dict:
    with _batch_states_lock:
        st = _batch_states.get(key)
        if st is None:
            import collections
            st = _batch_states[key] = {
                "lock": threading.Lock(), "pending": [],
                # one flush at a time: ``running`` while ``fn`` has a
                # batch, ``epoch`` counts the flushes begun, ``armed`` says
                # that the assembling batch's timer is set
                "running": False, "epoch": 0, "armed": False,
                # perf_counter() as the last flush's fn returned
                "returned": None,
                # Adaptive window state: current flush window plus the
                # recent per-request latencies the controller law reads.
                "window": window_s,
                "lat": collections.deque(maxlen=256),
            }
        return st


def _adapt_window(st: dict, target_p99_ms: float,
                  base_window_s: float) -> Optional[float]:
    """AIMD-flavored window law keyed off observed request p99: grow the
    flush window multiplicatively while comfortably under the SLO target
    (bigger batches amortize one forward over more requests), halve it the
    moment p99 breaches (latency recovers within a flush or two). Bounds
    keep a misconfigured target from freezing (window->0 busy-flush) or
    stalling (window >> SLO) the pipeline. -> the p99 it read, in ms."""
    lat = sorted(st["lat"])
    if not lat:
        return None
    p99_ms = lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1000.0
    lo, hi = base_window_s / 10.0, base_window_s * 10.0
    if p99_ms > target_p99_ms:
        st["window"] = max(lo, st["window"] * 0.5)
    elif p99_ms < 0.8 * target_p99_ms:
        st["window"] = min(hi, st["window"] * 1.25)
    return p99_ms


class _BatchWait:
    """One caller's ``serve.batch.wait``: begun on the caller's thread at
    enqueue, recorded by the flush that took it when that starts ``fn``."""

    __slots__ = ("ts", "started", "caller")

    def __init__(self):
        self.ts, self.started = time.time(), time.perf_counter()
        self.caller = events.current() or {}

    def record(self, fn_start: float, flush_id: str) -> None:
        events.span_record(
            "serve.batch.wait", self.ts, fn_start - self.started,
            ident=self.caller.get("ident"), parent=self.caller.get("span"),
            flush=flush_id)


def batch(_fn=None, *, max_batch_size: int = 8,
          batch_wait_timeout_s: float = 0.01,
          target_p99_ms: Optional[float] = None):
    """Dynamic request batching (parity: serve/batching.py @serve.batch):
    concurrent single calls coalesce into one list-call of the wrapped
    function — the TPU path to batched jitted forwards.

    One batch at a time, as the reference's batch queue: a batch goes to
    the function when it is full, or ``batch_wait_timeout_s`` after it
    began to assemble: at its first arrival where the function was idle,
    and where calls waited through a running batch, when that batch ENDS,
    so that the callers it answers (a closed loop's re-send at once) join
    the ones that waited. Until PR 51 every arrival set a timer of its
    own, and a timer that fired while the function ran carved what had
    arrived by then into a batch of its own behind it: three callers late
    by more than the window were a call of three rows for as long as the
    loop ran, whatever the call cost (8.4 s at 48 rows of a hybrid stack).

    With ``target_p99_ms`` set the flush window ADAPTS instead of staying
    fixed: it grows while observed p99 sits under the SLO target and
    halves on breach, so batch size tracks offered load without trading
    away the latency budget. ``batch_wait_timeout_s`` is then the initial
    window and anchors the adaptation bounds (x0.1 .. x10)."""
    def wrap(fn):
        import uuid
        state_key = uuid.uuid4().hex

        def arm(st, delay: float, cause: str) -> None:
            """Under the state's lock: the assembling batch's one timer.
            ``cause`` is what its flush records, if it is the timer that
            sends the batch: ``window`` (set at the batch's first arrival)
            or ``after_running`` (set by the end of the batch it waited
            through)."""
            if st["armed"]:
                return
            st["armed"] = True
            timer = threading.Timer(delay, timed_flush,
                                    (st["epoch"], cause))
            timer.daemon = True
            timer.start()

        def timed_flush(epoch: int, cause: str) -> None:
            st = _batch_state(state_key, batch_wait_timeout_s)
            with st["lock"]:
                if epoch != st["epoch"]:
                    return      # that batch filled and went before its time
                st["armed"] = False
            flush(cause)

        def flush(cause: str):
            st = _batch_state(state_key, batch_wait_timeout_s)
            with st["lock"]:
                if st["running"] or not st["pending"]:
                    return      # the running batch's end sees to the rest
                batch_items = st["pending"][:max_batch_size]
                del st["pending"][:max_batch_size]
                st["running"], st["armed"] = True, False
                st["epoch"] += 1
                window, left = st["window"], len(st["pending"])
            try:
                run(st, batch_items, window, cause, left)
            finally:
                with st["lock"]:
                    st["running"] = False
                    if st["pending"]:
                        # what waited through this batch assembles from
                        # now: the callers just answered have the window
                        arm(st, 0.0 if len(st["pending"]) >= max_batch_size
                            else st["window"], "after_running")

        def run(st, batch_items, window, cause, left_pending):
            items = [it[0] for it in batch_items]
            self_obj = batch_items[0][2]
            waits = [it[4] for it in batch_items if it[4] is not None]
            error = outs = None
            # A flush serves many requests and belongs to none of them: a
            # tree of its own, whichever thread runs it. Each request's
            # ``serve.batch.wait`` names it (``flush``).
            with events.span(
                    "serve.batch.flush", ctx=events.ROOT, rows=len(items),
                    max_batch_size=max_batch_size, window_s=window,
                    cause=cause, left_pending=left_pending) as flushed:
                fn_start = time.perf_counter()
                if waits:
                    flushed.set(
                        oldest_wait_s=fn_start - min(
                            w.started for w in waits),
                        newest_wait_s=fn_start - max(
                            w.started for w in waits))
                if st["returned"] is not None:
                    # the gap as the replica saw it: the previous batch's
                    # fn returned, this one's starts
                    flushed.set(since_last_s=fn_start - st["returned"])
                try:
                    outs = fn(self_obj, items) if self_obj is not None \
                        else fn(items)
                except BaseException as e:  # noqa: BLE001
                    error = e
                    flushed.set(error=repr(e))
            returned, r0 = time.time(), time.perf_counter()
            st["returned"] = r0
            if error is None and len(outs) != len(items):
                error = ValueError(
                    f"@serve.batch fn returned {len(outs)} results "
                    f"for {len(items)} inputs")
            for i, (_, slot, _, _, _) in enumerate(batch_items):
                if error is None:
                    slot["result"] = outs[i]
                else:
                    slot["error"] = error
                slot["event"].set()
            p99_ms = None
            if target_p99_ms is not None:
                done = time.monotonic()
                with st["lock"]:
                    st["lat"].extend(done - it[3] for it in batch_items)
                    p99_ms = _adapt_window(st, target_p99_ms,
                                           batch_wait_timeout_s)
            if flushed.id is not None:      # off every waiter's path
                events.span_record(
                    "serve.batch.reply", returned,
                    time.perf_counter() - r0, ident=flushed.ident,
                    parent=flushed.id, p99_ms=p99_ms)
                for w in waits:
                    w.record(fn_start, flushed.id)

        @functools.wraps(fn)
        def wrapper(*call_args):
            if len(call_args) == 2:
                self_obj, item = call_args
            else:
                self_obj, item = None, call_args[0]
            slot = {"event": threading.Event(), "result": None,
                    "error": None}
            st = _batch_state(state_key, batch_wait_timeout_s)
            with st["lock"]:
                st["pending"].append((item, slot, self_obj,
                                      time.monotonic(),
                                      _BatchWait() if events.enabled()
                                      else None))
                full = len(st["pending"]) >= max_batch_size
                if not full and not st["running"]:
                    arm(st, st["window"], "window")   # the first arrival's
            if full:
                flush("full")   # nothing where a batch runs: its end will
            slot["event"].wait(timeout=120)
            if slot["error"] is not None:
                raise slot["error"]
            return slot["result"]

        return wrapper

    if _fn is not None:
        return wrap(_fn)
    return wrap

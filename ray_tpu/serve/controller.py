"""Serve control plane: controller + replica actors + router.

Role parity: serve/controller.py:73 (ServeController reconcile loop),
_private/deployment_state.py (target vs running replicas FSM + DRAINING
state on scale-down), _private/replica.py (replica actor wrapping the
user callable, per-replica in-flight cap), _private/router.py:263
(queue-length-aware replica choice over a generation-stamped replica
list), _private/autoscaling_policy.py (replicas from in-flight load,
read from the metrics plane instead of per-replica RPC polls).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.cluster import fault_plane
from ray_tpu.util import events, lockcheck


class ReplicaBusyError(Exception):
    """A replica past its per-request in-flight cap rejected the call
    instead of queueing it; the handle retries on another replica."""


class Replica:
    """Actor wrapping one instance of the user's deployment callable."""

    def __init__(self, cls_or_fn_blob: bytes, init_args_blob: bytes,
                 deployment: str = "", max_ongoing: int = 0):
        import cloudpickle
        target = cloudpickle.loads(cls_or_fn_blob)
        args, kwargs = cloudpickle.loads(init_args_blob)
        if isinstance(target, type):
            self.callable = target(*args, **kwargs)
        else:
            self.callable = target
        self._inflight = 0
        self._deployment = deployment
        self._max_ongoing = int(max_ongoing)
        self._inflight_lock = threading.Lock()

    def _set_gauge(self) -> None:
        # Per-deployment occupancy gauge: ships to the conductor metrics
        # KV with this process's snapshot, where the controller's
        # autoscaler reads it (no queue_len RPC fan-out on the hot path).
        try:
            from ray_tpu.util import metrics as m
            m.builtin(m.Gauge, "rt_serve_replica_ongoing",
                      tag_keys=("deployment",)).set(
                float(self._inflight),
                tags={"deployment": self._deployment})
        except Exception:
            pass

    def handle_request(self, method: str, args_blob: bytes):
        with events.span("serve.replica.call") as call:
            return self._handle_request(method, args_blob, call)

    def _handle_request(self, method: str, args_blob: bytes, call):
        import cloudpickle
        fault_plane.fire("serve.replica.call", deployment=self._deployment,
                         method=method)
        with self._inflight_lock:
            if self._max_ongoing and self._inflight >= self._max_ongoing:
                # Reject past the cap instead of queueing: the handle sees
                # ReplicaBusyError and re-picks — backpressure propagates
                # replica -> handle -> proxy instead of hiding in an
                # unbounded actor mailbox.
                raise ReplicaBusyError(
                    f"replica of {self._deployment!r} at in-flight cap "
                    f"({self._max_ongoing})")
            self._inflight += 1
            call.set(inflight=self._inflight)
        self._set_gauge()
        args, kwargs = cloudpickle.loads(args_blob)
        try:
            fn = self.callable if method == "__call__" else \
                getattr(self.callable, method)
            if not callable(fn):
                raise AttributeError(f"deployment has no method {method!r}")
            out = fn(*args, **kwargs)
            import inspect
            if inspect.isawaitable(out):
                # Replica methods run on pool threads (max_concurrency>1):
                # drive the coroutine on a fresh loop, not a thread-global.
                import asyncio
                loop = asyncio.new_event_loop()
                try:
                    out = loop.run_until_complete(out)
                finally:
                    loop.close()
            return out
        finally:
            with self._inflight_lock:
                self._inflight -= 1
            self._set_gauge()

    def queue_len(self) -> int:
        return self._inflight

    def reconfigure(self, user_config) -> bool:
        hook = getattr(self.callable, "reconfigure", None)
        if hook is not None:
            hook(user_config)
        return True

    def check_health(self) -> bool:
        hook = getattr(self.callable, "check_health", None)
        if hook is not None:
            hook()
        return True


class ServeController:
    """Singleton named actor reconciling deployment specs to replicas."""

    CONTROLLER_NAME = "RTPU_SERVE_CONTROLLER"

    def __init__(self, http_port: int = 0):
        self.deployments: Dict[str, dict] = {}   # name -> spec
        self.replicas: Dict[str, List[Any]] = {}  # name -> RUNNING handles
        # DRAINING replicas: name -> [{"handle", "deadline", "zero_polls"}].
        # Out of the routing table (generation bumped when they leave
        # ``replicas``), killed once idle or past serve_drain_timeout_s.
        self._draining: Dict[str, List[dict]] = {}
        # Routing-table generation per deployment: bumped on ANY membership
        # change of the RUNNING list so handles detect staleness cheaply.
        self._generation: Dict[str, int] = {}
        # Replica lifecycle for the init-grace window: actor_id -> spawn
        # time; ids that have answered >=1 health ping.
        self._replica_started: Dict[Any, float] = {}
        self._replica_ready: set = set()
        self._lock = lockcheck.named_lock("serve.controller")
        # serializes reconcile passes (deploy() and the loop both enter;
        # the controller actor itself runs with max_concurrency > 1)
        self._reconcile_lock = lockcheck.named_lock("serve.reconcile")
        self._stopped = False
        self.http_port = http_port
        self.http_actor = None
        self._reconciler = threading.Thread(target=self._reconcile_loop,
                                            daemon=True,
                                            name="serve-reconciler")
        self._reconciler.start()

    # -- deployment management ------------------------------------------
    def deploy(self, name: str, cls_blob: bytes, init_args_blob: bytes,
               num_replicas: int, ray_actor_options: dict,
               user_config=None, route_prefix: Optional[str] = None,
               max_concurrent_queries: int = 100,
               autoscaling: Optional[dict] = None,
               init_grace_s: float = 120.0,
               max_ongoing_requests: int = 0) -> bool:
        with self._lock:
            self.deployments[name] = {
                "name": name, "cls_blob": cls_blob,
                "init_args_blob": init_args_blob,
                "num_replicas": num_replicas,
                "ray_actor_options": ray_actor_options or {},
                "user_config": user_config,
                "route_prefix": route_prefix,
                "max_concurrent_queries": max_concurrent_queries,
                "autoscaling": autoscaling,
                "init_grace_s": init_grace_s,
                "max_ongoing_requests": max_ongoing_requests,
            }
        self._reconcile_once()
        return True

    @staticmethod
    def _resolved_max_ongoing(spec: dict) -> int:
        cap = int(spec.get("max_ongoing_requests") or 0)
        if cap <= 0:
            from ray_tpu import config
            cap = int(config.get("serve_max_ongoing_requests"))
        return max(1, cap)

    def _bump_gen(self, name: str) -> None:
        self._generation[name] = self._generation.get(name, 0) + 1

    def delete_deployment(self, name: str) -> bool:
        with self._lock:
            self.deployments.pop(name, None)
        # Spec removed (route disappears at the next proxy refresh), then
        # replicas leave the routing table and drain instead of dying with
        # requests still on board.
        with self._reconcile_lock:
            current = self.replicas.pop(name, [])
            if current:
                self._bump_gen(name)
            for a in current:
                self._start_drain(name, a)
            self._drain_tick()
        return True

    def _start_drain(self, name: str, handle) -> None:
        from ray_tpu import config
        try:
            fault_plane.fire("serve.replica.drain", deployment=name)
        except Exception:
            # An injected drain fault degrades to an immediate kill — the
            # replica must still leave the cluster.
            self._kill_replica(handle)
            return
        try:
            from ray_tpu.util import events
            events.emit("serve.drain", name)
        except Exception:
            pass
        self._draining.setdefault(name, []).append({
            "handle": handle,
            "deadline": time.time() + float(
                config.get("serve_drain_timeout_s")),
            "zero_polls": 0,
        })

    def _drain_tick(self) -> None:
        """Poll DRAINING replicas; kill the idle and the overdue ones."""
        import ray_tpu as rt
        for name in list(self._draining):
            keep = []
            for rec in self._draining[name]:
                done = time.time() > rec["deadline"]
                if not done:
                    try:
                        qlen = rt.get(rec["handle"].queue_len.remote(),
                                      timeout=5)
                        # Two consecutive idle polls: a request the handle
                        # submitted just before the generation bump may not
                        # have STARTED yet (inflight still 0 in the gap
                        # between mailbox and execution).
                        rec["zero_polls"] = rec["zero_polls"] + 1 \
                            if qlen == 0 else 0
                        done = rec["zero_polls"] >= 2
                    except Exception:
                        done = True   # unreachable/dead: nothing to drain
                if done:
                    self._kill_replica(rec["handle"])
                else:
                    keep.append(rec)
            if keep:
                self._draining[name] = keep
            else:
                del self._draining[name]

    def _kill_replica(self, handle) -> None:
        import ray_tpu as rt
        try:
            rt.kill(handle)
        except Exception:
            pass
        self._replica_started.pop(handle._rt_actor_id, None)
        self._replica_ready.discard(handle._rt_actor_id)

    @staticmethod
    def _actor_dead(handle) -> bool:
        """Authoritative liveness from the conductor's actor FSM — a
        replica that is DEAD must be replaced immediately even inside the
        init-grace window (a stuck ping is ambiguous; DEAD is not)."""
        try:
            from ray_tpu.core.api import _global_runtime
            info = _global_runtime().conductor.call(
                "get_actor_info", actor_id=handle._rt_actor_id.binary())
            return (info or {}).get("state") == "DEAD"
        except Exception:
            return False

    def _spawn_replica(self, spec: dict):
        import ray_tpu as rt
        opts = dict(spec["ray_actor_options"])
        max_ongoing = self._resolved_max_ongoing(spec)
        cls = rt.remote(Replica)
        handle = cls.options(
            num_cpus=opts.get("num_cpus", 1),
            num_tpus=opts.get("num_tpus", 0),
            resources=opts.get("resources", {}),
            # Concurrency must exceed the in-flight cap so the over-cap
            # rejection path can actually run (a saturated thread pool
            # would queue the probe call behind the work it should shed).
            max_concurrency=max(spec["max_concurrent_queries"],
                                max_ongoing + 2),
        ).remote(spec["cls_blob"], spec["init_args_blob"],
                 spec["name"], max_ongoing)
        self._replica_started[handle._rt_actor_id] = time.time()
        if spec.get("user_config") is not None:
            # The reconfigure wait covers __init__ too (the actor call
            # queues behind construction), so its deadline is the
            # deployment's OWN init grace — a 10-minute model load with
            # init_grace_s=900 must not fail at a fixed 120s, and a
            # fail-fast init_grace_s=15 must not stall reconcile for 120s.
            rt.get(handle.reconfigure.remote(spec["user_config"]),
                   timeout=float(spec.get("init_grace_s", 120.0)))
        return handle

    def _reconcile_once(self) -> None:
        with self._reconcile_lock:
            self._reconcile_locked()
            self._drain_tick()

    def _reconcile_locked(self) -> None:
        import ray_tpu as rt
        with self._lock:
            specs = dict(self.deployments)
        for name, spec in specs.items():
            current = self.replicas.setdefault(name, [])
            # Replace dead replicas (health check by ping). A replica whose
            # __init__ is still running (model load, framework imports —
            # routine for ML deployments) answers nothing yet: give it an
            # initialization GRACE window before a failed ping is treated
            # as death (parity: serve's replica startup timeout,
            # RAY_SERVE_REPLICA... init deadline vs health period).
            grace = float(spec.get("init_grace_s", 120.0))
            from ray_tpu.core.exceptions import GetTimeoutError
            alive = []
            for a in current:
                try:
                    rt.get(a.check_health.remote(), timeout=10)
                    self._replica_ready.add(a._rt_actor_id)
                    alive.append(a)
                except GetTimeoutError:
                    # ONLY a silent ping (no answer yet) earns the grace;
                    # a replica that ANSWERED with an error is unhealthy
                    # and replaced immediately (the except below).
                    started = self._replica_started.get(a._rt_actor_id, 0.0)
                    initializing = (a._rt_actor_id not in
                                    self._replica_ready and
                                    time.time() - started < grace and
                                    not self._actor_dead(a))
                    if initializing:
                        alive.append(a)   # still booting — keep waiting
                        continue
                    self._kill_replica(a)
                except Exception:
                    self._kill_replica(a)
            if len(alive) != len(current):
                self._bump_gen(name)
            current[:] = alive
            target = spec["num_replicas"]
            if len(current) != target:
                self._bump_gen(name)
            while len(current) < target:
                current.append(self._spawn_replica(spec))
            # Scale-down: newest replicas drain gracefully — they leave
            # the routing table NOW (generation bumped above) but keep
            # serving their in-flight requests until idle or the drain
            # deadline.
            while len(current) > target:
                self._start_drain(name, current.pop())
        # Lifecycle maps only ever track LIVE handles (scale-downs,
        # deletes, shutdowns all funnel through here eventually).
        live = {a._rt_actor_id for rs in self.replicas.values() for a in rs}
        live |= {rec["handle"]._rt_actor_id
                 for recs in self._draining.values() for rec in recs}
        for aid in [k for k in self._replica_started if k not in live]:
            self._replica_started.pop(aid, None)
        self._replica_ready &= live

    def _reconcile_loop(self) -> None:
        # Two cadences: drain polling is latency-sensitive (an idle
        # DRAINING replica should die within ~a second so scale-downs and
        # deletes settle fast), while full reconcile + autoscale carry
        # health-ping RPC fan-out and stay coarse.
        tick = 0
        while not self._stopped:
            time.sleep(0.5)
            tick += 1
            try:
                if tick % 4 == 0:
                    self._reconcile_once()   # includes a drain tick
                    self._autoscale()
                else:
                    with self._reconcile_lock:
                        self._drain_tick()
            except Exception:
                pass

    # -- autoscaling ------------------------------------------------------
    @staticmethod
    def _metrics_ongoing(name: str) -> Optional[float]:
        """Total in-flight requests for a deployment, summed from the
        replica-shipped ``rt_serve_replica_ongoing`` gauges in the
        conductor metrics KV (the r10 plane). None when no replica has
        shipped a snapshot yet — the caller falls back to RPC polling."""
        import pickle
        try:
            from ray_tpu.core.api import _global_runtime
            conductor = _global_runtime().conductor
            total, found = 0.0, False
            for key in conductor.call("kv_keys", ns="metrics"):
                blob = conductor.call("kv_get", ns="metrics", key=key)
                if blob is None:
                    continue
                entry = pickle.loads(blob).get("rt_serve_replica_ongoing")
                if not entry:
                    continue
                for tags, value in entry["points"]:
                    if dict(tags).get("deployment") == name:
                        total += value
                        found = True
            return total if found else None
        except Exception:
            return None

    def _autoscale(self) -> None:
        """Queue-length autoscaling (parity: autoscaling_policy.py — scale
        to total_ongoing / target_ongoing_requests, clamped). Load comes
        from the metrics registry the replicas already ship to; the
        queue_len RPC fan-out remains only as the cold-start fallback."""
        import ray_tpu as rt
        with self._lock:
            specs = dict(self.deployments)
        for name, spec in specs.items():
            cfg = spec.get("autoscaling")
            if not cfg:
                continue
            replicas = self.replicas.get(name, [])
            if not replicas:
                continue
            total = self._metrics_ongoing(name)
            if total is None:
                try:
                    total = sum(rt.get(
                        [r.queue_len.remote() for r in replicas],
                        timeout=15))
                except Exception:
                    continue
            target_ongoing = cfg.get("target_num_ongoing_requests", 2)
            desired = max(cfg.get("min_replicas", 1),
                          min(cfg.get("max_replicas", 10),
                              -(-int(total) // target_ongoing) or 1))
            if desired != spec["num_replicas"]:
                with self._lock:
                    if name in self.deployments:
                        self.deployments[name]["num_replicas"] = desired

    # -- routing ---------------------------------------------------------
    def get_replicas(self, name: str) -> List[Any]:
        return list(self.replicas.get(name, []))

    def get_routing(self, name: str) -> dict:
        """Routing view for handles: RUNNING replicas only (DRAINING ones
        are already gone), the table generation (staleness check), and the
        per-replica in-flight cap."""
        with self._lock:
            spec = self.deployments.get(name)
        max_ongoing = self._resolved_max_ongoing(spec) if spec else 0
        return {
            "replicas": list(self.replicas.get(name, [])),
            "generation": self._generation.get(name, 0),
            "max_ongoing": max_ongoing,
        }

    def get_deployment_names(self) -> List[str]:
        with self._lock:
            return list(self.deployments)

    def get_routes(self) -> Dict[str, str]:
        with self._lock:
            return {spec["route_prefix"] or f"/{name}": name
                    for name, spec in self.deployments.items()}

    def draining_count(self) -> int:
        return sum(len(v) for v in self._draining.values())

    def status(self) -> Dict[str, dict]:
        with self._lock:
            out = {name: {
                "num_replicas_target": spec["num_replicas"],
                "num_replicas_running": len(self.replicas.get(name, [])),
                "num_replicas_draining": len(self._draining.get(name, [])),
                "route_prefix": spec["route_prefix"],
            } for name, spec in self.deployments.items()}
        # Deleted deployments linger while replicas drain (their spec is
        # gone but the drain records are not) — status must show them
        # until they disappear for real.
        for name, recs in self._draining.items():
            if name not in out and recs:
                out[name] = {
                    "num_replicas_target": 0,
                    "num_replicas_running": 0,
                    "num_replicas_draining": len(recs),
                    "route_prefix": None,
                }
        return out

    def start_http(self, host: str, port: int) -> int:
        import ray_tpu as rt
        from ray_tpu.serve.http_proxy import HTTPProxy
        if self.http_actor is None:
            cls = rt.remote(HTTPProxy)
            self.http_actor = cls.options(
                num_cpus=0.5, max_concurrency=64).remote(host, port)
            self.http_port = rt.get(self.http_actor.port.remote(),
                                    timeout=60)
        return self.http_port

    def http_stats(self) -> dict:
        import ray_tpu as rt
        if self.http_actor is None:
            return {}
        return rt.get(self.http_actor.stats.remote(), timeout=30)

    def http_reconfigure(self, overrides: dict) -> dict:
        """Forward live config overrides to the proxy process (value None
        clears). The driver's own set_override only reaches processes
        spawned afterwards; this is the path to an already-running
        ingress."""
        import ray_tpu as rt
        if self.http_actor is None:
            return {}
        return rt.get(self.http_actor.reconfigure.remote(dict(overrides)),
                      timeout=30)

    def graceful_shutdown(self) -> bool:
        import ray_tpu as rt
        self._stopped = True
        for name in list(self.deployments):
            self.delete_deployment(name)
        # Bounded wait for drains to settle, then force whatever is left.
        deadline = time.time() + 15.0
        while self.draining_count() and time.time() < deadline:
            time.sleep(0.2)
            with self._reconcile_lock:
                self._drain_tick()
        for recs in self._draining.values():
            for rec in recs:
                self._kill_replica(rec["handle"])
        self._draining.clear()
        if self.http_actor is not None:
            try:
                rt.kill(self.http_actor)
            except Exception:
                pass
        return True

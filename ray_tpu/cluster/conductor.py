"""Conductor: the cluster control plane (GCS equivalent).

Role parity: src/ray/gcs/gcs_server/gcs_server.h:77 and its per-entity
managers — node membership + health checks (gcs_health_check_manager.h),
actor registration/restart FSM + actor scheduling (gcs_actor_manager.h:281,
gcs_actor_scheduler.h:111), placement groups with 2PC prepare/commit across
node daemons (gcs_placement_group_scheduler.h:265), cluster-wide KV
(gcs_kv_manager.h), the object location directory (the reference resolves
locations through object owners, ownership_based_object_directory.h; here
the directory is centralized), and a task-event store powering the state
API/timeline (gcs_task_manager.h:61).

One conductor per cluster. All state is in-memory tables behind one lock,
with condition-variable long-polls standing in for the reference's pub/sub
channels (src/ray/pubsub/publisher.h:302).
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict, deque
from typing import Any, Dict, List, Optional, Set, Tuple

from ray_tpu import config
from ray_tpu.cluster import fault_plane
from ray_tpu.cluster.protocol import RpcServer, get_client
from ray_tpu.util import events as _events
from ray_tpu.util import lockcheck

# Actor FSM states (parity: gcs_actor_manager.h:249 state diagram).
DEPENDENCIES_UNREADY = "DEPENDENCIES_UNREADY"
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"


class ActorInfo:
    def __init__(self, actor_id: bytes, spec: dict):
        self.actor_id = actor_id
        self.spec = spec              # class blob, args, opts (pickled pieces)
        self.state = PENDING_CREATION
        self.address: Optional[str] = None   # worker rpc address when ALIVE
        self.node_id: Optional[bytes] = None
        self.num_restarts = 0
        self.death_reason = ""
        self.incarnation = 0


class PlacementGroupInfo:
    def __init__(self, pg_id: bytes, bundles: List[Dict[str, float]],
                 strategy: str, name: str, slice_topology: str = ""):
        self.pg_id = pg_id
        self.bundles = bundles
        self.strategy = strategy
        self.name = name
        self.slice_topology = slice_topology  # SLICE strategy filter (v4-8)
        self.slice_id: Optional[str] = None   # chosen slice once CREATED
        self.state = "PENDING"        # PENDING | CREATED | REMOVED
        self.bundle_nodes: List[Optional[bytes]] = [None] * len(bundles)
        self.placing = False          # a 2PC attempt is in flight
        self.retry_scheduled = False  # a retry Timer is pending


class Conductor:
    """In-memory control-plane tables + schedulers, served over RpcServer."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 health_timeout_s: Optional[float] = None,
                 persist_dir: Optional[str] = None):
        import uuid
        self._lock = lockcheck.named_lock("conductor.state")
        self._cv = threading.Condition(self._lock)
        # Epoch: fresh per conductor process. Daemons and ref trackers
        # compare it on every exchange; a change means "the conductor
        # restarted — re-advertise your volatile state" (gcs_init_data.h
        # role: durable tables reload from disk, volatile state resyncs
        # from the fleet).
        self._epoch = uuid.uuid4().hex
        self._journal = None
        self._compact_due = False
        if persist_dir is not None:
            from ray_tpu.cluster.persistence import StateJournal
            self._journal = StateJournal(
                persist_dir.rstrip("/") + "/conductor")
        self._nodes: Dict[bytes, dict] = {}          # node_id -> info
        self._kv: Dict[Tuple[str, bytes], bytes] = {}
        self._functions: Dict[str, bytes] = {}       # function_id -> blob
        self._actors: Dict[bytes, ActorInfo] = {}
        self._named_actors: Dict[Tuple[str, str], bytes] = {}
        self._object_locations: Dict[bytes, Set[bytes]] = defaultdict(set)
        # oid -> device placement string for array objects whose producer
        # was device-resident (r16): locate_object surfaces it so pullers
        # sharing the producer's mesh can prefer a device-to-device source.
        self._object_devices: Dict[bytes, str] = {}
        # oid -> (spill url, size). Survives the writing node's death —
        # that is the point: locate_object keeps advertising the URL so
        # any node restores from the durable copy instead of declaring
        # the object lost (local_object_manager.h spilled-url role).
        self._object_spilled: Dict[bytes, tuple] = {}
        # Objects whose every registered copy died with its node (and no
        # spill). Lets locate_object tell getters "lost, stop waiting"
        # instead of being indistinguishable from not-yet-computed; cleared
        # when a copy re-registers (lineage reconstruction).
        self._lost_objects: Set[bytes] = set()
        # --- distributed refcounting (reference_count.h:61, centralized;
        #     counts driven by ordered event streams from every process) ---
        self._refcounts: Dict[bytes, int] = {}
        self._ref_children: Dict[bytes, List[bytes]] = {}
        self._ref_tombstones: Set[bytes] = set()   # freed; stray seals die
        self._ref_tombstone_order: deque = deque()
        self._ref_batches_seen: Set[str] = set()   # at-least-once dedup
        self._ref_batch_order: deque = deque()
        self._free_q: deque = deque()              # (node_addr, oid) deletes
        self._spill_del_q: deque = deque()         # spill URLs to delete
        self._free_cv = threading.Condition()
        self._pgs: Dict[bytes, PlacementGroupInfo] = {}
        # Flight-recorder event store (util/events.py sink; parity role:
        # GcsTaskManager's bounded task-event store). Own lock: batches
        # arrive from every process's flusher/heartbeat and must not
        # contend with the control tables.
        self._ring_lock = threading.Lock()
        self._ring_events: List[dict] = []
        self._ring_dropped = 0
        self._job_counter = 0
        self._health_timeout_s = (
            health_timeout_s if health_timeout_s is not None
            else float(config.get("health_check_timeout_s")))
        self._stopped = False
        # worker-log pubsub ring (log streaming to drivers / `job logs`).
        # Own CV: log polls must not wake on (or scan under) the global
        # control-plane lock's notify_all traffic.
        self._log_cv = threading.Condition()
        self._log_buffer: deque = deque(maxlen=20000)
        self._log_seq = 0
        # Structured cluster events (parity: src/ray/util/event.h + the
        # dashboard's cluster-events table). Bounded ring; deque append is
        # atomic so emitters may hold any other lock.
        self._events: deque = deque(maxlen=10000)
        self._event_seq = 0
        self._event_lock = threading.Lock()  # seq counter, not self._lock
        if self._journal is not None:
            self._restore()
        self.server = RpcServer(self, host=host, port=port)
        self.address = self.server.address
        self._health_thread = threading.Thread(
            target=self._health_loop, daemon=True, name="conductor-health")
        self._health_thread.start()
        self._free_thread = threading.Thread(
            target=self._free_loop, daemon=True, name="conductor-free")
        self._free_thread.start()

    # ------------------------------------------------------------------
    # Durable state (parity: gcs_table_storage.h writes, gcs_init_data.h
    # bulk load). Only control tables persist; see persistence.py.
    # ------------------------------------------------------------------
    def _log(self, kind: str, data: dict) -> None:
        """Journal one durable mutation. Caller may hold self._lock (the
        journal has its own lock and does no RPC)."""
        if self._journal is None:
            return
        # Fault points bracketing the durable write: a crash on "pre"
        # loses the mutation (clients re-drive via at-least-once RPC); a
        # crash on "post" leaves a committed-but-unacked record the
        # journal's CRC framing and dedup-by-id replay must absorb.
        fault_plane.fire("conductor.journal.append", kind=kind, stage="pre")
        try:
            if self._journal.append(kind, data):
                self._compact_due = True
        except OSError:
            pass
        fault_plane.fire("conductor.journal.append", kind=kind, stage="post")

    def _emit_event(self, severity: str, source: str, event_type: str,
                    message: str, **metadata) -> None:
        """Record one structured cluster event (event.h / dashboard
        ClusterEvents role). severity: INFO | WARNING | ERROR. Callers may
        hold self._lock; the dedicated seq lock keeps event_ids unique
        across concurrent RPC handler threads."""
        with self._event_lock:
            self._event_seq += 1
            seq = self._event_seq
        self._events.append({
            "event_id": seq,
            "timestamp": time.time(),
            "severity": severity,
            "source": source,
            "event_type": event_type,
            "message": message,
            "metadata": metadata,
        })

    def rpc_report_event(self, severity: str, source: str, event_type: str,
                         message: str, metadata: Optional[dict] = None
                         ) -> None:
        """Daemons/workers publish their events (OOM kills, job state,
        worker crash storms) into the same stream."""
        self._emit_event(severity, source, event_type, message,
                         **(metadata or {}))

    def rpc_list_events(self, limit: int = 1000,
                        source: Optional[str] = None,
                        severity: Optional[str] = None,
                        event_type: Optional[str] = None) -> List[dict]:
        out = [e for e in list(self._events)
               if (source is None or e["source"] == source)
               and (severity is None or e["severity"] == severity)
               and (event_type is None or e["event_type"] == event_type)]
        return out[-limit:]

    def _actor_record(self, a: "ActorInfo") -> dict:
        return {"actor_id": a.actor_id, "state": a.state,
                "address": a.address, "node_id": a.node_id,
                "num_restarts": a.num_restarts,
                "death_reason": a.death_reason,
                "incarnation": a.incarnation}

    def _durable_state(self) -> dict:
        """Full durable-state snapshot. Caller holds self._lock."""
        return {
            "nodes": [
                {k: v for k, v in info.items() if k != "last_heartbeat"}
                for info in self._nodes.values()],
            "actors": [
                {"spec": a.spec, **self._actor_record(a)}
                for a in self._actors.values()],
            "pgs": [
                {"pg_id": pg.pg_id, "bundles": pg.bundles,
                 "strategy": pg.strategy, "name": pg.name,
                 "slice_topology": pg.slice_topology, "state": pg.state,
                 "bundle_nodes": pg.bundle_nodes, "slice_id": pg.slice_id}
                for pg in self._pgs.values()],
            "kv": dict(self._kv),
            "functions": dict(self._functions),
            "job_counter": self._job_counter,
        }

    def _apply_snapshot(self, snap: dict) -> None:
        now = time.monotonic()
        for info in snap.get("nodes", ()):
            info = dict(info)
            info["last_heartbeat"] = now  # grace: health re-evaluates
            self._nodes[info["node_id"]] = info
        for rec in snap.get("actors", ()):
            a = ActorInfo(rec["actor_id"], rec["spec"])
            self._apply_actor_record(a, rec)
            self._actors[a.actor_id] = a
            name = a.spec["opts"].get("name") or ""
            ns = a.spec["opts"].get("namespace") or "default"
            if name and a.state != DEAD:
                self._named_actors[(ns, name)] = a.actor_id
        for rec in snap.get("pgs", ()):
            pg = PlacementGroupInfo(rec["pg_id"], rec["bundles"],
                                    rec["strategy"], rec["name"],
                                    slice_topology=rec["slice_topology"])
            pg.state = rec["state"]
            pg.bundle_nodes = list(rec["bundle_nodes"])
            pg.slice_id = rec["slice_id"]
            self._pgs[pg.pg_id] = pg
        self._kv.update(snap.get("kv", {}))
        self._functions.update(snap.get("functions", {}))
        self._job_counter = snap.get("job_counter", 0)

    @staticmethod
    def _apply_actor_record(a: "ActorInfo", rec: dict) -> None:
        a.state = rec["state"]
        a.address = rec["address"]
        a.node_id = rec["node_id"]
        a.num_restarts = rec["num_restarts"]
        a.death_reason = rec["death_reason"]
        a.incarnation = rec["incarnation"]

    def _restore(self) -> None:
        snap, records = self._journal.load()
        if snap:
            self._apply_snapshot(snap)
        for kind, data in records:
            try:
                self._replay(kind, data)
            except Exception:
                continue
        # Restored in-flight actors re-enter scheduling once nodes return.
        pending = [a.actor_id for a in self._actors.values()
                   if a.state in (PENDING_CREATION, RESTARTING)]
        for actor_id in pending:
            threading.Timer(0.5, self._schedule_actor, (actor_id,)).start()

    def _replay(self, kind: str, data: dict) -> None:
        now = time.monotonic()
        if kind == "node":
            info = dict(data)
            info["last_heartbeat"] = now
            self._nodes[info["node_id"]] = info
        elif kind == "node_dead":
            info = self._nodes.get(data["node_id"])
            if info is not None:
                info["alive"] = False
        elif kind == "actor":
            self._replay_actor(data)
        elif kind == "actors":
            for rec in data["items"]:
                self._replay_actor(rec)
        elif kind == "actor_state":
            a = self._actors.get(data["actor_id"])
            if a is not None:
                self._apply_actor_record(a, data)
                if a.state == DEAD:
                    self._drop_name(a)
        elif kind == "pg":
            pg = PlacementGroupInfo(
                data["pg_id"], data["bundles"], data["strategy"],
                data["name"], slice_topology=data["slice_topology"])
            self._pgs[pg.pg_id] = pg
        elif kind == "pg_state":
            pg = self._pgs.get(data["pg_id"])
            if pg is not None:
                pg.state = data["state"]
                pg.bundle_nodes = list(data["bundle_nodes"])
                pg.slice_id = data["slice_id"]
        elif kind == "pg_removed":
            self._pgs.pop(data["pg_id"], None)
        elif kind == "kv":
            self._kv[(data["ns"], data["key"])] = data["value"]
        elif kind == "kv_batch":
            for rec in data["items"]:
                self._kv[(rec["ns"], rec["key"])] = rec["value"]
        elif kind == "kv_del":
            self._kv.pop((data["ns"], data["key"]), None)
        elif kind == "fn":
            self._functions[data["function_id"]] = data["blob"]
        elif kind == "job":
            self._job_counter = data["counter"]

    def _replay_actor(self, data: dict) -> None:
        a = ActorInfo(data["actor_id"], data["spec"])
        self._actors[a.actor_id] = a
        name = a.spec["opts"].get("name") or ""
        ns = a.spec["opts"].get("namespace") or "default"
        if name:
            self._named_actors[(ns, name)] = a.actor_id

    def _maybe_compact(self) -> None:
        if not self._compact_due or self._journal is None or self._stopped:
            return
        self._compact_due = False
        # Capture + truncate under the conductor lock: every _log() call
        # site holds it, so no mutation can slip between the snapshot
        # capture and the journal truncation (a frame landing in that
        # window would be in neither file — silent durability loss).
        with self._lock:
            try:
                self._journal.snapshot(self._durable_state())
            except OSError:
                pass

    # ------------------------------------------------------------------
    # Node membership + resource view (parity: GcsNodeManager + RaySyncer)
    # ------------------------------------------------------------------
    def rpc_register_node(self, node_id: bytes, address: str,
                          resources: Dict[str, float], store_socket: str,
                          is_head: bool = False,
                          tpu_slice: Optional[dict] = None) -> dict:
        with self._cv:
            self._nodes[node_id] = {
                "node_id": node_id,
                "address": address,
                "resources_total": dict(resources),
                "resources_available": dict(resources),
                "store_socket": store_socket,
                "is_head": is_head,
                "tpu_slice": dict(tpu_slice) if tpu_slice else None,
                "alive": True,
                "last_heartbeat": time.monotonic(),
            }
            self._log("node", {k: v for k, v in self._nodes[node_id].items()
                               if k != "last_heartbeat"})
            self._emit_event(
                "INFO", "conductor", "NODE_ADDED",
                f"node {node_id.hex()[:8]} joined at {address}",
                node_id=node_id.hex(), address=address, is_head=is_head)
            self._cv.notify_all()
        # A new slice host may complete a gang a pending slice PG waits on.
        with self._lock:
            pending = [pg for pg in self._pgs.values()
                       if pg.state == "PENDING"]
        for pg in pending:
            self._try_place_pg(pg)
        return {"ok": True, "epoch": self._epoch}

    # ------------------------------------------------------------------
    # TPU slice view (the differentiator: ICI-contiguous gang placement;
    # the reference's nearest analog is the PG scheduler's bundle packing,
    # gcs_placement_group_scheduler.h:265, which has no topology notion)
    # ------------------------------------------------------------------
    def _slice_view(self) -> Dict[str, dict]:
        """Group live TPU nodes by slice. Caller must hold self._lock."""
        slices: Dict[str, dict] = {}
        for info in self._nodes.values():
            if not info["alive"] or not info.get("tpu_slice"):
                continue
            ts = info["tpu_slice"]
            s = slices.setdefault(ts["slice_id"], {
                "slice_id": ts["slice_id"],
                "accelerator_type": ts["accelerator_type"],
                "generation": ts["generation"],
                "num_hosts": ts["num_hosts"],
                "hosts": [],
            })
            s["hosts"].append(info)
        for s in slices.values():
            s["hosts"].sort(key=lambda n: n["tpu_slice"]["worker_id"])
            s["complete"] = len(s["hosts"]) >= s["num_hosts"]
        return slices

    def rpc_get_slices(self) -> List[dict]:
        with self._lock:
            return [{
                "slice_id": s["slice_id"],
                "accelerator_type": s["accelerator_type"],
                "generation": s["generation"],
                "num_hosts": s["num_hosts"],
                "registered_hosts": len(s["hosts"]),
                "complete": s["complete"],
                "node_ids": [n["node_id"] for n in s["hosts"]],
            } for s in self._slice_view().values()]

    def rpc_heartbeat(self, node_id: bytes,
                      resources_available: Dict[str, float],
                      pending_demand: Optional[List[Dict[str, float]]] = None,
                      events: Optional[dict] = None) -> dict:
        with self._lock:
            info = self._nodes.get(node_id)
            if info is None or not info["alive"]:
                return {"ok": False, "reregister": True,
                        "epoch": self._epoch}
            info["last_heartbeat"] = time.monotonic()
            info["resources_available"] = dict(resources_available)
            info["pending_demand"] = list(pending_demand or [])
        if events:
            # Flight-recorder piggyback: the daemon rides its ring delta
            # on the heartbeat it already pays for (events.heartbeat_payload).
            self.rpc_push_ring_events(
                node_id=node_id.hex(), pid=events.get("pid", 0),
                events=events.get("events", ()),
                dropped=events.get("dropped", 0))
        return {"ok": True, "epoch": self._epoch}

    def rpc_cluster_load(self) -> dict:
        """Autoscaler input (parity: the GCS load report monitor.py reads):
        per-shape pending demand + per-node availability."""
        with self._lock:
            demand: List[Dict[str, float]] = []
            nodes = []
            for info in self._nodes.values():
                if not info["alive"]:
                    continue
                demand.extend(info.get("pending_demand", []))
                nodes.append({
                    "node_id": info["node_id"],
                    "resources_total": dict(info["resources_total"]),
                    "resources_available": dict(info["resources_available"]),
                    "is_head": info["is_head"],
                })
            # unplaceable pending placement groups are demand too
            for pg in self._pgs.values():
                if pg.state == "PENDING":
                    demand.extend(pg.bundles)
        return {"demand": demand, "nodes": nodes}

    def rpc_drain_node(self, node_id: bytes) -> dict:
        self._mark_node_dead(node_id, "drained")
        return {"ok": True}

    def rpc_get_nodes(self) -> List[dict]:
        with self._lock:
            return [dict(v) for v in self._nodes.values()]

    def rpc_cluster_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        with self._lock:
            for info in self._nodes.values():
                if info["alive"]:
                    for k, v in info["resources_total"].items():
                        out[k] += v
        return dict(out)

    def rpc_available_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        with self._lock:
            for info in self._nodes.values():
                if info["alive"]:
                    for k, v in info["resources_available"].items():
                        out[k] += v
        return dict(out)

    def _health_loop(self) -> None:
        while not self._stopped:
            time.sleep(self._health_timeout_s / 4)
            self._maybe_compact()
            now = time.monotonic()
            dead = []
            with self._lock:
                for nid, info in self._nodes.items():
                    if info["alive"] and (
                            now - info["last_heartbeat"] > self._health_timeout_s):
                        dead.append(nid)
            for nid in dead:
                self._mark_node_dead(nid, "health check timed out")

    def _mark_node_dead(self, node_id: bytes, reason: str) -> None:
        to_restart: List[ActorInfo] = []
        with self._cv:
            info = self._nodes.get(node_id)
            if info is None or not info["alive"]:
                return
            info["alive"] = False
            self._log("node_dead", {"node_id": node_id})
            self._emit_event(
                "WARNING", "conductor", "NODE_DEAD",
                f"node {node_id.hex()[:8]} marked dead: {reason}",
                node_id=node_id.hex(), reason=reason)
            # Drop its object locations; owners re-resolve and recover.
            for oid, locs in list(self._object_locations.items()):
                locs.discard(node_id)
                if not locs and oid not in self._object_spilled:
                    del self._object_locations[oid]
                    self._lost_objects.add(oid)
            # Actors on this node die (and maybe restart).
            for a in self._actors.values():
                if a.node_id == node_id and a.state in (ALIVE, PENDING_CREATION,
                                                        RESTARTING):
                    to_restart.append(a)
            # Placement groups lose bundles on this node -> back to PENDING.
            for pg in self._pgs.values():
                if pg.state == "CREATED" and node_id in pg.bundle_nodes:
                    pg.state = "PENDING"
                    pg.slice_id = None
                    pg.bundle_nodes = [
                        None if n == node_id else n for n in pg.bundle_nodes]
            self._cv.notify_all()
        for a in to_restart:
            self._on_actor_death(a.actor_id, f"node died: {reason}")
        # Reap the dead node's per-process metrics snapshots: the KV keys
        # are (node, pid)-scoped, so a node's death identifies exactly its
        # entries (util/metrics.py satellite — stale keys used to linger
        # forever and shadow reused pids).
        prefix = f"proc-{node_id.hex()}-".encode()
        with self._lock:
            stale = [k for (n, k) in self._kv
                     if n == "metrics" and k.startswith(prefix)]
            for k in stale:
                self._kv.pop(("metrics", k), None)
        # Re-place any PGs knocked back to PENDING.
        with self._lock:
            pending = [pg for pg in self._pgs.values() if pg.state == "PENDING"]
        for pg in pending:
            self._try_place_pg(pg)

    # ------------------------------------------------------------------
    # KV + function table (parity: gcs_kv_manager.h, gcs_function_manager.h)
    # ------------------------------------------------------------------
    def rpc_kv_put(self, ns: str, key: bytes, value: bytes,
                   overwrite: bool = True) -> bool:
        with self._cv:
            if not overwrite and (ns, key) in self._kv:
                return False
            self._kv[(ns, key)] = value
            self._log("kv", {"ns": ns, "key": key, "value": value})
            self._cv.notify_all()
        return True

    def rpc_kv_multi_put(self, items: List[tuple],
                         overwrite: bool = True) -> List[bool]:
        """Coalesced KV writes: one lock acquisition + ONE journal record
        for N (ns, key, value) triples — a wave of writes costs O(1)
        round-trips and fsyncs instead of O(N) (parity: the reference's
        InternalKVMultiSet batching)."""
        out: List[bool] = []
        logged: List[dict] = []
        with self._cv:
            for ns, key, value in items:
                if not overwrite and (ns, key) in self._kv:
                    out.append(False)
                    continue
                self._kv[(ns, key)] = value
                logged.append({"ns": ns, "key": key, "value": value})
                out.append(True)
            if logged:
                self._log("kv_batch", {"items": logged})
                self._cv.notify_all()
        return out

    def rpc_kv_get(self, ns: str, key: bytes,
                   wait_timeout: float = 0.0) -> Optional[bytes]:
        deadline = time.monotonic() + wait_timeout
        with self._cv:
            while True:
                v = self._kv.get((ns, key))
                if v is not None or wait_timeout <= 0:
                    return v
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._cv.wait(remaining)

    def rpc_kv_del(self, ns: str, key: bytes) -> bool:
        with self._lock:
            self._log("kv_del", {"ns": ns, "key": key})
            return self._kv.pop((ns, key), None) is not None

    def rpc_kv_keys(self, ns: str, prefix: bytes = b"") -> List[bytes]:
        with self._lock:
            return [k for (n, k) in self._kv if n == ns and k.startswith(prefix)]

    def rpc_put_function(self, function_id: str, blob: bytes) -> None:
        with self._lock:
            self._functions[function_id] = blob
            self._log("fn", {"function_id": function_id, "blob": blob})

    def rpc_get_function(self, function_id: str) -> Optional[bytes]:
        with self._lock:
            return self._functions.get(function_id)

    # ------------------------------------------------------------------
    # Object directory (centralizes ownership_based_object_directory.h)
    # ------------------------------------------------------------------
    def rpc_add_object_location(self, oid: bytes, node_id: bytes) -> None:
        fault_plane.fire("conductor.location.add", n=1)
        with self._cv:
            if oid in self._ref_tombstones:
                # Sealed after its refcount hit zero (fire-and-forget task
                # whose return refs were dropped pre-execution): delete the
                # stray copy instead of registering a leaked location.
                info = self._nodes.get(node_id)
                if info is not None and info["alive"]:
                    self._enqueue_delete(info["address"], oid)
                return
            self._object_locations[oid].add(node_id)
            self._lost_objects.discard(oid)
            self._cv.notify_all()

    def rpc_add_object_locations(self, oids: List[bytes],
                                 node_id: bytes,
                                 devices: Optional[List[str]] = None) -> None:
        """Bulk registration: a daemon replaying its store inventory after
        a conductor epoch change (persistence.py), or a plane's batched
        per-result registrations (object_plane._LocationBatcher). Same
        tombstone semantics as the single-oid path: a copy sealed after
        its refcount hit zero is a leak — delete it at the source.
        ``devices`` (parallel to ``oids``, r16) tags array objects with
        their producer's device placement for locate_object."""
        fault_plane.fire("conductor.location.add", n=len(oids))
        with self._cv:
            info = self._nodes.get(node_id)
            addr = info["address"] if info and info["alive"] else None
            for i, oid in enumerate(oids):
                if oid in self._ref_tombstones:
                    if addr is not None:
                        self._enqueue_delete(addr, oid)
                    continue
                self._object_locations[oid].add(node_id)
                if devices and i < len(devices) and devices[i]:
                    self._object_devices[oid] = devices[i]
                self._lost_objects.discard(oid)
            self._cv.notify_all()

    def rpc_remove_object_location(self, oid: bytes, node_id: bytes) -> None:
        """A puller found the directory stale: the holder denied having the
        object or was unreachable. Dropping the entry keeps other getters
        from hammering the same dead copy; if it was the last one (and no
        spill), the object is lost and waiters are told so."""
        with self._cv:
            locs = self._object_locations.get(oid)
            if locs:
                locs.discard(node_id)
                if not locs and oid not in self._object_spilled:
                    del self._object_locations[oid]
                    self._lost_objects.add(oid)
                    self._cv.notify_all()

    def rpc_add_spilled(self, oid: bytes, url: str, size: int = 0) -> None:
        with self._cv:
            if oid in self._ref_tombstones:
                # Freed while the spill write was in flight: the spilling
                # daemon keeps the registry entry, so its own delete path
                # (rpc_delete_objects -> _drop_spilled) removes the file.
                return
            self._object_spilled[oid] = (url, int(size))
            self._lost_objects.discard(oid)
            self._cv.notify_all()

    def rpc_remove_spilled(self, oid: bytes, url: str) -> None:
        """A restorer found the spill URL unreadable (node-local spill
        dir died with its node): scrub it so locate rounds stop pointing
        getters at a dead copy. Guarded by URL so a fresh re-spill under
        the same oid is never scrubbed by a stale failure report."""
        with self._cv:
            ent = self._object_spilled.get(oid)
            if ent is None or ent[0] != url:
                return
            del self._object_spilled[oid]
            if not self._object_locations.get(oid):
                self._object_locations.pop(oid, None)
                self._lost_objects.add(oid)
            self._cv.notify_all()

    def rpc_locate_object(self, oid: bytes, timeout: float = 0.0) -> dict:
        """Resolve an object to live node addresses (+ spill url if any)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                locs = [self._nodes[n] for n in self._object_locations.get(oid, ())
                        if n in self._nodes and self._nodes[n]["alive"]]
                sp = self._object_spilled.get(oid)
                lost = not locs and not sp and oid in self._lost_objects
                if locs or sp or lost or timeout <= 0:
                    return {
                        "nodes": [{"node_id": n["node_id"],
                                   "address": n["address"]} for n in locs],
                        "spilled": sp[0] if sp else None,
                        "spilled_size": sp[1] if sp else 0,
                        "lost": lost,
                        "device": self._object_devices.get(oid, ""),
                    }
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {"nodes": [], "spilled": None,
                            "spilled_size": 0, "lost": False, "device": ""}
                self._cv.wait(min(remaining, 1.0))

    def rpc_objects_exist(self, oids: List[bytes]) -> List[bool]:
        """Batched readiness probe for dependency gating (the role of the
        raylet's DependencyManager wait-before-dispatch)."""
        with self._lock:
            return [bool(self._object_locations.get(o)) or
                    o in self._object_spilled for o in oids]

    def rpc_wait_objects(self, oids: List[bytes], num_needed: int,
                         timeout: float = 0.0) -> List[bool]:
        """Event-driven ray.wait / dependency-gate backend: long-poll until
        at least ``num_needed`` of ``oids`` exist somewhere (location or
        spill), then return the full existence bitmap. Replaces client-side
        polling (parity: the reference's object-eviction/location pubsub,
        src/ray/pubsub/publisher.h:302 — waiters park on the conductor's CV
        and wake on add_object_location instead of spinning)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                exist = [bool(self._object_locations.get(o)) or
                         o in self._object_spilled for o in oids]
                if sum(exist) >= num_needed or timeout <= 0:
                    return exist
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return exist
                self._cv.wait(min(remaining, 1.0))

    # ------------------------------------------------------------------
    # Distributed refcounting (reference_count.h:61, centralized ledger)
    # ------------------------------------------------------------------
    def rpc_ref_update(self, deltas: List[tuple],
                       epoch: Optional[str] = None,
                       batch_id: Optional[str] = None) -> dict:
        """Apply an ordered batch of count events from one process.

        Each event is ``(key, +1|-1)`` or ``(parent_key, [child_keys])``
        (the parent object contains refs to the children). Order within the
        batch is program order in the sender — applying sequentially is
        what keeps handoffs race-free (see core/refcount.py docstring).

        ``epoch`` fences failover: deltas recorded against a dead
        conductor's ledger are rejected with resync=True, and the tracker
        replays its full local truth instead (refcount ledgers are
        volatile; gcs_init_data.h reloads only durable tables)."""
        if epoch is not None and epoch != self._epoch:
            return {"epoch": self._epoch, "resync": True}
        if batch_id is not None:
            with self._lock:
                if batch_id in self._ref_batches_seen:
                    return {"epoch": self._epoch}  # at-least-once dedup
                self._ref_batches_seen.add(batch_id)
                self._ref_batch_order.append(batch_id)
                while len(self._ref_batch_order) > 4096:
                    self._ref_batches_seen.discard(
                        self._ref_batch_order.popleft())
        to_free: List[bytes] = []
        with self._lock:
            stack = list(deltas)
            for key, ev in stack:
                if isinstance(ev, list):
                    if key in self._ref_tombstones:
                        continue  # parent already freed; don't pin children
                    self._ref_children.setdefault(key, []).extend(ev)
                    for child in ev:
                        self._refcounts[child] = \
                            self._refcounts.get(child, 0) + 1
                    continue
                c = self._refcounts.get(key, 0) + ev
                if c <= 0:
                    had = key in self._refcounts
                    self._refcounts.pop(key, None)
                    # Free ONLY on a tracked 1->0 transition. A -1 against
                    # an absent key (decref outliving a conductor restart)
                    # must NOT free: the matching +1 may be lost state, and
                    # other processes may still hold the object. Those
                    # objects fall back to LRU/spill reclamation.
                    if had:
                        to_free.extend(self._collect_free(key))
                else:
                    self._refcounts[key] = c
                    # A live count always overrides a stale tombstone (a
                    # revived lineage output that regained holders).
                    self._ref_tombstones.discard(key)
        if to_free:
            with self._cv:
                self._cv.notify_all()
        return {"epoch": self._epoch}

    def _collect_free(self, key: bytes) -> List[bytes]:
        """Free ``key`` and cascade to children whose counts hit zero.
        Caller holds self._lock. Returns the freed keys."""
        freed = []
        stack = [key]
        while stack:
            k = stack.pop()
            if k in self._ref_tombstones:
                continue
            self._ref_tombstones.add(k)
            self._ref_tombstone_order.append(k)
            while len(self._ref_tombstone_order) > 200_000:
                old = self._ref_tombstone_order.popleft()
                self._ref_tombstones.discard(old)
            freed.append(k)
            self._object_devices.pop(k, None)
            for n in self._object_locations.pop(k, ()):
                info = self._nodes.get(n)
                if info is not None and info["alive"]:
                    self._enqueue_delete(info["address"], k)
            sp = self._object_spilled.pop(k, None)
            if sp is not None:
                # Spill copies are refcounted like any other copy: the
                # backend file dies on the 1->0 transition (deleted off
                # the RPC path by the free loop; the spilling daemon's
                # own delete handler covers node-local dirs we can't
                # reach from here).
                self._spill_del_q.append(sp[0])
            self._lost_objects.discard(k)
            for child in self._ref_children.pop(k, ()):
                c = self._refcounts.get(child, 0) - 1
                if c <= 0:
                    self._refcounts.pop(child, None)
                    stack.append(child)
                else:
                    self._refcounts[child] = c
        return freed

    def rpc_ref_revive(self, keys: List[bytes]) -> None:
        """Clear tombstones before lineage reconstruction re-executes a
        task whose (freed) outputs are needed as dependencies again — the
        recovered copies must be allowed to register locations."""
        with self._lock:
            for k in keys:
                self._ref_tombstones.discard(k)
                # Reconstruction is in flight: stop telling getters the
                # object is unrecoverably lost (they'd give up while the
                # re-executed task is still producing the new copy).
                self._lost_objects.discard(k)

    def _enqueue_delete(self, addr: str, oid: bytes) -> None:
        with self._free_cv:
            self._free_q.append((addr, oid))
            self._free_cv.notify()

    def _free_loop(self) -> None:
        """Background deleter: store frees must not block RPC handlers.
        Deletes are grouped per node into ONE batched RPC — churn of many
        small objects must not become thousands of serial round trips."""
        while not self._stopped:
            with self._free_cv:
                while not self._free_q and not self._spill_del_q \
                        and not self._stopped:
                    self._free_cv.wait(1.0)
                batch = []
                while self._free_q:
                    batch.append(self._free_q.popleft())
                spill_urls = []
                while self._spill_del_q:
                    spill_urls.append(self._spill_del_q.popleft())
            by_addr: Dict[str, List[bytes]] = {}
            for addr, oid in batch:
                by_addr.setdefault(addr, []).append(oid)
            for addr, oids in by_addr.items():
                try:
                    get_client(addr).call("delete_objects", oids=oids)
                except Exception:
                    pass
            if spill_urls:
                from ray_tpu.cluster import spill as _spill
                for url in spill_urls:
                    try:
                        _spill.delete_url(url)
                    except Exception:
                        pass

    def rpc_free_object(self, oid: bytes) -> None:
        with self._lock:
            nodes = [self._nodes[n]["address"]
                     for n in self._object_locations.pop(oid, ())
                     if n in self._nodes and self._nodes[n]["alive"]]
            self._object_devices.pop(oid, None)
            sp = self._object_spilled.pop(oid, None)
            self._lost_objects.discard(oid)
        if sp is not None:
            with self._free_cv:
                self._spill_del_q.append(sp[0])
                self._free_cv.notify()
        for addr in nodes:
            try:
                get_client(addr).call("delete_object", oid=oid)
            except Exception:
                pass

    # ------------------------------------------------------------------
    # Actor manager + scheduler (parity: gcs_actor_manager.h:281,
    # gcs_actor_scheduler.h:111 ScheduleByRaylet mode)
    # ------------------------------------------------------------------
    def rpc_register_actor(self, actor_id: bytes, spec: dict) -> dict:
        out = self.rpc_register_actors(
            [{"actor_id": actor_id, "spec": spec}])[0]
        if out.get("error"):
            raise ValueError(out["error"])
        return out

    def rpc_register_actors(self, items: List[dict]) -> List[dict]:
        """Wave registration: one lock acquisition + ONE journal record for
        N actors (parity: Ray's async batched GCS actor registration;
        perf pointer python/ray/_private/ray_perf.py). Each item is
        {"actor_id", "spec"}; the reply aligns with the request — per-item
        "existing" (dedup/get_if_exists hit) or "error" (name collision,
        raised by the single-actor shim, reported in-band here so one bad
        name cannot fail a whole wave)."""
        results: List[Optional[dict]] = [None] * len(items)
        to_schedule: List[bytes] = []
        logged: List[dict] = []
        with self._cv:
            for i, item in enumerate(items):
                actor_id, spec = item["actor_id"], item["spec"]
                name = spec["opts"].get("name") or ""
                ns = spec["opts"].get("namespace") or "default"
                if actor_id in self._actors:
                    # At-least-once delivery (reconnecting client resent
                    # after a lost response): actor ids are caller-
                    # generated, so a duplicate IS the same creation — ack
                    # it, don't collide on the name.
                    results[i] = {"existing": None}
                    continue
                if name:
                    existing = self._named_actors.get((ns, name))
                    if existing is not None and \
                            self._actors[existing].state != DEAD:
                        if spec["opts"].get("get_if_exists"):
                            results[i] = {"existing": existing}
                        else:
                            results[i] = {
                                "existing": None,
                                "error": f"Actor name {name!r} already "
                                         f"taken in namespace {ns!r}"}
                        continue
                    self._named_actors[(ns, name)] = actor_id
                self._actors[actor_id] = ActorInfo(actor_id, spec)
                logged.append({"actor_id": actor_id, "spec": spec})
                to_schedule.append(actor_id)
                results[i] = {"existing": None}
            if logged:
                self._log("actors", {"items": logged})
                self._cv.notify_all()
        self._schedule_actors(to_schedule)
        return results

    def _pick_node_for(self, resources: Dict[str, float],
                       strategy: Any = None) -> Optional[dict]:
        """Feasibility-checked bin-pack over the live resource view.

        Parity: hybrid_scheduling_policy.h:50 — prefer the most-available
        feasible node (scored by remaining capacity) so load spreads once
        nodes fill; placement-group strategies pin to the bundle's node.
        """
        with self._lock:
            if isinstance(strategy, dict) and strategy.get("type") == "pg":
                pg = self._pgs.get(strategy["pg_id"])
                if pg is None or pg.state != "CREATED":
                    return None
                idx = strategy.get("bundle_index", 0)
                if idx == -1:
                    idx = 0
                nid = pg.bundle_nodes[idx]
                info = self._nodes.get(nid)
                return dict(info) if info and info["alive"] else None
            if isinstance(strategy, dict) and strategy.get("type") == "node":
                info = self._nodes.get(strategy["node_id"])
                if info and info["alive"]:
                    return dict(info)
                return None if not strategy.get("soft") else self._best_fit(
                    resources)
            if isinstance(strategy, dict) and strategy.get("type") == "slice":
                # Constrain the candidate set to hosts of complete slices
                # matching the requested topology, then best-fit within it.
                topo = strategy.get("topology") or ""
                candidates: List[dict] = []
                for s in self._slice_view().values():
                    if not s["complete"]:
                        continue
                    if topo and s["accelerator_type"] != topo:
                        continue
                    candidates.extend(s["hosts"])
                return self._best_fit(resources, candidates)
            return self._best_fit(resources)

    def _best_fit(self, resources: Dict[str, float],
                  candidates: Optional[List[dict]] = None) -> Optional[dict]:
        best, best_score = None, -1.0
        pool = self._nodes.values() if candidates is None else candidates
        for info in pool:
            if not info["alive"]:
                continue
            avail = info["resources_available"]
            total = info["resources_total"]
            if any(avail.get(k, 0.0) + 1e-9 < v for k, v in resources.items()
                   if v > 0):
                continue
            # Score: fraction of capacity left after placing (pack towards
            # busy-but-feasible nodes is the reference PACK flavor; we spread
            # by preferring the emptiest feasible node for throughput).
            score = sum(avail.get(k, 0.0) / max(total.get(k, 1.0), 1e-9)
                        for k in ("CPU", "TPU"))
            if score > best_score:
                best, best_score = info, score
        return dict(best) if best else None

    def _schedule_actor(self, actor_id: bytes) -> None:
        self._schedule_actors([actor_id])

    def _schedule_actors(self, actor_ids: List[bytes]) -> None:
        """Place a wave of actors: node picks happen in one pass, then the
        conductor sends ONE ``start_actors`` RPC per target daemon instead
        of one ``start_actor`` per actor (the round-5 profile pinned wave
        collapse on exactly these serialized per-actor round-trips)."""
        # Fault point: delay/raise while a wave is being placed (a raise
        # here fails the scheduling pass; pending actors re-enter via the
        # retry timers / restart FSM, which is what chaos runs verify).
        fault_plane.fire("conductor.actor.schedule", count=len(actor_ids))
        by_node: Dict[str, List[dict]] = {}
        node_of: Dict[str, bytes] = {}
        for actor_id in actor_ids:
            with self._lock:
                a = self._actors.get(actor_id)
                if a is None or a.state == DEAD:
                    continue
                spec = a.spec
            node = self._pick_node_for(
                spec["opts"].get("resources_req", {"CPU": 1.0}),
                spec["opts"].get("scheduling_strategy"))
            if node is None:
                # No feasible node now: retry when membership/resources
                # change.
                threading.Timer(0.2, self._schedule_actor,
                                args=(actor_id,)).start()
                continue
            with self._lock:
                a = self._actors.get(actor_id)
                if a is None or a.state == DEAD:
                    continue
                a.node_id = node["node_id"]
                incarnation = a.incarnation
            by_node.setdefault(node["address"], []).append(
                {"actor_id": actor_id, "spec": spec,
                 "incarnation": incarnation})
            node_of[node["address"]] = node["node_id"]
        for addr, batch in by_node.items():
            try:
                get_client(addr).call("start_actors", items=batch)
            except Exception as e:  # node unreachable -> mark dead
                self._mark_node_dead(node_of[addr], f"unreachable: {e}")

    def rpc_actor_started(self, actor_id: bytes, address: str,
                          node_id: bytes, incarnation: int) -> None:
        with self._cv:
            a = self._actors.get(actor_id)
            if a is None or a.incarnation != incarnation:
                return
            a.state = ALIVE
            a.address = address
            a.node_id = node_id
            self._log("actor_state", self._actor_record(a))
            self._cv.notify_all()

    def rpc_actor_creation_failed(self, actor_id: bytes, incarnation: int,
                                  error_blob: bytes) -> None:
        with self._cv:
            a = self._actors.get(actor_id)
            if a is None or a.incarnation != incarnation:
                return
            a.state = DEAD
            a.death_reason = "creation failed"
            a.spec["creation_error"] = error_blob
            self._drop_name(a)
            self._log("actor_state", self._actor_record(a))
            self._cv.notify_all()

    def rpc_report_actor_death(self, actor_id: bytes, reason: str,
                               incarnation: Optional[int] = None) -> None:
        self._on_actor_death(actor_id, reason, incarnation)

    def _on_actor_death(self, actor_id: bytes, reason: str,
                        incarnation: Optional[int] = None) -> None:
        """Restart FSM (parity: gcs_actor_manager.h ALIVE->RESTARTING->...).

        ``incarnation`` dedupes reports: one worker death can be observed
        both by the daemon reaper and by a failed RPC — only the first
        report for the current incarnation burns a restart.
        """
        with self._cv:
            a = self._actors.get(actor_id)
            if a is None or a.state == DEAD:
                return
            if incarnation is not None and incarnation != a.incarnation:
                return  # stale report about an already-replaced incarnation
            max_restarts = a.spec["opts"].get("max_restarts", 0)
            if max_restarts == -1 or a.num_restarts < max_restarts:
                a.num_restarts += 1
                a.incarnation += 1
                a.state = RESTARTING
                a.address = None
                self._log("actor_state", self._actor_record(a))
                self._emit_event(
                    "WARNING", "conductor", "ACTOR_RESTARTING",
                    f"actor {a.spec.get('class_name', '')} "
                    f"{actor_id.hex()[:8]} restarting "
                    f"({a.num_restarts}/{max_restarts}): {reason}",
                    actor_id=actor_id.hex(), reason=reason)
                self._cv.notify_all()
                restart = True
            else:
                a.state = DEAD
                a.death_reason = reason
                a.address = None
                self._drop_name(a)
                self._log("actor_state", self._actor_record(a))
                self._emit_event(
                    "ERROR", "conductor", "ACTOR_DEAD",
                    f"actor {a.spec.get('class_name', '')} "
                    f"{actor_id.hex()[:8]} died: {reason}",
                    actor_id=actor_id.hex(), reason=reason)
                self._cv.notify_all()
                restart = False
        if restart:
            self._schedule_actor(actor_id)

    def _drop_name(self, a: ActorInfo) -> None:
        name = a.spec["opts"].get("name") or ""
        ns = a.spec["opts"].get("namespace") or "default"
        if name and self._named_actors.get((ns, name)) == a.actor_id:
            del self._named_actors[(ns, name)]

    def rpc_kill_actor(self, actor_id: bytes, no_restart: bool = True) -> None:
        with self._cv:
            a = self._actors.get(actor_id)
            if a is None:
                return
            if no_restart:
                a.spec["opts"]["max_restarts"] = 0
            addr = a.address
        if addr:
            try:
                get_client(addr).call("kill_actor", actor_id=actor_id)
            except Exception:
                pass
        self._on_actor_death(actor_id, "killed via kill()")

    def rpc_get_actor_info(self, actor_id: bytes,
                           wait_alive_timeout: float = 0.0) -> dict:
        """Resolve an actor's state/address; optionally long-poll until it
        leaves PENDING/RESTARTING (parity: actor address pubsub)."""
        deadline = time.monotonic() + wait_alive_timeout
        with self._cv:
            while True:
                a = self._actors.get(actor_id)
                if a is None:
                    return {"state": "UNKNOWN"}
                if a.state in (ALIVE, DEAD) or wait_alive_timeout <= 0:
                    return self._actor_info_of(a)
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return self._actor_info_of(a)
                self._cv.wait(min(remaining, 1.0))

    def rpc_get_actor_infos(self, actor_ids: List[bytes],
                            wait_alive_timeout: float = 0.0) -> List[dict]:
        """Batched get_actor_info: ONE long-poll covers a whole wave (the
        driver-side shared resolver multiplexes every pending actor of a
        process into this). Returns as soon as any actor newly leaves
        PENDING/RESTARTING — the caller unblocks what resolved and re-polls
        for the rest — or at the timeout. Unregistered ids report UNKNOWN
        but keep the poll alive: with driver-side registration coalescing a
        wave member may be an in-flight register away."""
        deadline = time.monotonic() + wait_alive_timeout

        def snapshot():
            infos, resolved = [], 0
            for aid in actor_ids:
                a = self._actors.get(aid)
                if a is None:
                    infos.append({"state": "UNKNOWN"})
                else:
                    infos.append(self._actor_info_of(a))
                    if a.state in (ALIVE, DEAD):
                        resolved += 1
            return infos, resolved

        with self._cv:
            infos, baseline = snapshot()
            if wait_alive_timeout <= 0 or baseline == len(actor_ids):
                return infos
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return infos
                self._cv.wait(min(remaining, 1.0))
                infos, resolved = snapshot()
                if resolved > baseline or resolved == len(actor_ids):
                    return infos

    @staticmethod
    def _actor_info_of(a: "ActorInfo") -> dict:
        return {"state": a.state, "address": a.address,
                "node_id": a.node_id,
                "incarnation": a.incarnation,
                "death_reason": a.death_reason,
                "creation_error": a.spec.get("creation_error"),
                "class_name": a.spec.get("class_name", ""),
                "methods": a.spec.get("methods"),
                "is_async": a.spec.get("is_async", False)}

    def rpc_get_named_actor(self, name: str, namespace: str = "default") -> Optional[bytes]:
        with self._lock:
            return self._named_actors.get((namespace or "default", name))

    def rpc_list_actors(self) -> List[dict]:
        with self._lock:
            return [{"actor_id": a.actor_id.hex(), "state": a.state,
                     "class_name": a.spec.get("class_name", ""),
                     "name": a.spec["opts"].get("name", ""),
                     "node_id": a.node_id.hex() if a.node_id else None,
                     "num_restarts": a.num_restarts,
                     "pid": None}
                    for a in self._actors.values()]

    # ------------------------------------------------------------------
    # Placement groups (parity: gcs_placement_group_manager.h:223 +
    # 2PC prepare/commit of gcs_placement_group_scheduler.h:265)
    # ------------------------------------------------------------------
    def rpc_create_placement_group(self, pg_id: bytes,
                                   bundles: List[Dict[str, float]],
                                   strategy: str, name: str = "",
                                   slice_topology: str = "") -> None:
        pg = PlacementGroupInfo(pg_id, bundles, strategy, name,
                                slice_topology=slice_topology)
        with self._lock:
            self._pgs[pg_id] = pg
            self._log("pg", {"pg_id": pg_id, "bundles": bundles,
                             "strategy": strategy, "name": name,
                             "slice_topology": slice_topology})
        self._try_place_pg(pg)

    def _try_place_pg(self, pg: PlacementGroupInfo) -> None:
        """Pick nodes per strategy, then 2PC: prepare on every node; commit
        all on success, return-on-any-failure (retry later). Single-placer:
        concurrent triggers (registration handlers, retry timers, node-death
        replacement) collapse onto one in-flight attempt — two attempts
        committing different plans would leak the losing plan's bundles."""
        with self._lock:
            if pg.state != "PENDING" or pg.placing:
                return
            pg.placing = True
            live = [dict(v) for v in self._nodes.values() if v["alive"]]
        try:
            plan = self._plan_bundles(pg, live)
            if plan is None:
                self._schedule_pg_retry(pg)
                return
            prepared: List[Tuple[bytes, str, int]] = []
            ok = True
            for idx, node in enumerate(plan):
                try:
                    granted = get_client(node["address"]).call(
                        "prepare_bundle", pg_id=pg.pg_id, bundle_index=idx,
                        resources=pg.bundles[idx])
                except Exception:
                    granted = False
                if not granted:
                    ok = False
                    break
                prepared.append((node["node_id"], node["address"], idx))
            with self._lock:
                removed = pg.state == "REMOVED"
            if ok and not removed:
                for _, addr, idx in prepared:
                    try:
                        get_client(addr).call("commit_bundle", pg_id=pg.pg_id,
                                              bundle_index=idx)
                    except Exception:
                        pass
                with self._cv:
                    if pg.state == "REMOVED":
                        removed = True  # raced remove: roll back below
                    else:
                        pg.bundle_nodes = [n["node_id"] for n in plan]
                        pg.state = "CREATED"
                        self._log("pg_state", {
                            "pg_id": pg.pg_id, "state": pg.state,
                            "bundle_nodes": pg.bundle_nodes,
                            "slice_id": pg.slice_id})
                        self._cv.notify_all()
            if not ok or removed:
                for _, addr, idx in prepared:
                    try:
                        get_client(addr).call("return_bundle", pg_id=pg.pg_id,
                                              bundle_index=idx)
                    except Exception:
                        pass
                if not removed:
                    self._schedule_pg_retry(pg)
        finally:
            with self._lock:
                pg.placing = False

    def _schedule_pg_retry(self, pg: PlacementGroupInfo) -> None:
        """At most one pending retry timer per PG (triggers can arrive from
        every node registration; unchecked they'd multiply timer chains)."""
        with self._lock:
            if pg.retry_scheduled or pg.state != "PENDING":
                return
            pg.retry_scheduled = True

        def fire():
            with self._lock:
                pg.retry_scheduled = False
            self._try_place_pg(pg)

        threading.Timer(0.5, fire).start()

    def _plan_bundles(self, pg: PlacementGroupInfo,
                      live: List[dict]) -> Optional[List[dict]]:
        """STRICT_PACK: all on one node. PACK: prefer few nodes. SPREAD:
        round-robin distinct nodes. STRICT_SPREAD: distinct node per bundle.
        Bundle feasibility is checked against available resources."""
        def fits(avail, res):
            return all(avail.get(k, 0.0) + 1e-9 >= v for k, v in res.items())

        avail = {n["node_id"]: dict(n["resources_available"]) for n in live}
        by_id = {n["node_id"]: n for n in live}

        def take(nid, res):
            for k, v in res.items():
                avail[nid][k] = avail[nid].get(k, 0.0) - v

        plan: List[dict] = []
        if pg.strategy == "SLICE":
            # ICI-contiguity: every bundle lands on hosts of ONE complete
            # slice, bundle i on the slice's rank-i host (so jax process
            # indices line up with TPU_WORKER_ID and collectives ride ICI).
            # A request no single slice can hold is refused (stays PENDING)
            # rather than silently spread across slices — stricter than the
            # reference's STRICT_PACK (one *node*), which is the closest
            # analog (gcs_placement_group_scheduler.h:265).
            with self._lock:
                slices = self._slice_view()
            for s in sorted(slices.values(), key=lambda s: s["slice_id"]):
                if not s["complete"]:
                    continue
                if pg.slice_topology and \
                        s["accelerator_type"] != pg.slice_topology:
                    continue
                if len(pg.bundles) > len(s["hosts"]):
                    continue
                ok = True
                for i, b in enumerate(pg.bundles):
                    host = s["hosts"][i]
                    if not fits(avail.get(host["node_id"], {}), b):
                        ok = False
                        break
                    take(host["node_id"], b)
                if ok:
                    pg.slice_id = s["slice_id"]
                    return [by_id[h["node_id"]] for h in
                            s["hosts"][:len(pg.bundles)]]
                # restore tentative takes before trying the next slice
                avail.update({n["node_id"]: dict(n["resources_available"])
                              for n in live})
            return None
        if pg.strategy in ("STRICT_PACK", "PACK"):
            order = sorted(live, key=lambda n: -sum(
                n["resources_available"].get(k, 0.0) for k in ("CPU", "TPU")))
            if pg.strategy == "STRICT_PACK":
                for n in order:
                    a = dict(avail[n["node_id"]])
                    if all(fits_and_take(a, b) for b in pg.bundles):
                        return [n] * len(pg.bundles)
                return None
            for b in pg.bundles:
                placed = False
                for n in plan + order:  # prefer already-used nodes (PACK)
                    nid = n["node_id"]
                    if fits(avail[nid], b):
                        take(nid, b)
                        plan.append(by_id[nid])
                        placed = True
                        break
                if not placed:
                    return None
            return plan
        # SPREAD / STRICT_SPREAD
        used: Set[bytes] = set()
        for b in pg.bundles:
            candidates = sorted(
                live, key=lambda n: (n["node_id"] in used, -sum(
                    avail[n["node_id"]].get(k, 0.0) for k in ("CPU", "TPU"))))
            placed = False
            for n in candidates:
                nid = n["node_id"]
                if pg.strategy == "STRICT_SPREAD" and nid in used:
                    continue
                if fits(avail[nid], b):
                    take(nid, b)
                    used.add(nid)
                    plan.append(n)
                    placed = True
                    break
            if not placed:
                return None
        return plan

    def rpc_pg_ready(self, pg_id: bytes, timeout: float = 0.0) -> dict:
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                pg = self._pgs.get(pg_id)
                if pg is None:
                    return {"state": "UNKNOWN"}
                if pg.state == "CREATED" or timeout <= 0:
                    return {"state": pg.state,
                            "bundle_nodes": list(pg.bundle_nodes),
                            "slice_id": pg.slice_id}
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {"state": pg.state,
                            "bundle_nodes": list(pg.bundle_nodes),
                            "slice_id": pg.slice_id}
                self._cv.wait(min(remaining, 1.0))

    def rpc_remove_placement_group(self, pg_id: bytes) -> None:
        with self._lock:
            pg = self._pgs.pop(pg_id, None)
            if pg is None:
                return
            pg.state = "REMOVED"
            self._log("pg_removed", {"pg_id": pg_id})
            targets = [(self._nodes[n]["address"], i)
                       for i, n in enumerate(pg.bundle_nodes)
                       if n in self._nodes and self._nodes[n]["alive"]]
        for addr, idx in targets:
            try:
                get_client(addr).call("return_bundle", pg_id=pg_id,
                                      bundle_index=idx)
            except Exception:
                pass

    def rpc_list_placement_groups(self) -> List[dict]:
        with self._lock:
            return [{"pg_id": pg.pg_id.hex(), "state": pg.state,
                     "strategy": pg.strategy, "name": pg.name,
                     "slice_id": pg.slice_id,
                     "bundles": pg.bundles} for pg in self._pgs.values()]

    # ------------------------------------------------------------------
    # Task events / jobs (parity: gcs_task_manager.h:61, GcsJobManager)
    # ------------------------------------------------------------------
    def rpc_get_task_events(self) -> List[dict]:
        """One dict an executed task, actor task or actor creation, oldest
        first, made from the flight recorder's ``task.exec`` records (a
        worker stores one as an execution ends; they ship with its ring):
        what ``state.list_tasks``, the dashboard's task view and
        ``rt.timeline()`` read. Bounded as the ring store is."""
        return [_events.task_view(e)
                for e in self.rpc_get_ring_events(kind="task.exec")]

    # Flight-recorder event store (util/events.py sink; GcsTaskManager's
    # bounded-store role for the compact ring events every plane emits).
    def rpc_push_ring_events(self, node_id: str, pid: int, events,
                             dropped: int = 0) -> dict:
        recs = [{"ts": e[0], "kind": e[1], "ident": e[2], "value": e[3],
                 "attrs": e[4], "node_id": node_id, "pid": pid}
                for e in events]
        with self._ring_lock:
            self._ring_events.extend(recs)
            self._ring_dropped += int(dropped)
            if len(self._ring_events) > 200_000:
                del self._ring_events[:len(self._ring_events) - 200_000]
        return {"ok": True}

    def rpc_get_ring_events(self, limit: int = 0,
                            kind: Optional[str] = None,
                            spans_only: bool = False,
                            ident: Optional[str] = None) -> List[dict]:
        """``spans_only``: the records that are spans (util/events.py:
        their attrs carry the span's id); ``ident``: one request's, one
        lease's or one fit()'s records."""
        with self._ring_lock:
            evs = list(self._ring_events)
        if kind:
            evs = [e for e in evs
                   if e["kind"] == kind or e["kind"].startswith(kind + ".")]
        if spans_only:
            evs = [e for e in evs if e["attrs"] and "span" in e["attrs"]]
        if ident:
            evs = [e for e in evs if e["ident"] == ident]
        return evs[-limit:] if limit else evs

    def rpc_debug_state(self) -> dict:
        """Internal-table sizes + queue depths (raylet debug_state.txt
        parity, conductor slice). Cheap: counts only, no copies."""
        with self._lock:
            nodes_alive = sum(1 for n in self._nodes.values() if n["alive"])
            actor_states: Dict[str, int] = {}
            for a in self._actors.values():
                actor_states[a.state] = actor_states.get(a.state, 0) + 1
            kv_ns: Dict[str, int] = {}
            for (n, _k) in self._kv:
                kv_ns[n] = kv_ns.get(n, 0) + 1
            out = {
                "role": "conductor",
                "epoch": self._epoch,
                "nodes_alive": nodes_alive,
                "nodes_total": len(self._nodes),
                "actors": actor_states,
                "named_actors": len(self._named_actors),
                "functions": len(self._functions),
                "kv_keys_by_ns": kv_ns,
                "object_locations": len(self._object_locations),
                "objects_spilled": len(self._object_spilled),
                "objects_lost": len(self._lost_objects),
                "refcount_entries": len(self._refcounts),
                "ref_tombstones": len(self._ref_tombstones),
                "placement_groups": len(self._pgs),
            }
        with self._free_cv:
            out["free_queue"] = len(self._free_q)
        with self._ring_lock:
            out["ring_events"] = len(self._ring_events)
            out["ring_events_dropped"] = self._ring_dropped
        out["cluster_events"] = len(self._events)
        return out

    def rpc_next_job_id(self) -> int:
        with self._lock:
            self._job_counter += 1
            self._log("job", {"counter": self._job_counter})
            return self._job_counter

    # ------------------------------------------------------------------
    # Worker-log pubsub (parity: the log channel of src/ray/pubsub +
    # python/ray/_private/log_monitor.py:104 — daemons tail worker files
    # and publish; drivers long-poll and print)
    # ------------------------------------------------------------------
    def rpc_push_logs(self, lines: List[dict]) -> None:
        with self._log_cv:
            for line in lines:
                self._log_seq += 1
                self._log_buffer.append((self._log_seq, line))
            self._log_cv.notify_all()

    def rpc_poll_logs(self, after_seq: int, timeout: float = 0.0) -> dict:
        deadline = time.monotonic() + timeout
        with self._log_cv:
            while True:
                if self._log_seq > after_seq:
                    # seqs are monotonic: walk back from the tail only as
                    # far as needed instead of scanning the whole ring
                    n = min(len(self._log_buffer),
                            self._log_seq - after_seq)
                    out = [l for s, l in list(self._log_buffer)[-n:]
                           if s > after_seq]
                    return {"lines": out, "seq": self._log_seq}
                if timeout <= 0:
                    return {"lines": [], "seq": self._log_seq}
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {"lines": [], "seq": self._log_seq}
                self._log_cv.wait(min(remaining, 1.0))

    def rpc_ping(self) -> str:
        return "pong"

    def stop(self) -> None:
        self._stopped = True
        self.server.stop()
        if self._journal is not None:
            self._journal.close()


def fits_and_take(avail: Dict[str, float], res: Dict[str, float]) -> bool:
    if any(avail.get(k, 0.0) + 1e-9 < v for k, v in res.items()):
        return False
    for k, v in res.items():
        avail[k] = avail.get(k, 0.0) - v
    return True

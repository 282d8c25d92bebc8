"""Flight-recorder event ring: near-free lifecycle events on every plane.

Role parity: task_event_buffer.h:188 (bounded, buffered, asynchronously
shipped task events) + profile_event.h (compact per-process profile
events merged into one cluster timeline). Every plane calls

    events.emit("pull.chunk", ident=oid_hex, value=nbytes)

and pays one cached-flag check, a tuple build, and a ring-slot store —
no RPC, no allocation growth (the ring is preallocated and overwrites
the oldest entry when full, counting what it dropped). A background
flusher ships ring deltas to the conductor in batches, so NOTHING on the
submit/execute/pull hot paths performs a synchronous conductor RPC.
Processes that already run a periodic conductor RPC (the node daemon's
heartbeat) piggyback their delta on it via ``heartbeat_payload()``
instead of paying a second connection.

Event shape (a plain tuple — cheapest thing that pickles):

    (ts, kind, ident, value, attrs)

``kind`` is a dotted event name ("task.submit", "rpc.frame", ...),
``ident`` an optional correlation id (task id hex, object id hex),
``value`` a number whose meaning the kind fixes (latency seconds,
bytes, window occupancy), ``attrs`` an optional small dict.

A SPAN is the same tuple with a fixed reading (there is one span system,
and it is this ring):

    ts     start, by ``time.time()`` (the host's CLOCK_REALTIME, which is
           also the clock a ``jax.profiler`` trace counts from: its
           ``profile_start_time`` + an event's ``start_ns``)
    value  duration in seconds, taken with ``time.perf_counter()``
    ident  what every span of one request / one lease / one ``fit()`` shares
    attrs  {"span": id, "parent": id or None, ...counts}

``span(kind)`` is the context manager; the current span lives in a context
variable, so a child finds its parent and ``ident`` untold.
``span_record`` writes an interval that began on one thread and ended on
another. ``current()`` is the context a task spec carries (``trace_ctx``);
``adopt`` makes it current around the callee's execution, and
``span(kind, ctx=...)`` opens a span as its child. In a process
that has imported jax, ``span`` also enters
``jax.profiler.TraceAnnotation("rt." + kind)``, so a traced run shows the
runtime's spans beside the device's operations. ``last_session()`` holds the
span records of the runtime this process last shut down.

On top of the ring:

- the flusher folds drained events into the built-in per-plane metrics
  registry (util/metrics.py) — counters/histograms update in batch off
  the hot path (metrics_agent role);
- ``register_probe`` lets planes expose point-in-time gauges (RPC
  in-flight, cache sizes) sampled once per flush instead of per call;
- a slow-op watchdog (``watch_begin``/``watch_end``) reports any
  task/pull/RPC outliving ``slow_op_threshold_s`` to the conductor as
  a structured cluster event carrying the surrounding ring context;
- ``start_host_watch`` records the process's own pauses (``host.pause``
  with whose pause it was, ``gc.pause``, one ``host.watch`` a second) in
  the processes where a pause holds a chip or a reply back: a stalled
  step or call finds its owner in the ring of any run, traced or not.
"""

from __future__ import annotations

import collections
import contextvars
import gc
import itertools
import os
import resource
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu import config

# Canonical registry of flight-recorder event kinds: every
# ``emit("…")`` literal in the tree must be minted here (rtcheck's
# name-drift checker enforces both directions; ``test.*`` kinds used by
# the test suite live outside the scanned tree). The doc states what
# ``value`` means for the kind.
EVENT_KINDS: Dict[str, str] = {
    # task plane
    "task.submit": "value unused; ident = task id",
    "task.exec": "value = execution seconds, ts = the end; ident = task id "
                 "(an actor creation: the actor id); attrs carry task (the "
                 "name), kind (task / actor_task / actor_creation) and, "
                 "of a failed one, error: the conductor answers "
                 "get_task_events from these (rt.timeline(), "
                 "state.list_tasks(), the dashboard's task view)",
    "task.reply": "value = end-to-end seconds",
    "task.retry": "value = retries remaining",
    "task.execute": "span: value = seconds; a task whose spec carried a "
                    "trace_ctx, parent = the caller's span",
    "lease.grant": "span: value = seconds from the request to the grant "
                   "(or to the actor alive); attrs carry TPU",
    # an actor call's four stations, recorded only for a call whose spec
    # carries a trace_ctx (made under an open span); all four are children
    # of that span and carry its ident. One host, one clock: the wire is
    # call.turn.ts - end(call.submit), the wake end(call.get) -
    # end(call.return); across hosts the difference is skewed by the
    # hosts' clocks and the readers refuse it.
    "call.submit": "span: value = seconds from submit_actor_task's first "
                   "line to the push frame handed to the socket; attrs "
                   "carry bytes (the args blob) and window_wait_s (queued "
                   "behind the per-actor ordered send window)",
    "call.turn": "span: value = seconds from rpc_push_actor_task's first "
                 "line to the user's method's first line; attrs carry "
                 "turn_wait_s (the seqno turn), pool_wait_s (the hand-off "
                 "to a pool thread or the actor's loop) and resolve_s (the "
                 "arguments fetched and unpickled)",
    "call.return": "span: value = seconds from the user's method's return "
                   "to its returns stored and sealed (or, inline, handed "
                   "to the reply); attrs carry bytes, inline (1: rode the "
                   "reply, its seal is the lazy sealer's, after the ack), "
                   "seal_wait_s (in the store's put) and lock_wait_s (of "
                   "that, waiting for the process's store connection)",
    "call.get": "span: value = seconds from the first line of the get that "
                "resolves the ref to the value in hand; attrs carry "
                "parked_s (in wait_inline and the conductor's "
                "locate_object long poll), woken_ts (time.time() as the "
                "last of those parks ended: woken to value in hand is the "
                "span's end less this) and lock_wait_s (of woken to value "
                "in hand, in line for the process's store connection: it "
                "starts again from 0 as a park ends)",
    # rpc plane
    "rpc.frame": "value = frame round-trip seconds; attrs carry bytes",
    # object plane
    "pull.window": "value = window bytes granted",
    "pull.chunk": "value = chunk bytes fetched",
    "pull.done": "value = total pulled bytes",
    "pull.failover": "value = failed-source ordinal",
    "pull.shm_direct": "value = bytes served shm-direct",
    "push.chunk": "value = chunk bytes pushed",
    "object.put.backpressure": "value = delay seconds",
    "inline.hit": "value = inline bytes served from cache",
    "inline.miss": "value unused; ident = object id",
    # device-native array objects (r16)
    "object.array.put": "value = array blob bytes stored zero-copy",
    "object.bcast.leg": "value = bytes moved by one broadcast tree leg",
    "object.bcast.done": "value = broadcast seconds; attrs carry "
                         "members/bytes/fallback",
    "object.bcast.fallback": "value = members re-striped onto the "
                             "classic pull path",
    # spill / evict tier
    "object.spill.write": "value = bytes spilled",
    "object.spill.restore": "value = bytes restored",
    "object.evict": "value = shm bytes evicted",
    # compiled graphs
    "cgraph.execute": "value = execution seconds",
    "cgraph.slot.write": "value = slot write seconds",
    "cgraph.slot.wait": "value = reader-blocked seconds",
    "pipeline.stage.op": "value = stage op seconds",
    "pipeline.step": "value = step seconds",
    # serve ingress
    "serve.request": "span: value = request seconds, body parsed to "
                     "last byte written; mints the request's ident; "
                     "attrs carry code",
    "serve.proxy.admit": "span: value = seconds queued for an ongoing slot",
    "serve.proxy.thread_wait": "span: value = seconds from run_in_executor "
                               "to the call's first line on a pool thread",
    "serve.handle.slot_wait": "span: value = seconds in the handle's "
                              "replica-slot loop",
    "serve.handle.call": "span: value = seconds from submit to resolved; "
                         "attrs carry retries",
    "serve.replica.call": "span: value = seconds in handle_request; attrs "
                          "carry inflight on entry",
    "serve.batch.wait": "span: value = seconds from enqueue to the start "
                        "of the flush that took it; attrs carry flush",
    "serve.batch.reply": "span: value = seconds from fn's return to the "
                         "last waiter woken; attrs carry p99_ms on the "
                         "adaptive path",
    "serve.shed": "value unused; attrs carry reason",
    "serve.timeout": "value = deadline seconds",
    "serve.retry": "value = attempt ordinal",
    "serve.drain": "value = drained ongoing count",
    "serve.batch.flush": "span: value = seconds in the batched fn; attrs "
                         "carry rows/max_batch_size/window_s/oldest_wait_s, "
                         "newest_wait_s (their difference: how far apart the "
                         "batch's callers arrived), cause (full: the arrival "
                         "that filled it; window: the assembling batch's "
                         "timer; after_running: armed by the end of the batch "
                         "it waited through), left_pending (calls that stayed "
                         "queued as it went) and since_last_s (from the "
                         "previous flush's fn returning to this one's "
                         "starting, on this batcher; absent on the first)",
    # trainer gang
    "train.fit": "span: value = seconds of one fit(); mints the ident",
    "train.backend.start": "span: value = seconds in BackendExecutor.start",
    "train.gang.start": "span: value = seconds of placement + actor "
                        "creation",
    "train.loop": "span: value = seconds of the user's loop on one rank",
    "train.report": "span: value = seconds in session.report; attrs carry "
                    "iteration and period_s (seconds since this rank's "
                    "previous report began; absent on the first: a loop that "
                    "reports once a step reads its step period here)",
    "train.step": "span: value = seconds from a step's dispatch to its "
                  "metrics on the host, opened by the user's loop; attrs "
                  "carry the step's counters (the expert layers' "
                  "moe_rows_here, moe_rows_dropped, moe_rows_walked, "
                  "moe_load_max, moe_load_mean)",
    "generate.call": "span: value = seconds of one compiled generate call, "
                     "dispatch to tokens on the host, opened by its caller "
                     "(models.generate.call_span); attrs carry rows/prompt/"
                     "new/loop_steps/cache_slots/cache_bytes, the token "
                     "loop's decode_segments with the cache positions a "
                     "slot's attention reads over the call's steps and "
                     "those written by then (cache_positions_read/"
                     "cache_positions_needed), attention_path (the path "
                     "the prompt's attention took: flash/blockwise/"
                     "reference as ops.attention.auto_path chose, or "
                     "latent), of a looped stack exit_steps_mean, and of "
                     "a stack by kind cache_bytes_latent/_index/_window, "
                     "prefill_chunks, index_topk, keys_scored, "
                     "keys_attended, sparse_kernel_queries (of its queries, "
                     "those attended in rt_sparse_attend), "
                     "window_kernel_queries (of its window layers' prompt "
                     "queries, those attended in rt_flash_fwd with a "
                     "window), moe_rows_here, "
                     "moe_rows_dropped, moe_rows_walked, and of a stack with "
                     "gated-delta-rule layers the cache's bytes by what "
                     "holds them "
                     "(cache_bytes_state: the float32 recurrent states, "
                     "cache_bytes_tail: the convolutions' last inputs, "
                     "cache_bytes_kv: the softmax layers' keys and values) "
                     "and its slots by kind (linear_slots, full_slots)",
    "train.pump": "span: value = seconds of one synchronized report "
                  "round; attrs carry iteration/lag_s",
    # start-up
    "init": "span: value = seconds in rt.init()",
    "init.probe": "span: value = seconds of the chip-probe subprocess; "
                  "attrs carry chips/platform",
    "worker.spawn": "span: value = seconds from Popen to registered; "
                    "attrs carry chips",
    "worker.boot": "span: value = seconds from main()'s first line to "
                   "register_worker acknowledged",
    # the process itself (start_host_watch: a chip-owning worker, the
    # serve proxy's process, the driver); ident = pid:<pid>, no parent
    "host.pause": "span: value = seconds the process's 10 ms ticker was "
                  "woken late (20 ms or more), ts = the wake it asked for; "
                  "attrs say whose pause it was: cpu_s (the process's CPU "
                  "seconds over it: about value = a thread of this process "
                  "held the interpreter or a core all along, about 0 = the "
                  "process did not run), gc_s (seconds of garbage collection "
                  "that ended inside it), and, deltas since a read at most a "
                  "second before, runq_s (the ticker thread's run-queue "
                  "delay: about value = no core for it; left out on a "
                  "kernel without /proc schedstat), majflt and nivcsw "
                  "(getrusage); skipped / skipped_s: pauses over 20 records "
                  "a second that were summed into this one and not recorded",
    "host.watch": "span: value = seconds it covers (one a second from the "
                  "same ticker, so a window without host.pause reads 0 and "
                  "not nothing); attrs carry ticks, late (wakes 20 ms or "
                  "more late), pause_max_s, own_cpu_s (the ticker thread's "
                  "own CPU seconds: what the watch costs) and, where the "
                  "process had already brought a jax backend up, the first "
                  "local device's bytes_in_use, largest_free_block_bytes "
                  "and num_allocs (memory_stats())",
    "gc.pause": "span: value = seconds of one garbage collection, recorded "
                "for every generation-2 collection and any of 5 ms or more; "
                "attrs carry generation and collected",
    # infrastructure
    "fault.fired": "value unused; ident = site, attrs carry action",
    "lock.cycle": "value unused; attrs carry the lock cycle",
    "lock.long_hold": "value = hold seconds; ident = lock name",
}

_lock = threading.Lock()
_buf: List[Any] = []
_cap = 0
_seq = 0          # next write position (monotonic over process life)
_cursor = 0       # first event not yet shipped
_dropped = 0      # overwritten-before-shipping count

_enabled_gen: Optional[int] = None
_enabled_v = False

_node_hex = ""
_conductor_addr: Optional[str] = None
_flusher: Optional[threading.Thread] = None
_flusher_lock = threading.Lock()
_flush_stop = threading.Event()

# Drained-but-unacked delta: drain() advances the cursor before the ship
# RPC, so a failed push must park its events here for the next tick or a
# busy conductor silently loses them (metrics are folded exactly once, on
# the first attempt).
_ship_lock = threading.Lock()
_unshipped: List[tuple] = []
_unshipped_dropped = 0

# slow-op watchdog: token -> (kind, ident, start_ts)
_watch_lock = threading.Lock()
_watch: Dict[int, Tuple[str, Optional[str], float]] = {}
_watch_next = 0
_watch_reported: set = set()

# point-in-time gauge probes: name -> fn() -> {metric_name: value}
_probes: Dict[str, Callable[[], Dict[str, float]]] = {}

# in-flight op scans for the watchdog: name -> fn() -> [(kind, ident,
# elapsed_s)]. Planes that already track their in-flight work (the
# pipelined RPC channels' meta sidecars) expose it here instead of
# paying per-op watch_begin/watch_end registration.
_inflight_scans: Dict[str, Callable[[], List[tuple]]] = {}
_scan_reported: set = set()


def enabled() -> bool:
    """Cached flag read (config.get walks os.environ — too hot for a
    per-event call)."""
    global _enabled_gen, _enabled_v
    if _enabled_gen != config.generation:
        _refresh()
    return _enabled_v


def _refresh() -> None:
    global _enabled_gen, _enabled_v, _buf, _cap
    _enabled_v = bool(config.get("events_enabled"))
    _enabled_gen = config.generation
    if _enabled_v and not _cap:
        with _lock:
            if not _cap:
                cap = max(64, int(config.get("event_ring_size")))
                _buf = [None] * cap
                _cap = cap


def emit(kind: str, ident: Optional[str] = None, value: float = 0.0,
         attrs: Optional[dict] = None) -> None:
    """Append one event to the ring. O(1), never blocks on I/O."""
    if not enabled():
        return
    _store((time.time(), kind, ident, value, attrs))


def _store(ev: tuple) -> None:
    global _seq
    with _lock:
        _buf[_seq % _cap] = ev
        _seq += 1


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
# Ids: a per-process nonce + counter (uuid4 draws urandom per call). A
# forked worker draws a nonce of its own.
_nonce = os.urandom(4).hex()
_ids = itertools.count()


def _renonce() -> None:
    global _nonce
    _nonce = os.urandom(4).hex()


os.register_at_fork(after_in_child=_renonce)

# (ident, span id) of the innermost open span of this thread or task, and
# behind them, of a ``span`` opened here (not of an adopted context), its
# attrs: what ``counters`` hands to the code below it.
_current: "contextvars.ContextVar[Optional[tuple]]" = \
    contextvars.ContextVar("span", default=None)
_last_session: List[dict] = []


def new_span_id() -> str:
    return f"{_nonce}{next(_ids) & 0xFFFFFFFF:08x}"


def current() -> Optional[dict]:
    """The open span as the ``trace_ctx`` of a task spec, or None: a
    submit outside any span attaches nothing."""
    cur = _current.get()
    return None if cur is None else {"ident": cur[0], "span": cur[1]}


def counters(key: str) -> Optional[dict]:
    """The attrs of the span open in this thread if it counts ``key`` (it
    was opened with ``key=0.0``), else None: how a layer below the span (the
    store connection, the locate long poll) adds seconds to the caller's
    span without being handed it. One context-variable read where nothing
    counts."""
    cur = _current.get()
    if cur is None or len(cur) < 3 or key not in cur[2]:
        return None
    return cur[2]


def _annotation(kind: str):
    """``TraceAnnotation("rt.<kind>")`` where jax is already imported (the
    proxy, daemon, conductor and driver never import it for this); with no
    profiler session it costs a flag test."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation("rt." + kind)
    except Exception:       # jax half imported on another thread
        return None


# span(kind, ctx=ROOT): a tree of its own, whatever span is open around it.
ROOT = {"ident": None, "span": None}


class span:
    """``with span("serve.replica.call", inflight=3) as sp:`` times the
    body and records it on exit, as the child of the span open around it.
    ``sp.set(code=200)`` adds counts known only at the end."""

    __slots__ = ("kind", "ident", "attrs", "id", "parent", "ts", "_t0",
                 "_token", "_ann", "_ctx")

    def __init__(self, kind: str, ident: Optional[str] = None,
                 ctx: Optional[dict] = None, **attrs):
        """``ctx``: a ``trace_ctx`` to be the child of (or ``ROOT``: of
        none) in place of the span open around this one."""
        self.kind, self.ident, self.attrs, self._ctx = kind, ident, attrs, ctx
        self.id = self.parent = self._token = self._ann = None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "span":
        if not enabled():
            return self
        cur = _current.get() if self._ctx is None else \
            (self._ctx["ident"], self._ctx["span"])
        if cur is not None and cur[1] is not None:
            self.parent = cur[1]
            if self.ident is None:
                self.ident = cur[0]
        self.id = new_span_id()
        if self.ident is None:
            self.ident = self.id
        self._token = _current.set((self.ident, self.id, self.attrs))
        self.ts = time.time()
        self._ann = _annotation(self.kind)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, etype, exc, tb) -> None:
        if self._token is None:
            return
        duration = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(etype, exc, tb)
        _current.reset(self._token)
        self._token = None
        attrs = {"span": self.id, "parent": self.parent, **self.attrs}
        if exc is not None:
            attrs["error"] = repr(exc)
        _store((self.ts, self.kind, self.ident, duration, attrs))


def span_record(kind: str, start: float, duration: float,
                ident: Optional[str] = None, parent: Optional[str] = None,
                **attrs) -> Optional[str]:
    """Record an interval that began on one thread and ends on another
    (the executor hop, the waiter woken by a flush): ``start`` by
    ``time.time()``, ``duration`` by ``time.perf_counter()``. ``span=`` in
    ``attrs`` is an id minted ahead with ``new_span_id()`` so that children
    could name it before it ended. Returns the span's id."""
    if not enabled():
        return None
    sid = attrs.pop("span", None) or new_span_id()
    _store((start, kind, ident or sid, duration,
            {"span": sid, "parent": parent, **attrs}))
    return sid


class adopt:
    """Make a ``trace_ctx`` (``current()`` of the caller, carried by a task
    spec or an RPC) the current span around the callee's execution, so that
    what the callee records is its child. None adopts nothing."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: Optional[dict]):
        self._ctx, self._token = ctx, None

    def __enter__(self) -> "adopt":
        if self._ctx:
            self._token = _current.set(
                (self._ctx.get("ident"), self._ctx.get("span")))
        return self

    def __exit__(self, *exc) -> None:
        if self._token is not None:
            _current.reset(self._token)
            self._token = None


def keep_session(records: List[dict]) -> None:
    """``rt.shutdown()`` leaves the session's span records here."""
    global _last_session
    _last_session = list(records)


def task_view(record: dict) -> dict:
    """A ``task.exec`` record (the conductor's dict) as the task views show
    it: ``state.list_tasks``, the dashboard's task list, ``rt.timeline()``'s
    execution slices."""
    attrs = record["attrs"] or {}
    return {"task_id": record["ident"] or "", "name": attrs.get("task", ""),
            "kind": attrs.get("kind", "task"),
            "start": record["ts"] - (record["value"] or 0.0),
            "end": record["ts"], "node_id": record["node_id"],
            "pid": record["pid"], "error": attrs.get("error", "")}


def last_session() -> List[dict]:
    """Span records (the conductor's dicts: node_id, pid, ts, kind, ident,
    value, attrs) of the runtime this process last shut down: the run's
    post-mortem, read after the cluster is gone."""
    return list(_last_session)


def snapshot(limit: int = 0) -> List[tuple]:
    """Current ring contents, oldest first (debug dumps / watchdog
    context). Does not move the flush cursor."""
    with _lock:
        if not _cap or _seq == 0:
            return []
        start = max(0, _seq - _cap)
        evs = [_buf[i % _cap] for i in range(start, _seq)]
    return evs[-limit:] if limit and limit < len(evs) else evs


def drain() -> Tuple[List[tuple], int]:
    """Events appended since the last drain (oldest first) plus how many
    were overwritten before they could ship."""
    global _cursor, _dropped
    with _lock:
        if not _cap:
            return [], 0
        end = _seq
        start = _cursor
        if end - start > _cap:
            _dropped += (end - _cap) - start
            start = end - _cap
        evs = [_buf[i % _cap] for i in range(start, end)]
        _cursor = end
        d, _dropped = _dropped, 0
    return evs, d


# ----------------------------------------------------------------------
# slow-op watchdog
# ----------------------------------------------------------------------
def watch_begin(kind: str, ident: Optional[str] = None) -> Optional[int]:
    """Register an in-flight op with the watchdog. Returns a token for
    watch_end, or None when events are disabled (watch_end(None) is a
    no-op, so call sites need no branching)."""
    if not enabled():
        return None
    global _watch_next
    with _watch_lock:
        token = _watch_next
        _watch_next += 1
        _watch[token] = (kind, ident, time.time())
    return token


def watch_end(token: Optional[int]) -> None:
    if token is None:
        return
    with _watch_lock:
        _watch.pop(token, None)
        _watch_reported.discard(token)


def _check_slow_ops(cli) -> None:
    thr = float(config.get("slow_op_threshold_s"))
    if thr <= 0:
        return
    now = time.time()
    with _watch_lock:
        slow = [(tok, k, i, now - t0)
                for tok, (k, i, t0) in _watch.items()
                if now - t0 > thr and tok not in _watch_reported]
        for tok, *_ in slow:
            _watch_reported.add(tok)
    # Registration-free ops (RPC frames): scan, dedup on approximate
    # start time (the same stuck op reports once across sweeps), prune
    # keys whose op finished.
    live = set()
    for fn in list(_inflight_scans.values()):
        try:
            for kind, ident, elapsed in fn():
                key = (kind, ident, round(now - elapsed, 1))
                live.add(key)
                if elapsed > thr and key not in _scan_reported:
                    _scan_reported.add(key)
                    slow.append((key, kind, ident, elapsed))
        except Exception:
            pass
    _scan_reported.intersection_update(live)
    for tok, kind, ident, elapsed in slow:
        try:
            cli.call(
                "report_event", severity="WARNING",
                source=f"events-{_node_hex[:8]}-{os.getpid()}",
                event_type="SLOW_OPERATION",
                message=f"{kind} {ident or ''} in flight for "
                        f"{elapsed:.1f}s (> {thr}s)",
                metadata={"kind": kind, "ident": ident,
                          "elapsed_s": round(elapsed, 3),
                          "pid": os.getpid(),
                          "ring_tail": snapshot(limit=50)})
        except Exception:
            if isinstance(tok, int):
                with _watch_lock:
                    _watch_reported.discard(tok)  # retry next sweep
            else:
                _scan_reported.discard(tok)


# ----------------------------------------------------------------------
# gauge probes (sampled once per flush, zero hot-path cost)
# ----------------------------------------------------------------------
def register_probe(name: str,
                   fn: Callable[[], Dict[str, float]]) -> None:
    """Register a callable returning {metric_name: value} gauges,
    sampled by the flusher (RPC in-flight, cache sizes, store usage)."""
    _probes[name] = fn


def register_inflight_scan(name: str,
                           fn: Callable[[], List[tuple]]) -> None:
    """Register a callable returning [(kind, ident, elapsed_s)] for ops
    currently in flight. The watchdog sweeps these alongside
    watch_begin-registered ops — the zero-hot-path-cost alternative for
    planes that already track their outstanding work."""
    _inflight_scans[name] = fn


def _sample_probes() -> None:
    from ray_tpu.util import metrics as _metrics
    for fn in list(_probes.values()):
        try:
            for name, value in fn().items():
                _metrics.builtin(_metrics.Gauge, name).set(value)
        except Exception:
            pass


# ----------------------------------------------------------------------
# event -> built-in metrics folding (runs in the flusher, not inline)
# ----------------------------------------------------------------------
def _fold_metrics(evs: List[tuple], dropped: int) -> None:
    from ray_tpu.util import metrics as m
    C, H = m.Counter, m.Histogram
    for ev in evs:
        kind, value, attrs = ev[1], ev[3], ev[4]
        if kind == "task.submit":
            m.builtin(C, "rt_tasks_submitted_total").inc()
        elif kind == "task.exec":
            if attrs and attrs.get("kind", "task") != "task":
                continue    # an actor's call or creation: a task view only
            m.builtin(C, "rt_tasks_executed_total").inc()
            m.builtin(H, "rt_task_exec_s").observe(value)
        elif kind == "task.reply":
            m.builtin(C, "rt_task_replies_total").inc()
        elif kind == "task.retry":
            m.builtin(C, "rt_task_retries_total").inc()
        elif kind == "lease.grant":
            m.builtin(H, "rt_lease_latency_s",
                      boundaries=[0.001, 0.005, 0.02, 0.1, 0.5, 2, 10]
                      ).observe(value)
        elif kind == "rpc.frame":
            # One event covers attrs["frames"] frames (channel-side
            # aggregation); value is the triggering frame's latency.
            a = attrs or {}
            t = a.get("transport", "")
            m.builtin(H, "rt_rpc_frame_latency_s", tag_keys=("transport",),
                      boundaries=[0.0002, 0.001, 0.005, 0.02, 0.1, 1]
                      ).observe(value, tags={"transport": t})
            m.builtin(C, "rt_rpc_frames_total",
                      tag_keys=("transport",)).inc(
                a.get("frames", 1), tags={"transport": t})
            m.builtin(C, "rt_rpc_frame_bytes_total",
                      tag_keys=("transport",)).inc(
                a.get("bytes", 0), tags={"transport": t})
        elif kind == "pull.window":
            m.builtin(C, "rt_pull_windows_total").inc()
        elif kind == "pull.chunk":
            m.builtin(C, "rt_pull_bytes_total").inc(value)
        elif kind == "pull.failover":
            m.builtin(C, "rt_pull_failovers_total").inc()
        elif kind == "pull.shm_direct":
            m.builtin(C, "rt_pull_shm_direct_total").inc()
            m.builtin(C, "rt_pull_bytes_total").inc(value)
        elif kind == "push.chunk":
            m.builtin(C, "rt_push_bytes_total").inc(value)
        elif kind == "object.spill.write":
            m.builtin(C, "rt_spill_objects_total").inc()
            m.builtin(C, "rt_spill_bytes_total").inc(value)
        elif kind == "object.spill.restore":
            m.builtin(C, "rt_spill_restores_total").inc()
            m.builtin(C, "rt_spill_restore_bytes_total").inc(value)
        elif kind == "object.evict":
            m.builtin(C, "rt_evict_objects_total").inc()
            m.builtin(C, "rt_evict_bytes_total").inc(value)
        elif kind == "object.put.backpressure":
            m.builtin(C, "rt_put_backpressure_total").inc()
        elif kind == "object.array.put":
            m.builtin(C, "rt_array_puts_total").inc()
            m.builtin(C, "rt_array_put_bytes_total").inc(value)
        elif kind == "object.bcast.leg":
            m.builtin(C, "rt_bcast_legs_total").inc()
            m.builtin(C, "rt_bcast_bytes_total").inc(value)
        elif kind == "object.bcast.done":
            m.builtin(C, "rt_bcast_total").inc()
        elif kind == "object.bcast.fallback":
            m.builtin(C, "rt_bcast_fallback_total").inc(value or 1)
        elif kind == "inline.hit":
            m.builtin(C, "rt_inline_cache_hits_total").inc(value or 1)
        elif kind == "inline.miss":
            m.builtin(C, "rt_inline_cache_misses_total").inc(value or 1)
        elif kind == "fault.fired":
            m.builtin(C, "rt_faults_fired_total").inc()
        elif kind == "cgraph.execute":
            m.builtin(C, "rt_cgraph_executes_total").inc()
        elif kind == "cgraph.slot.write":
            m.builtin(C, "rt_cgraph_slot_writes_total").inc()
            m.builtin(H, "rt_cgraph_slot_write_s",
                      boundaries=[0.00005, 0.0002, 0.001, 0.005, 0.02, 0.1]
                      ).observe(value)
        elif kind == "cgraph.slot.wait":
            m.builtin(H, "rt_cgraph_slot_wait_s",
                      boundaries=[0.00005, 0.0002, 0.001, 0.005, 0.02, 0.1,
                                  1, 10]).observe(value)
        elif kind == "pipeline.stage.op":
            a = attrs or {}
            k = a.get("kind", "")
            m.builtin(C, "rt_pipeline_stage_ops_total",
                      tag_keys=("kind",)).inc(tags={"kind": k})
            m.builtin(H, "rt_pipeline_stage_op_s", tag_keys=("kind",),
                      boundaries=[0.0002, 0.001, 0.005, 0.02, 0.1, 0.5, 2]
                      ).observe(value, tags={"kind": k})
        elif kind == "pipeline.step":
            m.builtin(C, "rt_pipeline_steps_total").inc()
            a = attrs or {}
            eff = a.get("efficiency")
            if eff is not None:
                m.builtin(m.Gauge, "rt_pipeline_efficiency").set(eff)
        elif kind == "serve.request":
            # value = request latency (s); attrs carry the HTTP code.
            a = attrs or {}
            code = str(a.get("code", ""))
            m.builtin(C, "rt_serve_requests_total",
                      tag_keys=("code",)).inc(tags={"code": code})
            m.builtin(H, "rt_serve_request_s",
                      boundaries=[0.001, 0.005, 0.02, 0.1, 0.5, 2, 10, 60]
                      ).observe(value)
        elif kind == "serve.shed":
            m.builtin(C, "rt_serve_shed_total").inc(value or 1)
        elif kind == "serve.timeout":
            m.builtin(C, "rt_serve_timeout_total").inc(value or 1)
        elif kind == "serve.retry":
            m.builtin(C, "rt_serve_retries_total").inc(value or 1)
        elif kind == "serve.drain":
            m.builtin(C, "rt_serve_drains_total").inc(value or 1)
        elif kind == "lock.cycle":
            m.builtin(C, "rt_lock_cycles_total").inc()
        elif kind == "lock.long_hold":
            m.builtin(C, "rt_lock_long_holds_total").inc()
        elif kind == "serve.batch.flush":
            # a span: attrs carry the batch's rows and the window in force
            # (and the observed p99 on the adaptive path).
            a = attrs or {}
            m.builtin(H, "rt_serve_batch_size",
                      boundaries=[1, 2, 4, 8, 16, 32, 64, 128]
                      ).observe(a.get("rows", 0))
            if a.get("window_s") is not None:
                m.builtin(m.Gauge, "rt_serve_batch_window_ms").set(
                    a["window_s"] * 1000.0)
        elif kind == "serve.batch.reply":
            a = attrs or {}
            if a.get("p99_ms") is not None:
                m.builtin(m.Gauge, "rt_serve_p99_ms").set(a["p99_ms"])
    if dropped:
        m.builtin(C, "rt_events_dropped_total").inc(dropped)


# ----------------------------------------------------------------------
# the process's own pauses
# ----------------------------------------------------------------------
# A stall of a step or a call has an owner: the device, or the host. The
# host's part is seen from inside: a thread that asks to be woken every
# TICK_S and is woken PAUSE_S or more late was kept from running, by the
# interpreter lock, by the scheduler or by a stop of the whole process, and
# so was every other thread of the process. What it reads beside its two
# clocks says which.
TICK_S = 0.010
PAUSE_S = 0.020
PAUSES_A_SECOND = 20        # recorded; the rest is summed into the next
GC_PAUSE_S = 0.005

_watcher: Optional[threading.Thread] = None
_gc_seconds = 0.0           # of every collection this process ended
_gc_began = 0.0
# Collections the hook found worth a ``gc.pause``: (start by time.time(),
# seconds, generation, collected), for the ticker's next wake to record.
_gc_found: collections.deque = collections.deque(maxlen=1024)


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks``: a collection holds the interpreter, so it is a
    ``host.pause`` with ``cpu_s`` about ``value``; this names it. The
    collector runs in whichever thread reaches its threshold, also one that
    holds ``_lock`` inside ``drain()`` or ``snapshot()``, so nothing here
    takes a lock: the hook appends, the ticker thread records."""
    global _gc_seconds, _gc_began
    if phase == "start":
        _gc_began = time.perf_counter()
        return
    took = time.perf_counter() - _gc_began
    _gc_seconds += took
    if info.get("generation") == 2 or took >= GC_PAUSE_S:
        _gc_found.append((time.time() - took, took, info.get("generation"),
                          info.get("collected")))


def _record_collections() -> None:
    ident = f"pid:{os.getpid()}"
    while _gc_found:
        start, took, generation, collected = _gc_found.popleft()
        span_record("gc.pause", start, took, ident=ident,
                    generation=generation, collected=collected)


def _thread_runq_s() -> Optional[float]:
    """This thread's seconds on a run queue, waiting for a core; None on a
    kernel that keeps no schedstat (``runq_s`` is then left out)."""
    try:
        with open("/proc/thread-self/schedstat") as f:
            return int(f.read().split()[1]) * 1e-9
    except (OSError, ValueError, IndexError):
        return None


def _device_memory() -> dict:
    """The first local device's allocator counters, in a process that has
    already imported jax and brought a backend up (never for this). No
    import and no lock of jax's is taken here: an import from this thread
    beside the main thread's ``import jax`` hands one of them a half-made
    module, and ``jax.local_devices()`` waits on the lock that a backend's
    start-up holds for seconds, which would read as a pause."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    try:
        backend = getattr(bridge, "_default_backend", None)
        if backend is None:
            return {}
        stats = backend.local_devices()[0].memory_stats() or {}
    except Exception:       # not this jax's layout, or not on this device
        return {}
    return {k: stats[k] for k in ("bytes_in_use", "largest_free_block_bytes",
                                  "num_allocs") if k in stats}


class _HostWatch:
    """The ticker's books. ``woke(asked, now)`` is one wake, by
    ``time.perf_counter()``; once a second it stores ``host.watch`` and
    reads what costs a system call."""

    def __init__(self, now: float):
        self.ident = f"pid:{os.getpid()}"
        self.cpu = time.process_time()
        self.gc_s = _gc_seconds
        self.skipped, self.skipped_s = 0, 0.0
        self.own_cpu = time.thread_time()
        self._open_second(now)

    def _open_second(self, now: float) -> None:
        self.second, self.second_ts = now, time.time()
        self.ticks = self.late = 0
        self.pause_max = 0.0
        self.recorded = 0
        self._read_slow()

    def _read_slow(self) -> None:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        self.runq_s, self.majflt, self.nivcsw = \
            _thread_runq_s(), ru.ru_majflt, ru.ru_nivcsw

    def woke(self, asked: float, now: float) -> None:
        cpu, gc_s = time.process_time(), _gc_seconds
        late = now - asked
        self.ticks += 1
        if late >= PAUSE_S:
            self.late += 1
            self.pause_max = max(self.pause_max, late)
            if self.recorded >= PAUSES_A_SECOND:
                self.skipped += 1
                self.skipped_s += late
            else:
                self.recorded += 1
                before = self.runq_s, self.majflt, self.nivcsw
                self._read_slow()
                attrs = {"cpu_s": cpu - self.cpu, "gc_s": gc_s - self.gc_s,
                         "majflt": self.majflt - before[1],
                         "nivcsw": self.nivcsw - before[2]}
                if self.runq_s is not None and before[0] is not None:
                    attrs["runq_s"] = self.runq_s - before[0]
                if self.skipped:
                    attrs.update(skipped=self.skipped,
                                 skipped_s=self.skipped_s)
                    self.skipped, self.skipped_s = 0, 0.0
                span_record("host.pause", time.time() - late, late,
                            ident=self.ident, **attrs)
        self.cpu, self.gc_s = cpu, gc_s
        if now - self.second >= 1.0:
            own = time.thread_time()
            span_record("host.watch", self.second_ts, now - self.second,
                        ident=self.ident, ticks=self.ticks, late=self.late,
                        pause_max_s=self.pause_max,
                        own_cpu_s=own - self.own_cpu, **_device_memory())
            self.own_cpu = own
            self._open_second(now)


def _host_watch_loop() -> None:
    gc.callbacks.append(_on_gc)
    try:
        last = time.perf_counter()
        watch = _HostWatch(last)
        while not _flush_stop.is_set() and enabled():
            time.sleep(TICK_S)      # a third cheaper a wake than Event.wait
            watch.woke(last + TICK_S, time.perf_counter())
            if _gc_found:
                _record_collections()
            # read again: what ``woke`` took (the second's system calls, a
            # wait for ``_lock`` behind a long drain) is no lateness of the
            # next wake
            last = time.perf_counter()
    finally:
        gc.callbacks.remove(_on_gc)
        _record_collections()


def start_host_watch() -> None:
    """Record this process's pauses (``host.pause``, ``gc.pause``) and one
    ``host.watch`` a second, from now until the process's flusher stops. For
    the processes in which a pause holds a chip or a reply back: a worker
    spawned with chips, the serve proxy's process, the driver. A process
    with no flusher, or with ``events_enabled`` false, gets no thread."""
    global _watcher
    if not enabled() or _flusher is None or not _flusher.is_alive():
        return
    with _flusher_lock:
        if _watcher is None or not _watcher.is_alive():
            _watcher = threading.Thread(target=_host_watch_loop, daemon=True,
                                        name="events-host-watch")
            _watcher.start()


# ----------------------------------------------------------------------
# shipping
# ----------------------------------------------------------------------
def configure(node_id, conductor_address: str,
              start_flusher: bool = True) -> None:
    """Bind this process's ring to a cluster identity and (optionally)
    start the background flusher. Idempotent; a later call with
    start_flusher=True upgrades a piggyback-only process (head mode:
    daemon and driver share one process)."""
    global _node_hex, _conductor_addr, _flusher
    _node_hex = (node_id.hex() if isinstance(node_id, (bytes, bytearray))
                 else str(node_id))
    _conductor_addr = conductor_address
    from ray_tpu.util import metrics as _metrics
    _metrics.set_node(_node_hex)
    if not start_flusher:
        return
    with _flusher_lock:
        if _flusher is None or not _flusher.is_alive():
            _flush_stop.clear()
            _flusher = threading.Thread(target=_flush_loop, daemon=True,
                                        name="events-flush")
            _flusher.start()


def heartbeat_payload() -> Optional[dict]:
    """Drain for piggybacking on an already-periodic conductor RPC (the
    daemon heartbeat): None when there is nothing to ship."""
    global _unshipped, _unshipped_dropped
    evs, dropped = drain()
    if evs or dropped:
        try:
            _fold_metrics(evs, dropped)
        except Exception:
            pass
    with _ship_lock:
        if _unshipped or _unshipped_dropped:
            evs = _unshipped + evs
            dropped += _unshipped_dropped
            _unshipped, _unshipped_dropped = [], 0
    if not evs and not dropped:
        return None
    return {"pid": os.getpid(), "events": evs, "dropped": dropped}


def _park(evs: List[tuple], dropped: int) -> None:
    """A drained delta whose RPC failed waits here for the next ship."""
    global _unshipped, _unshipped_dropped
    with _ship_lock:
        keep = max(64, _cap or 16384)
        merged = evs + _unshipped
        _unshipped = merged[-keep:]
        _unshipped_dropped += dropped + max(0, len(merged) - keep)


def heartbeat_undelivered(payload: Optional[dict]) -> None:
    """The heartbeat that carried ``heartbeat_payload()``'s delta failed,
    or the conductor answered it without reading it: keep the delta for
    the next one (it was drained, so nothing else still holds it).
    At-least-once, as ``flush_now``'s parking is: a heartbeat that arrived
    and whose reply was lost is sent again, and the conductor then holds
    those records twice (a span's id tells a reader that minds)."""
    if payload:
        _park(list(payload["events"]), payload["dropped"])


def flush_now() -> None:
    """One flush pass: ship the ring delta to the conductor, fold
    metrics, sample probes."""
    global _unshipped, _unshipped_dropped
    addr = _conductor_addr
    if addr is None:
        return
    from ray_tpu.cluster.protocol import get_client
    cli = get_client(addr)
    evs, dropped = drain()
    if evs or dropped:
        try:
            _fold_metrics(evs, dropped)
        except Exception:
            pass
    with _ship_lock:
        if _unshipped or _unshipped_dropped:
            evs = _unshipped + evs
            dropped += _unshipped_dropped
            _unshipped, _unshipped_dropped = [], 0
    if evs or dropped:
        try:
            cli.call("push_ring_events", node_id=_node_hex, pid=os.getpid(),
                     events=evs, dropped=dropped)
        except Exception:
            _park(evs, dropped)
            raise
    _sample_probes()
    _check_slow_ops(cli)


def _flush_loop() -> None:
    while True:
        period = 0.5
        try:
            period = float(config.get("event_flush_period_s"))
        except Exception:
            pass
        if _flush_stop.wait(max(0.05, period)):
            return
        try:
            flush_now()
        except Exception:
            pass  # conductor down/restarting: next tick retries


def stop() -> None:
    """Stop the flusher (driver shutdown); best-effort final flush."""
    _flush_stop.set()
    try:
        flush_now()
    except Exception:
        pass


def reset_for_tests() -> None:
    """Forget ring + watchdog state (unit tests)."""
    global _buf, _cap, _seq, _cursor, _dropped, _enabled_gen
    global _watch_next, _unshipped, _unshipped_dropped
    _flush_stop.set()
    with _lock:
        _buf, _cap, _seq, _cursor, _dropped = [], 0, 0, 0, 0
        _enabled_gen = None
    with _ship_lock:
        _unshipped, _unshipped_dropped = [], 0
    with _watch_lock:
        _watch.clear()
        _watch_reported.clear()
        _watch_next = 0
    _scan_reported.clear()
    _gc_found.clear()

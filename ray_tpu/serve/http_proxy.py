"""HTTP ingress for serve deployments.

Role parity: serve/_private/http_proxy.py:250 — per-node proxy actor
translating HTTP to deployment calls. The reference runs uvicorn/starlette
(ASGI); here an asyncio HTTP/1.1 server keeps the image dependency-free
while matching the ASGI proxy's operational shape: one event loop, many
concurrent in-flight requests (each admitted deployment call blocks a thread
of the proxy's own, never the loop and never one of the loop's default
executor: admission is the only limit on how many are in flight),
keep-alive connections, and chunked Transfer-Encoding for streaming
responses (serve.StreamingResponse).

Admission control (parity: the proxy's backpressure +
max_queued_requests): each deployment gets a queue budget
(serve_max_queued_requests) and an ongoing budget (replicas x
serve_max_ongoing_requests). Past the queue budget requests shed with
503 + Retry-After instead of queueing unboundedly; admitted requests
carry a deadline (serve_request_timeout_s) and time out with 504, the
in-flight call cancelled rather than leaked.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import json
import queue
import threading
import time
import weakref
from typing import Iterable, Optional

from ray_tpu.util import events

# Proxies constructed in THIS process (in-process protocol tests; the
# production path runs one per proxy actor process). The conftest hygiene
# fixture asserts these are closed — a live proxy is a leaked event-loop
# thread.
_live_proxies: "weakref.WeakSet" = weakref.WeakSet()


class StreamingResponse:
    """Mark a deployment return value for chunked Transfer-Encoding: each
    element of ``chunks`` is written as one HTTP chunk (str or bytes).

    Delivery is chunked on the WIRE but materialized at the replica: the
    chunk list rides the object store whole before the proxy writes it
    (incremental token-by-token delivery would need per-chunk object refs
    — a future generator-over-refs protocol)."""

    def __init__(self, chunks: Iterable, content_type: str = "text/plain"):
        self.chunks = list(chunks)
        self.content_type = content_type

    def __reduce__(self):
        return (StreamingResponse, (self.chunks, self.content_type))


_REASONS = {400: "Bad Request", 404: "Not Found", 500: "Internal Error",
            501: "Not Implemented", 503: "Service Unavailable",
            504: "Gateway Timeout"}


def _http_error(code: int, msg: str,
                retry_after: Optional[int] = None) -> bytes:
    body = json.dumps({"error": msg}).encode()
    extra = f"Retry-After: {retry_after}\r\n" if retry_after is not None \
        else ""
    return (f"HTTP/1.1 {code} {_REASONS.get(code, 'Error')}\r\n"
            f"Content-Type: application/json\r\n{extra}"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


def _emit(kind: str, ident: str, value: float = 1.0, **attrs) -> None:
    try:
        events.emit(kind, ident, value=value,
                    attrs=attrs if attrs else None)
    except Exception:
        pass


class _CallThreads:
    """The threads admitted deployment calls block on: one a call, made
    when no idle one waits, reused while idle, ended and joined by
    ``close()``. Admission bounds how many calls are in flight, so it
    bounds these too and no size is configured (the loop's default executor
    has ``min(32, cpus + 4)`` threads, a limit that would depend on the
    host)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._idle: list = []       # inboxes of the threads with no call
        self._threads: list = []
        self._closed = False

    def submit(self, fn) -> concurrent.futures.Future:
        fut = concurrent.futures.Future()
        with self._lock:
            if self._closed:
                raise RuntimeError("serve proxy is closed")
            if self._idle:
                inbox = self._idle.pop()
            else:
                inbox = queue.SimpleQueue()
                thread = threading.Thread(
                    target=self._work, args=(inbox,), daemon=True,
                    name=f"serve-call-{len(self._threads)}")
                self._threads.append(thread)
                thread.start()
        inbox.put((fut, fn))
        return fut

    def _work(self, inbox: queue.SimpleQueue) -> None:
        while True:
            item = inbox.get()
            if item is None:
                return
            fut, fn = item
            out = error = None
            if fut.set_running_or_notify_cancel():
                try:
                    out = fn()
                except BaseException as e:  # noqa: BLE001 - the awaiter's
                    error = e
            # Idle BEFORE the call resolves: the resolution is what lets
            # admission take the next request in, and that one finds this
            # thread. So there are never more threads than admitted calls.
            with self._lock:
                closed = self._closed
                if not closed:
                    self._idle.append(inbox)
            if error is not None:
                fut.set_exception(error)
            elif not fut.cancelled():
                fut.set_result(out)
            if closed:
                return

    def close(self) -> None:
        """No new calls; idle threads end now, a busy one when its call
        does. Joins them all, each for no longer than a call may last
        (every call carries the request deadline)."""
        from ray_tpu import config
        with self._lock:
            self._closed = True
            idle, self._idle = self._idle, []
        for inbox in idle:
            inbox.put(None)
        patience = float(config.get("serve_request_timeout_s")) + 5.0
        for thread in self._threads:
            thread.join(patience)


class HTTPProxy:
    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._routes_cache: dict = {}
        self._routes_ts = 0.0
        self._routes_lock = threading.Lock()
        self._routes_refreshing = False
        self._fetch_future = None   # in-flight fetch shared by missers
        # dedicated 1-thread executor for route refreshes: routing never
        # queues behind anything else (deployment calls have threads of
        # their own, below)
        self._route_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="serve-routes")
        self._calls = _CallThreads()
        # Admission book, touched only on the loop thread: per-deployment
        # {"queued": n, "ongoing": n}. Counters for stats()/acceptance.
        self._adm: dict = {}
        self._counts = {"served": 0, "shed": 0, "timeouts": 0, "errors": 0}
        self._loop = asyncio.new_event_loop()
        self._server = None
        self._closed = False
        self._started = threading.Event()
        self._boot_error: Optional[BaseException] = None
        self._host, self._want_port = host, port
        self._port: Optional[int] = None
        self._thread = threading.Thread(target=self._run_loop, daemon=True,
                                        name="serve-proxy")
        self._thread.start()
        if not self._started.wait(10.0) or self._boot_error is not None:
            raise self._boot_error or RuntimeError(
                "serve proxy failed to start within 10s")
        _live_proxies.add(self)
        events.register_probe("serve.proxy", self._probe)
        events.start_host_watch()   # a pause here holds every reply back

    def _probe(self) -> dict:
        queued = sum(st["queued"] for st in self._adm.values())
        ongoing = sum(st["ongoing"] for st in self._adm.values())
        return {"rt_serve_queued": float(queued),
                "rt_serve_ongoing": float(ongoing)}

    # -- event loop -------------------------------------------------------
    def _run_loop(self) -> None:
        asyncio.set_event_loop(self._loop)

        async def boot():
            self._server = await asyncio.start_server(
                self._handle_conn, self._host, self._want_port)
            self._port = self._server.sockets[0].getsockname()[1]

        try:
            self._loop.run_until_complete(boot())
        except BaseException as e:  # noqa: BLE001 - re-raised in __init__
            self._boot_error = e
            self._started.set()
            return
        self._started.set()
        try:
            self._loop.run_forever()
        finally:
            try:
                if self._server is not None:
                    self._server.close()
                self._loop.close()
            except Exception:
                pass

    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        try:
            while True:  # keep-alive: serve requests until close/EOF
                line = await reader.readline()
                if not line or line in (b"\r\n", b"\n"):
                    return
                try:
                    method, target, _version = \
                        line.decode("latin1").split(" ", 2)
                except ValueError:
                    writer.write(_http_error(400, "bad request line"))
                    await writer.drain()
                    return
                headers = {}
                while True:
                    h = await reader.readline()
                    if h == b"":
                        return  # EOF mid-headers: aborted request, drop it
                    if h in (b"\r\n", b"\n"):
                        break
                    k, _, v = h.decode("latin1").partition(":")
                    headers[k.strip().lower()] = v.strip()
                if "chunked" in headers.get("transfer-encoding", ""):
                    # unsupported request framing: answer and CLOSE (the
                    # unread chunk bytes would otherwise be parsed as the
                    # next pipelined request)
                    writer.write(_http_error(
                        501, "chunked request bodies not supported"))
                    await writer.drain()
                    return
                try:
                    length = int(headers.get("content-length") or 0)
                except ValueError:
                    writer.write(_http_error(400, "bad Content-Length"))
                    await writer.drain()
                    return
                body = await reader.readexactly(length) if length else b""
                keep = headers.get("connection", "keep-alive") != "close"
                await self._dispatch(method, target, body, writer)
                await writer.drain()
                if not keep:
                    return
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    # -- admission --------------------------------------------------------
    def _adm_state(self, name: str) -> dict:
        st = self._adm.get(name)
        if st is None:
            st = self._adm[name] = {"queued": 0, "ongoing": 0}
        return st

    @staticmethod
    def _budget(name: str) -> int:
        """Ongoing budget: what the replica set can actually absorb
        (replicas x per-replica cap, from the handle's routing view).
        Before the first refresh lands the single-replica default
        applies — the first calls refresh it."""
        from ray_tpu import config
        from ray_tpu.serve.api import _handle_for
        h = _handle_for(name)
        cap = h._max_ongoing or int(config.get(
            "serve_max_ongoing_requests"))
        n = len(h._replicas)
        return max(1, max(1, n) * max(1, cap))

    def _reject(self, writer, name: str, code: int, msg: str) -> int:
        kind = "shed" if code == 503 else \
            "timeouts" if code == 504 else "errors"
        self._counts[kind] += 1
        if code == 503:
            _emit("serve.shed", name)
        writer.write(_http_error(
            code, msg, retry_after=1 if code == 503 else None))
        return code

    def _reject_failed_call(self, writer, name: str,
                            e: BaseException) -> int:
        from ray_tpu.core.exceptions import GetTimeoutError
        from ray_tpu.serve.api import _retryable
        from ray_tpu.serve.controller import ReplicaBusyError
        if isinstance(e, GetTimeoutError):
            # the in-flight call was cancelled by ServeCallRef
            return self._reject(writer, name, 504,
                                "deployment call timed out")
        if isinstance(e, (ReplicaBusyError, RuntimeError)) or _retryable(e):
            # _retryable covers the call that burned its one retry on a
            # SECOND dying replica: the failure is the cluster's, not the
            # request's — the client may retry (503), this is not a 500.
            return self._reject(writer, name, 503, repr(e))
        return self._reject(writer, name, 500, repr(e))

    async def _dispatch(self, method: str, target: str, body: bytes,
                        writer: asyncio.StreamWriter) -> None:
        path = target.split("?")[0]

        def match(routes):
            for prefix, dep in sorted(routes.items(),
                                      key=lambda kv: -len(kv[0])):
                if path == prefix or \
                        path.startswith(prefix.rstrip("/") + "/"):
                    return dep
            return None

        # cache read is a plain dict lookup (safe on the loop thread);
        # stale caches refresh in the dedicated route executor without
        # blocking this request
        routes = self._routes()
        name = match(routes)
        if name is None:
            # a just-deployed route may postdate the cache: one
            # authoritative refresh before 404ing. Coalesced single-flight:
            # concurrent misses (or an unknown-path flood) share ONE
            # controller RPC instead of amplifying per request.
            routes = await self._loop.run_in_executor(
                None, self._fetch_routes_coalesced)
            name = match(routes)
        if name is None:
            writer.write(_http_error(404, "no matching route"))
            return
        args, kwargs = (), {}
        if body:
            try:
                payload = json.loads(body)
                if isinstance(payload, dict):
                    kwargs = payload
                else:
                    args = (payload,)
            except json.JSONDecodeError:
                args = (body,)
        # The request's span: body parsed -> last byte written. It mints
        # the ident that the handle's, the replica's and the batcher's
        # spans of this request share.
        with events.span("serve.request", deployment=name) as request:
            code = await self._admit_and_call(name, path, args, kwargs,
                                              writer)
            request.set(code=code)
            await writer.drain()

    async def _admit_and_call(self, name: str, path: str, args: tuple,
                              kwargs: dict,
                              writer: asyncio.StreamWriter) -> int:
        """Admission, the deployment call on a thread of its own, the
        reply. -> the HTTP code written."""
        from ray_tpu import config
        t0 = time.monotonic()
        try:
            from ray_tpu.cluster import fault_plane
            fault_plane.fire("serve.proxy.admit", deployment=name,
                             path=path)
        except Exception:
            return self._reject(writer, name, 503, "admission rejected")
        st = self._adm_state(name)
        if st["queued"] >= int(config.get("serve_max_queued_requests")):
            return self._reject(writer, name, 503,
                                f"queue full for {name!r}")
        deadline = t0 + float(config.get("serve_request_timeout_s"))
        # Queue for an ongoing slot. The loop is single-threaded, so the
        # counters need no lock; check-then-act is atomic between awaits.
        st["queued"] += 1
        try:
            with events.span("serve.proxy.admit"):
                while st["ongoing"] >= self._budget(name):
                    if time.monotonic() >= deadline:
                        _emit("serve.timeout", name)
                        return self._reject(
                            writer, name, 504,
                            "timed out waiting for capacity")
                    await asyncio.sleep(0.005)
                st["ongoing"] += 1
        finally:
            st["queued"] -= 1

        # Admitted: the call goes straight to a thread of _CallThreads,
        # idle or made now, and waits for nothing on the way. A thread
        # hand-off carries no context variables: the request's span crosses
        # by hand, and the hand-off itself (submit -> call_blocking's first
        # line, a thread's wake-up or start) is a span that begins here and
        # ends there.
        request = events.current()
        queued, q0 = time.time(), time.perf_counter()

        def call_blocking():
            from ray_tpu.serve.api import _handle_for
            with events.adopt(request):
                events.span_record(
                    "serve.proxy.thread_wait", queued,
                    time.perf_counter() - q0,
                    ident=request and request["ident"],
                    parent=request and request["span"])
                return _handle_for(name).call(
                    *args,
                    timeout=max(0.05, deadline - time.monotonic()),
                    **kwargs)

        failure = None
        try:
            # slow model calls never stall the loop — other connections
            # keep being served (the ASGI property) — and never wait for
            # each other: as many run as admission let in
            out = await asyncio.wrap_future(
                self._calls.submit(call_blocking))
        except Exception as e:  # noqa: BLE001 - HTTP error surface
            failure = e
        finally:
            # before the reply is written: a client that has its answer
            # finds its slot given back
            st["ongoing"] -= 1
        if failure is not None:
            return self._reject_failed_call(writer, name, failure)
        self._counts["served"] += 1
        if isinstance(out, StreamingResponse):
            writer.write((
                "HTTP/1.1 200 OK\r\n"
                f"Content-Type: {out.content_type}\r\n"
                "Transfer-Encoding: chunked\r\n\r\n").encode())
            for chunk in out.chunks:
                data = chunk.encode() if isinstance(chunk, str) else \
                    bytes(chunk)
                if not data:
                    continue
                writer.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
                await writer.drain()
            writer.write(b"0\r\n\r\n")
            return 200
        data = json.dumps(out, default=str).encode()
        writer.write((
            "HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(data)}\r\n\r\n").encode() + data)
        return 200

    # -- control ----------------------------------------------------------
    def _routes(self) -> dict:
        """NON-BLOCKING cache read: returns the current table immediately;
        a stale table kicks off (at most one) background refresh on the
        dedicated route thread. Callers on the event loop never wait."""
        with self._routes_lock:
            stale = time.monotonic() - self._routes_ts > 1.0
            if stale and not self._routes_refreshing:
                self._routes_refreshing = True
                self._route_pool.submit(self._fetch_routes)
            return self._routes_cache

    def _fetch_routes(self) -> dict:
        """Blocking controller fetch (runs on the route thread only)."""
        import ray_tpu as rt
        from ray_tpu.serve.controller import ServeController
        try:
            controller = rt.get_actor(ServeController.CONTROLLER_NAME)
            fresh = rt.get(controller.get_routes.remote(), timeout=10)
            with self._routes_lock:
                self._routes_cache = fresh
        except Exception:
            pass  # keep serving the stale table
        finally:
            with self._routes_lock:
                # success OR failure advances the clock: a dead controller
                # backs off instead of retrying per request
                self._routes_ts = time.monotonic()
                self._routes_refreshing = False
        return self._routes_cache

    def _fetch_routes_coalesced(self) -> dict:
        """Authoritative fetch with single-flight coalescing: callers that
        arrive while a fetch is running wait for THAT fetch's result."""
        created = False
        with self._routes_lock:
            fut = self._fetch_future
            if fut is None:
                fut = self._fetch_future = \
                    self._route_pool.submit(self._fetch_routes)
                created = True
        if created:
            # registered OUTSIDE the lock: a completed future runs the
            # callback synchronously in this thread
            def clear(_f):
                with self._routes_lock:
                    self._fetch_future = None

            fut.add_done_callback(clear)
        try:
            return fut.result(timeout=15)
        except Exception:
            return self._routes_cache

    def port(self) -> int:
        return self._port

    def reconfigure(self, overrides: dict) -> dict:
        """Apply config overrides inside the proxy's process; a value of
        None clears the override. Admission reads config at request time,
        so operators can live-tune the ingress knobs (queue budget,
        per-replica cap, deadline) without bouncing the listener and
        dropping its keep-alive connections."""
        from ray_tpu import config
        for name, value in overrides.items():
            if value is None:
                config.clear_override(name)
            else:
                config.set_override(name, value)
        return {k: config.get(k) for k in overrides}

    def stats(self) -> dict:
        """Admission counters + live occupancy (acceptance checks and the
        controller's http_stats passthrough read these)."""
        return {
            "served": self._counts["served"],
            "shed": self._counts["shed"],
            "timeouts": self._counts["timeouts"],
            "errors": self._counts["errors"],
            "queued": sum(st["queued"] for st in self._adm.values()),
            "ongoing": sum(st["ongoing"] for st in self._adm.values()),
        }

    def close(self) -> None:
        """Stop the server, the loop thread and the call threads, and
        join them (idempotent). In-process protocol tests must call this;
        the actor path dies with its process."""
        if self._closed:
            return
        self._closed = True
        self._calls.close()     # before the loop: their replies still land
        try:
            self._loop.call_soon_threadsafe(self._loop.stop)
        except Exception:
            pass
        self._thread.join(10.0)
        self._route_pool.shutdown(wait=True, cancel_futures=True)
        _live_proxies.discard(self)

    @property
    def closed(self) -> bool:
        return self._closed

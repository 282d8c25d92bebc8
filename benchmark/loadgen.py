"""The load generator: one general generator that reads a traffic file.

The program has none. A traffic mix is data (``benchmark/traffic/*.json``);
this file turns its parameters into HTTP requests against the proxy and
into one row per request. Parameters it reads:

    clients           callers in a closed loop: each sends its next request
                      when the previous reply has arrived
    request_timeout_s client-side limit for one request; past it the
                      request counts as failed

A request is timed from when it was sent to the first and to the last byte
of the reply's body. All times are ``time.time()`` of this machine, so that
they compare with the replica's. One process, one thread per caller: the
callers wait on sockets nearly all of the time. An open loop (arrivals on a
schedule drawn from the seed) comes with the first cell that needs one.
"""

from __future__ import annotations

import http.client
import socket
import threading
import time
from typing import Callable, List


class Loadgen:
    """``body(rid)`` makes request ``rid``'s bytes; ``parse(bytes)`` returns
    ``(ok, units, extra)``: whether the reply is well formed, how many units
    of work (tokens) it delivered, and what the app wants to keep of it."""

    def __init__(self, host: str, port: int, path: str, traffic: dict,
                 body: Callable[[int], bytes],
                 parse: Callable[[bytes], tuple]):
        self.host, self.port, self.path = host, port, path
        self.traffic = traffic
        self.body, self.parse = body, parse
        self.timeout = float(traffic.get("request_timeout_s", 60.0))
        self.rows: List[dict] = []
        self._lock = threading.Lock()
        self._next_rid = 0
        self._conns: dict = {}

    def _rid(self) -> int:
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            return rid

    def _one(self, client: int, phase: str) -> dict:
        rid = self._rid()
        row = {"rid": rid, "client": client, "phase": phase, "ok": False,
               "units": 0}
        payload = self.body(rid)
        conn = self._conns.get(client)
        if conn is None:
            conn = self._conns[client] = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout)
        try:
            if conn.sock is None:
                # http.client sends headers and body apart; with Nagle on,
                # the body waits ~40 ms for the server's delayed ACK
                conn.connect()
                conn.sock.setsockopt(socket.IPPROTO_TCP,
                                     socket.TCP_NODELAY, 1)
            row["send"] = time.time()
            conn.request("POST", self.path, body=payload,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            head = resp.read(1)
            row["first"] = time.time()
            data = head + resp.read()
            row["last"] = time.time()
            row["status"] = resp.status
            if resp.status == 200:
                row["ok"], row["units"], row["extra"] = self.parse(data)
            else:
                row["error"] = data[:200].decode("latin1")
        except (OSError, http.client.HTTPException) as e:
            row["error"] = repr(e)
            row.setdefault("last", time.time())
            conn.close()
            self._conns.pop(client, None)
        with self._lock:
            self.rows.append(row)
        return row

    def _threads(self, target, n: int) -> None:
        threads = [threading.Thread(target=target, args=(c,), daemon=True,
                                    name=f"bench-caller-{c}")
                   for c in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def warmup(self) -> List[dict]:
        """One request from every caller at once: opens each connection and
        fills the server's batches as the window will."""
        before = len(self.rows)
        self._threads(lambda c: self._one(c, "warmup"),
                      int(self.traffic["clients"]))
        return self.rows[before:]

    def window(self, seconds: float) -> dict:
        start = time.time()
        end = start + seconds

        def caller(c: int) -> None:
            while time.time() < end:
                self._one(c, "window")
        self._threads(caller, int(self.traffic["clients"]))
        for conn in self._conns.values():
            conn.close()
        self._conns.clear()
        return {"start": start, "end": end, "seconds": seconds,
                "rows": [r for r in self.rows if r["phase"] == "window"]}

"""benchmark/loadgen.py against a small HTTP server in this process."""

import http.server
import json
import threading
import time

import pytest

from bench_paths import BENCH  # noqa: F401  (puts the checkout on sys.path)
from benchmark import loadgen


class Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    disable_nagle_algorithm = True
    delay = 0.02

    def do_POST(self):
        body = json.loads(self.rfile.read(
            int(self.headers["Content-Length"])))
        time.sleep(1.0 if body["rid"] in self.server.slow else self.delay)
        if body["rid"] in self.server.fail:
            data, code = b'{"error": "no"}', 503
        else:
            data, code = json.dumps({"tokens": [1, 2, 3],
                                     "rid": body["rid"]}).encode(), 200
        self.send_response(code)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *_):
        pass


@pytest.fixture()
def server():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    srv.fail = set()
    srv.slow = set()
    thread = threading.Thread(target=srv.serve_forever, daemon=True,
                              name="bench-test-server")
    thread.start()
    yield srv
    srv.shutdown()
    srv.server_close()
    thread.join(timeout=5)
    assert not thread.is_alive()


def make(server, traffic):
    def body(rid):
        return json.dumps({"rid": rid}).encode()

    def parse(data):
        reply = json.loads(data)
        return True, len(reply["tokens"]), {"rid": reply["rid"]}
    return loadgen.Loadgen("127.0.0.1", server.server_address[1], "/x",
                           traffic, body, parse)


def test_closed_loop_sends_the_next_when_the_reply_has_come(server):
    gen = make(server, {"clients": 3})
    warm = gen.warmup()
    assert len(warm) == 3 and all(r["ok"] for r in warm)
    assert sorted(r["rid"] for r in warm) == [0, 1, 2]
    out = gen.window(seconds=0.5)
    rows = out["rows"]
    assert all(r["phase"] == "window" and r["ok"] for r in rows)
    assert all(r["units"] == 3 and r["extra"]["rid"] == r["rid"]
               for r in rows)
    # 3 callers x ~0.5 s / ~25 ms a request; never more than 3 in flight
    assert 30 <= len(rows) <= 75
    for c in range(3):
        mine = sorted((r for r in rows if r["client"] == c),
                      key=lambda r: r["send"])
        assert all(b["send"] >= a["last"] for a, b in zip(mine, mine[1:]))
    assert all(r["send"] <= r["first"] <= r["last"] for r in rows)
    assert all(r["send"] < out["end"] for r in rows)


def test_a_refused_request_is_a_failed_row_and_the_caller_goes_on(server):
    server.fail = {1, 2}
    gen = make(server, {"clients": 2})
    rows = gen.window(seconds=0.3)["rows"]
    bad = [r for r in rows if not r["ok"]]
    assert sorted(r["rid"] for r in bad) == [1, 2]
    assert all(r["status"] == 503 and r["units"] == 0 for r in bad)
    assert len(rows) > 4


def test_a_request_past_its_time_limit_is_failed_and_the_caller_reconnects(
        server):
    gen = make(server, {"clients": 1, "request_timeout_s": 0.2})
    server.slow = {0}
    rows = gen.window(seconds=0.6)["rows"]
    assert not rows[0]["ok"] and "timed out" in rows[0]["error"]
    assert "first" not in rows[0] and rows[0]["last"] >= rows[0]["send"]
    later = [r for r in rows[1:] if r["ok"]]
    assert later, "the caller opens a new connection and goes on"


def test_the_window_holds_its_own_rows_and_not_the_warm_up(server):
    gen = make(server, {"clients": 2})
    warm = gen.warmup()
    out = gen.window(seconds=0.2)
    assert {r["rid"] for r in warm} == {0, 1}
    assert all(r["phase"] == "warmup" for r in warm)
    assert min(r["rid"] for r in out["rows"]) == 2
    assert out["end"] - out["start"] == pytest.approx(0.2)
    assert out["seconds"] == 0.2
    # the last request of a caller is sent before the end and runs to its
    # reply: the window's work is whole requests
    assert all(r["send"] < out["end"] <= max(x["last"] for x in out["rows"])
               for r in out["rows"])

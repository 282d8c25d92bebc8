"""The readers of an actor call's stations (``call.*``, ``batch.gap_*``;
``benchmark/metrics/_calls.py``), on hand-made span lists with hand-worked
answers: the arithmetic of each, a delay planted in one station of the way
out read in that station's metric, two hosts refused, and a program without
the spans (the parent of the PR that added them) read as nothing."""

import pytest

from bench_paths import CHECKOUT  # noqa: F401 - puts the checkout on sys.path
from benchmark import spans as spans_mod
from benchmark.manifest import Manifest
from ray_tpu.util import events

MF = Manifest()
CELLS = {name: MF.cell(name) for name in ("mistral7b-serve-closed32",
                                          "ouro2.6b-serve-closed16")}
SERVE = CELLS["mistral7b-serve-closed32"]
NEW = ["call.way_in_ms", "call.way_out_ms", "call.turn_ms",
       "call.return_ms", "call.wake_ms", "call.fetch_ms",
       "batch.gap_out_ms", "batch.gap_in_ms"]
PROXY, REPLICA = 1, 7              # the two processes' pids

_ids = iter(range(1, 10_000))


def span(kind, ts, value, ident="x", parent=None, pid=PROXY, node="n0",
         **attrs):
    return {"node_id": node, "pid": pid, "ts": ts, "kind": kind,
            "ident": ident, "value": value,
            "attrs": {"span": f"c{next(_ids)}", "parent": parent, **attrs}}


def read(name, record, cell=SERVE):
    return MF.reader(name)(record, cell)


def metric_fault():
    return MF.reader_module("call.way_in_ms")._calls.MetricFault


@pytest.fixture()
def session(monkeypatch, tmp_path):
    monkeypatch.setattr(spans_mod, "CHECKOUT", str(tmp_path))
    monkeypatch.setattr(spans_mod, "trace_dir",
                        lambda: str(tmp_path / "no-trace"))
    spans_mod._summarised.clear()
    yield events.keep_session
    events.keep_session([])
    spans_mod._summarised.clear()


def record():
    # the window runs from 100 to the last reply at 130
    return {"window_start": 100.0, "facts": {"platform": "tpu"},
            "window": {"rows": [{"last": 130.0}]}}


def request(ident, ts, way_in, replica, way_out, flush, *, turn=0.002,
            returned=0.004, wake=0.010, lock=0.001, fetch=0.003,
            replica_node="n0"):
    """One request: the proxy's serve.handle.call begins 10 ms into
    serve.request; the replica's method begins ``way_in`` later and runs
    ``replica`` seconds; the handle's call ends ``way_out`` after it. The
    stations: call.turn ends where the method begins, call.return begins
    where it ends, call.get is woken ``wake`` after call.return's end and
    ends ``lock + fetch`` later."""
    hc_ts = ts + 0.010
    rc_ts = hc_ts + way_in
    rc_end = rc_ts + replica
    hc_end = rc_end + way_out
    req = span("serve.request", ts, hc_end + 0.005 - ts, ident, code=200)
    hc = span("serve.handle.call", hc_ts, hc_end - hc_ts, ident,
              req["attrs"]["span"], retries=0)
    call = hc["attrs"]["span"]
    rc = span("serve.replica.call", rc_ts, replica, ident, call,
              pid=REPLICA, node=replica_node, inflight=3)
    ret_end = rc_end + returned
    get_end = ret_end + wake + lock + fetch
    return [
        req, hc, rc,
        span("call.submit", hc_ts + 0.0001, 0.001, ident, call, bytes=600,
             window_wait_s=0.0002),
        span("call.turn", rc_ts - turn, turn, ident, call, pid=REPLICA,
             node=replica_node, turn_wait_s=0.0, pool_wait_s=turn / 2,
             resolve_s=turn / 4),
        span("call.return", rc_end, returned, ident, call, pid=REPLICA,
             node=replica_node, bytes=900, inline=0,
             seal_wait_s=returned / 2, lock_wait_s=returned / 4),
        span("call.get", hc_ts + 0.002, get_end - hc_ts - 0.002, ident,
             call, parked_s=replica, woken_ts=ret_end + wake,
             lock_wait_s=lock),
        # another actor call under the request's ident, not the request's:
        # the handle refreshing its routing table under slot_wait
        span("call.get", ts + 0.001, 0.5, ident, "slot-wait", parked_s=0.1,
             woken_ts=ts + 0.101, lock_wait_s=0.2),
        span("serve.batch.wait", rc_ts + 0.001, 0.05, ident,
             rc["attrs"]["span"], pid=REPLICA, node=replica_node,
             flush=flush),
    ]


def serve_spans(replica_node="n0"):
    flush = dict(pid=REPLICA, node=replica_node, max_batch_size=32,
                 window_s=0.1)
    f1 = span("serve.batch.flush", 101.0, 4.0, "f1", rows=3, **flush)
    f2 = span("serve.batch.flush", 105.2, 4.0, "f2", rows=3, **flush)
    f3 = span("serve.batch.flush", 109.5, 4.0, "f3", rows=1, **flush)
    one, two = f1["attrs"]["span"], f2["attrs"]["span"]
    out = [f1, f2, f3]
    # served by f1 (ends at 105.0): ways in 10, 20, 30 ms, out 20, 40, 60
    kw = dict(replica_node=replica_node)
    out += request("a", 100.90, 0.010, 4.08, 0.020, one, **kw)
    out += request("b", 100.91, 0.020, 4.06, 0.040, one, returned=0.006,
                   wake=0.020, lock=0.002, fetch=0.004, turn=0.004, **kw)
    out += request("c", 100.92, 0.030, 4.04, 0.060, one, returned=0.008,
                   wake=0.030, lock=0.003, fetch=0.005, turn=0.006, **kw)
    # served by f2 (ends at 109.2), the first of them sent at 105.04
    out += request("d", 105.04, 0.015, 4.135, 0.025, two, **kw)
    out += request("e", 105.08, 0.015, 4.095, 0.050, two, **kw)
    # before the window: not read
    out += request("warmup", 90.0, 0.5, 1.0, 0.5, "f0", **kw)
    return out


def test_call_readers_on_hand_made_spans(session):
    session(serve_spans())
    r = record()
    # a, b, c, d, e: ways in 10, 20, 30, 15, 15 ms; out 20, 40, 60, 25, 50
    # (medians of their own: both ways together read 30, 60, 90, 40, 65,
    # whose median is b's 60 and not 15 + 40)
    assert read("call.way_in_ms", r) == pytest.approx(15.0)
    assert read("call.way_out_ms", r) == pytest.approx(40.0)
    assert read("ingress.call_overhead_ms", r) == pytest.approx(60.0)
    # call.turn 2, 4, 6, 2, 2 ms; call.return 4, 6, 8, 4, 4 ms
    assert read("call.turn_ms", r) == pytest.approx(2.0)
    assert read("call.return_ms", r) == pytest.approx(4.0)
    # woken_ts less end(call.return): 10, 20, 30, 10, 10
    assert read("call.wake_ms", r) == pytest.approx(10.0)
    # woken to value in hand: 4, 6, 8, 4, 4 ms; the routing refresh's 400
    # ms is not a child of any request's serve.handle.call
    assert read("call.fetch_ms", r) == pytest.approx(4.0)


def test_gap_readers_on_hand_made_spans(session):
    session(serve_spans())
    r = record()
    # f1 ends at 105.0; its last reply (c: 100.92 + 0.010 + 0.030 + 4.04 +
    # 0.060 + 0.005) ends at 105.065, and f2 starts at 105.2. f2 ends at
    # 109.2; its last reply (e: 105.08 + 0.010 + 0.015 + 4.095 + 0.050 +
    # 0.005) at 109.255, and f3 starts at 109.5.
    assert read("batch.gap_out_ms", r) == pytest.approx((65.0 + 55.0) / 2)
    assert read("batch.gap_in_ms", r) == pytest.approx((135.0 + 245.0) / 2)
    # the gap they divide, by the accepted reader: 200 and 300 ms
    assert read("batch.gap_ms", r) == pytest.approx(250.0)


@pytest.mark.parametrize("station", ["return", "wake", "fetch"])
def test_a_delay_on_the_way_out_is_read_in_its_station(session, station):
    """call.return_ms, call.wake_ms and call.fetch_ms tile call.way_out_ms
    but for the edges between the spans (here 1 ms from call.get's end to
    serve.handle.call's): 30 ms planted in one of them is read there, in
    the way out, and in neither of the other two."""
    base = dict(returned=0.004, wake=0.010, fetch=0.003)
    planted = dict(base, **{"returned" if station == "return" else station:
                            0.030 + base.get(station, 0.004)})
    f = dict(pid=REPLICA, node="n0", max_batch_size=32, window_s=0.1)
    flush = span("serve.batch.flush", 101.0, 4.0, "f1", rows=3, **f)
    way_out = sum(planted.values()) + 0.001 + 0.001        # lock, the edge
    session([flush] + [s for i in range(3) for s in request(
        f"r{i}", 100.9 + i / 100, 0.010, 4.0, way_out,
        flush["attrs"]["span"], **planted)])
    r = record()
    got = {n: read(f"call.{n}_ms", r)
           for n in ("return", "wake", "fetch", "way_out")}
    assert got["return"] + got["wake"] + got["fetch"] + 1.0 == \
        pytest.approx(got["way_out"])
    want = {"return": 4.0, "wake": 10.0, "fetch": 4.0}     # fetch: + lock
    want[station] += 30.0
    assert {n: got[n] for n in want} == pytest.approx(want)


@pytest.mark.parametrize("name", ["call.way_in_ms", "call.way_out_ms",
                                  "call.wake_ms", "batch.gap_out_ms",
                                  "batch.gap_in_ms"])
def test_stamps_of_two_hosts_are_refused(session, name):
    session(serve_spans(replica_node="n1"))
    with pytest.raises(metric_fault(), match="n1"):
        read(name, record())


@pytest.mark.parametrize("name", ["call.turn_ms", "call.return_ms",
                                  "call.fetch_ms"])
def test_one_process_own_seconds_are_read_on_any_host(session, name):
    session(serve_spans(replica_node="n1"))
    assert read(name, record()) > 0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_nothing_without_their_spans(session, name):
    """The parent of the PR that added the stations records the serve
    chain and no ``call.*``: the metric is left out, nothing raises."""
    r = record()
    session([])
    assert read(name, r) is None
    session([s for s in serve_spans()
             if not s["kind"].startswith(("call.", "serve.batch.wait"))])
    value = read(name, r)
    if name.startswith("call.way_"):
        assert value is not None        # they read the serve chain alone
    else:
        assert value is None
    r["facts"] = {"platform": "cpu"}    # a rehearsal: no metric
    session(serve_spans())
    assert read(name, r) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_listed_for_both_serving_cells(name):
    entry = next(m for m in MF.data["per_layer"] if m["name"] == name)
    assert entry["workloads"] == list(CELLS)
    assert entry["layer"] == "serve ingress" and entry["unit"] == "ms"
    assert entry["moves"] == "serve.request_p95_s"
    needs = MF.reader_module(name).NEEDS
    for cell in CELLS:
        assert set(needs) <= set(MF.span_needs(cell))
        assert name in [m["name"] for m in MF.metrics("per_layer", cell)]

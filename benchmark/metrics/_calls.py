"""An actor call's stations, as the readers of the ``call.*`` and
``batch.gap_*`` metrics need them. Not a metric: no manifest entry names it.

The runtime records four spans for an actor call made under an open span
(``ray_tpu/util/events.py``: ``call.submit`` and ``call.get`` in the
caller's process, ``call.turn`` and ``call.return`` in the callee's), all
children of that span. A request's call is the one whose four are children
of its ``serve.handle.call``; another call made under the request's ident
(the handle refreshing its routing table) is not the request's.

A difference of two processes' stamps is a time only on one host, whose
clocks are one clock: where the two spans' ``node_id`` differ the reader
raises ``MetricFault`` and the run fails, it does not report a skew.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import spans as spans_mod
from _common import MetricFault, median

STATIONS = ("call.submit", "call.turn", "call.return", "call.get")
REQUEST = ("serve.request", "serve.handle.call", "serve.replica.call")


def end(span: dict) -> float:
    return span["ts"] + span["value"]


def same_host(a: dict, b: dict) -> None:
    if a["node_id"] != b["node_id"]:
        raise MetricFault(
            f"{a['kind']} was recorded on node {a['node_id']} and "
            f"{b['kind']} on node {b['node_id']}: a difference of their "
            "stamps is skewed by the two hosts' clocks, and is not read")


def requests(record: dict, cell: dict) -> Optional[List[Dict[str, dict]]]:
    """kind -> span for each request of the window that has its
    ``serve.handle.call`` and ``serve.replica.call``, the four stations
    among them where the program records them (of a call that was retried:
    the last of each kind). None without spans."""
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    by_parent: Dict[str, Dict[str, dict]] = {}
    for s in sorted(spans, key=lambda s: s["ts"]):
        if s["kind"] in STATIONS:
            by_parent.setdefault(s["attrs"].get("parent"), {})[s["kind"]] = s
    out = []
    for r in spans_mod.window_requests(record, spans):
        if "serve.handle.call" in r and "serve.replica.call" in r:
            call = r["serve.handle.call"]["attrs"]["span"]
            out.append({**{k: r[k] for k in REQUEST},
                        **by_parent.get(call, {})})
    return out


def median_ms(record: dict, cell: dict, needs, seconds) -> Optional[float]:
    """1000 x the median of ``seconds(request)`` over the window's requests
    that have every kind of ``needs``; None without any."""
    found = requests(record, cell)
    if not found:
        return None
    values = [seconds(r) for r in found if all(k in r for k in needs)]
    return 1000.0 * median(values) if values else None


def gap_parts(record: dict, cell: dict) -> Optional[List[tuple]]:
    """For each two consecutive flushes of the window (of the replica that
    flushed most) the two parts of the gap between them, in seconds: from
    the first one's end to its last reply out of the proxy (the last
    ``serve.request`` end among the requests whose ``serve.batch.wait``
    names that flush), and from there to the next one's start. None
    without spans; refuses a proxy on another host than the flush."""
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    request = {s["ident"]: s for s in spans if s["kind"] == "serve.request"}
    last: Dict[str, dict] = {}
    for s in spans_mod.of_kind(spans, "serve.batch.wait"):
        reply, flush = request.get(s["ident"]), s["attrs"].get("flush")
        if reply is not None and (flush not in last
                                  or end(reply) > end(last[flush])):
            last[flush] = reply
    flushed = spans_mod.flushes(spans_mod.in_window(record, spans))
    out = []
    for a, b in zip(flushed, flushed[1:]):
        reply = last.get(a["attrs"]["span"])
        if reply is not None:
            same_host(a, reply)
            out.append((end(reply) - end(a), b["ts"] - end(reply)))
    return out

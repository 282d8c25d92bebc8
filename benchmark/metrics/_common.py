"""Arithmetic the readers share. Not a metric: no manifest entry names it."""

from __future__ import annotations

import math


class MetricFault(Exception):
    """A reader found what it reads, and it contradicts what the metric's
    arithmetic assumes: the run fails, the metric does not drop out."""


def median(values):
    values = sorted(values)
    if not values:
        return None
    mid = len(values) // 2
    return values[mid] if len(values) % 2 else \
        (values[mid - 1] + values[mid]) / 2


def p95(values):
    """Nearest rank: the smallest value with at least 95% at or below."""
    values = sorted(values)
    return values[max(0, math.ceil(0.95 * len(values)) - 1)] \
        if values else None


def clean_steps(window: dict):
    """(period, dispatch-to-ready) of every step but the last, leaving out
    the steps whose period touches the profiler's start or stop."""
    steps, out = window["steps"], []
    for (d0, r0, _), (d1, _, _) in zip(steps, steps[1:]):
        if any(a < d1 and b > d0 for a, b in window["profiler"]):
            continue
        out.append((d1 - d0, r0 - d0))
    return out


def latencies(record: dict, upto: str):
    """Client-side seconds from send to ``first`` or ``last`` byte, over
    every request sent in the window; a failed one counts as the client's
    time limit."""
    limit = record["request_timeout_s"]
    return [r[upto] - r["send"] if r["ok"] and upto in r else limit
            for r in record["window"]["rows"]]


def served(record: dict):
    """(client row, [enter, exit] in the replica, the batch that served it)
    for each request of the window that succeeded."""
    by_rid = {}
    for b in record["batches"]:
        for rid in b["rids"]:
            by_rid[str(rid)] = b
    for r in record["window"]["rows"]:
        rid = str(r["rid"])
        if r["ok"] and rid in record["requests"] and rid in by_rid:
            yield r, record["requests"][rid], by_rid[rid]


def idle_share_pct(record: dict):
    reduced = record.get("trace") or {}
    if not reduced.get("window_s"):
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])

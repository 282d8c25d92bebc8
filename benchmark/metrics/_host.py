"""What the program records of its own processes and of where a step or a
flush happened (``host.pause``, ``host.watch``, ``train.report``'s
``period_s``, ``serve.batch.flush``'s ``cause`` / ``since_last_s``), as the
readers of ``host.pause_max_ms.*``, ``train.period_max_over_median``,
``batch.since_last_ms`` and ``batch.unfilled_flush_share`` need them. Not a
metric: no manifest entry names it.

A program that records none of these (the parent of the PR that added them)
gives every function here nothing to read, and its metric is left out of
the line. A step or a gap that overlaps an interval that the app's record
gives as the profiler's (its start, its stop) is left out and counted on
stderr: a traced run is the one run in which a pause is the profiler's own.
A pause is held to more (``pause_max_ms``): the profiler stops its own
process, the machine every process at once.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Set, Tuple

from benchmark import spans as spans_mod
from benchmark.hermetic import log
from _calls import end

Interval = Tuple[float, float]


def profiler_intervals(record: dict) -> List[Interval]:
    """The profiler's start and stop, in epoch seconds. A training app
    keeps them in seconds from the window's start, a serving app's replica
    by ``time.time()``."""
    window = record.get("window") or {}
    if "steps" in window:
        lo = record["window_start"]
        return [(lo + a, lo + b) for a, b in window.get("profiler") or ()]
    return [(a, b) for a, b in record.get("profiler") or ()]


def clear_of(record: dict, intervals: Sequence[Interval], what: str,
             metric: str) -> List[Interval]:
    """``intervals`` without those the profiler touched; how many went is
    said on stderr."""
    profiler = profiler_intervals(record)
    kept = [(lo, hi) for lo, hi in intervals
            if not any(a < hi and b > lo for a, b in profiler)]
    if len(kept) < len(intervals):
        log(f"{metric}: {len(intervals) - len(kept)} of {len(intervals)} "
            f"{what} overlap the profiler's start or stop and are left out")
    return kept


def processes(spans: Sequence[dict]) -> Set[tuple]:
    return {(s["node_id"], s["pid"]) for s in spans}


def _overlap(a: dict, b: dict) -> bool:
    return a["ts"] < end(b) and end(a) > b["ts"]


def _covered(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """The share of ``lo`` to ``hi`` that ``intervals`` lie over."""
    total, upto = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, upto), min(b, hi)
        if b > a:
            total, upto = total + b - a, b
    return total / (hi - lo) if hi > lo else 0.0


def pause_max_ms(record: dict, spans: Sequence[dict], owners: Set[tuple],
                 profiled: Set[tuple], metric: str) -> Optional[float]:
    """1000 x the longest pause that overlaps the window in the processes
    ``owners`` ((node_id, pid)); 0.0 where every one of them has a
    ``host.watch`` over the window (its ticker ran) and none recorded a
    pause; None where one has none: nothing watched it.

    The profiler stops the process it runs in (``profiled``) and no other.
    A ``host.pause`` of such a process that overlaps the profiler's start or
    stop is left out as the profiler's, unless another process of that host
    recorded a pause over half of it or more: then the machine stood still
    (its pauses are of one length in every process, to a millisecond), which
    is what this metric is for. Where that leaves nothing the metric reads
    0.0, of the window outside the profiler's intervals: a process whose
    ticker ran is never left out of the line (the driver holds a traced
    run's line to every metric of its cell), so the share of the window that
    the profiler's intervals cover in every watched process is said on
    stderr beside it.

    A process records at most 20 pauses a second and counts the rest, so a
    ``host.watch`` that lies inside the window clear of the profiler is read
    too: its ``pause_max_s`` is the longest of its second, recorded or not.
    """
    lo, hi = spans_mod.window_bounds(record)

    def inside(kind):
        return [s for s in spans_mod.of_kind(spans, kind)
                if s["ts"] < hi and end(s) > lo]
    watches = [s for s in inside("host.watch")
               if (s["node_id"], s["pid"]) in owners]
    blind = owners - processes(watches)
    if not owners or blind:
        log(f"{metric}: no host.watch over the window in "
            f"{sorted(blind) or 'any process'}: its ticker did not run")
        return None
    profiler = profiler_intervals(record)

    def profilers(s, neighbours=()):
        return (s["node_id"], s["pid"]) in profiled \
            and any(a < end(s) and b > s["ts"] for a, b in profiler) \
            and not any(n["node_id"] == s["node_id"] and n["pid"] != s["pid"]
                        and min(end(n), end(s)) - max(n["ts"], s["ts"])
                        >= 0.5 * s["value"] for n in neighbours)
    every = inside("host.pause")
    mine = [s for s in every if (s["node_id"], s["pid"]) in owners]
    kept = [s for s in mine if not profilers(s, every)]
    if len(kept) < len(mine):
        log(f"{metric}: {len(mine) - len(kept)} of {len(mine)} pauses "
            f"overlap the profiler's start or stop and are left out (in its "
            f"own process, no pause of another process beside them)")
    unrecorded = [w for w in watches if w["ts"] >= lo and end(w) <= hi
                  and not profilers(w)
                  and w["attrs"].get("pause_max_s", 0.0)
                  > max([s["value"] for s in kept if _overlap(s, w)] or [0.0])]
    for w in unrecorded:
        log(f"{metric}: host.watch at {w['ts']:.3f} in pid {w['pid']} counts "
            f"{w['attrs'].get('late')} late wakes, the longest "
            f"{w['attrs']['pause_max_s']:.4f}s, longer than any host.pause "
            f"recorded over that second (20 a second are, the rest counted)")
    if not kept and not unrecorded:
        if mine and owners <= profiled:
            log(f"{metric}: all {len(mine)} pauses are left out and the "
                f"profiler's intervals cover "
                f"{100 * _covered(profiler, lo, hi):.0f} % of the window in "
                f"every watched process: 0 is of the rest of it")
        return 0.0
    longest = max(kept, key=lambda s: s["value"], default=None)
    if longest is not None:
        log(f"{metric}: {len(kept)} pause(s) in {len(owners)} process(es); "
            f"the longest {longest['value']:.4f}s at {longest['ts']:.3f} in "
            f"pid {longest['pid']}: " + " ".join(
                f"{k}={v}" for k, v in sorted(longest["attrs"].items())
                if k not in ("span", "parent")))
    return 1000.0 * max([s["value"] for s in kept]
                        + [w["attrs"]["pause_max_s"] for w in unrecorded])


def window_flushes(record: dict, cell: dict) -> Optional[List[dict]]:
    """The window's ``serve.batch.flush`` spans (of the replica that
    flushed most) that say why they went: ``cause`` is what the program
    records since it counts a flush where it happens. None without any."""
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    found = [f for f in spans_mod.flushes(spans_mod.in_window(record, spans))
             if "cause" in f["attrs"]]
    return found or None

"""The runtime's own spans, for the readers under ``benchmark/metrics/``.

The program records spans on its flight-recorder ring
(``ray_tpu/util/events.py``): start by ``time.time()``, duration by
``time.perf_counter()``, an ident per request / lease / ``fit()``, the
span's id and its parent's. ``rt.shutdown()`` leaves the session's span
records in the driver, which is the process the readers run in. All of a
host's clocks are one clock (CLOCK_REALTIME), and so is the device trace's:
an ``.xplane.pb`` counts its events from ``profile_start_time``, epoch
nanoseconds, so ``device_clock() + start`` lays the device's operations on
the spans' timeline.

A program without spans (the parent of the PR that added them), or a run
without a trace, gives the readers nothing to read: every function here
then returns None or an empty list, and the metric is left out of the line;
``why_not`` says on stderr which record was missing.

A span of a process that is killed is only as safe as its last flush: the
trainer's worker stores ``train.loop`` as its loop ends, milliseconds before
``fit()`` kills it. So the harness also reads the conductor's records once
with the runtime still up (``keep_before_teardown``), and a session is what
``rt.shutdown()`` left plus whatever only that earlier read holds.

The first reader to ask for a run's spans also writes what an engineer
wants to see of them to stderr and to ``benchmark/out/spans-<cell>.json``:
each kind's count, median, p95 and self time over the window, the device's
idle time by innermost runtime span, the set-up as a tree, and the residual
between the two stamps of the spans that are also in the device trace.
"""

from __future__ import annotations

import functools
import json
import math
import os
import statistics
import tempfile
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import trace as trace_mod
from benchmark.hermetic import log

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNTIME_SPAN_PREFIX = "rt."        # TraceAnnotation("rt." + kind)
SETUP_KINDS = ("init", "init.probe", "lease.grant", "worker.spawn",
               "worker.boot", "train.fit", "train.backend.start",
               "train.gang.start", "train.loop")
Interval = Tuple[float, float]

KEEP_WAIT_S = 2.0                  # for a push that was in flight
HELD_CHARS = 600                   # of a session's kinds and pids, in a line

_summarised: set = set()           # cells whose summary this process wrote
_kept: List[dict] = []             # read with the runtime still up


def keep_before_teardown(needs: Sequence[str],
                         wait_s: float = KEEP_WAIT_S) -> None:
    """With the runtime still up (after ``fit()``, before
    ``serve.shutdown()`` and ``rt.shutdown()``): ship this process's own
    tail, read the conductor's span records, and where a kind of ``needs``
    (what the cell's readers read) is not there yet, read again for at most
    ``wait_s`` seconds. What was read is kept for ``session()``. Outside
    the window and outside ``setup_s``; a program without spans, or a read
    that fails, keeps nothing and says so."""
    try:
        from ray_tpu.state import api as state
        from ray_tpu.util import events
    except ImportError:
        return
    if not hasattr(events, "last_session"):
        return
    deadline = time.monotonic() + wait_s
    while True:
        try:
            events.flush_now()
            records = state.list_spans()
        except Exception as e:          # noqa: BLE001 - said, not raised
            log(f"spans: the conductor's records could not be read before "
                f"teardown: {e!r}")
            return
        lacking = [k for k in needs if not of_kind(records, k)]
        if not lacking or time.monotonic() >= deadline:
            break
        time.sleep(0.1)
    _kept[:] = records
    found = ", ".join(
        f"{k} x{len(of_kind(records, k))} "
        f"{sorted({str(r['ident']) for r in of_kind(records, k)})[:2]}"
        for k in needs)
    log(f"spans: before teardown the conductor holds {len(records)} span "
        f"records; of what this cell's readers need: {found or 'nothing'}"
        + (f"; NOT THERE after {wait_s:g}s: {', '.join(lacking)}; it "
           f"holds {held(records)}" if lacking else ""))
    if lacking:
        # a record is lost with a process that died unflushed: what the
        # cluster says it saw die, and when
        try:
            for e in [e for e in state.list_cluster_events()
                      if e["severity"] != "INFO"][-8:]:
                log(f"spans:   cluster event at {e['timestamp']:.3f} "
                    f"{e['event_type']}: {e['message']}")
        except Exception as e:          # noqa: BLE001 - said, not raised
            log(f"spans:   cluster events could not be read: {e!r}")


def session() -> Optional[List[dict]]:
    """The span records of the runtime this process last shut down (the
    conductor's dicts: node_id, pid, ts, kind, ident, value, attrs), and
    behind them the records that only the read before teardown holds; None
    where the program keeps none."""
    try:
        from ray_tpu.util import events
    except ImportError:
        return None
    last = getattr(events, "last_session", None)
    spans = list(last() or ()) if last is not None else []
    if _kept:
        have = {s["attrs"]["span"] for s in spans}
        spans += [s for s in _kept if s["attrs"]["span"] not in have]
    return spans or None


def held(spans: Iterable[dict]) -> str:
    """``pid 12: train.fit x1, train.pump x28; pid 40: ...``: which kinds
    of which processes a session holds, for a reader that misses one."""
    by_pid: Dict[int, Dict[str, int]] = {}
    for s in spans:
        kinds = by_pid.setdefault(s["pid"], {})
        kinds[s["kind"]] = kinds.get(s["kind"], 0) + 1
    text = "; ".join(
        f"pid {pid}: " + ", ".join(f"{k} x{n}" for k, n in sorted(ks.items()))
        for pid, ks in sorted(by_pid.items()))
    return text[:HELD_CHARS] + ("..." if len(text) > HELD_CHARS else "")


def why_not(record: dict, needs: Sequence[str]) -> str:
    """Why a reader of span kinds ``needs`` had nothing to read."""
    spans = session()
    if spans is None:
        return ("no session kept: the program left no span records "
                "(events.last_session())")
    platform = (record.get("facts") or {}).get("platform")
    if platform != "tpu":
        return (f"not a TPU run (platform {platform!r}): host times beside "
                "another backend are not this metric")
    lacking = [k for k in needs if not of_kind(spans, k)]
    if lacking:
        return (f"the session holds no {', '.join(lacking)}; it holds "
                f"{held(spans)}")
    inside = in_window(record, spans)
    counts = ", ".join(f"{k} x{len(of_kind(spans, k))} "
                       f"({len(of_kind(inside, k))} began inside the window)"
                       for k in needs)
    return (f"the session holds {counts or 'spans'}, but not as the reader "
            "needs them (ident, rank, counter, window, or a trace beside "
            f"them); it holds {held(spans)}")


def load(record: dict, cell: dict) -> Optional[List[dict]]:
    """``session()``, and the first time it is asked for in a run, the
    summary of it (stderr and ``benchmark/out/spans-<cell>.json``). A
    rehearsal gets the summary and no metric: host times beside a CPU
    backend are not the numbers of a machine with the chip."""
    spans = session()
    if spans and cell["name"] not in _summarised:
        _summarised.add(cell["name"])
        try:
            summarise(record, cell, spans)
        except Exception as e:          # noqa: BLE001 - a report, no metric
            log(f"spans: no summary: {e!r}")
    if (record.get("facts") or {}).get("platform") != "tpu":
        return None
    return spans


def window_bounds(record: dict) -> Interval:
    """From ``window_start`` to the last reply (serving) or the last loss
    on the host (training)."""
    lo = record["window_start"]
    window = record.get("window") or {}
    rows = [r["last"] for r in window.get("rows", ()) if "last" in r]
    if rows:
        return lo, max(rows)
    steps = window.get("steps") or []
    return lo, lo + (steps[-1][1] if steps else 0.0)


def in_window(record: dict, spans: Iterable[dict]) -> List[dict]:
    """The spans that began inside the measured window."""
    lo, hi = window_bounds(record)
    return [s for s in spans if lo <= s["ts"] <= hi]


def of_kind(spans: Iterable[dict], kind: str) -> List[dict]:
    return [s for s in spans if s["kind"] == kind]


def values(spans: Iterable[dict], kind: str) -> List[float]:
    return [s["value"] for s in spans if s["kind"] == kind]


def by_request(spans: Iterable[dict]) -> Dict[str, Dict[str, dict]]:
    """ident -> {kind: span} for every ident that has a ``serve.request``."""
    out: Dict[str, Dict[str, dict]] = {}
    for s in spans:
        out.setdefault(s["ident"], {})[s["kind"]] = s
    return {k: v for k, v in out.items() if "serve.request" in v}


def window_requests(record: dict, spans: Sequence[dict]
                    ) -> List[Dict[str, dict]]:
    """The requests whose ``serve.request`` began inside the window, each
    with all of its spans."""
    lo, hi = window_bounds(record)
    return [r for r in by_request(spans).values()
            if lo <= r["serve.request"]["ts"] <= hi]


def median_ms(record: dict, cell: dict, kind: str,
              per_request: bool = False) -> Optional[float]:
    """Median milliseconds of the window's spans of one kind (or, per
    request, of that kind over the window's requests); None without any."""
    spans = load(record, cell)
    if not spans:
        return None
    found = [r[kind]["value"] for r in window_requests(record, spans)
             if kind in r] if per_request else \
        values(in_window(record, spans), kind)
    return 1000.0 * statistics.median(found) if found else None


def flushes(spans: Iterable[dict]) -> List[dict]:
    """``serve.batch.flush`` spans of the replica that flushed most, in
    order of their start."""
    by_pid: Dict[tuple, List[dict]] = {}
    for s in of_kind(spans, "serve.batch.flush"):
        by_pid.setdefault((s["node_id"], s["pid"]), []).append(s)
    if not by_pid:
        return []
    return sorted(max(by_pid.values(), key=len), key=lambda s: s["ts"])


def gaps(flushed: Sequence[dict]) -> List[Interval]:
    """From each flush's end to the next one's start."""
    return [(a["ts"] + a["value"], b["ts"])
            for a, b in zip(flushed, flushed[1:])
            if b["ts"] > a["ts"] + a["value"]]


# ----------------------------------------------------------------------
# the device trace on the spans' clock
# ----------------------------------------------------------------------
def trace_dir() -> str:
    """Where ``run.path("trace")`` put the run's trace; it is still there
    when the readers run."""
    return os.path.join(tempfile.gettempdir(), "trace")


@functools.lru_cache(maxsize=2)
def _profile(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _xplane(directory: str) -> Optional[str]:
    try:
        return trace_mod.find_xplane(directory)
    except (FileNotFoundError, OSError):
        return None


def device_clock(directory: str) -> Optional[float]:
    """``profile_start_time`` of the run's ``.xplane.pb`` in epoch seconds:
    what an event's ``start_ns`` counts from."""
    path = _xplane(directory)
    if path is None:
        return None
    for plane in _profile(path).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
            return None if start is None else start * 1e-9
    return None


def device_window(directory: str) -> Optional[Tuple[Interval,
                                                    List[Interval]]]:
    """((start, end), busy intervals) of the traced window of the first
    chip, in seconds from the trace's origin: whole periods of the main
    module, as ``benchmark.trace.reduce_device`` takes them."""
    path = _xplane(directory)
    if path is None:
        return None
    devices, _ = trace_mod.read_xplane(path)
    if not devices:
        return None
    ops, _, modules = devices[sorted(devices)[0]]
    by_module: Dict[str, List[Tuple[float, float]]] = {}
    for name, start, dur in modules:
        by_module.setdefault(name.split("(")[0], []).append((start, dur))
    if not by_module:
        return None
    runs = sorted(max(by_module.values(),
                      key=lambda rs: sum(d for _, d in rs)))
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    busy = trace_mod.clip(trace_mod.union(
        (s, s + d) for _, s, d in ops if lo <= s < hi), lo, hi)
    return (lo, hi), busy


def device_idle(directory: str) -> Optional[List[Interval]]:
    """The idle intervals of the traced window in epoch seconds."""
    origin, window = device_clock(directory), device_window(directory)
    if origin is None or window is None:
        return None
    (lo, hi), busy = window
    return [(a + origin, b + origin)
            for a, b in trace_mod.subtract([(lo, hi)], busy)]


def runtime_events(directory: str) -> List[Tuple[str, float, float]]:
    """(kind, start in epoch seconds, seconds) of the runtime's spans that
    the chip-owning process also wrote into the device trace."""
    origin, path = device_clock(directory), _xplane(directory)
    if origin is None or path is None:
        return []
    out = []
    for plane in _profile(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name[len(RUNTIME_SPAN_PREFIX):],
                        origin + e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events
                       if e.name.startswith(RUNTIME_SPAN_PREFIX))
    return out


def clock_residuals(directory: str, spans: Sequence[dict]) -> List[float]:
    """For each runtime span in the device trace, the distance in seconds
    between its start there and the start of the nearest ring record of its
    kind: how well the two clocks agree."""
    starts: Dict[str, List[float]] = {}
    for s in spans:
        starts.setdefault(s["kind"], []).append(s["ts"])
    return [min(abs(t - start) for t in starts[kind])
            for kind, start, _ in runtime_events(directory)
            if kind in starts]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Seconds that lie in both; ``a`` and ``b`` each a union."""
    return trace_mod.total(a) - trace_mod.total(trace_mod.subtract(a, b))


def idle_by_span(idle: Sequence[Interval], spans: Sequence[dict]
                 ) -> Dict[str, float]:
    """Idle seconds by the innermost (shortest) runtime span, of any
    process of the host, that covers them; ``no runtime span`` where none
    does. A gap is cut at every span boundary inside it."""
    out: Dict[str, float] = {}
    for lo, hi in idle:
        near = [s for s in spans
                if s["ts"] < hi and s["ts"] + s["value"] > lo]
        cuts = sorted({lo, hi} | {
            t for s in near for t in (s["ts"], s["ts"] + s["value"])
            if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inner = [(s["value"], s["kind"]) for s in near
                     if s["ts"] <= mid < s["ts"] + s["value"]]
            who = min(inner)[1] if inner else "no runtime span"
            out[who] = out.get(who, 0.0) + (b - a)
    return out


# ----------------------------------------------------------------------
# the summary
# ----------------------------------------------------------------------
def self_times(spans: Sequence[dict]) -> Dict[str, float]:
    """span id -> its seconds minus what its children cover."""
    children: Dict[str, List[Interval]] = {}
    for s in spans:
        parent = s["attrs"].get("parent")
        if parent is not None:
            children.setdefault(parent, []).append(
                (s["ts"], s["ts"] + s["value"]))
    out = {}
    for s in spans:
        covered = trace_mod.clip(
            trace_mod.union(children.get(s["attrs"]["span"], ())),
            s["ts"], s["ts"] + s["value"])
        out[s["attrs"]["span"]] = max(
            0.0, s["value"] - trace_mod.total(covered))
    return out


def p95(values: Sequence[float]) -> float:
    """Nearest rank, as the readers' ``_common.p95``."""
    values = sorted(values)
    return values[max(0, math.ceil(0.95 * len(values)) - 1)]


def by_kind(spans: Sequence[dict], all_spans: Sequence[dict]) -> dict:
    selfs = self_times(all_spans)
    out: dict = {}
    for s in spans:
        row = out.setdefault(s["kind"],
                             {"values": [], "self_s": 0.0, "max": {}})
        row["values"].append(s["value"])
        row["self_s"] += selfs[s["attrs"]["span"]]
        for key, v in s["attrs"].items():       # the counts: their maxima
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["max"][key] = max(v, row["max"].get(key, v))
    return {kind: {"count": len(row["values"]),
                   "median_s": statistics.median(row["values"]),
                   "p95_s": p95(row["values"]),
                   "total_s": sum(row["values"]),
                   "self_s": row["self_s"], "attr_max": row["max"]}
            for kind, row in sorted(out.items())}


def setup_tree(record: dict, spans: Sequence[dict]) -> List[str]:
    """The spans of the set-up kinds that began before the window, as an
    indented tree in order of their start."""
    lo, _ = window_bounds(record)
    nodes = [s for s in spans
             if s["kind"] in SETUP_KINDS and s["ts"] < lo]
    ids = {s["attrs"]["span"] for s in nodes}
    children: Dict[Optional[str], List[dict]] = {}
    for s in sorted(nodes, key=lambda s: s["ts"]):
        parent = s["attrs"].get("parent")
        children.setdefault(parent if parent in ids else None,
                            []).append(s)
    t0 = min((s["ts"] for s in nodes), default=lo)
    lines: List[str] = []

    def walk(parent, depth):
        for s in children.get(parent, ()):
            extra = " ".join(f"{k}={v}" for k, v in sorted(s["attrs"].items())
                             if k not in ("span", "parent"))
            lines.append(f"{'  ' * depth}{s['kind']} +{s['ts'] - t0:.3f}s "
                         f"{s['value']:.3f}s pid={s['pid']} {extra}".rstrip())
            walk(s["attrs"]["span"], depth + 1)
    walk(None, 0)
    return lines


def device_names(directory: str) -> dict:
    """Where the names the program gives its kernels and scopes can be
    read in the device trace: the instruction names of the Mosaic calls, and
    how many events carry a name in their text or their stats."""
    path = _xplane(directory)
    if path is None:
        return {}
    mosaic, stat_keys = set(), set()
    found = {"rt_flash": [0, 0], "rt.generate": [0, 0]}   # in text, stats
    for plane in _profile(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if trace_mod.MOSAIC in e.name:
                    mosaic.add(e.name.split(" = ")[0].lstrip("%"))
                stats = list(e.stats)
                stat_keys.update(key for key, _ in stats)
                for name, counts in found.items():
                    counts[0] += name in e.name
                    counts[1] += any(name in str(v) for _, v in stats)
    return {"mosaic_instructions": sorted(mosaic)[:12],
            "events_naming_in_text": {k: v[0] for k, v in found.items()},
            "events_naming_in_stats": {k: v[1] for k, v in found.items()},
            "event_stat_keys": sorted(stat_keys)}


def summarise(record: dict, cell: dict, spans: Sequence[dict]) -> dict:
    directory = trace_dir()
    inside = in_window(record, spans)
    out = {"cell": cell["name"], "spans": len(spans),
           "in_window": len(inside), "window": list(window_bounds(record)),
           "by_kind": by_kind(inside, spans),
           "setup_tree": setup_tree(record, spans)}
    idle = device_idle(directory)
    if idle:
        shares = idle_by_span(idle, spans)
        out["device_idle_s"] = trace_mod.total(idle)
        out["device_idle_by_span"] = sorted(shares.items(),
                                            key=lambda kv: -kv[1])
        residuals = sorted(clock_residuals(directory, spans))
        if residuals:
            out["clock_residual_s"] = {
                "n": len(residuals), "max": residuals[-1],
                "median": residuals[len(residuals) // 2]}
        out["device_names"] = device_names(directory)
    log(f"spans: {len(spans)} in the session, {len(inside)} began in the "
        "window; per kind count / median / p95 / self seconds:")
    for kind, row in out["by_kind"].items():
        log(f"spans:   {kind:26s} {row['count']:5d} {row['median_s']:.6f} "
            f"{row['p95_s']:.6f} {row['self_s']:.4f} max {row['attr_max']}")
    log("spans: set-up tree:")
    for line in out["setup_tree"]:
        log("spans:   " + line)
    if idle:
        log(f"spans: device idle {out['device_idle_s']:.4f}s of the traced "
            "window, by innermost runtime span: " + ", ".join(
                f"{k} {v:.4f}" for k, v in out["device_idle_by_span"]))
        log(f"spans: clock residual {out.get('clock_residual_s')}; "
            f"device names {out['device_names']}")
    try:
        os.makedirs(os.path.join(CHECKOUT, "benchmark", "out"),
                    exist_ok=True)
        with open(os.path.join(CHECKOUT, "benchmark", "out",
                               f"spans-{cell['name']}.json"), "w") as f:
            json.dump(out, f, indent=1, default=str)
    except OSError as e:
        log(f"spans: could not keep the summary: {e!r}")
    return out

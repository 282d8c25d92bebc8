"""From a profiler trace (``.xplane.pb``) to numbers.

The process that holds the chip traces a few whole periods of its steady
state with ``jax.profiler`` and calls ``reduce_file`` on what that wrote;
the readers under ``benchmark/metrics/`` take their numbers from the
summary. The arithmetic lives here so that every PR reduces a trace the same
way; ``tests/benchmark/test_trace.py`` checks it on a synthetic timeline
with hand-worked answers and on a recorded v5e trace kept under
``benchmark/testdata/``.

What a TPU trace looks like (one ``/device:TPU:<n>`` plane per chip):
line ``XLA Modules`` has one event per executed program, ``XLA Ops`` one per
HLO operation on the core's one instruction stream, containers (``while``,
``call``) enclosing the operations of their bodies, and ``Async XLA Ops``
one span from each ``*-start`` to its ``*-done`` (DMA and collectives in
flight beside the stream). An event's name is the HLO text of its
operation, so a Mosaic kernel shows as a custom call with target
``tpu_custom_call``, whatever jax named the computation around it.

Definitions:
- window: from the start of the main module's first execution in the trace
  to the start of its last one: whole periods, each the program and the
  wait before the next. The main module is the one with most device time.
- busy: the union of the ``XLA Ops`` intervals inside the window; idle
  share is 1 - busy / window.
- an operation's self time is its duration minus that of the operations it
  encloses, so a ``while`` is not counted on top of its body.
- exposed collective time: time the instruction stream spends in a
  collective operation (an all-gather, an all-reduce, or the ``-done`` that
  waits for a transfer in flight); the core computes nothing meanwhile. A
  transfer in flight beside a computing stream is hidden and counts only in
  ``collective_s``. (The async line is not written for every chip of a
  host, so nothing that is averaged over chips may depend on it.)
"""

from __future__ import annotations

import re
from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # start, end, seconds
Event = Tuple[str, float, float]        # name, start, duration, seconds

COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|send|recv)\b")
MOSAIC = 'custom_call_target="tpu_custom_call"'
HOST_SPAN_PREFIX = "bench."
TOP = 10


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of ``a`` not covered by ``b``; both already unions."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float,
                                                      float, bool]]:
    """(name, start, duration, self seconds, is_leaf) for the events of one
    instruction stream, in which an event either encloses or is disjoint
    from another."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selfs = [e[2] for e in events]
    leaf = [True] * len(events)
    stack: List[int] = []
    for i in order:
        _, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] \
                <= start + 1e-12:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= dur
            leaf[stack[-1]] = False
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][2], max(selfs[i], 0.0),
             leaf[i]) for i in range(len(events))]


def op_label(hlo: str) -> str:
    """``%fusion.206 = (f32[4096,32768]{...}, ...) fusion(...)`` ->
    ``fusion.206 f32[4096,32768]``; a Mosaic kernel gets ``mosaic:``."""
    name, _, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    label = f"{name} {shape.group(0)}" if shape else name
    if MOSAIC in hlo:
        label = "mosaic:" + label
    return label[:96]


def reduce_device(ops: Sequence[Event], async_ops: Sequence[Event],
                  modules: Sequence[Event], host_spans: Sequence[Event]
                  ) -> dict:
    """The numbers of one chip; ``{}`` if the trace holds fewer than two
    executions of its main module (no whole period)."""
    by_module: dict = {}
    for name, start, dur in modules:
        by_module.setdefault(name.split("(")[0], []).append((start, dur))
    if not by_module:
        return {}
    main = max(by_module, key=lambda k: sum(d for _, d in by_module[k]))
    runs = sorted(by_module[main])
    if len(runs) < 2:
        return {}
    lo, hi = runs[0][0], runs[-1][0]
    periods = len(runs) - 1
    inside = [e for e in ops if e[1] >= lo and e[1] < hi]
    timed = self_times(inside)
    busy = union((s, s + d) for _, s, d in inside)
    busy = clip(busy, lo, hi)
    exposed = clip(union((s, s + d) for n, s, d, _, is_leaf in timed
                         if is_leaf and COLLECTIVE.match(n)), lo, hi)
    in_flight = clip(union(exposed + [
        (s, s + d) for n, s, d in async_ops
        if COLLECTIVE.match(n) and s >= lo and s < hi]), lo, hi)
    by_op: dict = {}
    mosaic_s, mosaic_calls = 0.0, 0
    for name, _, _, self_s, _ in timed:
        label = op_label(name)
        by_op[label] = by_op.get(label, 0.0) + self_s
        if MOSAIC in name:
            mosaic_s += self_s
            mosaic_calls += 1
    gaps: dict = {}
    for g_lo, g_hi in subtract([(lo, hi)], busy):
        mid = (g_lo + g_hi) / 2
        inner = [(d, n) for n, s, d in host_spans if s <= mid < s + d]
        who = min(inner)[1] if inner else "no bench span"
        gaps[who] = gaps.get(who, 0.0) + (g_hi - g_lo)
    return {
        "main_module": main, "periods": periods, "window_s": hi - lo,
        "busy_s": total(busy),
        "module_s": sum(d for _, d in runs[:-1]),
        "collective_s": total(in_flight),
        "exposed_collective_s": total(exposed),
        "mosaic_s": mosaic_s, "mosaic_calls": mosaic_calls,
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP],
    }


def combine(devices: Sequence[dict]) -> dict:
    """Average the chips of one run (they run one program in step)."""
    devices = [d for d in devices if d]
    if not devices:
        return {}
    n = len(devices)
    out = {"devices": n, "main_module": devices[0]["main_module"],
           "periods": devices[0]["periods"]}
    for key in ("window_s", "busy_s", "module_s", "collective_s",
                "exposed_collective_s", "mosaic_s"):
        out[key] = sum(d[key] for d in devices) / n
    out["mosaic_calls"] = devices[0]["mosaic_calls"]
    for key in ("device_ops", "idle_gaps"):
        merged: dict = {}
        for d in devices:
            for name, s in d[key]:
                merged[name] = merged.get(name, 0.0) + s / n
        out[key] = [[k, v] for k, v in
                    sorted(merged.items(), key=lambda kv: -kv[1])[:TOP]]
    return out


def start(trace_dir: str) -> None:
    """Start jax's profiler without the Python call-stack tracer: the
    ``bench.*`` annotations and the device planes are what is read, and the
    call stacks of a dozen waiting threads are most of a trace's bytes."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def read_xplane(path: str):
    """-> (devices: {plane name: (ops, async_ops, modules)}, host spans).
    Seconds from the trace's own origin; host and device share it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}

            def events(line):
                if line not in lines:
                    return []
                return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in lines[line].events]
            devices[plane.name] = (events("XLA Ops"),
                                   events("Async XLA Ops"),
                                   events("XLA Modules"))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in ln.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    return devices, host


def find_xplane(trace_dir: str) -> str:
    import glob
    import os
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_file(path: str) -> dict:
    devices, host = read_xplane(path)
    return combine([reduce_device(ops, async_ops, modules, host)
                    for ops, async_ops, modules in devices.values()])

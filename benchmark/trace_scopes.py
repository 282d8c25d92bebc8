"""Device time by the program's named scopes.

``jax.named_scope("rt.gdn.scan")`` lands in the ``op_name`` of the HLO
instructions made under it, and nowhere in the profiler's trace: an event of
the trace's ``XLA Ops`` line carries its instruction's name (``%fusion.12 =
...``) and no metadata. So the process that holds the chip keeps, from the
compiled step's own text, the map from instruction name to scope
(``scope_map``), and ``reduce_file`` adds up the self time of the trace's
events by it, over the same window of whole periods that
``benchmark/trace.py`` reduces.

A scope is a dotted name that starts with ``rt.``; an instruction under
several takes the innermost. The backward pass and the recomputed forward
keep the forward's scope inside theirs (``transpose(jvp(rt.moe.experts))``,
``checkpoint/rt.gdn.scan``), so a scope's time is forward, recompute and
backward together. A fusion takes its own ``op_name``'s scope, or, where
that has none, the scope most of its fused instructions carry. A Mosaic
kernel the compiler itself puts in (the TPU's grouped matmul for
``lax.ragged_dot`` is one: ``op_name="ragged-dot-none"``) carries no scope
of the program's; it takes the scope of the instructions that use its
result, through the ``get-tuple-element``s between them.
"""

from __future__ import annotations

import re
from collections import Counter

from benchmark import trace as trace_mod

SCOPE = re.compile(r"rt\.[a-z_]+(?:\.[a-z_]+)+")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
OPERAND = re.compile(r"%([\w.\-]+)")
INHERITS = ('custom_call_target="tpu_custom_call"', " get-tuple-element(")
HOPS = 4
TOP = 8             # instructions kept a scope, by self time
COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def innermost(op_name: str):
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def scope_map(hlo_text: str) -> dict:
    """{instruction name: scope} for every instruction of the module that
    lies under a scope."""
    own, called, members = {}, {}, {}
    users, inherits = {}, []
    computation = None
    for line in hlo_text.splitlines():
        start = COMPUTATION.match(line)
        if start:
            computation = start.group(1)
            members[computation] = Counter()
            continue
        found = INSTRUCTION.match(line)
        if not found:
            continue
        name = found.group(1)
        head = line[found.end():].split(", metadata=")[0][:4000]
        for operand in OPERAND.findall(head.split("custom_call_target")[0]):
            users.setdefault(operand, []).append(name)
        op_name = OP_NAME.search(line)
        scope = innermost(op_name.group(1)) if op_name else None
        if scope:
            own[name] = scope
            if computation:
                members[computation][scope] += 1
        elif " fusion(" in line:
            calls = CALLS.search(line)
            if calls:
                called[name] = calls.group(1)
        elif any(mark in line for mark in INHERITS):
            inherits.append(name)
    for name, computation in called.items():
        inside = members.get(computation)
        if inside:
            own[name] = inside.most_common(1)[0][0]
    for _ in range(HOPS):
        for name in inherits:
            if name not in own:
                found = Counter(own[u] for u in users.get(name, ())
                                if u in own)
                if found:
                    own[name] = found.most_common(1)[0][0]
    return own


def reduce_device(ops, modules, scopes: dict) -> dict:
    """Self seconds of one chip's instruction stream by scope, over the
    window of ``trace.reduce_device``; ``{}`` without a whole period."""
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
    inside = [e for e in ops if lo <= e[1] < hi]
    seconds, calls = Counter(), Counter()
    kernels, by_op = Counter(), {}
    for name, _, _, self_s, _ in trace_mod.self_times(inside):
        instruction = name.partition(" = ")[0].strip().lstrip("%")
        scope = scopes.get(instruction, "")
        seconds[scope] += self_s
        calls[scope] += 1
        by_op.setdefault(scope, Counter())[trace_mod.op_label(name)] += self_s
        if trace_mod.MOSAIC in name:
            kernels[scope] += self_s
    return {"periods": len(runs) - 1, "seconds": dict(seconds),
            "events": dict(calls), "mosaic_seconds": dict(kernels),
            "top": {scope: ops.most_common(TOP)
                    for scope, ops in by_op.items()}}


def reduce_file(path: str, scopes: dict) -> dict:
    """-> {"periods", "seconds": {scope: self seconds in the window; ""
    for what lies under no scope}, "events", "mosaic_seconds", "top": the
    instructions with most self time a scope} of the first chip (a one-chip
    cell's only one)."""
    devices, _ = trace_mod.read_xplane(path)
    for _, (ops, _async_ops, modules) in sorted(devices.items()):
        return reduce_device(ops, modules, scopes)
    return {}


def scope_seconds(record: dict, prefix: str):
    """Seconds of a traced run's window under the scopes that start with
    ``prefix``; None where the run reduced none (no trace, or a program
    without such scopes)."""
    reduced = (record.get("trace") or {}).get("scopes") or {}
    seconds = reduced.get("seconds") or {}
    found = [s for name, s in seconds.items() if name.startswith(prefix)]
    return sum(found) if found else None

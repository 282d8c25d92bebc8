"""What the ``dots3`` family's readers share. Not a metric: no manifest
entry names it."""

from __future__ import annotations

import importlib
import importlib.util
import os

from benchmark import ops, trace_scopes


def reader_of(metric: str):
    """The module of the accepted metric ``metric`` beside this file: a
    metric whose quantity the cell shares with accepted cells reads it with
    their reader (the accepted metric's ``workloads`` cannot take a cell
    without an edit to its file, so the cell's metric has a name of its
    own and no code of its own)."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     metric + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def least(record, cell):
    """``generate_least_seconds`` of the cell's family for the call as
    issued; None on a device without published peaks (a rehearsal)."""
    traffic, config = cell["traffic_data"], cell["config_data"]
    family = importlib.import_module("benchmark.ops_" + config["family"])
    try:
        return family.generate_least_seconds(
            config, traffic["max_batch_size"], traffic["prompt_tokens"],
            traffic["new_tokens"], config["param_dtype"],
            record["facts"]["kind"])
    except ops.UnknownDevice:
        return None


def part_roofline(record, cell, part: str, scope: str):
    """100 x the least seconds of ``part`` a call x periods over the
    scope's device seconds."""
    seconds = trace_scopes.scope_seconds(record, scope)
    if not seconds:
        return None
    found = least(record, cell)
    if found is None:
        return None
    periods = record["trace"]["scopes"]["periods"]
    return 100.0 * found[part + "_seconds"] * periods / seconds

"""``BENCHMARK.json`` and the files it names.

Whatever belongs to one configuration, one traffic mix or one metric is a
file of its own, found by the name in the manifest:

    <paths[0]>/configs/<config>.json     sizes, as run (the manifest's
                                         ``file`` says the same path)
    <paths[0]>/traffic/<traffic>.json    parameters of the mix; ``app`` names
                                         ``benchmark/apps/<app>.py``
    <paths[0]>/metrics/<metric>.json     definition, unit, layer, moves
    <paths[0]>/metrics/<metric>.py       ``read(record, cell) -> number|None``
                                         and, where it reads spans,
                                         ``NEEDS``: their kinds
    benchmark/reference/<family>.py      the plain reference of the
                                         configuration's ``family``

so a later PR adds a cell, a mix, a configuration or a layer metric by
adding files and one entry each. Nothing here knows a cell by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

from benchmark import spans as spans_mod
from benchmark.hermetic import log

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
END_TO_END_SOURCES = ("host_clock", "device_trace")


class ManifestError(Exception):
    pass


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from None


class Manifest:
    def __init__(self, path: str = ""):
        self.path = os.path.abspath(
            path or os.path.join(CHECKOUT, "BENCHMARK.json"))
        self.root = os.path.dirname(self.path)
        self.data = _json(self.path)
        self.home = os.path.join(self.root, self.data["paths"][0])
        self._modules = {}         # metric -> its reader module, loaded

    def cell(self, name: str) -> dict:
        """One entry of ``workloads`` with its configuration's and its
        traffic's files read in."""
        cells = {w["name"]: w for w in self.data["workloads"]}
        if name not in cells:
            raise ManifestError(f"no workload {name!r} in {self.path} "
                                f"(have: {sorted(cells)})")
        cell = dict(cells[name])
        configs = {c["name"]: c for c in self.data["configs"]}
        if cell["config"] not in configs:
            raise ManifestError(f"workload {name!r} names configuration "
                                f"{cell['config']!r}, which is not listed")
        cell["config_entry"] = configs[cell["config"]]
        cell["config_data"] = _json(
            os.path.join(self.root, cell["config_entry"]["file"]))
        cell["traffic_data"] = _json(os.path.join(
            self.home, "traffic", cell["traffic"] + ".json"))
        return cell

    def metrics(self, kind: str, cell_name: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.data[kind]
                if "workloads" not in m or cell_name in m["workloads"]]

    def reader_module(self, metric: str):
        if metric in self._modules:
            return self._modules[metric]
        path = os.path.join(self.home, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        if spec is None or not os.path.exists(path):
            raise ManifestError(f"metric {metric!r} has no reader {path}")
        module = importlib.util.module_from_spec(spec)
        metrics_dir = os.path.dirname(path)
        if metrics_dir not in sys.path:      # readers share ``_common.py``
            sys.path.insert(0, metrics_dir)
        spec.loader.exec_module(module)
        self._modules[metric] = module
        return module

    def reader(self, metric: str):
        return self.reader_module(metric).read

    def span_needs(self, cell_name: str) -> list:
        """The span kinds this cell's per-layer readers read (``NEEDS``)."""
        return list(dict.fromkeys(
            kind for m in self.metrics("per_layer", cell_name)
            for kind in getattr(self.reader_module(m["name"]), "NEEDS", ())))

    def read_metrics(self, kind: str, cell: dict, record: dict) -> dict:
        """name -> {"value", "unit"}; a reader that finds nothing to read
        returns None and its metric is left out of the line, by name and
        with the reader's reason on stderr."""
        out = {}
        for m in self.metrics(kind, cell["name"]):
            module = self.reader_module(m["name"])
            value = module.read(record, cell)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
                continue
            try:
                why = why_not_read(module, m, record)
            except Exception as e:      # noqa: BLE001 - a reason, no metric
                why = f"(and its reason could not be worked out: {e!r})"
            log(f"metric {m['name']} not read: {why}")
        return out


def why_not_read(module, metric: dict, record: dict) -> str:
    """From what the reader says it reads: the span kinds in its ``NEEDS``,
    or the trace for a ``device_trace`` metric."""
    if hasattr(module, "NEEDS"):
        return spans_mod.why_not(record, module.NEEDS)
    if metric["source"] == "device_trace" and not record.get("trace"):
        return "no trace: the run reduced no profile of the device"
    return "its reader found nothing to read in the run's record"

"""BENCHMARK.json against the contract it is checked by, and against the
files it names. Nothing here starts a process or imports jax."""

import json
import os
import re

import pytest

from bench_paths import BENCH, CHECKOUT, manifest_data

DATA = manifest_data()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection).*size"
                    r"|_dim$|_rank$|head_dim|expansion|experts_per_tok")
E2E = {m["name"]: m for m in DATA["end_to_end"]}
LAYER = {m["name"]: m for m in DATA["per_layer"]}
CELLS = {w["name"]: w for w in DATA["workloads"]}


def cells_of(metric: dict) -> list:
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_limits():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= DATA["run_seconds"] <= 51 and \
        isinstance(DATA["run_seconds"], int)
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= len(DATA["paths"]) <= 16
    for p in DATA["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(CHECKOUT, p))
    assert len(DATA["command"]) <= 32
    program = DATA["command"][1]
    assert any(program.startswith(p + "/") for p in DATA["paths"])
    assert os.path.isfile(os.path.join(CHECKOUT, program))
    # a full check of 24 cells at this length fits the driver's 43,200 s
    runs = 2 + 14 * 24
    assert runs * (DATA["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", DATA["configs"], ids=lambda c: c["name"])
def test_configuration_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["source"].startswith("https://")
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert any(entry["file"].startswith(p + "/") for p in DATA["paths"])
    with open(os.path.join(CHECKOUT, entry["file"])) as f:
        data = json.load(f)
    assert data["source"] == entry["source"]
    assert sorted(data["reduced"]) == sorted(entry["reduced"])
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key), key
    assert entry["name"] in {w["config"] for w in DATA["workloads"]}
    assert data["family"] and os.path.isfile(os.path.join(
        BENCH, "reference", data["family"] + ".py"))
    assert data["param_dtype"] in ("float32", "bfloat16")
    assert data["assumed"], "no network: recalled values are listed"


def test_configuration_files_are_distinct():
    files = [c["file"] for c in DATA["configs"]]
    assert len(set(files)) == len(files)
    names = [c["name"] for c in DATA["configs"]]
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("cell", DATA["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\t" not in cell["why"]
    assert cell["config"] in {c["name"] for c in DATA["configs"]}
    path = os.path.join(BENCH, "traffic", cell["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    assert os.path.isfile(os.path.join(BENCH, "apps",
                                       traffic["app"] + ".py"))
    reports = [m for m in E2E.values() if cell["name"] in cells_of(m)]
    assert "setup_s" in {m["name"] for m in reports}
    assert len(reports) >= 2, "setup_s and at least one other"
    assert any(cell["name"] in cells_of(m) for m in LAYER.values())


def test_cells_are_unique_and_few_take_four_chips():
    assert 1 <= len(CELLS) <= 24 and len(CELLS) == len(DATA["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in DATA["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in DATA["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", DATA["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    for cell in cells_of(metric):
        assert cell in CELLS
    check_metric_files(metric, "end_to_end")


@pytest.mark.parametrize("metric", DATA["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    moved = E2E[metric["moves"]]
    for cell in cells_of(metric):
        assert cell in CELLS
        assert cell in cells_of(moved), \
            f"{metric['name']} moves {moved['name']}, which {cell} " \
            "does not report"
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    check_metric_files(metric, "per_layer")


def check_metric_files(metric: dict, kind: str) -> None:
    """Each metric is a file pair of its own that says what the manifest
    says: definition, unit, layer, moves, cells; and a reader."""
    base = os.path.join(BENCH, "metrics", metric["name"])
    with open(base + ".json") as f:
        own = json.load(f)
    assert own.pop("kind") == kind
    assert len(own.pop("definition")) > 40
    assert own == metric
    with open(base + ".py") as f:
        assert "def read(record, cell)" in f.read()


def test_metric_names_are_unique_and_layers_are_in_perf_md():
    names = [m["name"] for m in DATA["end_to_end"] + DATA["per_layer"]]
    assert len(set(names)) == len(names)
    assert 1 <= len(DATA["end_to_end"]) <= 16
    assert 1 <= len(DATA["per_layer"]) <= 128
    with open(os.path.join(CHECKOUT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in DATA["per_layer"]}:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_separate_ingress_and_queue_metrics():
    assert LAYER["ingress.proxy_ms"]["moves"] == "serve.request_p95_s"
    assert LAYER["batch.queue_ms"]["moves"] == "serve.ttft_p95_s"
    assert LAYER["ingress.proxy_ms"]["layer"] != \
        LAYER["batch.queue_ms"]["layer"]

"""The readers of what PR 58 put on the ring (``host.pause``, ``host.watch``,
``train.report``'s ``period_s``, ``serve.batch.flush``'s ``cause`` and
``since_last_s``), on hand-made records with hand-worked answers; and the
manifest's five new entries. (The file's name sorts it last in its
directory: ``test_bench_harness.py``'s soak and the cells' rehearsals share
a checkout, and which tests an xdist worker's first batch holds decides
whether they meet, PERF.md section 7 (g).)"""

import json
import os

import pytest

from bench_paths import BENCH, manifest_data
from benchmark import spans as spans_mod
from benchmark.manifest import Manifest
from ray_tpu.util import events

MF = Manifest()
TRAIN = MF.cell("qwen3next-train-s8192-ep16share")
SERVE = MF.cell("falconh1-serve-closed64-p128-n384")
TRAIN_CELLS = ["mistral7b-train-1chip", "internlm2-train-fsdp4",
               "qwen3next-train-s8192-ep16share",
               "joyai-train-s8192-ep16share"]
SERVE_CELLS = ["mistral7b-serve-closed32", "ouro2.6b-serve-closed16",
               "dots3-serve-closed2-p32k-n128",
               "olmohybrid-serve-closed48-p128-n384",
               "falconh1-serve-closed64-p128-n384"]
NEW = {"host.pause_max_ms.train": ("ms", "program_span", "host process",
                                   "train.tokens_per_s", TRAIN_CELLS),
       "host.pause_max_ms.serve": ("ms", "program_span", "host process",
                                   "serve.request_p95_s", SERVE_CELLS),
       "train.period_max_over_median": ("x", "program_counter",
                                        "trainer gang", "train.tokens_per_s",
                                        TRAIN_CELLS),
       "batch.since_last_ms": ("ms", "program_counter", "serve batching",
                               "serve.request_p95_s", SERVE_CELLS),
       "batch.unfilled_flush_share": ("%", "program_counter",
                                      "serve batching",
                                      "serve.request_p95_s", SERVE_CELLS)}
WORKER, PROXY, OTHER, DRIVER = 7, 3, 9, 1
T0 = 1000.0                     # the window's start, epoch seconds

_ids = iter(range(1, 100_000))


def span(kind, ts, value, pid, ident="x", parent=None, node="n0", **attrs):
    return {"node_id": node, "pid": pid, "ts": ts, "kind": kind,
            "ident": ident, "value": value,
            "attrs": {"span": f"h{next(_ids)}", "parent": parent, **attrs}}


def pause(ts, value, pid, **attrs):
    return span("host.pause", ts, value, pid, ident=f"pid:{pid}",
                cpu_s=0.0, gc_s=0.0, runq_s=0.0, majflt=0, nivcsw=0, **attrs)


def watches(pid, lo, hi, node="n0", **attrs):
    return [span("host.watch", lo + i, 1.0, pid, ident=f"pid:{pid}",
                 node=node, **{"ticks": 99, "late": 0, "pause_max_s": 0.0,
                               **attrs})
            for i in range(int(hi - lo) + 1)]


def read(name, record, cell):
    return MF.reader(name)(record, cell)


@pytest.fixture()
def session(monkeypatch, tmp_path):
    monkeypatch.setattr(spans_mod, "CHECKOUT", str(tmp_path))
    monkeypatch.setattr(spans_mod, "trace_dir",
                        lambda: str(tmp_path / "no-trace"))
    spans_mod._summarised.clear()
    yield events.keep_session
    events.keep_session([])
    spans_mod._summarised.clear()


def only(monkeypatch, name):
    entry = next(m for m in MF.data["per_layer"] if m["name"] == name)
    monkeypatch.setattr(MF, "metrics", lambda kind, cell_name: [entry])


def not_read(capsys, name):
    said = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith(f"[bench] metric {name} not read: ")]
    assert len(said) == 1, said
    return said[0]


# ----------------------------------------------------------------------
# training
# ----------------------------------------------------------------------
PERIODS = [0.5, 0.5, 0.5, 0.9, 0.5, 0.7, 0.5]


def train_record():
    """Eight steps. Step i's loss is on the host at ``ready[i]`` and its
    report begins 1 ms later; the profiler starts inside step 6's period
    (the one of 0.7 s) and stops after the window."""
    ready, t = [], 0.49
    for p in [0.0] + PERIODS:
        t += p
        ready.append(t)
    steps = [[r - 0.49, r, 11.0] for r in ready]
    return {"window_start": T0,
            "window": {"steps": steps, "tokens_per_step": 16384,
                       "profiler": [[ready[6] - 0.3, ready[6] - 0.1],
                                    [ready[-1] + 1.0, ready[-1] + 3.0]]},
            "facts": {"kind": "TPU v5 lite", "platform": "tpu"}}


def train_spans(period=True):
    r = train_record()
    loop = span("train.loop", T0 - 50.0, 60.0, WORKER, rank=0)
    other = span("train.loop", T0 - 50.0, 60.0, OTHER, rank=1)
    out = [loop, other]
    began = [T0 + s[1] + 0.001 for s in r["window"]["steps"]]
    # the last warm-up step's report, 2.0 s before the window's first (the
    # batch placed and the checks made in between), and the window's
    for i, ts in enumerate([began[0] - 2.0] + began):
        attrs = {"iteration": i + 1}
        if period and i:
            attrs["period_s"] = ts - ([began[0] - 2.0] + began)[i - 1]
        out.append(span("train.report", ts, 0.00003, WORKER,
                        parent=loop["attrs"]["span"], **attrs))
    # another rank's, twice as uneven: not rank 0's, not read
    out += [span("train.report", T0 + 1.0 + i, 0.00003, OTHER,
                 parent=other["attrs"]["span"], iteration=i,
                 period_s=1.0 + i) for i in range(3)]
    return out


def test_the_period_is_the_longest_step_over_the_median(session, capsys):
    session(train_spans())
    # the first report of the window (its period began before it: 2.0 s),
    # step 6's (0.7 s, the profiler's start inside it) and the last step's
    # (it begins after the last loss) are left out: 0.5, 0.5, 0.5, 0.9, 0.5
    assert read("train.period_max_over_median", train_record(), TRAIN) == \
        pytest.approx(0.9 / 0.5)
    assert "train.period_max_over_median: 1 of 6 steps overlap the " \
        "profiler's start or stop and are left out" in capsys.readouterr().err


def test_the_parent_records_no_period_and_is_told_so(
        session, monkeypatch, capsys):
    only(monkeypatch, "train.period_max_over_median")
    session(train_spans(period=False))
    assert MF.read_metrics("per_layer", TRAIN, train_record()) == {}
    assert "train.report x" in not_read(capsys,
                                        "train.period_max_over_median")


def test_the_longest_pause_of_the_trainers_process(session, capsys):
    r = train_record()
    hi = T0 + r["window"]["steps"][-1][1]
    spans = train_spans() + watches(WORKER, T0 - 3, hi + 3) \
        + watches(OTHER, T0 - 3, hi + 3) + [
        pause(T0 + 0.2, 0.03, WORKER),
        pause(T0 + 1.2, 0.12, WORKER, skipped=2, skipped_s=0.05),
        pause(T0 - 5.0, 0.9, WORKER),               # before the window
        pause(T0 + 2.0, 0.5, PROXY),                # no trainer's process
        pause(hi - 0.05, 0.2, OTHER)]               # begins in, ends after
    session(spans)
    assert read("host.pause_max_ms.train", r, TRAIN) == pytest.approx(200.0)
    assert "the longest 0.2000s" in capsys.readouterr().err
    # one of 2.5 s over the profiler's start: the profiler's own, left out
    start = T0 + r["window"]["profiler"][0][0]
    session(spans + [pause(start - 0.1, 2.5, WORKER)])
    assert read("host.pause_max_ms.train", r, TRAIN) == pytest.approx(200.0)
    assert "host.pause_max_ms.train: 1 of 4 pauses overlap the profiler's " \
        "start or stop and are left out" in capsys.readouterr().err


def test_a_pause_every_process_shares_is_the_machines_not_the_profilers(
        session, capsys):
    """Over the profiler's start, but the driver stood still over the same
    instant: a profiler stops its own process and no other."""
    r = train_record()
    hi = T0 + r["window"]["steps"][-1][1]
    start = T0 + r["window"]["profiler"][0][0]
    spans = train_spans() + watches(WORKER, T0 - 3, hi + 3) \
        + watches(OTHER, T0 - 3, hi + 3)
    session(spans + [pause(start + 0.01, 0.104, WORKER),
                     pause(start + 0.011, 0.105, DRIVER),
                     pause(start + 0.15, 0.9, OTHER)])
    assert read("host.pause_max_ms.train", r, TRAIN) == pytest.approx(104.0)
    assert "1 of 2 pauses overlap the profiler's" in capsys.readouterr().err
    # on another host it is no neighbour
    far = pause(start + 0.011, 0.105, DRIVER)
    far["node_id"] = "n1"
    session(spans + [pause(start + 0.01, 0.104, WORKER), far])
    assert read("host.pause_max_ms.train", r, TRAIN) == 0.0


def test_every_pause_the_profilers_reads_zero_and_says_of_how_much(
        session, monkeypatch, capsys):
    """A watched process is never left out of the line (the driver holds a
    traced run's line to every metric of its cell: the four-chip cell's
    profiler took 26 s of a window of 45 to stop): 0 is then of the window
    outside the profiler's intervals, and stderr says how much that is."""
    r = train_record()
    hi = T0 + r["window"]["steps"][-1][1]
    start = T0 + r["window"]["profiler"][0][0]
    spans = train_spans() + watches(WORKER, T0 - 3, hi + 3) \
        + watches(OTHER, T0 - 3, hi + 3) + [pause(start - 0.1, 2.5, WORKER)]
    session(spans)
    assert read("host.pause_max_ms.train", r, TRAIN) == 0.0   # 0.2 s of 4
    assert "cover 4 % of the window" in capsys.readouterr().err
    r["window"]["profiler"][0] = [0.5, 3.5]                  # 3.0 s of 4.59
    only(monkeypatch, "host.pause_max_ms.train")
    assert MF.read_metrics("per_layer", TRAIN, r) == {
        "host.pause_max_ms.train": {"value": 0.0, "unit": "ms"}}
    assert "all 1 pauses are left out and the profiler's intervals cover " \
        "65 % of the window in every watched process: 0 is of the rest" in \
        capsys.readouterr().err


def test_a_flood_of_pauses_is_read_from_the_seconds_own_count(
        session, capsys):
    """Over 20 a second only the count and the longest are kept."""
    r = train_record()
    hi = T0 + r["window"]["steps"][-1][1]
    session(train_spans() + watches(OTHER, T0 - 3, hi + 3)
            + watches(WORKER, T0 - 3, T0 + 0.5)
            + watches(WORKER, T0 + 1.0, T0 + 1.5, late=31, pause_max_s=0.3)
            + watches(WORKER, T0 + 2.0, hi + 3)
            + [pause(T0 + 1.2, 0.03, WORKER),
               pause(T0 + 2.2, 0.05, WORKER)])
    assert read("host.pause_max_ms.train", r, TRAIN) == pytest.approx(300.0)
    assert "counts 31 late wakes, the longest 0.3000s" in \
        capsys.readouterr().err


def test_a_watched_window_without_a_pause_reads_zero(session):
    r = train_record()
    hi = T0 + r["window"]["steps"][-1][1]
    session(train_spans() + watches(WORKER, T0 - 3, hi + 3)
            + watches(OTHER, T0 - 3, hi + 3))
    assert read("host.pause_max_ms.train", r, TRAIN) == 0.0


def test_without_host_watch_the_pause_metric_is_left_out_and_named(
        session, monkeypatch, capsys):
    """The parent: no ticker, so no ``host.watch``, and a window without a
    pause must not read 0."""
    only(monkeypatch, "host.pause_max_ms.train")
    session(train_spans())
    assert MF.read_metrics("per_layer", TRAIN, train_record()) == {}
    assert "the session holds no host.watch" in \
        not_read(capsys, "host.pause_max_ms.train")
    # watched, but not the process that trains: left out too
    r = train_record()
    session(train_spans() + watches(PROXY, T0 - 3, T0 + 9))
    assert read("host.pause_max_ms.train", r, TRAIN) is None
    assert "no host.watch over the window in" in capsys.readouterr().err


# ----------------------------------------------------------------------
# serving
# ----------------------------------------------------------------------
def serve_record():
    """Eight calls of 1.0 s, 0.2 s apart but for one gap of 0.5 s; the
    profiler starts in the gap before the 6th call."""
    return {"window_start": T0,
            "window": {"rows": [{"rid": i, "send": T0 + 0.01, "ok": True,
                                 "first": T0 + 9.9, "last": T0 + 9.9}
                                for i in range(4)]},
            "profiler": [[T0 + 6.25, T0 + 6.35], [T0 + 20.0, T0 + 22.0]],
            "facts": {"kind": "TPU v5 lite", "platform": "tpu"}}


GAPS = [None, 0.2, 0.2, 0.5, 0.2, 0.2, 0.3, 0.2]
ROWS = [64, 64, 61, 64, 64, 64, 3, 64]
CAUSES = ["full", "full", "window", "full", "full", "full", "after_running",
          "full"]


def serve_spans(cause=True, proxy_node="n0"):
    out, ts = [], T0 + 0.1
    for i, gap in enumerate(GAPS):
        ts += (gap or 0.0)
        attrs = {"rows": ROWS[i], "max_batch_size": 64, "window_s": 0.1,
                 "oldest_wait_s": 0.11}
        if cause:
            attrs.update(cause=CAUSES[i], newest_wait_s=0.004,
                         left_pending=1 if i == 6 else 0)
            if gap is not None:
                attrs["since_last_s"] = gap
        out.append(span("serve.batch.flush", ts, 1.0, WORKER, **attrs))
        ts += 1.0
    out += [span("serve.request", T0 + 0.02 + i, 0.9, PROXY, ident=f"r{i}",
                 node=proxy_node, code=200) for i in range(4)]
    # another replica that flushed less: not the one that is read
    out.append(span("serve.batch.flush", T0 + 1.0, 0.1, OTHER, rows=1,
                    max_batch_size=64, cause="window", since_last_s=9.0))
    return out


def test_the_gap_between_two_calls_as_the_replica_saw_it(session, capsys):
    session(serve_spans())
    # 0.2, 0.2, 0.5, 0.2, 0.3, 0.2: the sixth call's gap (0.2, the
    # profiler started inside it) is left out
    assert read("batch.since_last_ms", serve_record(), SERVE) == \
        pytest.approx(200.0)
    assert "batch.since_last_ms: 1 of 7 gaps overlap the profiler's start " \
        "or stop and are left out" in capsys.readouterr().err


def test_the_share_of_flushes_that_went_unfilled_and_why(session, capsys):
    session(serve_spans())
    assert read("batch.unfilled_flush_share", serve_record(), SERVE) == \
        pytest.approx(100.0 * 2 / 8)
    said = [ln for ln in capsys.readouterr().err.splitlines()
            if "batch.unfilled_flush_share: at " in ln]
    assert len(said) == 2
    assert "cause=window rows=61 newest_wait_s=0.004" in said[0]
    assert "cause=after_running rows=3" in said[1]
    assert "left_pending=1 since_last_s=0.3 of max_batch_size=64" in said[1]
    full = [s for s in serve_spans() if s["kind"] != "serve.batch.flush"
            or s["attrs"]["rows"] == 64]
    session(full)
    assert read("batch.unfilled_flush_share", serve_record(), SERVE) == 0.0


@pytest.mark.parametrize("name", ["batch.since_last_ms",
                                  "batch.unfilled_flush_share"])
def test_the_parents_flushes_say_no_cause_and_are_not_read(
        session, monkeypatch, capsys, name):
    only(monkeypatch, name)
    session(serve_spans(cause=False))
    assert MF.read_metrics("per_layer", SERVE, serve_record()) == {}
    assert "serve.batch.flush x" in not_read(capsys, name)


def test_the_longest_pause_of_the_replica_and_the_proxy(session, capsys):
    r = serve_record()
    both = watches(WORKER, T0 - 2, T0 + 12) + watches(PROXY, T0 - 2, T0 + 12)
    session(serve_spans() + both + [
        pause(T0 + 3.0, 0.104, WORKER), pause(T0 + 4.0, 0.31, PROXY),
        pause(T0 + 5.0, 0.8, OTHER), pause(T0 + 6.0, 1.4, WORKER)])
    # 1.4 s over the profiler's start is the profiler's; the replica that
    # flushed less is not the cell's
    assert read("host.pause_max_ms.serve", r, SERVE) == pytest.approx(310.0)
    assert "1 of 3 pauses overlap the profiler's" in capsys.readouterr().err
    # the proxy runs no profiler: its pause over the profiler's stop counts
    session(serve_spans() + both + [pause(T0 + 6.3, 0.2, PROXY),
                                    pause(T0 + 6.0, 1.4, WORKER)])
    assert read("host.pause_max_ms.serve", r, SERVE) == pytest.approx(200.0)
    # all the replica's left out: the proxy, watched all along, says quiet
    session(serve_spans() + both + [pause(T0 + 6.0, 1.4, WORKER)])
    assert read("host.pause_max_ms.serve", r, SERVE) == 0.0
    session(serve_spans() + both)
    assert read("host.pause_max_ms.serve", r, SERVE) == 0.0
    # the proxy unwatched: nothing says its window was quiet
    session(serve_spans() + watches(WORKER, T0 - 2, T0 + 12))
    assert read("host.pause_max_ms.serve", r, SERVE) is None


def test_a_flush_and_a_proxy_on_two_hosts_are_refused(session):
    session(serve_spans(proxy_node="n1") + watches(WORKER, T0 - 2, T0 + 12)
            + watches(PROXY, T0 - 2, T0 + 12, node="n1"))
    with pytest.raises(Exception, match="skewed by the two hosts") as e:
        read("host.pause_max_ms.serve", serve_record(), SERVE)
    assert type(e.value).__name__ == "MetricFault"


def test_without_host_watch_the_serving_metric_is_left_out_and_named(
        session, monkeypatch, capsys):
    only(monkeypatch, "host.pause_max_ms.serve")
    session(serve_spans())
    assert MF.read_metrics("per_layer", SERVE, serve_record()) == {}
    assert "the session holds no host.watch" in \
        not_read(capsys, "host.pause_max_ms.serve")


# ----------------------------------------------------------------------
# the manifest
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_entry_says_what_its_file_says(name):
    unit, source, layer, moves, cells = NEW[name]
    entry = next(m for m in manifest_data()["per_layer"]
                 if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": cells}
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        beside = json.load(f)
    assert beside.pop("kind") == "per_layer"
    assert len(beside.pop("definition")) > 80
    assert beside == entry
    needs = MF.reader_module(name).NEEDS
    assert "host.pause" not in needs        # not there in a quiet run
    assert set(needs) <= {"train.report", "serve.batch.flush",
                          "serve.request", "host.watch"}


def test_the_five_are_appended_and_every_cell_reports_its_own():
    data = manifest_data()
    assert [m["name"] for m in data["per_layer"][-5:]] == [
        "host.pause_max_ms.train", "host.pause_max_ms.serve",
        "train.period_max_over_median", "batch.since_last_ms",
        "batch.unfilled_flush_share"]
    for cell in TRAIN_CELLS + SERVE_CELLS:
        reported = {m["name"] for m in MF.metrics("per_layer", cell)}
        want = {n for n, v in NEW.items() if cell in v[4]}
        assert reported & set(NEW) == want and len(want) in (2, 3)
        needs = MF.span_needs(cell)
        assert "host.watch" in needs
    with open(os.path.join(os.path.dirname(BENCH), "PERF.md")) as f:
        assert "| host process |" in f.read()


FALCON = "falconh1-serve-closed64-p128-n384"
FALCON_METRICS = ("generate_roofline.falcon_h1", "ssd.step_roofline",
                  "ssd.call_share", "ssd.cache_share", "falcon_h1.call_s",
                  "falcon_h1.prefill_share", "falcon_h1.rows_share")


def test_the_metrics_before_these_are_still_in_their_places():
    """Every assertion of the two tests of ``test_bench_falcon_h1.py`` that
    ``tests/conftest.py`` marks ``xfail(strict)`` since this PR (they hold
    that cell's seven metrics to the LAST seven places of ``per_layer`` and
    every other metric off that cell's and the olmo_hybrid cell's lists),
    with the places counted five before the end and the five metrics of all
    cells set aside."""
    import test_bench_falcon_h1 as falcon
    assert tuple(falcon.NEW_METRICS) == FALCON_METRICS
    data = manifest_data()
    olmo = "olmohybrid-serve-closed48-p128-n384"
    assert data["workloads"][-1]["name"] == FALCON
    assert data["configs"][-1]["name"] == "falcon-h1-34b-l9"
    assert len(data["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    for m in data["end_to_end"]:
        if m["name"].startswith("serve."):
            assert m["workloads"][-1] == FALCON, m["name"]
        elif "workloads" in m:
            assert FALCON not in m["workloads"]
    assert [w["name"] for w in data["workloads"]][-2] == olmo
    assert data["configs"][-2]["name"] == "olmo-hybrid-7b-l20"
    dots3 = "dots3-serve-closed2-p32k-n128"
    before = ["mistral7b-serve-closed32", "ouro2.6b-serve-closed16", dots3]
    for m in data["end_to_end"]:
        if m["name"] == "serve.tokens_per_s":
            assert m["workloads"] == before + [FALCON]
        elif m["name"].startswith("serve."):
            assert m["workloads"] == before + [olmo, FALCON]
        elif "workloads" in m:
            assert olmo not in m["workloads"]
    reported = {m["name"] for m in MF.metrics("end_to_end", olmo)}
    assert reported == {"serve.request_p95_s", "serve.ttft_p95_s", "setup_s"}
    assert {m["moves"] for m in data["per_layer"]
            if m["name"] in falcon.OLMO_METRICS} <= reported
    cell = MF.cell(dots3)
    want = {"app": "serve_dots3", "clients": 2, "prompt_tokens": 32768,
            "new_tokens": 128, "max_batch_size": 2,
            "batch_wait_timeout_s": 0.1, "max_ongoing_requests": 2,
            "request_timeout_s": 60.0}
    assert {k: cell["traffic_data"][k] for k in want} == want
    assert (cell["chips"], cell["traffic"]) == \
        (1, "serve-closed2-p32768-n128")
    reported = {m["name"] for m in MF.metrics("end_to_end", FALCON)}
    assert reported == {"serve.tokens_per_s", "serve.request_p95_s",
                        "serve.ttft_p95_s", "setup_s"}
    assert {m["moves"] for m in data["per_layer"]
            if m["name"] in FALCON_METRICS} <= reported
    assert [m["name"] for m in data["per_layer"][-12:-5]] == \
        list(FALCON_METRICS)
    assert [m["name"] for m in data["per_layer"][-18:-12]] == \
        list(falcon.OLMO_METRICS)
    for m in data["per_layer"]:
        for cell, own in ((FALCON, FALCON_METRICS),
                          (olmo, falcon.OLMO_METRICS)):
            if m["name"] in own:
                assert m["workloads"] == [cell]
                with open(os.path.join(BENCH, "metrics",
                                       m["name"] + ".json")) as f:
                    beside = json.load(f)
                assert {k: beside[k] for k in m} == m
                assert len(beside["definition"]) > 80
            elif m["name"] not in NEW:
                assert cell not in m.get("workloads", ())
    for cell, own in ((FALCON, FALCON_METRICS), (olmo, falcon.OLMO_METRICS)):
        assert [m["name"] for m in MF.metrics("per_layer", cell)
                if m["name"] in own] == list(own)

"""``benchmark/spans.py`` and the readers built on the runtime's spans, on
synthetic span lists with hand-worked answers, and the trace's clock on the
recorded v5e trace."""

import json
import os
import shutil
import time

import pytest

from bench_paths import BENCH, CHECKOUT
from benchmark import spans as spans_mod
from benchmark import trace as trace_mod
from benchmark.manifest import Manifest
from ray_tpu.util import events

MF = Manifest()
TRAIN = MF.cell("mistral7b-train-1chip")
SERVE = MF.cell("mistral7b-serve-closed32")
RECORDED = os.path.join(BENCH, "testdata", "train-v5e-3steps.xplane.pb")
# from the recorded trace: its origin, and its two long idle gaps (the
# loss reaching the host and the next dispatch), seconds from the origin
ORIGIN = 1790466830.582950146
LONG_IDLE = ((0.5486061, 0.5531486), (1.0544838, 1.0589895))
IDLE_TOTAL = 0.00905752
NEW = ["ingress.admit_wait_ms", "ingress.thread_wait_ms",
       "ingress.slot_wait_ms", "ingress.call_overhead_ms",
       "batch.flush_wait_ms", "batch.rows_share", "batch.gap_ms",
       "batch.gap_idle_share", "batch.call_max_over_median",
       "init.probe_s", "lease.spawn_s", "trainer.start_s",
       "trainer.report_ms"]

_ids = iter(range(1, 10_000))


def span(kind, ts, value, ident="x", parent=None, pid=1, **attrs):
    return {"node_id": "n0", "pid": pid, "ts": ts, "kind": kind,
            "ident": ident, "value": value,
            "attrs": {"span": f"s{next(_ids)}", "parent": parent, **attrs}}


def read(name, record, cell):
    return MF.reader(name)(record, cell)


@pytest.fixture()
def session(monkeypatch, tmp_path):
    """Put a span list where ``rt.shutdown()`` leaves a run's; no summary
    is written into the checkout, and no trace is found unless a test
    points ``trace_dir`` at one."""
    monkeypatch.setattr(spans_mod, "CHECKOUT", str(tmp_path))
    monkeypatch.setattr(spans_mod, "trace_dir",
                        lambda: str(tmp_path / "no-trace"))
    spans_mod._summarised.clear()
    yield events.keep_session
    events.keep_session([])
    spans_mod._summarised.clear()


def request(ident, ts, admit, thread, slot, call, replica, wait):
    """One request's chain; the durations are the arguments."""
    req = span("serve.request", ts, call + thread + 0.05, ident, code=200)
    rid = req["attrs"]["span"]
    hc = span("serve.handle.call", ts + admit + thread + slot, call, ident,
              rid, retries=0)
    rc = span("serve.replica.call", hc["ts"] + 0.004, replica, ident,
              hc["attrs"]["span"], pid=7, inflight=17)
    return [req,
            span("serve.proxy.admit", ts, admit, ident, rid),
            span("serve.proxy.thread_wait", ts + admit, thread, ident, rid),
            span("serve.handle.slot_wait", ts + admit + thread, slot, ident,
                 rid), hc, rc,
            span("serve.batch.wait", rc["ts"] + 0.001, wait, ident,
                 rc["attrs"]["span"], pid=7, flush="f")]


def serve_record():
    # the window runs from 100 to the last reply at 130
    return {"window_start": 100.0, "facts": {"platform": "tpu"},
            "window": {"rows": [{"last": 110.0}, {"last": 130.0},
                                {"ok": False}]}}


def serve_spans():
    out = []
    out += request("warmup", 90.0, 0.5, 0.5, 0.5, 1.0, 0.5, 0.5)
    out += request("a", 101.0, 0.001, 4.0, 0.004, 5.0, 4.99, 0.08)
    out += request("b", 102.0, 0.002, 5.0, 0.006, 5.2, 5.18, 0.09)
    out += request("c", 103.0, 0.003, 0.1, 0.005, 5.1, 5.07, 0.10)
    out += request("late", 131.0, 9.0, 9.0, 9.0, 9.0, 1.0, 9.0)
    flush = dict(pid=7, max_batch_size=32, window_s=0.1)
    out += [span("serve.batch.flush", 95.0, 4.5, "f0", rows=32, **flush),
            span("serve.batch.flush", 100.5, 4.5, "f1", rows=17, **flush),
            span("serve.batch.flush", 105.15, 4.5, "f2", rows=15, **flush),
            span("serve.batch.flush", 109.8, 9.0, "f3", rows=16, **flush),
            # another replica that flushed once: not the one that is read
            span("serve.batch.flush", 104.0, 1.0, "g", pid=8, rows=1,
                 max_batch_size=32, window_s=0.1)]
    return out


def test_serve_readers_on_hand_made_spans(session):
    session(serve_spans())
    r = serve_record()
    # medians over requests a, b, c: the warm-up and the late one are
    # outside the window
    assert read("ingress.admit_wait_ms", r, SERVE) == pytest.approx(2.0)
    assert read("ingress.thread_wait_ms", r, SERVE) == pytest.approx(4000.0)
    assert read("ingress.slot_wait_ms", r, SERVE) == pytest.approx(5.0)
    # 5.0 - 4.99, 5.2 - 5.18, 5.1 - 5.07 = 10, 20, 30 ms
    assert read("ingress.call_overhead_ms", r, SERVE) == pytest.approx(20.0)
    assert read("batch.flush_wait_ms", r, SERVE) == pytest.approx(90.0)
    # flushes f1, f2, f3 of replica 7: (17 + 15 + 16) / (3 * 32)
    assert read("batch.rows_share", r, SERVE) == pytest.approx(50.0)
    # f1 ends at 105.0, f2 starts at 105.15 and ends at 109.65, f3 at 109.8
    assert read("batch.gap_ms", r, SERVE) == pytest.approx(150.0)
    assert read("batch.call_max_over_median", r, SERVE) == \
        pytest.approx(2.0)
    assert read("batch.gap_idle_share", r, SERVE) is None     # no trace


def train_record():
    # the window runs from 200 to the last loss, 10 s later
    return {"window_start": 200.0, "facts": {"platform": "tpu"},
            "window": {"steps": [[0.0, 0.5, 11.0], [9.5, 10.0, 10.9]]}}


def train_spans():
    fit = span("train.fit", 150.0, 70.0, "fit")
    fid = fit["attrs"]["span"]
    loop0 = span("train.loop", 156.5, 60.0, "fit", fid, pid=5, rank=0)
    grant = span("lease.grant", 150.2, 6.0, "fit", fid, TPU=1)
    spawn = span("worker.spawn", 150.3, 1.25, "fit", grant["attrs"]["span"],
                 chips=1)
    lid = loop0["attrs"]["span"]
    return [span("init", 120.0, 20.0, "i"),
            span("init.probe", 121.0, 12.5, "i", chips=1, platform="tpu"),
            fit, grant, spawn,
            span("worker.spawn", 140.0, 0.2, "other", chips=0),
            span("worker.boot", 150.9, 0.6, "fit", spawn["attrs"]["span"],
                 pid=5),
            loop0, span("train.loop", 157.0, 60.0, "fit", fid, pid=6, rank=1),
            # an earlier fit() of this process: not the one that is read
            span("train.loop", 50.0, 1.0, "old", pid=4, rank=0),
            span("train.report", 190.0, 0.5, "fit", lid, iteration=1),
            span("train.report", 201.0, 0.00002, "fit", lid, iteration=2),
            span("train.report", 202.0, 0.00004, "fit", lid, iteration=3),
            span("train.report", 203.0, 0.00003, "fit", lid, iteration=4),
            span("train.report", 211.0, 0.7, "fit", lid, iteration=5)]


def test_train_and_setup_readers_on_hand_made_spans(session):
    session(train_spans())
    r = train_record()
    assert read("init.probe_s", r, TRAIN) == 12.5
    assert read("lease.spawn_s", r, TRAIN) == 1.25     # the one with chips
    assert read("trainer.start_s", r, TRAIN) == pytest.approx(6.5)
    assert read("trainer.report_ms", r, TRAIN) == pytest.approx(0.03)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_nothing_without_spans(session, name):
    """The parent of the PR that added the spans, or a runtime that kept
    none: the metric is left out of the line, nothing raises."""
    session([])
    cell = SERVE if name.startswith(("ingress.", "batch.")) else TRAIN
    record = serve_record() if cell is SERVE else train_record()
    assert read(name, record, cell) is None
    # spans of other layers only
    session([span("init", 1.0, 2.0)])
    assert read(name, record, cell) is None


def test_readers_survive_a_program_without_last_session(session,
                                                        monkeypatch):
    monkeypatch.delattr(events, "last_session")
    assert spans_mod.session() is None
    assert read("init.probe_s", train_record(), TRAIN) is None


def test_a_rehearsal_gets_the_summary_and_no_metric(session, tmp_path):
    session(train_spans())
    record = train_record()
    record["facts"] = {"platform": "cpu", "kind": "cpu"}
    assert read("trainer.start_s", record, TRAIN) is None
    assert read("init.probe_s", record, TRAIN) is None
    assert os.path.exists(tmp_path / "benchmark" / "out" /
                          f"spans-{TRAIN['name']}.json")


@pytest.fixture()
def recorded(tmp_path):
    """The recorded v5e trace where a run's profiler would have put it."""
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "host.xplane.pb")
    return str(tmp_path / "trace")


def test_device_clock_and_idle_on_the_recorded_trace(recorded, tmp_path):
    assert spans_mod.device_clock(recorded) == pytest.approx(ORIGIN,
                                                             abs=1e-6)
    (lo, hi), busy = spans_mod.device_window(recorded)
    # what benchmark.trace reduces the same file to
    reduced = trace_mod.reduce_file(trace_mod.find_xplane(recorded))
    assert hi - lo == pytest.approx(reduced["window_s"])
    assert trace_mod.total(busy) == pytest.approx(reduced["busy_s"])
    assert lo == pytest.approx(0.047299, abs=1e-6)   # first device event
    idle = spans_mod.device_idle(recorded)
    assert trace_mod.total(idle) == pytest.approx(IDLE_TOTAL, rel=1e-3)
    assert trace_mod.total(idle) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-3)
    longest = sorted(idle, key=lambda i: i[0] - i[1])[:2]
    for (a, b), (c, d) in zip(sorted(longest), LONG_IDLE):
        assert a - ORIGIN == pytest.approx(c, abs=2e-6)
        assert b - ORIGIN == pytest.approx(d, abs=2e-6)
    assert ORIGIN + lo <= idle[0][0] and idle[-1][1] <= ORIGIN + hi + 1e-6
    # no trace: nothing
    empty = str(tmp_path / "none")
    assert spans_mod.device_clock(empty) is None
    assert spans_mod.device_idle(empty) is None
    assert spans_mod.runtime_events(empty) == []
    # the recorded program had no rt.* spans, and its kernels no names
    assert spans_mod.runtime_events(recorded) == []
    names = spans_mod.device_names(recorded)
    assert "closed_call.9" in names["mosaic_instructions"]
    assert names["events_naming_in_text"] == {"rt_flash": 0,
                                              "rt.generate": 0}


def test_gap_idle_share_and_idle_by_span(session, recorded, monkeypatch):
    """Flushes laid over the recorded trace so that the gap between the
    first two covers the first long idle interval and nothing covers the
    second: 4.5425 of 9.0575 ms."""
    monkeypatch.setattr(spans_mod, "trace_dir", lambda: recorded)
    (a, b), (c, d) = LONG_IDLE
    flush = dict(pid=7, rows=16, max_batch_size=32, window_s=0.1)
    spans = [
        span("serve.batch.flush", ORIGIN + 0.10, a - 0.0001 - 0.10, "f1",
             **flush),
        span("serve.batch.flush", ORIGIN + b + 0.0001, 0.3, "f2", **flush),
        span("serve.batch.flush", ORIGIN + b + 0.3001, 0.1, "f3", **flush),
        # what the host was doing in the first long gap, innermost first
        span("serve.batch.reply", ORIGIN + a, 0.001, "f1"),
        span("serve.request", ORIGIN + a - 0.2, 0.5, "r"),
    ]
    session(spans)
    record = {"window_start": ORIGIN, "facts": {"platform": "tpu"},
              "window": {"rows": [{"last": ORIGIN + 2.0}]}}
    share = read("batch.gap_idle_share", record, SERVE)
    assert share == pytest.approx(100 * (b - a) / IDLE_TOTAL, rel=2e-3)
    assert share == pytest.approx(50.15, abs=0.2)
    idle = spans_mod.device_idle(recorded)
    shares = spans_mod.idle_by_span(idle, spans)
    # 1 ms of the first gap under the reply, its other 3.5425 ms under the
    # request; the second gap under no span; the rest is microseconds
    assert shares["serve.batch.reply"] == pytest.approx(0.001, abs=2e-6)
    assert shares["serve.request"] == pytest.approx(b - a - 0.001, abs=2e-5)
    assert shares["no runtime span"] == pytest.approx(d - c, abs=2e-5)
    assert sum(shares.values()) == pytest.approx(trace_mod.total(idle))


def test_summary_self_times_tree_and_file(session, tmp_path):
    spans = train_spans()
    session(spans)
    assert read("trainer.start_s", train_record(), TRAIN) is not None
    with open(tmp_path / "benchmark" / "out" /
              f"spans-{TRAIN['name']}.json") as f:
        out = json.load(f)
    assert out["spans"] == len(spans) and out["in_window"] == 3
    row = out["by_kind"]["train.report"]
    assert row["count"] == 3
    assert row["median_s"] == pytest.approx(0.00003)
    assert row["p95_s"] == pytest.approx(0.00004)
    assert row["self_s"] == pytest.approx(0.00009)
    assert row["attr_max"] == {"iteration": 4}
    tree = out["setup_tree"]
    assert [ln.split()[0] for ln in tree] == [
        "train.loop", "init", "init.probe", "worker.spawn", "train.fit",
        "lease.grant", "worker.spawn", "worker.boot", "train.loop",
        "train.loop"]
    assert tree[4].startswith("train.fit +100.000s 70.000s")
    assert tree[5].startswith("  lease.grant") and "TPU=1" in tree[5]
    assert tree[7].startswith("      worker.boot")
    # self time: a span's seconds minus what its children cover
    selfs = spans_mod.self_times(spans)
    grant = next(s for s in spans if s["kind"] == "lease.grant")
    assert selfs[grant["attrs"]["span"]] == pytest.approx(6.0 - 1.25)
    fit = next(s for s in spans if s["kind"] == "train.fit")
    # children: the grant 150.2-156.2, the loops 156.5-216.5 and 157-217
    assert selfs[fit["attrs"]["span"]] == pytest.approx(
        70.0 - 6.0 - (217.0 - 156.5))
    # written once a run: a second reader does not write it again
    os.remove(tmp_path / "benchmark" / "out" /
              f"spans-{TRAIN['name']}.json")
    assert read("trainer.report_ms", train_record(), TRAIN) is not None
    assert not os.path.exists(tmp_path / "benchmark" / "out" /
                              f"spans-{TRAIN['name']}.json")


def test_clock_residual_on_a_fresh_trace(tmp_path):
    """Spans recorded under a profiler session on the CPU: the trace's
    ``rt.*`` events, laid on the epoch by ``device_clock``, start where the
    ring records say, to within a millisecond."""
    import jax
    events.reset_for_tests()
    directory = str(tmp_path / "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        for _ in range(4):
            with events.span("test.traced"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    ring = [{"ts": e[0], "kind": e[1], "value": e[3]}
            for e in events.snapshot()]
    events.reset_for_tests()
    found = spans_mod.runtime_events(directory)
    assert [k for k, _, _ in found] == ["test.traced"] * 4
    residuals = spans_mod.clock_residuals(directory, ring)
    assert len(residuals) == 4 and max(residuals) < 1e-3
    assert spans_mod.device_idle(directory) is None      # no TPU plane


def test_checkout_constant():
    assert spans_mod.CHECKOUT == CHECKOUT


# ----------------------------------------------------------------------
# a metric that is left out says so (PR 28)
# ----------------------------------------------------------------------
# metric -> the one span kind taken out of an otherwise whole session
LACKS = [("ingress.admit_wait_ms", "serve.proxy.admit"),
         ("ingress.thread_wait_ms", "serve.proxy.thread_wait"),
         ("ingress.slot_wait_ms", "serve.handle.slot_wait"),
         ("ingress.call_overhead_ms", "serve.replica.call"),
         ("batch.flush_wait_ms", "serve.batch.wait"),
         ("batch.rows_share", "serve.batch.flush"),
         ("batch.gap_ms", "serve.batch.flush"),
         ("batch.gap_idle_share", "serve.batch.flush"),
         ("batch.call_max_over_median", "serve.batch.flush"),
         ("init.probe_s", "init.probe"),
         ("lease.spawn_s", "worker.spawn"),
         ("trainer.start_s", "train.fit"),
         ("trainer.report_ms", "train.report")]


def only(monkeypatch, name):
    """The manifest as if ``name`` were the cell's one per-layer metric:
    ``read_metrics`` is the harness's own path from reader to line."""
    entry = next(m for m in MF.data["per_layer"] if m["name"] == name)
    monkeypatch.setattr(MF, "metrics", lambda kind, cell_name: [entry])
    cell = SERVE if name.startswith(("ingress.", "batch.")) else TRAIN
    whole = serve_spans() if cell is SERVE else train_spans()
    record = serve_record() if cell is SERVE else train_record()
    return cell, record, whole


def test_every_span_reader_says_which_kinds_it_reads():
    assert sorted(name for name, _ in LACKS) == sorted(NEW)
    for name, kind in LACKS:
        assert kind in MF.reader_module(name).NEEDS
    needs = MF.span_needs(TRAIN["name"])
    assert {"train.fit", "train.loop", "train.report", "init.probe",
            "worker.spawn"} <= set(needs)
    assert not any(k.startswith("serve.") for k in needs)
    assert "serve.batch.flush" in MF.span_needs(SERVE["name"])


@pytest.mark.parametrize("name,kind", LACKS)
def test_a_metric_whose_record_is_missing_is_named_with_the_kind(
        session, monkeypatch, capsys, name, kind):
    cell, record, whole = only(monkeypatch, name)
    session([s for s in whole if s["kind"] != kind])
    assert MF.read_metrics("per_layer", cell, record) == {}
    said = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith(f"[bench] metric {name} not read: ")]
    assert len(said) == 1, said
    assert f"the session holds no {kind}" in said[0]
    assert "it holds pid " in said[0]          # what the session does hold


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_gets_its_line_and_the_reason(
        session, monkeypatch, capsys, name):
    """PR 25's parent case: the metric is left out of the line as before;
    stderr now says why."""
    cell, record, _ = only(monkeypatch, name)
    session([])
    assert MF.read_metrics("per_layer", cell, record) == {}
    assert f"[bench] metric {name} not read: no session kept" in \
        capsys.readouterr().err


def test_a_metric_that_is_read_says_nothing(session, monkeypatch, capsys):
    cell, record, whole = only(monkeypatch, "trainer.report_ms")
    session(whole)
    line = MF.read_metrics("per_layer", cell, record)
    assert line == {"trainer.report_ms": {
        "value": pytest.approx(0.03), "unit": "ms"}}
    assert "not read" not in capsys.readouterr().err


def test_in_window_and_rehearsal_reasons(session, monkeypatch, capsys):
    cell, record, whole = only(monkeypatch, "trainer.report_ms")
    session([s for s in whole
             if s["kind"] != "train.report" or s["ts"] < 200.0])
    assert MF.read_metrics("per_layer", cell, record) == {}
    assert "train.report x1 (0 began inside the window)" in \
        capsys.readouterr().err
    session(whole)
    record["facts"] = {"platform": "cpu"}
    assert MF.read_metrics("per_layer", cell, record) == {}
    assert "not read: not a TPU run (platform 'cpu')" in \
        capsys.readouterr().err


def test_a_trace_metric_without_a_trace_says_so(monkeypatch, capsys):
    entry = next(m for m in MF.data["per_layer"]
                 if m["name"] == "flash_roofline")
    monkeypatch.setattr(MF, "metrics", lambda kind, cell_name: [entry])
    record = dict(train_record(), trace={})
    assert MF.read_metrics("per_layer", TRAIN, record) == {}
    assert "[bench] metric flash_roofline not read: no trace" in \
        capsys.readouterr().err


def test_trainer_start_falls_back_to_the_loops_own_stamp(
        session, monkeypatch, capsys):
    cell, record, whole = only(monkeypatch, "trainer.start_s")
    record["stamps"] = {"entry": 156.5001, "called": 150.0}
    # both records: the span's value, and not a word
    session(whole)
    line = MF.read_metrics("per_layer", cell, record)
    assert line["trainer.start_s"]["value"] == pytest.approx(6.5)
    err = capsys.readouterr().err
    assert "FALLBACK" not in err and "not read" not in err
    # the worker's tail died with it: train.fit and no train.loop of rank 0
    # with its ident (rank 1's, and an older fit's, do not stand in)
    session([s for s in whole if not (
        s["kind"] == "train.loop" and s["ident"] == "fit"
        and s["attrs"]["rank"] == 0)])
    line = MF.read_metrics("per_layer", cell, record)
    assert line["trainer.start_s"]["value"] == pytest.approx(6.5001)
    err = capsys.readouterr().err
    assert "[bench] metric trainer.start_s: FALLBACK to the loop's own " \
        "first-line stamp" in err
    assert "train.fit fit but no train.loop of rank 0" in err
    assert "not read" not in err
    # and a record without that stamp has nothing to fall back to
    del record["stamps"]
    assert MF.read_metrics("per_layer", cell, record) == {}
    assert "metric trainer.start_s not read" in capsys.readouterr().err


@pytest.fixture()
def conductor(monkeypatch):
    """What the conductor would answer ``state.list_spans()`` with the
    runtime up; the driver's own flush is counted, not made."""
    from ray_tpu.state import api as state
    held = {"records": [], "flushes": 0}

    def flush_now():
        held["flushes"] += 1
    monkeypatch.setattr(events, "flush_now", flush_now)
    monkeypatch.setattr(state, "list_spans", lambda: list(held["records"]))
    monkeypatch.setattr(state, "list_cluster_events", lambda: [
        {"severity": "INFO", "timestamp": 1.0, "event_type": "NODE_ADDED",
         "message": "node up"},
        {"severity": "WARNING", "timestamp": 160.25,
         "event_type": "NODE_DEAD",
         "message": "node 2106ba20 marked dead: health check timed out"}])
    yield held
    spans_mod._kept.clear()


def test_session_is_the_shutdown_read_plus_what_only_the_early_read_holds(
        session, conductor, capsys):
    whole = train_spans()
    loop0 = next(s for s in whole if s["kind"] == "train.loop"
                 and s["ident"] == "fit" and s["attrs"]["rank"] == 0)
    conductor["records"] = whole
    spans_mod.keep_before_teardown(["train.fit", "train.loop"])
    assert conductor["flushes"] == 1
    err = capsys.readouterr().err
    assert "before teardown the conductor holds 15 span records" in err
    assert "train.fit x1 ['fit']" in err and "NOT THERE" not in err
    # rt.shutdown()'s read lost one record and gained a later one
    late = span("train.pump", 212.0, 0.1, "fit")
    session([s for s in whole if s is not loop0] + [late])
    got = spans_mod.session()
    assert len(got) == 16 and got[-1] is loop0 and got[-2] is late
    assert read("trainer.start_s", train_record(), TRAIN) == \
        pytest.approx(6.5)
    # the same records twice are one session
    session(whole)
    assert len(spans_mod.session()) == 15


def test_the_early_read_waits_a_bounded_time_for_a_kind_and_says_so(
        session, conductor, capsys):
    conductor["records"] = [s for s in train_spans()
                            if s["kind"] != "train.loop"]
    t0 = time.monotonic()
    spans_mod.keep_before_teardown(["train.fit", "train.loop"], wait_s=0.3)
    assert 0.3 <= time.monotonic() - t0 < 2.0
    assert conductor["flushes"] >= 2
    err = capsys.readouterr().err
    assert "NOT THERE after 0.3s: train.loop; it holds pid 1: " in err
    # what the cluster saw die, and when; nothing of the ordinary
    assert "cluster event at 160.250 NODE_DEAD: node 2106ba20 marked " \
        "dead: health check timed out" in err
    assert "NODE_ADDED" not in err
    assert len(spans_mod._kept) == 12


def test_the_early_read_of_a_failing_conductor_keeps_nothing(
        session, conductor, monkeypatch, capsys):
    from ray_tpu.state import api as state

    def down():
        raise RuntimeError("conductor gone")
    monkeypatch.setattr(state, "list_spans", down)
    spans_mod.keep_before_teardown(["train.fit"])
    assert spans_mod._kept == []
    assert "could not be read before teardown" in capsys.readouterr().err

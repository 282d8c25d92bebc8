"""The metric readers (benchmark/metrics/*.py) on hand-made records."""

import math

import pytest

from bench_paths import CHECKOUT
from benchmark.manifest import Manifest

MF = Manifest()
TRAIN = MF.cell("mistral7b-train-1chip")
SERVE = MF.cell("mistral7b-serve-closed32")


def read(name, record, cell):
    return MF.reader(name)(record, cell)


def train_record():
    # 7 steps, each ready 0.498 s after its dispatch and the next one
    # dispatched 2 ms later, but for two: the profiler starts between step
    # 2's loss and step 3's dispatch (0.3 s) and stops after step 4's
    dispatched = [0.0, 0.5, 1.0, 1.8, 2.3, 3.1, 3.6]
    steps = [[d, d + 0.498, 11.0] for d in dispatched]
    return {"window": {"steps": steps, "tokens_per_step": 16384,
                       "profiler": [[1.499, 1.799], [2.799, 3.099]]},
            "facts": {"kind": "TPU v5 lite", "platform": "tpu"},
            "stamps": {"called": 10.0, "entry": 16.5}, "setup_s": 44.0,
            "trace": {"periods": 2, "window_s": 1.0, "busy_s": 0.99,
                      "mosaic_s": 0.0768, "mosaic_calls": 16,
                      "exposed_collective_s": 0.1}}


def test_train_readers():
    r = train_record()
    assert read("setup_s", r, TRAIN) == 44.0
    assert read("lease.worker_ready_s", r, TRAIN) == 6.5
    # every step and all of the time: 7 steps, last loss at 4.098 s
    assert read("train.tokens_per_s", r, TRAIN) == \
        pytest.approx(7 * 16384 / 4.098)
    # periods 0, 1, 3 and 5 are clean (0.5 - 0.498 = 2 ms); 2 and 4 touch
    # the profiler (302 ms of "host") and are left out
    assert read("trainer.host_ms_per_step", r, TRAIN) == pytest.approx(2.0)
    mfu = read("train.mfu", r, TRAIN)
    assert mfu == pytest.approx(
        100 * 3_523_215_360 * (16384 / 0.5) / 197e12)
    assert read("device.idle_share.train", r, TRAIN) == pytest.approx(1.0)
    assert read("fsdp.exposed_ms_per_step", r, TRAIN) == pytest.approx(50.0)
    assert read("flash_roofline", r, TRAIN) == pytest.approx(
        100 * 2 * (22 * 137_438_953_472 / 197e12) / 0.0768)


def test_readers_that_find_nothing_return_nothing():
    r = train_record()
    r["trace"] = {}
    for name in ("flash_roofline", "device.idle_share.train",
                 "fsdp.exposed_ms_per_step"):
        assert read(name, r, TRAIN) is None
    r = train_record()
    r["facts"].update(kind="cpu", platform="cpu")        # a rehearsal
    assert read("train.mfu", r, TRAIN) is None
    assert read("flash_roofline", r, TRAIN) is None
    assert read("ingress.admitted_max", serve_record() | {"admitted_max": 0},
                SERVE) is None


def test_a_trace_that_contradicts_the_arithmetic_fails_the_run():
    """Not the calls ops.py counts: the share would be computed from
    arithmetic that no longer describes the program, so the reader raises
    and the metric does not quietly drop out of the line."""
    r = train_record()
    r["trace"]["mosaic_calls"] = 12
    with pytest.raises(Exception, match="12 Mosaic calls in 2 step") as e:
        read("flash_roofline", r, TRAIN)
    assert type(e.value).__name__ == "MetricFault"


def serve_record():
    rows, requests, batches = [], {}, []
    # 20 requests sent at t = 0; request i waits 10 ms in the proxy on the
    # way in and 5 ms on the way out; the batcher holds it 100 ms; generate
    # takes 4 s. Request 19 fails.
    for i in range(20):
        rows.append({"rid": i, "ok": i != 19, "units": 128, "send": 0.0, "first": 4.115 + i * 0.001,
                     "last": 4.115 + i * 0.001})
        requests[str(i)] = [0.010, 4.110 + i * 0.001]
    batches.append({"start": 0.110, "end": 4.110, "rows": 20,
                    "padded_rows": 32, "rids": list(range(20))})
    batches.append({"start": 5.0, "end": 9.5, "rows": 12, "padded_rows": 32,
                    "rids": []})
    return {"window": {"rows": rows, "start": 0.0, "end": 4.12,
                       "seconds": 4.12},
            "requests": requests, "batches": batches,
            "request_timeout_s": 60.0, "admitted_max": 17,
            "facts": {"kind": "TPU v5 lite"},
            "trace": {"periods": 2, "module_s": 9.12, "window_s": 9.6,
                      "busy_s": 9.12}}


def test_serve_readers_keep_ingress_and_queue_apart():
    r = serve_record()
    assert read("ingress.proxy_ms", r, SERVE) == pytest.approx(15.0)
    assert read("ingress.admitted_max", r, SERVE) == 17
    assert read("batch.queue_ms", r, SERVE) == pytest.approx(100.0)
    assert read("batch.fill", r, SERVE) == pytest.approx(100 * 32 / 64)
    assert read("generate.call_s", r, SERVE) == pytest.approx(4.25)
    assert read("device.idle_share.serve", r, SERVE) == pytest.approx(5.0)
    assert read("generate_roofline", r, SERVE) == pytest.approx(
        100 * 2 * 2.8406565 / 9.12, rel=1e-5)


def test_serve_tail_counts_a_failure_as_missing():
    r = serve_record()
    # 20 requests: the 95th percentile is the 19th smallest; the failed
    # one sorts last at the client's limit
    assert read("serve.request_p95_s", r, SERVE) == pytest.approx(4.133)
    assert read("serve.ttft_p95_s", r, SERVE) == pytest.approx(4.133)
    r["window"]["rows"][0]["ok"] = False         # two of 20 failed
    assert read("serve.request_p95_s", r, SERVE) == 60.0
    # all of the window's requests that succeeded, until the last reply
    r = serve_record()
    assert read("serve.tokens_per_s", r, SERVE) == \
        pytest.approx(19 * 128 / 4.133)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_listed_metric_has_a_reader_that_loads(kind):
    for metric in MF.data[kind]:
        assert callable(MF.reader(metric["name"]))
    assert math.isfinite(MF.data["run_seconds"])
    assert MF.root == CHECKOUT

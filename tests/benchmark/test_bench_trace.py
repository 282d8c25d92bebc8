"""benchmark/trace.py: the reduction from a profiler trace to busy and idle
time, per-kernel time, exposed collective time and the breakdown, on a
synthetic timeline with answers worked by hand, and on a small trace
recorded on a TPU v5e (3 train steps of mistral-7b-v0.3-l2, S = 2048 x 8,
taken by the discarded PR 23's harness from this program's train step)."""

import os

import pytest

from bench_paths import BENCH
from benchmark import trace

MOSAIC = ('%closed_call.9 = (bf16[256,2048,128]{2,1,0}) custom-call(), '
          'custom_call_target="tpu_custom_call"')


def test_interval_arithmetic():
    u = trace.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert trace.total(u) == 5
    assert trace.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert trace.subtract([(0, 10)], u) == [(3, 5), (7, 10)]
    assert trace.subtract([(0, 3), (5, 7)], [(1, 6)]) == [(0, 1), (6, 7)]
    assert trace.subtract(u, []) == u


def test_self_time_does_not_count_a_container_on_top_of_its_body():
    events = [("%while.1 = x", 0.0, 10.0), ("%fusion.1 = x", 1.0, 3.0),
              ("%fusion.2 = x", 5.0, 4.0), ("%copy.1 = x", 12.0, 1.0)]
    timed = {n.split(" ")[0]: (self_s, leaf)
             for n, _, _, self_s, leaf in trace.self_times(events)}
    assert timed == {"%while.1": (3.0, False), "%fusion.1": (3.0, True),
                     "%fusion.2": (4.0, True), "%copy.1": (1.0, True)}


def test_op_labels():
    assert trace.op_label(
        "%fusion.206 = (f32[4096,32768]{1,0:T(8,128)}, f32[]) fusion(...)"
    ) == "fusion.206 f32[4096,32768]"
    assert trace.op_label(MOSAIC).startswith("mosaic:closed_call.9 bf16[")
    assert trace.COLLECTIVE.match("%all-gather.3 = bf16[8] all-gather()")
    assert trace.COLLECTIVE.match("%collective-permute-done.1 = x")
    assert not trace.COLLECTIVE.match("%fusion.all-gather = x")


def synthetic():
    """Two whole periods of a 1.0 s program. In each: 0.1 s idle, then a
    while [0.1, 0.9) holding a fusion [0.1, 0.4), a Mosaic call [0.4, 0.6),
    an all-gather [0.6, 0.65) and a permute-done wait [0.65, 0.7), then
    nothing until the next program. A third execution starts at 2.0."""
    ops, modules = [], []
    for k in range(3):
        t = float(k)
        modules.append(("jit_step_fn(123)", t + 0.1, 0.8))
        ops += [("%while.9 = (s32[]) while()", t + 0.1, 0.8),
                ("%fusion.1 = f32[8,8]{1,0} fusion()", t + 0.1, 0.3),
                (MOSAIC, t + 0.4, 0.2),
                ("%all-gather.2 = bf16[4,8]{1,0} all-gather()", t + 0.6,
                 0.05),
                ("%collective-permute-done.7 = bf16[8] x()", t + 0.65, 0.05)]
    modules.append(("jit_tiny(9)", 0.95, 0.01))
    async_ops = [("%collective-permute-start.7 = x", 0.2, 0.5),
                 ("%copy-start.3 = x", 0.1, 0.1)]
    host = [("bench.step", 0.0, 0.93), ("bench.report", 0.93, 0.1),
            ("bench.step", 1.03, 0.9), ("bench.report", 1.93, 0.2),
            ("other", 0.0, 5.0)]
    return ops, async_ops, modules, host


def test_reduction_of_a_synthetic_timeline():
    ops, async_ops, modules, host = synthetic()
    host = [h for h in host if h[0].startswith(trace.HOST_SPAN_PREFIX)]
    r = trace.reduce_device(ops, async_ops, modules, host)
    assert r["main_module"] == "jit_step_fn" and r["periods"] == 2
    assert r["window_s"] == pytest.approx(2.0)       # 0.1 -> 2.1
    assert r["busy_s"] == pytest.approx(1.6)         # the while, twice
    assert r["module_s"] == pytest.approx(1.6)
    assert r["mosaic_calls"] == 2
    assert r["mosaic_s"] == pytest.approx(0.4)
    # on the stream: all-gather 0.05 + the wait 0.05, twice
    assert r["exposed_collective_s"] == pytest.approx(0.2)
    # in flight: [0.2, 0.7) in the first period, [1.6, 1.7) in the second
    assert r["collective_s"] == pytest.approx(0.6)
    top = dict(r["device_ops"])
    assert top["fusion.1 f32[8,8]"] == pytest.approx(0.6)
    assert top["while.9 s32[]"] == pytest.approx(0.4)   # 0.8 - 0.6, twice
    gaps = dict(r["idle_gaps"])
    # [0.9, 1.1) has its middle in bench.report, [1.9, 2.1) too
    assert gaps == {"bench.report": pytest.approx(0.4)}


def test_one_execution_is_no_whole_period():
    ops, async_ops, modules, host = synthetic()
    assert trace.reduce_device(ops, async_ops, modules[:1], host) == {}
    assert trace.reduce_device([], [], [], []) == {}
    assert trace.combine([{}, {}]) == {}


def test_chips_are_averaged():
    ops, async_ops, modules, host = synthetic()
    one = trace.reduce_device(ops, async_ops, modules, [])
    slow = [(n, s, d * 0.5) for n, s, d in ops]
    two = trace.reduce_device(slow, [], modules, [])
    both = trace.combine([one, two])
    assert both["devices"] == 2
    # halved: the while covers 0.4, the two collectives after it 0.025 each
    assert both["busy_s"] == pytest.approx((1.6 + 0.9) / 2)
    assert both["window_s"] == pytest.approx(2.0)
    assert len(both["device_ops"]) <= trace.TOP
    assert all(len(row) == 2 for row in both["device_ops"])


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce_file(os.path.join(
        BENCH, "testdata", "train-v5e-3steps.xplane.pb"))


def test_recorded_v5e_trace_busy_and_idle(recorded):
    assert recorded["devices"] == 1
    assert recorded["main_module"] == "jit_step_fn"
    assert recorded["periods"] == 2
    assert recorded["window_s"] == pytest.approx(1.011690443, abs=1e-6)
    assert recorded["busy_s"] == pytest.approx(1.002633335, abs=1e-6)
    idle = 1 - recorded["busy_s"] / recorded["window_s"]
    assert idle == pytest.approx(0.00895, abs=1e-4)


def test_recorded_v5e_trace_kernels_and_breakdown(recorded):
    # 2 layers x (forward, remat'd forward, dkv, dq) x 2 periods
    assert recorded["mosaic_calls"] == 16
    assert recorded["mosaic_s"] == pytest.approx(0.076804755, abs=1e-6)
    assert recorded["exposed_collective_s"] == 0     # one chip
    name, seconds = recorded["device_ops"][0]
    assert name == "fusion.206 f32[4096,32768]"      # the f32 head's update
    assert seconds == pytest.approx(0.069734, abs=1e-5)
    assert len(recorded["device_ops"]) == 10
    assert recorded["idle_gaps"][0][0] == "bench.step"
    total_self = sum(s for _, s in recorded["device_ops"])
    assert total_self < recorded["busy_s"]

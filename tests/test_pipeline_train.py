"""MPMD pipeline-parallel training over compiled-graph channels.

Covers the static microbatch scheduler (dag/schedule.py: gpipe / 1F1B /
interleaved-1F1B program generation + the executability validator), the
CompiledPipeline runtime (train/pipeline.py: resident per-stage loops on
shm channel rings, measured bubble efficiency against the m/(m+s-1)
bound, poison propagation when a stage fails mid-schedule), numerics
(pipeline loss trajectory == single-process reference), DP-of-PP
composition, and the per-stage timeline lanes with microbatch flow
joins. The conftest hygiene fixture asserts every test here leaves no
live pipelines and no leaked channel shm segments behind.
"""

import time

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu.cluster import fault_plane
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.core import api as core_api
from ray_tpu.core.exceptions import TaskError
from ray_tpu.core.runtime_cluster import ClusterRuntime
from ray_tpu.dag import schedule as ps


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 16})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()


# Workers unpickle the factory by reference: it must resolve from an
# importable module, not this test file. functools.partial over optax.sgd
# ships as a reference to optax.sgd plus the bound lr; calling it yields
# the GradientTransformation.
def _sgd_factory():
    import functools

    import optax
    return functools.partial(optax.sgd, 0.1)


_SGD = None


def _sgd():
    global _SGD
    if _SGD is None:
        _SGD = _sgd_factory()
    return _SGD


def _small_cfg(**kw):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig
    base = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4,
                max_seq=32, dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def _reference_losses(batches, pp_stages, lr=0.1):
    """Single-process trajectory: same init as the pipeline (pp-stacked
    layers reshaped flat), full-batch value_and_grad + sgd."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import (transformer_init,
                                            transformer_loss)
    ref_cfg = _small_cfg(pp_stages=pp_stages, num_microbatches=4)
    params = transformer_init(jax.random.PRNGKey(0), ref_cfg)
    flat_cfg = _small_cfg()
    params_flat = dict(params)
    params_flat["layers"] = jax.tree.map(
        lambda a: a.reshape((4,) + a.shape[2:]), params["layers"])
    tx = optax.sgd(lr)
    opt = tx.init(params_flat)

    def lossfn(p, batch):
        return transformer_loss(p, batch, flat_cfg)

    vg = jax.jit(jax.value_and_grad(lossfn))
    out = []
    for b in batches:
        loss, g = vg(params_flat, {"tokens": jnp.asarray(b["tokens"])})
        upd, opt = tx.update(g, opt, params_flat)
        params_flat = optax.apply_updates(params_flat, upd)
        out.append(float(loss))
    return out


def _batches(n, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, 64, size=(batch, seq))
             .astype(np.int32)} for _ in range(n)]


# ---------------------------------------------------------------------------
# schedule generation (pure, no cluster)
# ---------------------------------------------------------------------------


def test_gpipe_runs_all_forwards_before_backwards():
    progs = ps.stage_programs("gpipe", num_stages=2, num_microbatches=4)
    for prog in progs:
        kinds = [op.kind for op in prog]
        assert "B" not in kinds[:kinds.index("B")]
        first_b = kinds.index("B")
        assert all(k == "F" for k in kinds[:first_b])
        assert all(k == "B" for k in kinds[first_b:])


def test_1f1b_steady_state_interleaves():
    progs = ps.stage_programs("1f1b", num_stages=2, num_microbatches=4)
    stage0 = [(op.kind, op.mb) for op in progs[0]]
    # textbook 1F1B on the first stage: 2-deep warmup, then alternation
    assert stage0 == [("F", 0), ("F", 1), ("B", 0), ("F", 2),
                      ("B", 1), ("F", 3), ("B", 2), ("B", 3)]
    # last stage degenerates to strict FBFB
    last = [(op.kind, op.mb) for op in progs[1]]
    assert last == [("F", 0), ("B", 0), ("F", 1), ("B", 1),
                    ("F", 2), ("B", 2), ("F", 3), ("B", 3)]


def test_interleaved_assigns_chunks_round_robin():
    s, v, m = 2, 2, 4
    progs = ps.stage_programs("interleaved_1f1b", num_stages=s,
                              num_microbatches=m, num_chunks=v)
    for a, prog in enumerate(progs):
        parts = {op.part for op in prog}
        assert parts == {p for p in range(s * v)
                         if ps.partition_owner(p, s) == a}
        assert len(prog) == 2 * v * m      # F+B per owned (part, mb)


@pytest.mark.parametrize("kind", ps.SCHEDULES)
@pytest.mark.parametrize("s,m,v", [(2, 4, 1), (3, 6, 1), (4, 8, 1),
                                   (2, 8, 2), (3, 9, 1)])
def test_programs_validate_executable(kind, s, m, v):
    if v > 1 and kind != "interleaved_1f1b":
        pytest.skip("chunks only for interleaved")
    progs = ps.stage_programs(kind, num_stages=s, num_microbatches=m,
                              num_chunks=v)
    ps.validate_programs(progs, num_stages=s, num_microbatches=m,
                         num_chunks=v)


def test_validate_rejects_chunk_count_mismatch():
    progs = ps.stage_programs("interleaved_1f1b", num_stages=2,
                              num_microbatches=4, num_chunks=2)
    with pytest.raises(ValueError, match="partition outside"):
        ps.validate_programs(progs, num_stages=2, num_microbatches=4)


def test_bubble_bound_formula():
    assert ps.bubble_bound(4, 2) == pytest.approx(4 / 5)
    assert ps.bubble_bound(8, 4) == pytest.approx(8 / 11)
    # interleaving shrinks the bubble by the chunk count
    assert ps.bubble_bound(8, 4, num_chunks=2) == pytest.approx(
        8 / (8 + 3 / 2))
    assert ps.bubble_bound(4, 2) < ps.bubble_bound(4, 2, num_chunks=2)


def test_unknown_schedule_rejected():
    with pytest.raises(ValueError, match="schedule"):
        ps.stage_programs("zigzag", num_stages=2, num_microbatches=4)


# ---------------------------------------------------------------------------
# efficiency gate (synthetic stages: sleeps overlap even on one core)
# ---------------------------------------------------------------------------


def test_1f1b_efficiency_meets_bound(cluster):
    """Measured steady-state pipeline efficiency must reach 80% of the
    bubble bound m/(m+s-1) — the PR's headline acceptance gate."""
    from ray_tpu.train.pipeline import CompiledPipeline, SleepStage
    s, m = 3, 6
    cls = rt.remote(SleepStage)
    actors = [cls.options(num_cpus=1).remote(0.01, 0.02) for _ in range(s)]
    rt.get([a.ping.remote() for a in actors])
    pipe = CompiledPipeline(actors, num_microbatches=m, schedule="1f1b")
    try:
        assert pipe.bound == pytest.approx(m / (m + s - 1))
        effs = []
        for t in range(4):
            r = pipe.step([b"x" * 64] * m)
            if t >= 1:            # step 0 has no prior collect: wall=None
                effs.append(r["efficiency"])
        assert all(e is not None for e in effs)
        assert min(effs) >= 0.8 * pipe.bound, \
            f"efficiency {effs} below 0.8 x bound {pipe.bound}"
    finally:
        pipe.teardown()
        for a in actors:
            rt.kill(a)


def test_gpipe_less_efficient_than_1f1b_bound(cluster):
    """gpipe holds every activation to the flush: its all-F-then-all-B
    program still completes and reports a sane efficiency in (0, 1]."""
    from ray_tpu.train.pipeline import CompiledPipeline, SleepStage
    s, m = 2, 4
    cls = rt.remote(SleepStage)
    actors = [cls.options(num_cpus=1).remote(0.005, 0.01) for _ in range(s)]
    rt.get([a.ping.remote() for a in actors])
    pipe = CompiledPipeline(actors, num_microbatches=m, schedule="gpipe")
    try:
        for _ in range(3):
            r = pipe.step([b"x" * 16] * m)
        assert r["efficiency"] is not None and 0 < r["efficiency"] <= 1.05
    finally:
        pipe.teardown()
        for a in actors:
            rt.kill(a)


# ---------------------------------------------------------------------------
# numerics: pipeline trajectory == single-process reference
# ---------------------------------------------------------------------------


def test_pipeline_loss_matches_reference(cluster):
    from ray_tpu.train.pipeline import PipelineTrainer
    batches = _batches(3)
    tr = PipelineTrainer(_small_cfg(), num_stages=2, num_microbatches=4,
                         schedule="1f1b", tx_factory=_sgd(),
                         seed=0).start()
    try:
        got = [tr.step(b)["loss"] for b in batches]
    finally:
        tr.shutdown()
    ref = _reference_losses(batches, pp_stages=2)
    np.testing.assert_allclose(got, ref, atol=2e-3)


@pytest.mark.slow
def test_interleaved_loss_matches_reference(cluster):
    from ray_tpu.train.pipeline import PipelineTrainer
    batches = _batches(3)
    tr = PipelineTrainer(_small_cfg(), num_stages=2, num_microbatches=4,
                         schedule="interleaved_1f1b", num_chunks=2,
                         tx_factory=_sgd(), seed=0).start()
    try:
        got = [tr.step(b)["loss"] for b in batches]
    finally:
        tr.shutdown()
    ref = _reference_losses(batches, pp_stages=4)
    np.testing.assert_allclose(got, ref, atol=2e-3)


@pytest.mark.slow
def test_dp_replicas_match_full_batch_reference(cluster):
    """2 DP replicas x 2 PP stages: replica grads averaged per stage must
    reproduce the full-batch single-process trajectory."""
    from ray_tpu.train.pipeline import PipelineTrainer
    batches = _batches(3)
    tr = PipelineTrainer(_small_cfg(), num_stages=2, num_microbatches=2,
                         dp_replicas=2, schedule="1f1b",
                         tx_factory=_sgd(), seed=0).start()
    try:
        got = [tr.step(b)["loss"] for b in batches]
    finally:
        tr.shutdown()
    ref = _reference_losses(batches, pp_stages=2)
    np.testing.assert_allclose(got, ref, atol=2e-3)


# ---------------------------------------------------------------------------
# chaos: stage failure mid-schedule poisons downstream, fails fast
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_stage_crash_mid_schedule_fails_fast(cluster):
    """Kill (inject a fault into) one stage's resident loop mid-schedule:
    POISON propagates through every downstream ring, the in-flight step
    raises a clean error well under 10s, teardown leaks nothing and the
    actors still serve classic RPCs."""
    from ray_tpu.dag import channel, compiled
    from ray_tpu.train.pipeline import CompiledPipeline, SleepStage
    from ray_tpu import config
    s, m = 3, 4
    # Plans reach worker processes via spawn-time env: arm BEFORE the
    # stage actors exist, and ship the blob through runtime_env so the
    # module-scoped cluster cannot hand these actors recycled workers
    # that predate the plan.  Stage 1 runs 9 ops per step (4 F + 4 B +
    # the apply barrier): nth=11 lets step 0 complete, then fires
    # mid-schedule of step 1.
    fault_plane.load_plan(
        [{"site": "cgraph.loop.crash", "action": "raise",
          "match": {"stage": 1}, "nth": 11, "times": 1}])
    renv = {"env_vars": {
        config._SYSTEM_CONFIG_ENV: config.serialized_overrides()}}
    cls = rt.remote(SleepStage)
    actors = [cls.options(num_cpus=1, runtime_env=renv).remote(0.005, 0.01)
              for _ in range(s)]
    try:
        rt.get([a.ping.remote() for a in actors])
        pipe = CompiledPipeline(actors, num_microbatches=m,
                                schedule="1f1b")
        try:
            pipe.step([b"x" * 32] * m)     # step 0: clean
            t0 = time.monotonic()
            with pytest.raises(TaskError, match="injected fault"):
                for _ in range(4):
                    pipe.step([b"x" * 32] * m, timeout=10.0)
            assert time.monotonic() - t0 < 10.0
        finally:
            pipe.teardown()
        # teardown restored classic task service on every stage actor
        assert rt.get([a.ping.remote() for a in actors],
                      timeout=30) == ["pong"] * s
    finally:
        fault_plane.clear_plan()
        for a in actors:
            rt.kill(a)
    deadline = time.monotonic() + 2.0
    while channel.leaked_segments() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not compiled._live_graphs
    assert not channel.leaked_segments()


# ---------------------------------------------------------------------------
# timeline: per-stage lanes + microbatch flow joins
# ---------------------------------------------------------------------------


def test_timeline_stage_lanes_and_flow_joins(cluster):
    """rt.timeline() grows one lane per pipeline stage and flow arrows
    ("s" at F on partition 0, "t" through the chain, "f" at B back on
    partition 0) joining each microbatch across stages."""
    from ray_tpu.train.pipeline import CompiledPipeline, SleepStage
    s, m = 2, 4
    cls = rt.remote(SleepStage)
    actors = [cls.options(num_cpus=1).remote(0.002, 0.004)
              for _ in range(s)]
    rt.get([a.ping.remote() for a in actors])
    pipe = CompiledPipeline(actors, num_microbatches=m, schedule="1f1b")
    gid = pipe._gid.hex()[:8]
    try:
        for _ in range(2):
            pipe.step([b"x" * 16] * m)
        deadline = time.time() + 30
        joined, lanes = set(), set()
        stages = {f"stage{i}" for i in range(s)}
        while time.time() < deadline:
            evs = core_api.timeline()
            pevs = [e for e in evs if e.get("pid") == f"pipe-{gid}"]
            lanes = {e["tid"] for e in pevs if e["ph"] == "X"}
            flows = [e for e in pevs if e.get("cat") == "pipeline_flow"]
            ids_s = {e["id"] for e in flows if e["ph"] == "s"}
            ids_f = {e["id"] for e in flows if e["ph"] == "f"}
            joined = ids_s & ids_f
            # every stage's own lane: the driver's ("driver", from its
            # pipeline.step records) is a lane too, and partition 0 alone
            # holds both ends of every flow, so a count of lanes was met
            # before stage 1's worker had shipped its ring (every 0.5 s)
            if len(joined) >= m and stages <= lanes:
                break
            time.sleep(0.25)
        assert stages <= lanes
        assert len(joined) >= m, f"flow joins incomplete: {joined}"
        # flow ids carry the microbatch: graph:step:mb
        assert all(fid.count(":") == 2 for fid in joined)
    finally:
        pipe.teardown()
        for a in actors:
            rt.kill(a)

"""Trainer stack tests: WorkerGroup gang, session.report streaming,
checkpointing, stop conditions (parity:
python/ray/train/tests/test_data_parallel_trainer.py style — tiny model,
small worker counts)."""

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.core import api as core_api
from ray_tpu.core.runtime_cluster import ClusterRuntime


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 8})
    rt_ = ClusterRuntime(address=c.address)
    core_api._runtime = rt_
    yield c
    core_api._runtime = None
    rt_.shutdown()
    c.shutdown()


def test_single_worker_loop_reports(cluster):
    from ray_tpu.train import DataParallelTrainer, ScalingConfig, RunConfig

    def loop(config):
        from ray_tpu.air import session
        for i in range(config["iters"]):
            session.report({"loss": 1.0 / (i + 1), "iter": i})

    trainer = DataParallelTrainer(
        loop, train_loop_config={"iters": 3},
        scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=1),
        run_config=RunConfig(name="t1"))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["iter"] == 2
    assert len(result.metrics_history) == 3


def test_two_worker_gang_rank_metrics(cluster):
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    def loop(config):
        from ray_tpu.air import session
        session.report({"rank": session.get_world_rank(),
                        "world": session.get_world_size()})

    trainer = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2, cpus_per_worker=1))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["world"] == 2
    assert result.metrics["rank"] == 0  # rank-0 metrics win


def test_checkpoint_roundtrip(cluster):
    from ray_tpu.train import (Checkpoint, DataParallelTrainer, ScalingConfig)

    def loop(config):
        from ray_tpu.air import session
        start = 0
        ck = session.get_checkpoint()
        if ck is not None:
            start = ck.to_dict()["step"]
        for i in range(start, start + 2):
            session.report(
                {"step_done": i},
                checkpoint=Checkpoint.from_dict(
                    {"step": i + 1, "w": np.ones(4) * (i + 1)}))

    trainer = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1))
    r1 = trainer.fit()
    assert r1.checkpoint is not None
    assert r1.checkpoint.to_dict()["step"] == 2

    trainer2 = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        resume_from_checkpoint=r1.checkpoint)
    r2 = trainer2.fit()
    assert r2.metrics["step_done"] == 3  # resumed from step 2
    np.testing.assert_allclose(r2.checkpoint.to_dict()["w"], np.ones(4) * 4)


def test_stop_condition(cluster):
    from ray_tpu.train import DataParallelTrainer, RunConfig, ScalingConfig

    def loop(config):
        from ray_tpu.air import session
        for i in range(1000):
            session.report({"i": i})

    trainer = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(stop={"training_iteration": 5}))
    result = trainer.fit()
    assert result.error is None
    assert len(result.metrics_history) <= 6


def test_jax_loop_trains(cluster):
    """A real jax training loop through the trainer (tiny MLP, CPU)."""
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    def loop(config):
        import jax
        import jax.numpy as jnp
        import optax
        from ray_tpu.air import session

        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (4, 1)) * 0.1
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 4))
        y = x @ jnp.ones((4, 1))
        tx = optax.sgd(0.1)
        opt = tx.init(w)

        @jax.jit
        def step(w, opt, x, y):
            def loss_fn(w):
                return jnp.mean((x @ w - y) ** 2)
            loss, g = jax.value_and_grad(loss_fn)(w)
            up, opt = tx.update(g, opt)
            return optax.apply_updates(w, up), opt, loss

        losses = []
        for i in range(20):
            w, opt, loss = step(w, opt, x, y)
            losses.append(float(loss))
        session.report({"first_loss": losses[0], "last_loss": losses[-1]})

    trainer = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=2))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["last_loss"] < result.metrics["first_loss"] * 0.2


def test_failure_surfaces(cluster):
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    def loop(config):
        raise RuntimeError("user loop exploded")

    trainer = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1))
    result = trainer.fit()
    assert result.error is not None
    assert "user loop exploded" in str(result.error)


def test_jax_trainer_lm_steps_on_virtual_devices(cluster):
    """The framework trainer end to end: a JaxTrainer worker builds a mesh
    over its (virtual CPU) devices, compiles the LM step and reports a
    falling loss — the path chip_smoke.py drives at full width on a TPU."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.air import session
        from ray_tpu.models import TransformerConfig
        from ray_tpu.parallel import MeshSpec, build_mesh
        from ray_tpu.train import make_lm_train_step

        n = jax.device_count()
        cfg = TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                n_heads=4, max_seq=32, tied_embeddings=True)
        mesh = build_mesh(MeshSpec(dp=n))
        init_fn, step_fn, place_batch = make_lm_train_step(cfg, mesh)
        state = init_fn(jax.random.PRNGKey(0))
        embed = state.params["embed"]
        # every leaf of the state starts out spread over the mesh (adam's
        # moments used to sit whole on device 0 until the first step)
        state_devices = sorted({len(x.sharding.device_set)
                                for x in jax.tree.leaves(state)})
        batch = place_batch({"tokens": jnp.asarray(
            np.random.default_rng(0).integers(0, 256, (n, 32)), jnp.int32)})
        for _ in range(config["steps"]):
            state, metrics = step_fn(state, batch)
            session.report({
                "loss": float(metrics["loss"]),
                "devices": n,
                "platform": jax.devices()[0].platform,
                "param_devices": len(embed.sharding.device_set),
                "state_devices": state_devices,
                "batch_devices": len(batch["tokens"].sharding.device_set)})

    result = JaxTrainer(
        loop, train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="jax_lm",
                             stop={"training_iteration": 3})).fit()
    assert result.error is None, result.error
    losses = [m["loss"] for m in result.metrics_history]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    last = result.metrics
    assert last["platform"] == "cpu" and last["devices"] == 8
    assert last["param_devices"] == 8 and last["batch_devices"] == 8
    assert last["state_devices"] == [8]

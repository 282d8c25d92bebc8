"""Trainers: BaseTrainer / DataParallelTrainer / JaxTrainer.

Role parity: python/ray/train/base_trainer.py:554 (BaseTrainer.fit),
data_parallel_trainer.py:56 (DataParallelTrainer -> BackendExecutor ->
WorkerGroup), torch/torch_trainer.py:15 (framework trainer). The reference
routes fit() through a single-trial Tune run (base_trainer.py:579); here
fit() drives the BackendExecutor directly, and ray_tpu.tune.Tuner wraps a
trainer the same way when sweeping.

TPU-first: the framework trainer is JaxTrainer — the user loop builds a
mesh from ScalingConfig.mesh and a pjit step; on multi-host gangs
JaxBackend has already done jax.distributed.initialize, so
jax.devices() spans the slice and the same pjit code scales (SURVEY §3.4).
"""

from __future__ import annotations

import os
import tempfile
from typing import Any, Callable, Dict, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import (CheckpointConfig, FailureConfig, RunConfig,
                                ScalingConfig)
from ray_tpu.air.result import Result
from ray_tpu.train.backend_executor import (Backend, BackendExecutor,
                                            JaxBackend, TrainingFailedError)


_CKPT_MARKER = "_COMPLETE"


def _find_restorable_checkpoint(trial_dir: str) -> Optional[str]:
    """Newest COMPLETE persisted checkpoint, surviving a crash at any
    point of _persist_checkpoint's swap: prefer checkpoint_latest, then
    .tmp (newer but unswapped — complete iff marked), then .old."""
    final = os.path.join(trial_dir, "checkpoint_latest")
    for cand in (final, final + ".tmp", final + ".old"):
        if os.path.isdir(cand) and \
                os.path.exists(os.path.join(cand, _CKPT_MARKER)):
            return cand
    # Pre-marker layouts (or externally written dirs): accept a bare
    # checkpoint_latest rather than silently restarting from scratch.
    if os.path.isdir(final):
        return final
    return None


class BaseTrainer:
    def __init__(self, *, scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint

    def fit(self) -> Result:
        raise NotImplementedError

    @classmethod
    def restore(cls, path: str) -> "BaseTrainer":
        """Rebuild a trainer from a previous run's trial dir and resume
        from its latest persisted checkpoint (parity:
        base_trainer.py:567-579 BaseTrainer.restore — experiment-level
        resume after DRIVER death, vs. the in-fit elastic restart that
        only survives worker death)."""
        from ray_tpu.core import serialization
        spec_path = os.path.join(path, "trainer.pkl")
        if not os.path.exists(spec_path):
            raise FileNotFoundError(
                f"no trainer state found under {path!r} (trainer.pkl "
                "missing — was fit() ever started here?)")
        with open(spec_path, "rb") as f:
            trainer = serialization.loads(f.read())
        ckpt_dir = _find_restorable_checkpoint(path)
        if ckpt_dir is not None:
            trainer.resume_from_checkpoint = Checkpoint.from_directory(
                ckpt_dir)
        return trainer

    @staticmethod
    def can_restore(path: str) -> bool:
        return os.path.exists(os.path.join(path, "trainer.pkl"))

    def _save_spec(self, trial_dir: str) -> None:
        """Persist this trainer's construction so restore() can rebuild it
        in a fresh process (written once, before training starts)."""
        from ray_tpu.core import serialization
        spec_path = os.path.join(trial_dir, "trainer.pkl")
        if not os.path.exists(spec_path):
            tmp = spec_path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(serialization.dumps(self))
            os.replace(tmp, spec_path)

    @staticmethod
    def _persist_checkpoint(trial_dir: str, ckpt: Checkpoint) -> None:
        """Write the latest checkpoint under the trial dir so a dead
        driver can resume from disk, not just from memory. Directory swaps
        cannot be single-rename-atomic; every intermediate state is
        covered by a COMPLETE marker + the restore fallback chain
        (_find_restorable_checkpoint): .tmp carries the marker only once
        fully written, .old keeps the previous complete checkpoint until
        the new one is in place."""
        final = os.path.join(trial_dir, "checkpoint_latest")
        tmp, old = final + ".tmp", final + ".old"
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
        ckpt.to_directory(tmp)
        with open(os.path.join(tmp, _CKPT_MARKER), "w") as f:
            f.write("1")
        shutil.rmtree(old, ignore_errors=True)
        if os.path.isdir(final):
            os.replace(final, old)
        os.replace(tmp, final)
        shutil.rmtree(old, ignore_errors=True)

    def as_trainable(self) -> Callable[[dict], Result]:
        """A Tune-compatible trainable closing over this trainer (parity:
        base_trainer.py:666 as_trainable)."""
        trainer = self

        def trainable(config: dict) -> Result:
            import copy
            t = copy.copy(trainer)
            merged = dict(getattr(t, "train_loop_config", None) or {})
            merged.update(config)
            t.train_loop_config = merged
            return t.fit()

        return trainable


class DataParallelTrainer(BaseTrainer):
    """N identical workers running one loop (parity:
    data_parallel_trainer.py:56)."""

    _backend_cls: Callable[[], Backend] = Backend

    def __init__(self, train_loop_per_worker: Callable, *,
                 train_loop_config: Optional[dict] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None,
                 backend: Optional[Backend] = None,
                 datasets: Optional[dict] = None):
        super().__init__(scaling_config=scaling_config, run_config=run_config,
                         resume_from_checkpoint=resume_from_checkpoint)
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.backend = backend or self._backend_cls()
        # name -> ray_tpu.data.Dataset, split per-rank at fit() and exposed
        # in workers via session.get_dataset_shard (air session parity).
        self.datasets = datasets or {}

    def fit(self) -> Result:
        from ray_tpu.util import events
        with events.span("train.fit"):
            return self._fit()

    def _fit(self) -> Result:
        cfg = self.run_config
        trial_dir = os.path.join(
            cfg.storage_path or tempfile.gettempdir(),
            cfg.name or "rtpu_train")
        os.makedirs(trial_dir, exist_ok=True)
        self._save_spec(trial_dir)
        stop = cfg.stop or {}
        failure = cfg.failure_config or FailureConfig()
        attempts = 0
        num_workers = self.scaling_config.num_workers
        while True:
            executor = BackendExecutor(
                self.backend, num_workers,
                self.scaling_config.worker_resources(),
                self.scaling_config.placement_strategy,
                slice_topology=self.scaling_config.topology)
            state = {"last_metrics": {}, "last_checkpoint":
                     self.resume_from_checkpoint, "history": []}

            def on_report(merged):
                state["last_metrics"] = merged["metrics"]
                state["history"].append(merged["metrics"])
                if merged["checkpoint"] is not None:
                    state["last_checkpoint"] = merged["checkpoint"]
                    try:
                        self._persist_checkpoint(trial_dir,
                                                 merged["checkpoint"])
                    except Exception:
                        pass  # persistence is best-effort; in-memory
                        # state still drives this fit()'s own restarts
                for key, bound in stop.items():
                    if key == "training_iteration":
                        if merged["iteration"] >= bound:
                            return "stop"
                    elif merged["metrics"].get(key) is not None and \
                            merged["metrics"][key] >= bound:
                        return "stop"
                return None

            try:
                # First formation waits the full window (nodes may still be
                # joining). On an elastic RESTART capacity just shrank, and
                # the worker count was planned from a membership view that
                # can lag the failure — an infeasible gang should fail fast
                # and re-plan against the settled cluster, not park on the
                # placement timeout.
                executor.start(ready_timeout=15.0 if attempts else 120.0)
                executor.run(self.train_loop_per_worker,
                             self.train_loop_config, on_report,
                             trial_dir=trial_dir,
                             checkpoint=state["last_checkpoint"],
                             datasets=self.datasets)
                return Result(metrics=state["last_metrics"],
                              checkpoint=state["last_checkpoint"],
                              metrics_history=state["history"],
                              config=dict(self.train_loop_config),
                              path=trial_dir)
            except TrainingFailedError as e:
                attempts += 1
                if failure.max_failures != -1 and \
                        attempts > failure.max_failures:
                    return Result(metrics=state["last_metrics"],
                                  checkpoint=state["last_checkpoint"],
                                  metrics_history=state["history"],
                                  error=e,
                                  config=dict(self.train_loop_config),
                                  path=trial_dir)
                # elastic restart from the last checkpoint (SURVEY §5:
                # a lost host kills the XLA program; recovery = re-form
                # the gang + checkpoint restore, not per-task retry)
                self.resume_from_checkpoint = state["last_checkpoint"]
                if failure.elastic:
                    # Mesh-shrink: re-plan the gang against the SURVIVING
                    # cluster. A smaller world_size resumes from the last
                    # checkpoint now instead of parking on a lost host
                    # (SURVEY §7 hard part: re-form a smaller mesh).
                    num_workers = self._feasible_workers(
                        num_workers, failure.min_workers)
            finally:
                executor.shutdown()

    def _feasible_workers(self, want: int, floor: int,
                          settle_timeout: float = 30.0) -> int:
        """How many workers the LIVE cluster can host right now. Waits
        briefly for membership to settle (the dead node's health timeout)
        whenever even ``floor`` workers don't fit yet."""
        import math
        import time as _time

        import ray_tpu as rt
        res = self.scaling_config.worker_resources()
        deadline = _time.monotonic() + settle_timeout
        from ray_tpu.cluster.protocol import get_client
        while True:
            slots = 0
            assessable = False
            try:
                for n in rt.nodes():
                    if not n["Alive"] or ":" not in str(n.get("address", "")):
                        continue  # local-mode runtime: nothing to re-plan
                    assessable = True
                    # The conductor's health view lags a crash by its
                    # timeout; a direct daemon ping settles liveness NOW (a
                    # dead daemon refuses instantly; timeout=1.0 bounds the
                    # CONNECT too, so a power-failed host can't park the
                    # re-plan on the OS SYN-retry clock).
                    try:
                        get_client(n["address"], timeout=1.0).call(
                            "ping", _timeout=1.0)
                    except Exception:
                        continue
                    cap = min((n["Resources"].get(k, 0.0) / v
                               for k, v in res.items() if v > 0),
                              default=0.0)
                    slots += int(math.floor(cap))
            except Exception:
                slots = 0
            if not assessable:
                return want
            if slots >= floor or _time.monotonic() >= deadline:
                return max(floor, min(want, slots))
            _time.sleep(0.5)


class JaxTrainer(DataParallelTrainer):
    """The framework trainer (role of TorchTrainer, torch_trainer.py:15)."""

    def __init__(self, train_loop_per_worker: Callable, *,
                 distributed: bool = True, **kwargs):
        super().__init__(train_loop_per_worker,
                         backend=JaxBackend(distributed=distributed),
                         **kwargs)


class TorchTrainer(DataParallelTrainer):
    """torch loops in the gang (parity: torch/torch_trainer.py:15): a gloo
    process group spans the workers; train.torch_utils.prepare_model /
    prepare_data_loader give DDP + per-rank sharding. Host-CPU only here —
    accelerator math is the jax stack's job (JaxTrainer)."""

    def __init__(self, train_loop_per_worker: Callable, **kwargs):
        from ray_tpu.train.backend_executor import TorchBackend
        super().__init__(train_loop_per_worker, backend=TorchBackend(),
                         **kwargs)

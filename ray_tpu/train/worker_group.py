"""WorkerGroup: gang of train-worker actors.

Role parity: python/ray/train/_internal/worker_group.py:92 (WorkerGroup) and
:17 (RayTrainWorker) — N actors, one per host slot, placed in one placement
group; ``execute`` fans a function to all workers; ``start_training`` runs
the user loop in a thread per worker with an active session.

TPU-first delta: workers are *gang-scheduled* (all bundles of one PG, with
STRICT_PACK keeping a pjit gang on one ICI slice), because a multi-host XLA
program needs every process to enter the same computation (SURVEY.md §7
"SPMD vs actor impedance").
"""

from __future__ import annotations

import threading
import traceback
from typing import Any, Callable, Dict, List, Optional


class RayTrainWorker:
    """Actor hosting one rank of the training gang."""

    def __init__(self, world_rank: int, world_size: int, local_rank: int):
        self.world_rank = world_rank
        self.world_size = world_size
        self.local_rank = local_rank
        self._session = None
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[dict] = None
        self._done = threading.Event()

    def setup_env(self, env: Dict[str, str]) -> bool:
        import os
        os.environ.update(env)
        return True

    def execute(self, fn: Callable, *args, **kwargs):
        return fn(*args, **kwargs)

    def start_training(self, loop_fn: Callable, config: dict,
                      trial_dir: str = "", checkpoint=None,
                      dataset_shards=None) -> bool:
        from ray_tpu.air import session as session_mod
        sess = session_mod._Session(
            self.world_rank, self.world_size, self.local_rank,
            trial_dir=trial_dir, config=config, checkpoint=checkpoint,
            dataset_shards=dataset_shards)
        self._session = sess
        self._done.clear()
        self._error = None
        from ray_tpu.util import events
        caller = events.current()    # the driver's span, across the thread

        def run():
            session_mod._set_session(sess)
            try:
                with events.span("train.loop", ctx=caller,
                                 rank=self.world_rank):
                    if _accepts_config(loop_fn):
                        loop_fn(config)
                    else:
                        loop_fn()
            except StopIteration:
                pass
            except BaseException:  # noqa: BLE001 - shipped to the driver
                self._error = {"traceback": traceback.format_exc()}
            finally:
                session_mod._set_session(None)
                with sess.report_event:
                    self._done.set()
                    sess.report_event.notify_all()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name=f"train-rank{self.world_rank}")
        self._thread.start()
        return True

    def next_report(self, index: int, timeout: float = 10.0):
        """Block until report[index] exists (or the loop finished)."""
        sess = self._session
        if sess is None:
            return {"status": "no_session"}
        import time
        deadline = time.monotonic() + timeout
        with sess.report_event:
            while len(sess.reports) <= index:
                if self._done.is_set():
                    if self._error:
                        return {"status": "error", **self._error}
                    return {"status": "finished"}
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {"status": "pending"}
                sess.report_event.wait(remaining)
            r = sess.reports[index]
            return {"status": "report", "metrics": r["metrics"],
                    "checkpoint": r["checkpoint"],
                    "iteration": r["iteration"], "ts": r["ts"]}

    def request_stop(self) -> bool:
        if self._session is not None:
            self._session.stop_requested = True
        return True

    def shutdown_worker(self) -> bool:
        return True


def _identity(x):
    return x


def _accepts_config(fn: Callable) -> bool:
    import inspect
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    return len(sig.parameters) >= 1


class WorkerGroup:
    """Driver-side handle over the gang (parity: worker_group.py:92)."""

    def __init__(self, num_workers: int, resources_per_worker: Dict[str, float],
                 placement_strategy: str = "PACK", slice_topology: str = "",
                 ready_timeout: float = 120.0):
        import ray_tpu as rt
        from ray_tpu.util.placement_group import placement_group
        from ray_tpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy)
        self.num_workers = num_workers
        bundles = [dict(resources_per_worker) for _ in range(num_workers)]
        if slice_topology:
            # Slice-gang: bundle i -> rank-i host of ONE ICI slice, so the
            # jax.distributed process group matches TPU_WORKER_ID order.
            self.pg = placement_group(bundles, strategy="SLICE",
                                      slice_topology=slice_topology)
        else:
            self.pg = placement_group(bundles, strategy=placement_strategy)
        try:
            self.pg.ready(timeout=ready_timeout)
            cls = rt.remote(RayTrainWorker)
            self.workers = []
            for rank in range(num_workers):
                strategy = PlacementGroupSchedulingStrategy(
                    self.pg, placement_group_bundle_index=rank)
                w = cls.options(
                    num_cpus=resources_per_worker.get("CPU", 1.0),
                    num_tpus=resources_per_worker.get("TPU", 0.0),
                    resources={k: v for k, v in resources_per_worker.items()
                               if k not in ("CPU", "TPU")},
                    scheduling_strategy=strategy,
                ).remote(rank, num_workers, rank)
                self.workers.append(w)
        except BaseException:  # noqa: BLE001 - tear down the half-formed gang, then re-raise
            # half-formed gang: kill any actors already created AND release
            # the PG, so a retry plans against clean capacity (zombie ranks
            # would double-book the bundles the conductor just returned)
            from ray_tpu.util.placement_group import remove_placement_group
            for w in getattr(self, "workers", []):
                try:
                    rt.kill(w)
                except Exception:
                    pass
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
            raise

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        import ray_tpu as rt
        return rt.get([w.execute.remote(fn, *args, **kwargs)
                       for w in self.workers], timeout=600)

    def broadcast_weights(self, params: Any,
                          apply_fn: Optional[Callable] = None) -> List[Any]:
        """Ship one weight payload to every rank via the collective-backed
        object plane (r16): ONE put + a broadcast tree pre-places the
        object on each distinct worker node, then every rank resolves it
        from its local store as a read-only array view. ``apply_fn(params)``
        runs on each rank with the resolved value (default: return it)."""
        import ray_tpu as rt
        from ray_tpu.train import weight_sync
        ref = weight_sync.broadcast_to_actors(params, self.workers)
        if apply_fn is None:
            futs = [w.execute.remote(_identity, ref) for w in self.workers]
        else:
            futs = [w.execute.remote(apply_fn, ref) for w in self.workers]
        return rt.get(futs, timeout=600)

    def execute_single(self, rank: int, fn: Callable, *args, **kwargs) -> Any:
        import ray_tpu as rt
        return rt.get(self.workers[rank].execute.remote(fn, *args, **kwargs),
                      timeout=600)

    def shutdown(self) -> None:
        import ray_tpu as rt
        from ray_tpu.util.placement_group import remove_placement_group
        for w in self.workers:
            try:
                rt.kill(w)
            except Exception:
                pass
        try:
            remove_placement_group(self.pg)
        except Exception:
            pass

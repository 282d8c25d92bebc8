"""BackendExecutor: orchestrates the training gang.

Role parity: python/ray/train/_internal/backend_executor.py:43 — start the
WorkerGroup, run the backend's on_start (rendezvous), start the user loop on
every worker, then pump reports until all ranks finish.

The JaxBackend replaces the reference's _TorchBackend
(train/torch/config.py:155): instead of dist.init_process_group(nccl), it
seeds ``jax.distributed.initialize`` with a coordinator on rank 0
(coordination-service rendezvous; collectives then compile into the step
function and ride ICI) — SURVEY.md §3.4 "TPU mapping".
"""

from __future__ import annotations

import socket
import time
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import events


class Backend:
    def on_start(self, worker_group: WorkerGroup) -> None:
        pass

    def on_shutdown(self, worker_group: WorkerGroup) -> None:
        pass


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _init_jax_distributed(coordinator: str, num_processes: int,
                          process_id: int) -> bool:
    import jax
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


class JaxBackend(Backend):
    """Multi-process SPMD rendezvous (parity role: _TorchBackend)."""

    def __init__(self, distributed: bool = True):
        self.distributed = distributed

    def on_start(self, worker_group: WorkerGroup) -> None:
        if not self.distributed or worker_group.num_workers == 1:
            return
        # Rank 0's host picks the coordinator port; every rank calls
        # jax.distributed.initialize against it (replaces NCCL unique-id
        # rendezvous through the GCS KV, reference nccl_util.py).
        ip = worker_group.execute_single(
            0, lambda: socket.gethostbyname(socket.gethostname()))
        port = worker_group.execute_single(0, _free_port)
        coordinator = f"{ip}:{port}"
        import ray_tpu as rt
        refs = [
            w.execute.remote(_init_jax_distributed, coordinator,
                             worker_group.num_workers, rank)
            for rank, w in enumerate(worker_group.workers)
        ]
        rt.get(refs, timeout=120)


def _init_torch_distributed(master_addr: str, master_port: int,
                            world_size: int, rank: int,
                            backend: str = "gloo") -> bool:
    import os

    import torch.distributed as dist
    if dist.is_initialized():
        return True
    os.environ["MASTER_ADDR"] = master_addr
    os.environ["MASTER_PORT"] = str(master_port)
    # gloo default: the CPU-host collective backend (the reference's
    # non-GPU path, train/torch/config.py backend="gloo"); TPU-side math
    # never goes through torch — this exists for torch data/eval loops.
    dist.init_process_group(backend, rank=rank, world_size=world_size)
    return True


class TorchBackend(Backend):
    """torch.distributed rendezvous over the gang (parity:
    train/torch/config.py:113 _TorchBackend.on_start). The group is
    initialized even at world size 1 so loops using torch.distributed
    APIs behave identically in debug (1-worker) runs."""

    def __init__(self, backend: str = "gloo"):
        self.backend_name = backend

    def on_start(self, worker_group: WorkerGroup) -> None:
        ip = worker_group.execute_single(
            0, lambda: socket.gethostbyname(socket.gethostname()))
        port = worker_group.execute_single(0, _free_port)
        import ray_tpu as rt
        refs = [
            w.execute.remote(_init_torch_distributed, ip, port,
                             worker_group.num_workers, rank,
                             self.backend_name)
            for rank, w in enumerate(worker_group.workers)
        ]
        rt.get(refs, timeout=120)

    def on_shutdown(self, worker_group: WorkerGroup) -> None:
        def _destroy():
            import torch.distributed as dist
            if dist.is_initialized():
                dist.destroy_process_group()
            return True
        try:
            worker_group.execute(_destroy)
        except Exception:
            pass  # workers may already be gone


class TrainingFailedError(RuntimeError):
    pass


class BackendExecutor:
    def __init__(self, backend: Backend, num_workers: int,
                 resources_per_worker: Dict[str, float],
                 placement_strategy: str = "PACK",
                 slice_topology: str = ""):
        self.backend = backend
        self.num_workers = num_workers
        self.resources_per_worker = resources_per_worker
        self.placement_strategy = placement_strategy
        self.slice_topology = slice_topology
        self.worker_group: Optional[WorkerGroup] = None

    def start(self, ready_timeout: float = 120.0) -> None:
        try:
            with events.span("train.backend.start",
                             workers=self.num_workers):
                with events.span("train.gang.start"):
                    self.worker_group = WorkerGroup(
                        self.num_workers, self.resources_per_worker,
                        self.placement_strategy,
                        slice_topology=self.slice_topology,
                        ready_timeout=ready_timeout)
                self.backend.on_start(self.worker_group)
        except Exception as e:  # noqa: BLE001 - retryable via FailureConfig
            raise TrainingFailedError(f"gang formation failed: {e!r}") from e

    def run(self, train_loop: Callable, config: dict,
            on_report: Callable[[dict], Any],
            trial_dir: str = "",
            checkpoint: Optional[Checkpoint] = None,
            datasets: Optional[Dict[str, Any]] = None) -> List[dict]:
        """Start the loop on all ranks and pump synchronized reports.

        ``on_report`` receives the merged report each round (rank-0 metrics
        + rank-0 checkpoint); returning "stop" requests cooperative stop.
        Returns the full merged report history.
        """
        import ray_tpu as rt
        wg = self.worker_group
        # Per-rank dataset shards (session.get_dataset_shard): each named
        # Dataset splits into world_size EQUAL-row pieces — collective-per-
        # step loops need the same step count on every rank or the gang
        # deadlocks on the uneven tail (Dataset.split(equal=True) parity).
        shards_by_rank: List[Optional[dict]] = [None] * len(wg.workers)
        if datasets:
            per_name = {name: ds.split(len(wg.workers), equal=True)
                        for name, ds in datasets.items()}
            shards_by_rank = [
                {name: splits[rank] for name, splits in per_name.items()}
                for rank in range(len(wg.workers))]
        try:
            rt.get([w.start_training.remote(train_loop, config, trial_dir,
                                            checkpoint,
                                            dataset_shards=shards_by_rank[i])
                    for i, w in enumerate(wg.workers)], timeout=600)
        except Exception as e:  # noqa: BLE001 - gang infra failure
            raise TrainingFailedError(f"gang start failed: {e!r}") from e
        history: List[dict] = []
        index = 0
        finished = False
        while not finished:
            # One synchronized round: wait for report[index] on every rank
            # (session.report is a barrier in the reference's semantics).
            with events.span("train.pump") as pump:
                round_reports, done = self._round(wg, index)
                finished = finished or done
                rank0 = round_reports[0]
                if rank0 is not None:
                    # lag: this round's end over rank 0's report() start
                    pump.set(iteration=rank0["iteration"],
                             lag_s=time.time() - rank0["ts"])
            if all(r is None for r in round_reports):
                break
            if rank0 is not None:
                merged = {"metrics": rank0["metrics"],
                          "checkpoint": rank0["checkpoint"],
                          "iteration": rank0["iteration"]}
                history.append(merged)
                if on_report(merged) == "stop":
                    for w in wg.workers:
                        w.request_stop.remote()
                    finished = True
            index += 1
        return history

    @staticmethod
    def _round(wg: WorkerGroup, index: int):
        """Wait for report[index] on every rank. -> (the ranks' reports,
        None for a rank that finished; whether any rank finished)."""
        import ray_tpu as rt
        finished = False
        round_reports: List[Optional[dict]] = [None] * len(wg.workers)
        pending = set(range(len(wg.workers)))
        while pending:
            # Poll the whole round concurrently under ONE shared
            # deadline: submit every rank's long-poll up front, then
            # collect. Serial per-rank polling with a fresh 120s get
            # each meant one hung rank delayed dead-rank detection on
            # every rank queued behind it by up to 120s apiece. A rank
            # still training answers "pending" within its 30s
            # long-poll, re-arming the next wave's deadline — only a
            # rank that cannot answer at all eats the full window.
            wave = {rank: wg.workers[rank].next_report.remote(index, 30.0)
                    for rank in sorted(pending)}
            wave_deadline = time.monotonic() + 120.0
            for rank, ref in wave.items():
                try:
                    r = rt.get(ref, timeout=max(
                        5.0, wave_deadline - time.monotonic()))
                except TrainingFailedError:
                    raise
                except Exception as e:  # noqa: BLE001 - rank died
                    # A dead rank (node loss, OOM kill) fails the whole
                    # gang: an SPMD program cannot continue minus one
                    # process — the trainer re-forms the gang (possibly
                    # smaller, FailureConfig.elastic) from the last
                    # checkpoint.
                    raise TrainingFailedError(
                        f"rank {rank} failed: {e!r}") from e
                if r["status"] == "report":
                    round_reports[rank] = r
                    pending.discard(rank)
                elif r["status"] == "finished":
                    round_reports[rank] = None
                    pending.discard(rank)
                    finished = True
                elif r["status"] == "error":
                    raise TrainingFailedError(r["traceback"])
                # "pending": poll again
        return round_reports, finished

    def shutdown(self) -> None:
        if self.worker_group is not None:
            self.backend.on_shutdown(self.worker_group)
            self.worker_group.shutdown()
            self.worker_group = None

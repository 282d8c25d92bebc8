"""ray_tpu.train — distributed training library (JAX-first).

Parity surface: reference python/ray/train (BaseTrainer base_trainer.py:554,
DataParallelTrainer data_parallel_trainer.py:56, BackendExecutor
backend_executor.py:43). The torch/NCCL backend is replaced by pjit-compiled
steps over a TPU mesh; `jax_step` is the single-controller compiled-step
factory, the Trainer/WorkerGroup layer orchestrates multi-host SPMD.
"""

from ray_tpu.train.jax_step import (
    TrainState,
    make_lm_train_step,
    make_resnet_train_step,
    make_vit_train_step,
    step_span,
)

_LAZY = {
    "ScalingConfig": ("ray_tpu.air.config", "ScalingConfig"),
    "RunConfig": ("ray_tpu.air.config", "RunConfig"),
    "CheckpointConfig": ("ray_tpu.air.config", "CheckpointConfig"),
    "FailureConfig": ("ray_tpu.air.config", "FailureConfig"),
    "Checkpoint": ("ray_tpu.air.checkpoint", "Checkpoint"),
    "Result": ("ray_tpu.air.result", "Result"),
    "session": ("ray_tpu.air", "session"),
    "report": ("ray_tpu.air.session", "report"),
    "JaxTrainer": ("ray_tpu.train.trainer", "JaxTrainer"),
    "TorchTrainer": ("ray_tpu.train.trainer", "TorchTrainer"),
    "TorchBackend": ("ray_tpu.train.backend_executor", "TorchBackend"),
    "torch_utils": ("ray_tpu.train.torch_utils", None),
    "DataParallelTrainer": ("ray_tpu.train.trainer", "DataParallelTrainer"),
    "BaseTrainer": ("ray_tpu.train.trainer", "BaseTrainer"),
    "BackendExecutor": ("ray_tpu.train.backend_executor", "BackendExecutor"),
    "JaxBackend": ("ray_tpu.train.backend_executor", "JaxBackend"),
    "WorkerGroup": ("ray_tpu.train.worker_group", "WorkerGroup"),
    "PipelineTrainer": ("ray_tpu.train.pipeline", "PipelineTrainer"),
    "CompiledPipeline": ("ray_tpu.train.pipeline", "CompiledPipeline"),
    "PipelineStageActor": ("ray_tpu.train.pipeline", "PipelineStageActor"),
}

__all__ = ["TrainState", "step_span", "make_lm_train_step", "make_resnet_train_step",
           "make_vit_train_step",
           *_LAZY]


def __getattr__(name):
    # Heavier trainer machinery is imported lazily so `import ray_tpu.train`
    # stays light for pure-step users.
    entry = _LAZY.get(name)
    if entry is None:
        raise AttributeError(name)
    import importlib
    mod = importlib.import_module(entry[0])
    return mod if entry[1] is None else getattr(mod, entry[1])

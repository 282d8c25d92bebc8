"""Per-task / per-actor option validation and defaults.

Role parity: python/ray/_private/ray_option_utils.py — a single table of
valid options with type/value checks, shared by ``@remote`` decorators and
``.options(...)`` overrides.

TPU-first deltas: the accelerator option is ``num_tpus`` (chips), and
``scheduling_strategy`` accepts slice-aware placement-group strategies.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple


@dataclass
class TaskOptions:
    num_cpus: float = 1.0
    num_tpus: float = 0.0
    resources: Dict[str, float] = field(default_factory=dict)
    num_returns: int = 1
    max_retries: Any = None        # None = config default; -1 = infinite
    retry_exceptions: Any = False  # bool or tuple of exception types
    name: str = ""
    scheduling_strategy: Any = None
    runtime_env: Optional[dict] = None
    _metadata: Dict[str, Any] = field(default_factory=dict)


@dataclass
class ActorOptions:
    num_cpus: float = 1.0
    num_tpus: float = 0.0
    resources: Dict[str, float] = field(default_factory=dict)
    max_restarts: int = 0
    max_task_retries: int = 0
    max_concurrency: int = 1
    name: str = ""
    namespace: str = ""
    lifetime: str = "ref_counted"  # or "detached"
    scheduling_strategy: Any = None
    runtime_env: Optional[dict] = None
    get_if_exists: bool = False


_TASK_KEYS = {f for f in TaskOptions.__dataclass_fields__ if not f.startswith("_")}
_ACTOR_KEYS = set(ActorOptions.__dataclass_fields__)


def _check_resources(opts) -> None:
    if opts.num_cpus < 0 or opts.num_tpus < 0:
        raise ValueError("num_cpus / num_tpus must be >= 0")
    if opts.num_tpus != int(opts.num_tpus):
        raise ValueError("fractional num_tpus is not allowed: a chip "
                         "belongs to one process at a time")
    for k, v in opts.resources.items():
        if not isinstance(k, str) or (isinstance(v, (int, float)) and v < 0):
            raise ValueError(f"bad custom resource {k!r}: {v!r}")
        if k in ("CPU", "TPU"):
            raise ValueError(f"use num_cpus/num_tpus instead of resources[{k!r}]")


def make_task_options(base: Optional[TaskOptions] = None, **updates) -> TaskOptions:
    bad = set(updates) - _TASK_KEYS
    if bad:
        raise ValueError(f"Invalid task options: {sorted(bad)}; "
                         f"valid: {sorted(_TASK_KEYS)}")
    merged = TaskOptions(**{**(_as_dict(base, _TASK_KEYS) if base else {}), **updates})
    if merged.num_returns < 0:
        raise ValueError("num_returns must be >= 0")
    _check_resources(merged)
    if "runtime_env" in updates:
        from ray_tpu.runtime_env import validate_runtime_env
        merged.runtime_env = validate_runtime_env(merged.runtime_env)
    return merged


def make_actor_options(base: Optional[ActorOptions] = None, **updates) -> ActorOptions:
    bad = set(updates) - _ACTOR_KEYS
    if bad:
        raise ValueError(f"Invalid actor options: {sorted(bad)}; "
                         f"valid: {sorted(_ACTOR_KEYS)}")
    merged = ActorOptions(**{**(_as_dict(base, _ACTOR_KEYS) if base else {}), **updates})
    if merged.max_concurrency < 1:
        raise ValueError("max_concurrency must be >= 1")
    if merged.lifetime not in ("ref_counted", "detached"):
        raise ValueError("lifetime must be 'ref_counted' or 'detached'")
    if merged.max_restarts < -1:
        raise ValueError("max_restarts must be >= -1 (-1 = infinite)")
    _check_resources(merged)
    if "runtime_env" in updates:
        from ray_tpu.runtime_env import validate_runtime_env
        merged.runtime_env = validate_runtime_env(merged.runtime_env)
    return merged


def _as_dict(opts, keys) -> Dict[str, Any]:
    return {k: getattr(opts, k) for k in keys}


def resource_demand(opts) -> Dict[str, float]:
    """The scheduler-visible resource shape of a task/actor."""
    d = dict(opts.resources)
    if opts.num_cpus:
        d["CPU"] = float(opts.num_cpus)
    if opts.num_tpus:
        d["TPU"] = float(opts.num_tpus)
    return d

"""TPU topology discovery and the slice model.

A *slice* is the unit of gang scheduling: an ICI-connected set of chips that
one XLA program can address (v4-8, v5e-16, ...). The scheduler treats a slice
request as a placement-group whose bundles must land on the hosts of one
contiguous slice (SURVEY.md §7 phase 4); this module is the pure-data side:
what topologies exist, how many chips per host, and which jax devices belong
to the local process.

Known-generation table follows public TPU system documentation. A chip
belongs to one process at a time, so the driver, the conductor and the node
daemon never open a JAX backend: what they advertise comes from TPU env vars
or from one sacrificial probe subprocess (probe_chips) that has exited
before any worker starts. Only a chip-owning worker asks jax directly
(generation()).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import re
import subprocess
import sys
from typing import Dict, Optional, Sequence, Tuple

# chips-per-host for each generation's standard host form factor.
_CHIPS_PER_HOST: Dict[str, int] = {
    "v2": 4,
    "v3": 4,
    "v4": 4,
    "v5e": 8,
    "v5p": 4,
    "v6e": 8,
    "cpu": 8,  # virtual CPU "slice" used by tests
}

# ICI mesh shapes for common slice sizes (chips -> (x, y) or (x, y, z)).
# v4/v5p are 3D tori; v2/v3/v5e/v6e are 2D meshes.
_MESH_2D: Dict[int, Tuple[int, int]] = {
    1: (1, 1), 2: (1, 2), 4: (2, 2), 8: (2, 4), 16: (4, 4),
    32: (4, 8), 64: (8, 8), 128: (8, 16), 256: (16, 16),
}


@dataclasses.dataclass(frozen=True)
class SliceSpec:
    """A requested or discovered TPU slice.

    accelerator_type follows the cloud naming, e.g. "v5e-16" = 16 v5e chips.
    """
    generation: str          # "v4", "v5e", ...
    num_chips: int
    topology: Tuple[int, ...]  # ICI mesh/torus shape

    @property
    def accelerator_type(self) -> str:
        return f"{self.generation}-{self.num_chips}"

    @property
    def num_hosts(self) -> int:
        per = _CHIPS_PER_HOST.get(self.generation, 4)
        return max(1, math.ceil(self.num_chips / per))

    @property
    def chips_per_host(self) -> int:
        return min(self.num_chips, _CHIPS_PER_HOST.get(self.generation, 4))

    @staticmethod
    def parse(accelerator_type: str) -> "SliceSpec":
        """Parse "v5e-16" / "v4-8" style names ("v5litepod-4" is how a v5e
        host names itself in TPU_ACCELERATOR_TYPE)."""
        m = re.fullmatch(r"(v\d+[a-z]*)-(\d+)", accelerator_type)
        if not m:
            raise ValueError(
                f"Bad accelerator type {accelerator_type!r}; expected e.g. 'v5e-16'")
        gen, n = m.group(1), int(m.group(2))
        gen = {"v5litepod": "v5e"}.get(gen, gen)
        return SliceSpec(gen, n, slice_mesh_shape(gen, n))


def slice_mesh_shape(generation: str, num_chips: int) -> Tuple[int, ...]:
    """ICI mesh shape for a slice of `num_chips` chips."""
    if generation in ("v4", "v5p"):
        # 3D torus: factor into the most-cubic shape of multiples of 4 where
        # possible; fall back to (1,1,n).
        best = (1, 1, num_chips)
        best_cost = num_chips + 2
        for x in range(1, int(round(num_chips ** (1 / 3))) + 2):
            if num_chips % x:
                continue
            rem = num_chips // x
            for y in range(x, int(math.isqrt(rem)) + 1):
                if rem % y:
                    continue
                z = rem // y
                cost = x + y + z
                if cost < best_cost:
                    best, best_cost = (x, y, z), cost
        return best
    shape = _MESH_2D.get(num_chips)
    if shape is None:
        # non-standard size: nearly-square 2D factorization
        x = max(d for d in range(1, int(math.isqrt(num_chips)) + 1)
                if num_chips % d == 0)
        shape = (x, num_chips // x)
    return shape


# The one generation table: device_kind substring -> generation, first
# match wins ("v5 lite" before "v5").
_KIND_GENERATIONS = [("v6", "v6e"), ("v5p", "v5p"), ("v5 lite", "v5e"),
                     ("v5e", "v5e"), ("v5", "v5p"), ("v4", "v4"),
                     ("v3", "v3"), ("v2", "v2")]


def generation_of(device_kind: str) -> str:
    """TPU generation ("v5e", "v4", ...) of a jax ``device_kind``. A kind
    the table does not know is an error, never a default: block sizes and
    peaks keyed by generation would silently be another chip's."""
    kind = device_kind.lower()
    for pat, gen in _KIND_GENERATIONS:
        if pat in kind:
            return gen
    raise ValueError(f"unknown TPU device_kind {device_kind!r}; add it to "
                     "tpu/topology.py _KIND_GENERATIONS")


@functools.cache
def generation() -> str:
    """Generation of THIS process's devices, for generation-keyed tables
    (ops/flash block sizes, bench peak-FLOPs lookup). Opens the backend:
    only a chip-owning worker may call it."""
    import jax
    return generation_of(jax.devices()[0].device_kind)


def _pinned_platforms() -> list:
    """Platforms this process is pinned to (the jax_platforms config knob,
    else JAX_PLATFORMS); empty = jax picks."""
    import jax
    plats = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    return [p.strip() for p in plats.split(",") if p.strip()]


def platform_pinned_off_tpu() -> bool:
    """True when this process is explicitly pinned to a non-TPU platform.
    A process that said "cpu" must never touch the chip, not even through
    the probe."""
    plats = _pinned_platforms()
    return bool(plats) and "tpu" not in plats


class TpuProbeError(RuntimeError):
    """The device probe failed or timed out; carries the probe's stderr."""


# Source for the sacrificial device probe. Module-level so tests can
# substitute a wedged backend (e.g. a sleep) without a real TPU.
_PROBE_SRC = """
import json, sys, jax
devs = jax.local_devices()
json.dump({"platform": devs[0].platform, "device_kind": devs[0].device_kind,
           "count": len(devs)}, sys.stdout)
"""

_probe_cache: Optional[dict] = None


def probe_chips(require_tpu: bool = False) -> dict:
    """What jax sees on this host — {platform, device_kind, count} —
    found by a THROWAWAY subprocess under a hard deadline, so the caller
    never holds the chip and a wedged PJRT backend (uninterruptible C++)
    cannot hang it. Cached: one probe per process, gone before any worker
    starts. ``require_tpu`` pins the probe to JAX_PLATFORMS=tpu, where a
    chip that will not open is an error instead of jax's CPU fallback.
    Failure or timeout raises TpuProbeError with the probe's stderr."""
    global _probe_cache
    if _probe_cache is None:
        from ray_tpu import config
        timeout_s = config.get("tpu_probe_timeout_s")
        env = dict(os.environ)
        if require_tpu:
            env["JAX_PLATFORMS"] = "tpu"
        from ray_tpu.util import events
        with events.span("init.probe") as sp:
            try:
                out = subprocess.run([sys.executable, "-c", _PROBE_SRC],
                                     env=env, capture_output=True, text=True,
                                     timeout=timeout_s)
            except subprocess.TimeoutExpired as e:
                raise TpuProbeError(
                    f"TPU probe timed out after {timeout_s}s; stderr:\n"
                    f"{e.stderr or ''}") from None
            if out.returncode != 0:
                raise TpuProbeError(
                    f"TPU probe exited {out.returncode}; stderr:\n"
                    f"{out.stderr}")
            _probe_cache = json.loads(out.stdout)
            sp.set(chips=_probe_cache["count"],
                   platform=_probe_cache["platform"])
    if require_tpu and _probe_cache["platform"] != "tpu":
        raise TpuProbeError(
            f"TPUs were asked for but jax reports {_probe_cache}")
    return _probe_cache


def local_chip_count(required: bool = False) -> int:
    """Chips on this host. ``required`` (the caller asked for TPUs) or a
    JAX_PLATFORMS that names tpu makes a missing chip an error."""
    from ray_tpu import config
    override = int(config.get("tpu_chips_per_host_override"))
    if override > 0:
        return override
    if not required and platform_pinned_off_tpu():
        return 0
    required = required or "tpu" in _pinned_platforms()
    info = probe_chips(require_tpu=required)
    return info["count"] if info["platform"] == "tpu" else 0


def detect_slice() -> Optional[dict]:
    """Discover this host's TPU-slice membership for the scheduler.

    The slice is the gang-scheduling unit (an ICI-connected chip set one
    XLA program addresses); the node daemon advertises this dict at
    registration so the conductor can place slice-granular placement
    groups with ICI contiguity (parity role: the GPU/accelerator fields of
    the reference's node resource spec, python/ray/_private/
    resource_spec.py, extended with the slice identity Ray lacks).

    Chip count and device_kind come from the probe. On Cloud TPU VMs the
    runtime also exposes TPU_ACCELERATOR_TYPE / TPU_WORKER_ID /
    TPU_WORKER_HOSTNAMES (MEGASCALE_SLICE_ID on multislice), which name the
    whole slice this host is part of; without them, or where they name a
    one-host slice of another size than the probe found, the slice is this
    host's own chips. Returns None where chips are faked
    (tpu_chips_per_host_override: no device to describe).
    """
    from ray_tpu import config
    if int(config.get("tpu_chips_per_host_override")) > 0:
        return None
    info = probe_chips(require_tpu=True)
    probed = f"{generation_of(info['device_kind'])}-{info['count']}"
    at = os.environ.get("TPU_ACCELERATOR_TYPE") or probed
    hostnames = [h for h in os.environ.get(
        "TPU_WORKER_HOSTNAMES", "").split(",") if h]
    spec = SliceSpec.parse(at)
    num_hosts = len(hostnames) or spec.num_hosts
    if num_hosts == 1 and spec.num_chips != info["count"]:
        # The env names a slice this host does not hold (a one-chip v5e
        # machine exports v5litepod-4): on a single host the probe is what
        # the workers will see, so the slice is the probed one.
        at, spec = probed, SliceSpec.parse(probed)
    slice_id = (os.environ.get("MEGASCALE_SLICE_ID")
                or os.environ.get("TPU_NAME")
                or ",".join(hostnames)
                or f"local-{at}")
    return {
        "slice_id": slice_id,
        # The TPU-VM resource name (distinct from slice_id on multislice,
        # where MEGASCALE_SLICE_ID is an index): cloud providers join on
        # this for scale-down (autoscaler/gcp.py node_id_map).
        "tpu_name": os.environ.get("TPU_NAME") or None,
        "accelerator_type": at,
        "generation": spec.generation,
        "device_kind": info["device_kind"],
        "worker_id": int(os.environ.get("TPU_WORKER_ID", "0") or 0),
        "num_hosts": num_hosts,
    }


def lease_size_refusal(n_chips: int, chips_on_host: int) -> Optional[str]:
    """Why one process cannot be given ``n_chips`` of a host with
    ``chips_on_host`` physical chips, or None if it can: one chip or the
    whole host. Other sizes need a box of ICI-adjacent chips, which
    nothing here allots. The daemon asks before it allots anything."""
    if n_chips in (1, chips_on_host):
        return None
    return (f"a TPU lease takes one chip or all {chips_on_host} of the "
            f"host, not {n_chips}")


def chip_visibility_env(chips: Sequence[int], chips_on_host: int
                        ) -> Dict[str, Optional[str]]:
    """libtpu's visibility variables for the one process that is to own
    ``chips`` of a host with ``chips_on_host`` PHYSICAL chips, however many
    of them the node advertises (None = the variable must be unset).
    Established on a v5e 2x2 host (libtpu 0.0.34): TPU_VISIBLE_CHIPS plus
    the 1,1,1 bounds pair gives a process exactly that chip, renumbered as
    device 0, and several such processes run side by side with no port
    settings; the full list with the host's own bounds left alone gives
    all chips with their physical coords."""
    refusal = lease_size_refusal(len(chips), chips_on_host)
    if refusal:
        raise ValueError(refusal)
    env: Dict[str, Optional[str]] = {
        "JAX_PLATFORMS": "tpu",
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
    }
    whole_host = len(chips) == chips_on_host
    env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = None if whole_host else "1,1,1"
    env["TPU_PROCESS_BOUNDS"] = None if whole_host else "1,1,1"
    return env


def process_owns_chips() -> bool:
    """Whether this process is the one a daemon spawned with chips: its
    environment carries ``chip_visibility_env``'s list."""
    return bool(os.environ.get("TPU_VISIBLE_CHIPS"))


def default_compile_cache_dir() -> str:
    """Where chip-owning processes keep jax's persistent compilation
    cache when JAX_COMPILATION_CACHE_DIR is not set (where it is, jax
    reads it itself and nothing here overrides it): one fixed path inside
    the checkout — the path is part of the cache key, so it must not move
    between runs."""
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

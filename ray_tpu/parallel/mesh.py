"""Device-mesh construction over TPU slices.

One `jax.sharding.Mesh` with named axes ("dp","fsdp","tp","pp","sp","ep") is
the substrate of every parallelism strategy. The reference's analog is the
torch process-group bootstrap (reference python/ray/train/torch/config.py:113
dist.init_process_group); here there is no rendezvous per-strategy — you pick
axis sizes once and XLA compiles the collectives.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("dcn_dp", "pp", "dp", "fsdp", "sp", "ep", "tp")
# tp innermost: tensor-parallel collectives are per-layer and latency-bound,
# so tp must map to the fastest (most-adjacent) ICI dimension. pp outermost
# within a slice: stage-to-stage transfers happen once per microbatch.
# dcn_dp outermost of all: it is the ONLY axis allowed to cross slice
# boundaries — pure data parallelism between slices, so the sole
# inter-slice collective is the once-per-step gradient all-reduce, which is
# the one communication pattern that tolerates DCN latency (multislice
# recipe; the reference's nearest analog is multi-node NCCL DDP,
# reference python/ray/train/torch/config.py:113).


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Axis sizes for the global device mesh. 1 = strategy off.

    ``dcn_dp`` > 1 spans multiple TPU slices over DCN; all other axes must
    fit within one slice (their collectives ride ICI).
    """
    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    dcn_dp: int = 1

    @property
    def num_devices(self) -> int:
        return (self.dp * self.fsdp * self.tp * self.pp * self.sp *
                self.ep * self.dcn_dp)

    @property
    def devices_per_slice(self) -> int:
        return self.num_devices // self.dcn_dp

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def active_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in AXIS_ORDER if getattr(self, a) > 1)

    @staticmethod
    def auto(num_devices: int, *, tp: int = 1, pp: int = 1, sp: int = 1,
             ep: int = 1, fsdp: Optional[int] = None,
             dcn_dp: int = 1) -> "MeshSpec":
        """Fill the remaining devices with (fsdp or dp) parallelism."""
        model = tp * pp * sp * ep * dcn_dp
        if num_devices % model:
            raise ValueError(
                f"tp*pp*sp*ep*dcn_dp={model} does not divide "
                f"num_devices={num_devices}")
        rest = num_devices // model
        if fsdp is None:
            return MeshSpec(dp=rest, tp=tp, pp=pp, sp=sp, ep=ep,
                            dcn_dp=dcn_dp)
        if rest % fsdp:
            raise ValueError(f"fsdp={fsdp} does not divide remainder {rest}")
        return MeshSpec(dp=rest // fsdp, fsdp=fsdp, tp=tp, pp=pp, sp=sp,
                        ep=ep, dcn_dp=dcn_dp)


def mesh_shape_for(spec: MeshSpec) -> Tuple[Tuple[str, int], ...]:
    return tuple((a, getattr(spec, a)) for a in AXIS_ORDER)


def _snake_iter(dims: Sequence[int]):
    """Yield every index of a grid of shape `dims` along a Hamiltonian path
    where consecutive indices differ by exactly 1 in exactly one dimension
    (generalized boustrophedon). dims[0] is the fastest-varying dimension.

    This is the adjacency guarantee the mesh builder rides on: a logical
    axis laid over K consecutive path positions occupies K chips connected
    by a chain of single-hop ICI links.
    """
    ndim = len(dims)
    total = 1
    for s in dims:
        total *= s
    for n in range(total):
        digits = []
        rem = n
        for size in dims:
            digits.append(rem % size)
            rem //= size
        # A dimension's direction reverses whenever the combined position of
        # all more-significant dimensions has odd parity, so every carry
        # into a higher digit moves the path one step, never a jump back.
        coord = [0] * ndim
        acc = 0
        for i in reversed(range(ndim)):
            c = digits[i] if acc % 2 == 0 else dims[i] - 1 - digits[i]
            coord[i] = c
            acc += c
        yield tuple(coord)


def _topology_ordered(devs: Sequence) -> Optional[List]:
    """Reorder TPU devices so consecutive list entries are ICI-adjacent.

    Uses `device.coords` (the chip's position on the physical torus) and
    `core_on_chip`: cores of one chip are innermost (zero-hop), then chips
    follow a snake path over the torus (single-hop steps). Returns None if
    coords are unavailable (CPU/GPU), duplicated, or the device set is not
    a full box — then the caller keeps jax's own ordering rather than
    guessing adjacency it cannot verify.

    Without it, `np.reshape` row-major over `jax.devices()` puts the
    latency-bound tp axis on non-adjacent chips of a 3D torus (the
    reference has no analog: torch process groups have no topology model
    at all, reference python/ray/train/torch/config.py:113).
    """
    recs = []
    for d in devs:
        coords = getattr(d, "coords", None)
        if coords is None:
            return None
        try:
            c = tuple(int(x) for x in coords)
        except (TypeError, ValueError):
            return None
        recs.append((c, int(getattr(d, "core_on_chip", 0) or 0), d))
    if not recs:
        return None
    ndim = len(recs[0][0])
    if any(len(c) != ndim for c, _, _ in recs):
        return None
    dims = tuple(max(c[i] for c, _, _ in recs) + 1 for i in range(ndim))
    ncores = max(core for _, core, _ in recs) + 1
    grid = {}
    for c, core, d in recs:
        if (c, core) in grid:
            return None
        grid[(c, core)] = d
    expected = ncores
    for s in dims:
        expected *= s
    if len(grid) != expected:
        return None
    out = []
    for idx in _snake_iter(dims):
        for core in range(ncores):
            out.append(grid[(idx, core)])
    return out


def _group_by_slice(devs: Sequence, num_slices: int) -> List[List]:
    """Partition devices into per-slice groups for a dcn_dp mesh.

    Real multislice TPU devices carry ``slice_index``; group by it. Virtual
    or single-slice device sets (no/constant slice_index) are split evenly —
    the dry-run/CPU stand-in for N slices.
    """
    by_idx: Dict[int, List] = {}
    for d in devs:
        idx = getattr(d, "slice_index", None)
        if idx is None:
            by_idx = {}
            break
        by_idx.setdefault(int(idx), []).append(d)
    if by_idx:
        # REAL slice membership: it must be consistent with the request —
        # silently regrouping would lay ICI axes (tp/pp) across DCN.
        groups = [by_idx[k] for k in sorted(by_idx)][:num_slices]
        if len(by_idx) < num_slices or len({len(g) for g in groups}) != 1:
            raise ValueError(
                f"dcn_dp={num_slices} needs {num_slices} equal slices; "
                f"devices report slice sizes "
                f"{ {k: len(v) for k, v in sorted(by_idx.items())} }")
        return groups
    per = len(devs) // num_slices
    return [list(devs[i * per:(i + 1) * per]) for i in range(num_slices)]


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None, *,
               topology_aware: bool = True):
    """Build a jax Mesh with the spec's axes over `devices`.

    With `topology_aware` (default), devices are first reordered along a
    snake path over their physical torus coordinates so that the innermost
    logical axis (tp — per-layer, latency-bound collectives) maps to
    ICI-adjacent chips and each outer axis to a physically contiguous
    block. Off-TPU (no coords) the jax device order is kept as-is.

    dcn_dp > 1: devices are grouped per slice (``slice_index``), each
    slice's block is topology-ordered independently, and the dcn_dp axis
    strides across slices — so every intra-slice axis stays on ICI and only
    the data axis crosses DCN.
    """
    import jax
    devs = list(devices) if devices is not None else list(jax.devices())
    if spec.num_devices > len(devs):
        raise ValueError(
            f"MeshSpec needs {spec.num_devices} devices, have {len(devs)}")
    if spec.dcn_dp > 1:
        groups = _group_by_slice(devs, spec.dcn_dp)
        per_slice = spec.devices_per_slice
        ordered_groups = []
        for g in groups:
            if len(g) < per_slice:
                raise ValueError(
                    f"dcn_dp={spec.dcn_dp} needs {per_slice} devices per "
                    f"slice, a slice has {len(g)}")
            if topology_aware:
                og = _topology_ordered(g)
                g = og if og is not None else list(g)
            ordered_groups.append(g[:per_slice])
        devs = [d for g in ordered_groups for d in g]
    else:
        if topology_aware:
            ordered = _topology_ordered(devs)
            if ordered is not None:
                devs = ordered
        # Taking a prefix of the snake path keeps a physically contiguous
        # sub-volume when the spec uses fewer devices than the slice has.
        devs = devs[: spec.num_devices]
    shape = [getattr(spec, a) for a in AXIS_ORDER]
    arr = np.array(devs, dtype=object).reshape(shape)
    return jax.sharding.Mesh(arr, AXIS_ORDER)


def local_mesh(**axis_sizes):
    """Convenience: build_mesh(MeshSpec(**axis_sizes)) on all local devices."""
    return build_mesh(MeshSpec(**axis_sizes))


def best_dp_fsdp_split(num_devices: int, params_bytes: int,
                       hbm_bytes_per_chip: int = 16 << 30) -> MeshSpec:
    """Heuristic: use pure DP until replicated params+opt-state (~4x params
    for adam in f32 master) would not fit; then shard with fsdp."""
    need = params_bytes * 4
    if need <= hbm_bytes_per_chip // 2:
        return MeshSpec(dp=num_devices)
    fsdp = 1
    while fsdp < num_devices and need // fsdp > hbm_bytes_per_chip // 2:
        fsdp *= 2
    return MeshSpec(dp=num_devices // fsdp, fsdp=fsdp)

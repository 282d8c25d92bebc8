"""TPU device plane: topology discovery, slice model, and device constants.

Role parity: the reference treats accelerators as opaque countable resources
("GPU": k) plus CUDA_VISIBLE_DEVICES plumbing (reference
python/ray/_private/worker.py, src/ray/common/ray_config_def.h resource
names). Here the TPU chip and the ICI-connected slice are first-class: the
scheduler reasons about slice topologies (e.g. v5e-8 = 2x4 ICI mesh), and the
compute plane maps slices onto `jax.sharding.Mesh` axes.
"""

from ray_tpu.tpu.topology import (
    SliceSpec,
    TpuProbeError,
    generation_of,
    local_chip_count,
    probe_chips,
    slice_mesh_shape,
)

__all__ = [
    "SliceSpec",
    "TpuProbeError",
    "generation_of",
    "local_chip_count",
    "probe_chips",
    "slice_mesh_shape",
]

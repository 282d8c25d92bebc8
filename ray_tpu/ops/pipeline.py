"""Pipeline parallelism: GPipe-schedule microbatching over a mesh axis.

Each rank of the "pp" mesh axis holds one *stage* (a contiguous chunk of
layers). Activations hop stage-to-stage with `lax.ppermute` (one ICI
neighbor transfer per tick) while microbatches stream through; after
num_microbatches + num_stages - 1 ticks every microbatch has traversed every
stage. Differentiable end-to-end (scan + ppermute + where are all
AD-compatible), so the same schedule serves forward and backward.

The reference has no in-tree pipeline parallelism (SURVEY.md §2d: PP "not
in-tree"); this is new TPU-first capability.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply_local(stage_fn: Callable, stage_params: Any, x,
                         *, axis: str = "pp", num_microbatches: int):
    """GPipe loop body; call inside shard_map with `axis` a mesh axis.

    stage_fn(stage_params, act) -> act applies this rank's stage.
    stage_params: this rank's stage weights (already sharded by shard_map).
    x: [num_microbatches, mb, ...] full input, replicated across `axis`
       (only rank 0 reads it).
    Returns [num_microbatches, mb, ...] outputs, replicated (materialized on
    the last rank, broadcast at the end).
    """
    n = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    m = num_microbatches
    perm = [(i, (i + 1) % n) for i in range(n)]  # rank r -> r+1
    zero_mb = jnp.zeros_like(x[0])
    out0 = jnp.zeros_like(x)

    def tick(carry, t):
        inbox, out = carry
        mb_idx = t - rank           # microbatch this rank works on at tick t
        active = (mb_idx >= 0) & (mb_idx < m)
        # stage 0 pulls from the input stream; others use the inbox
        src = jnp.where(rank == 0,
                        x[jnp.clip(mb_idx, 0, m - 1)], inbox)
        y = stage_fn(stage_params, src)
        y = jnp.where(active, y, zero_mb)
        # last rank records its finished microbatch
        write_idx = jnp.clip(mb_idx, 0, m - 1)
        is_last = rank == n - 1
        out = jnp.where(
            active & is_last,
            lax.dynamic_update_index_in_dim(out, y, write_idx, 0),
            out)
        inbox = lax.ppermute(y, axis, perm)
        return (inbox, out), None

    (inbox, out), _ = lax.scan(tick, (zero_mb, out0), jnp.arange(m + n - 1))
    # broadcast the last rank's outputs to every rank (masked psum)
    mask = (rank == n - 1).astype(out.dtype)
    return lax.psum(out * mask, axis_name=axis)


def pipeline_apply(stage_fn: Callable, stage_params: Any, x, mesh: Mesh, *,
                   axis: str = "pp", num_microbatches: int = None,
                   params_stage_dim: int = 0,
                   batch_axes=("dcn_dp", "dp", "fsdp")):
    """shard_map-wrapped pipeline over `mesh`.

    stage_params: pytree whose leaves have a leading stage dim of size
    mesh.shape[axis]; sliced per-rank by shard_map.
    x: [num_microbatches, mb, ...] with mb sharded over batch_axes.
    """
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if num_microbatches is None:
        num_microbatches = x.shape[0]
    data = tuple(a for a in batch_axes if a in mesh.axis_names)
    x_spec = P(None, data)  # [microbatch, mb, ...]: mb sharded on data axes
    p_spec = jax.tree.map(lambda _: P(axis), stage_params)

    def body(sp, xx):
        # strip the per-rank stage dim of 1 that shard_map leaves behind
        sp = jax.tree.map(lambda a: a[0], sp)
        return pipeline_apply_local(stage_fn, sp, xx, axis=axis,
                                    num_microbatches=num_microbatches)

    return jax.shard_map(body, mesh=mesh, in_specs=(p_spec, x_spec),
                     out_specs=x_spec, check_vma=False)(stage_params, x)

"""In-program collectives: thin, named wrappers over XLA collectives.

The reference's data-plane collectives are NCCL/GLOO groups driven from
Python per-op (reference python/ray/util/collective/collective.py:258-640);
on TPU the equivalents are *compiled into the step function* and ride ICI.
These helpers are meant for use inside `shard_map`-ped functions where mesh
axes are visible as named axes. The host-level, actor-to-actor collective
API with the reference's signatures lives in ray_tpu.util.collective.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax


AxisName = Union[str, Sequence[str]]


def allreduce_sum(x, axis: AxisName):
    return lax.psum(x, axis_name=axis)


def allreduce_mean(x, axis: AxisName):
    return lax.pmean(x, axis_name=axis)


def allreduce_max(x, axis: AxisName):
    return lax.pmax(x, axis_name=axis)


def allreduce_min(x, axis: AxisName):
    return lax.pmin(x, axis_name=axis)


def allgather(x, axis: AxisName, *, concat_dim: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name=axis, axis=concat_dim, tiled=tiled)


def reducescatter(x, axis: AxisName, *, scatter_dim: int = 0):
    return lax.psum_scatter(x, axis_name=axis, scatter_dimension=scatter_dim,
                            tiled=True)


def alltoall(x, axis: AxisName, *, split_dim: int, concat_dim: int):
    return lax.all_to_all(x, axis_name=axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=True)


def ring_permute(x, axis: str, *, shift: int = 1):
    """Send to (i+shift) mod n along `axis` — the ICI-neighbor hop used by
    ring attention and pipeline stages."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def broadcast_from(x, axis: str, *, root: int = 0):
    """Every member gets root's value (select-and-psum, compiles to an ICI
    broadcast)."""
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name=axis)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.axis_size(axis)


def broadcast_rounds(n: int, *, fanout: int = 2, root: int = 0):
    """Host-level broadcast schedule: rounds of (src, dst) legs spreading
    one copy from ``root`` to all ``n`` members, each holder re-sending to
    up to ``fanout`` new members per round (binomial tree at fanout=2, so
    ceil(log2 n) rounds instead of the n-1 serial pulls of the classic
    path). Pure schedule — the object plane drives the legs over the r08
    pipelined RPC layer (the CPU-host, gloo-style stand-in for an ICI
    collective; reference python/ray/util/collective gloo backend role).

    Members are 0..n-1; legs inside a round are independent and may run
    concurrently. A failed leg is the caller's problem (it re-stripes the
    missing member onto the classic pull path).
    """
    if n <= 0:
        return []
    if fanout < 1:
        fanout = 1
    have = [root % n]
    pending = [i for i in range(n) if i != root % n]
    rounds = []
    while pending:
        legs = []
        senders = list(have)
        for src in senders:
            for _ in range(fanout):
                if not pending:
                    break
                dst = pending.pop(0)
                legs.append((src, dst))
                have.append(dst)
        rounds.append(legs)
    return rounds

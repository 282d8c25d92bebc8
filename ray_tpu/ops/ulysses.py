"""Ulysses (all-to-all) sequence parallelism.

Alternative SP scheme to ring attention: instead of rotating kv around the
ring, two `all_to_all`s re-shard the arrays from sequence-sharded to
head-sharded, run ordinary full-sequence attention locally on each device's
subset of heads, and shard back. Cost is 2 all-to-alls of activation size;
best when num_heads >= axis size and the sequence fits per-device memory
once gathered per-head.

Absent from the reference (SURVEY.md §5); new TPU-first capability.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import attention_reference, blockwise_attention


def ulysses_attention_local(q, k, v, *, axis: str = "sp",
                            causal: bool = True,
                            scale: Optional[float] = None,
                            block_size: int = 1024):
    """Call inside shard_map; q,k,v local chunks [B, S_local, H, D] with the
    sequence dim sharded over `axis`. H must be divisible by axis size."""
    n = lax.axis_size(axis)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"heads={h} not divisible by sp axis size {n}")
    if k.shape[2] != h:  # GQA: replicate kv heads before the head split
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # seq-sharded -> head-sharded: [B, S/n, H, D] -> [B, S, H/n, D]
    def to_heads(x):
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)
    def to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)
    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    seq = qh.shape[1]
    if seq >= 4096:
        out = blockwise_attention(qh, kh, vh, causal=causal, scale=scale,
                                  block_size=block_size)
    else:
        out = attention_reference(qh, kh, vh, causal=causal, scale=scale)
    return to_seq(out)


def ulysses_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                      causal: bool = True, scale: Optional[float] = None,
                      batch_axes=("dcn_dp", "dp", "fsdp")):
    """shard_map-wrapped Ulysses attention; q,k,v global [B, S, H, D]."""
    spec = P(tuple(a for a in batch_axes if a in mesh.axis_names),
             axis, None, None)
    fn = functools.partial(ulysses_attention_local, axis=axis, causal=causal,
                          scale=scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)

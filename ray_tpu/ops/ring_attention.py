"""Ring attention: sequence parallelism over an ICI ring.

Each device in the "sp" mesh axis holds a contiguous sequence chunk of
q/k/v. kv chunks rotate around the ring with `lax.ppermute` (one ICI
neighbor hop per step — bandwidth-optimal on the torus) while each device
accumulates online-softmax partial results for its local q chunk
(ops/attention.py:_block_step math). After axis_size steps every q position
has attended to the full sequence without any device ever materializing the
full kv.

The reference has no sequence parallelism anywhere (SURVEY.md §5); this is
new TPU-first capability. Causality is handled with global-position masks,
so the same code serves pure ring (causal=False) and blockwise-causal LM
training.

Use inside shard_map (ring_attention_local) or let `ring_attention` wrap
shard_map for you given a mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import NEG_INF, _block_step


def ring_attention_local(q, k, v, *, axis: str = "sp", causal: bool = True,
                         scale: Optional[float] = None):
    """Ring attention body; call inside shard_map with `axis` a mesh axis.

    q,k,v: local chunks [B, S_local, H, D]; sequence dim sharded over `axis`.
    Returns the local output chunk [B, S_local, H, D].
    """
    n = lax.axis_size(axis)
    me = lax.axis_index(axis)
    b, s, h, d = q.shape
    _, sk, hk, _ = k.shape
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale_ = scale if scale is not None else d ** -0.5

    q_pos = me * s + jnp.arange(s)
    acc0 = jnp.zeros((b, s, h, d), jnp.float32)
    m0 = jnp.full((b, h, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    perm = [(i, (i - 1) % n) for i in range(n)]  # chunk j -> device j-1

    def step(carry, t):
        kv, acc, m, l = carry
        kb, vb = kv
        src = (me + t) % n  # global chunk index currently held
        bias = None
        if causal:
            k_pos = src * sk + jnp.arange(sk)
            bias = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0, NEG_INF)
        acc, m, l = _block_step(q, kb, vb, acc, m, l, bias, scale_)
        # rotate kv for the next step (last rotation is redundant but keeps
        # the scan body uniform; XLA overlaps the permute with compute)
        kv = (lax.ppermute(kb, axis, perm), lax.ppermute(vb, axis, perm))
        return (kv, acc, m, l), None

    (kv, acc, m, l), _ = lax.scan(step, ((k, v), acc0, m0, l0),
                                  jnp.arange(n))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                   causal: bool = True, scale: Optional[float] = None,
                   batch_axes=("dcn_dp", "dp", "fsdp")):
    """shard_map-wrapped ring attention over `mesh`.

    q,k,v: global [B, S, H, D]; batch sharded over `batch_axes`, seq over
    `axis`. Other mesh axes must not shard these arrays.
    """
    spec = P(tuple(a for a in batch_axes if a in mesh.axis_names),
             axis, None, None)
    fn = functools.partial(ring_attention_local, axis=axis, causal=causal,
                           scale=scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)

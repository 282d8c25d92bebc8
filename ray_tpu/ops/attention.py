"""Attention primitives: reference and memory-efficient blockwise forms.

All take [batch, seq, heads, head_dim] ("BSHD") q/k/v. The blockwise form is
the online-softmax formulation (the math under FlashAttention and Ring
Attention): the kv sequence is processed in chunks with a running max and
denominator, so peak memory is O(block^2) instead of O(seq^2) and the same
inner step serves ring attention (ops/ring_attention.py) where kv chunks
arrive over ICI instead of from a local slice.

Differentiable by construction (lax.scan); the Pallas fused kernels in
ops/flash.py are the TPU fast path with the same signature.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.flash import _on_tpu, flash_attention
from ray_tpu.parallel.sharding import DEFAULT_RULES, LogicalRules

NEG_INF = -1e30


def attention_reference(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        segment_ids=None, window: Optional[int] = None,
                        first_key=None):
    """Plain softmax attention. q,k,v: [B, S, H, D] (k/v may have fewer heads
    for GQA — heads must divide evenly). ``window``, ``first_key``: the
    causal band of ops/flash.py ``flash_attention``, by key index."""
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    if hk != hq:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(mask, k=sk - sq - window)
            if first_key is not None:
                mask &= jnp.arange(sk) >= first_key
    if segment_ids is not None:
        seg_q, seg_k = segment_ids
        seg_mask = seg_q[:, :, None] == seg_k[:, None, :]
        mask = seg_mask[:, None] if mask is None else (mask & seg_mask[:, None])
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _block_step(q, kb, vb, acc, m, l, logits_bias, scale):
    """One online-softmax update: attend q block against one kv block.

    q: [B, Bq, H, D]; kb/vb: [B, Bk, H, D]; acc: [B, Bq, H, D] f32;
    m, l: [B, H, Bq] f32 running max / denominator.
    logits_bias: [Bq, Bk] additive mask bias (0 or NEG_INF) or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
    if logits_bias is not None:
        s = s + logits_bias[None, None]
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows: keep m finite so exp() stays 0, not nan
    m_safe = jnp.maximum(m_new, NEG_INF / 2)
    p = jnp.exp(s - m_safe[..., None])
    corr = jnp.exp(m - m_safe)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vb.dtype), vb).astype(jnp.float32)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return acc_new, m_new, l_new


def blockwise_attention(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        block_size: int = 512):
    """Memory-efficient attention via lax.scan over kv blocks. [B,S,H,D]."""
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else d ** -0.5
    bk = min(block_size, sk)
    if sk % bk:
        raise ValueError(f"seq_k={sk} not divisible by block_size={bk}")
    nblk = sk // bk
    kb = k.reshape(b, nblk, bk, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, bk, h, d).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.arange(sq) + (sk - sq)  # align causal diag when sq != sk
    acc0 = jnp.zeros((b, sq, h, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)

    def step(carry, inp):
        acc, m, l = carry
        idx, kblk, vblk = inp
        bias = None
        if causal:
            kpos = idx * bk + jnp.arange(bk)
            bias = jnp.where(q_pos[:, None] >= kpos[None, :], 0.0, NEG_INF)
        acc, m, l = _block_step(q, kblk, vblk, acc, m, l, bias, scale)
        return (acc, m, l), None

    (acc, m, l), _ = lax.scan(
        step, (acc0, m0, l0), (jnp.arange(nblk), kb, vb))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def auto_path(sq: int, sk: int, d: int) -> str:
    """The path ``impl="auto"`` takes for ``sq`` queries over ``sk`` keys of
    head width ``d``: ``flash`` on a TPU when all three are multiples of
    128 (a 192-wide head is not), else ``blockwise`` from 2,048 queries on,
    else ``reference``. The caller of a compiled program puts it on its
    span (``generate.call_span``'s ``attention_path``), so that leaving the
    flash path is seen."""
    if _on_tpu() and sq % 128 == 0 and sk % 128 == 0 and d % 128 == 0:
        return "flash"
    return "blockwise" if sq >= 2048 else "reference"


def mha(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
        block_size: int = 512, impl: str = "auto", mesh=None,
        rules: LogicalRules = DEFAULT_RULES):
    """Dispatch: 'reference' | 'blockwise' | 'flash' (Pallas) | 'auto'.

    auto = flash on TPU when shapes are tile-aligned, else blockwise for long
    sequences, else reference. 'flash' is the kernel or an error, never a
    stand-in. ``mesh``/``rules``: the mesh q/k/v are sharded over and the
    rules that laid them out (flash runs per shard; the jnp forms are
    partitioned by XLA).
    """
    if impl == "reference":
        return attention_reference(q, k, v, causal=causal, scale=scale)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   block_size=block_size)
    if impl == "flash":
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               mesh=mesh, rules=rules)
    path = auto_path(q.shape[1], k.shape[1], q.shape[3])
    if path == "flash":
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               mesh=mesh, rules=rules)
    if path == "blockwise":
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   block_size=block_size)
    return attention_reference(q, k, v, causal=causal, scale=scale)

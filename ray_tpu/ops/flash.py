"""Fused flash-attention Pallas kernels for TPU (forward + backward).

FlashAttention-2 style: the kv-block loop is the innermost (sequential) grid
dimension, with the running max / denominator / accumulator living in VMEM
scratch that persists across that dimension; softmax is never materialized
in HBM. Backward recomputes probabilities blockwise from the saved
log-sum-exp and accumulates dq / dk / dv in scratch.

MXU notes: matmuls via dot_general with preferred_element_type=float32;
block sizes default to 128 (MXU tile); causal blocks entirely above the
diagonal are skipped with pl.when.

A window (``flash_attention(..., window=w)``, static, causal only) bands the
attention by key index: a query attends to the ``w`` keys up to and with
its own. The three kernels take one more clause in which blocks run (a
block of queries against a block of keys that lies wholly more than the
window behind it does not) and in the mask, and the forward's index map of
K and V clamps a key block to the band, so that a grid step that does not
run fetches nothing new: at 2,048 queries over 2,560 keys in blocks of 512,
window 513, a block of queries visits two key blocks of five. Queries
may be the last of a longer key sequence, as before; ``first_key`` (a
prefetched int32 scalar, traced or not) leaves out the keys before that
index, which a prompt's first chunk has in its window's place and which do
not exist. Callers: models/latent.py hands a window layer's prefill chunk
here (the forward alone runs in that cell; the backward kernels carry the
band so that the rule is whole under a gradient). Without a window the
traced program is what it was before there was one.

Differentiable via jax.custom_vjp. TPU only: flash_attention raises on any
other backend (ops/attention.py mha(impl="auto") picks a CPU form there).
A Mosaic call cannot be partitioned by GSPMD, so under a multi-device mesh
the kernel runs per shard inside shard_map, q/k/v split the way the
caller's LogicalRules lay out a [batch, seq, heads, kv] activation.

What outlives the forward pass. The backward kernels read, beside q, k, v
and the output's gradient, two things only the forward kernel makes: ``out``
(for delta = sum(dout * out)) and the log-sum-exp of each query's scores.
Where ``_worth_keeping`` says so, the forward rule marks both with the
checkpoint name ``KEPT`` (jax.ad_checkpoint.checkpoint_name), the
log-sum-exp as a column ``[BH, S]`` float32: the kernel writes it over 128
lanes for the TPU's tiling, and ``_flash_bwd`` spreads a column over the
lanes again, as it does delta. A caller that remats the layer around the
kernel under ``save_only_these_names(KEPT)`` (models/transformer.py
``_layer_bodies``) then runs the forward kernel once, not a second time in
the backward pass; under any other policy, and without remat, the name does
nothing. Both are named, or the kernel would run again for the one left
out; q, k and v are not: they come out of the caller's projections and
transposes and are made again with them. At 2 rows x 32 heads, S = 8,192,
Dv = 128 in bfloat16 a layer keeps 134,217,728 + 2,097,152 bytes (the
log-sum-exp over its lanes would be 268,435,456). Whether that is worth it
is the trade ``KEEP_FROM`` states: the multiply-adds a second run of the
forward would execute for each byte kept. Below it nothing is named and the
residuals are the kernel's outputs as they are (taking the column and
spreading it again is three passes over ``[BH, S, 128]`` a layer, 1.3 ms
at that size, which only what is kept repays).
"""

from __future__ import annotations

import functools
import math
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.parallel.sharding import DEFAULT_RULES, LogicalRules
from ray_tpu.tpu.topology import generation

NEG_INF = -1e30
# the name on what the backward kernels read of the forward kernel, where
# it is worth keeping across a caller's remat
KEPT = "flash_kept"
# Multiply-adds that a second run of the forward kernel executes for each
# byte that keeping ``out`` and the log-sum-exp holds until the backward
# pass: they are kept from here on. The v5e's kernels execute 46-56 T
# multiply-adds a second, so at 2,000 a gigabyte kept buys about 40 ms a
# step. A causal S = 8,192 reads 5,041 at q/k 192 and v 128 and 4,064 at a
# head of 256; S = 2,048 at a head of 128 reads 1,008 and keeps nothing.
KEEP_FROM = 2000


def _dot(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """a @ b.T"""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _first_key(refs, window):
    """A banded call's refs lead with the prefetched scalar, the index of
    the first key that exists -> (that index or None, the other refs)."""
    return (refs[0][0], refs[1:]) if window is not None else (None, refs)


def _reaches(qi, ki, first, *, block_q, block_k, q_offset, window):
    """Whether any query of block ``qi`` attends to any key of block
    ``ki``: the block's last query row is not before the key block's first
    column and, under a window, its first row is within the window of the
    key block's last column, which exists."""
    run = (qi * block_q + q_offset + block_q - 1) >= ki * block_k
    if window is not None:
        k_last = ki * block_k + block_k - 1
        run = run & (qi * block_q + q_offset - k_last < window) \
            & (k_last >= first)
    return run


def _band(qi, ki, first, *, block_q, block_k, q_offset, window):
    """[Bq, Bk]: the (query, key) pairs of block (qi, ki) inside the causal
    band: key index <= the query's, and under a window less than ``window``
    before it and not before ``first``."""
    rows = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    query, key = rows + qi * block_q + q_offset, cols + ki * block_k
    mask = query >= key
    if window is not None:
        mask = mask & (query - key < window) & (key >= first)
    return mask


def _band_blocks(i, first, *, block_q, block_k, q_offset, window):
    """(lowest, highest) key block that query block ``i``'s band reaches:
    what an index map clamps a key block to, so that a grid step that does
    not run fetches the block its neighbour holds already."""
    q_first = i * block_q + q_offset
    lowest = jnp.maximum(jnp.maximum(q_first - (window - 1), first), 0)
    return lowest // block_k, (q_first + block_q - 1) // block_k


def _fwd_kernel(*refs, scale, causal, **at):
    """``at``: block_q, block_k, q_offset and window, what `_reaches` and
    `_band` place a grid step by (the backward kernels' too)."""
    first, refs = _first_key(refs, at["window"])
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # the block's last q row against its first k column, and under a
    # window its first q row against its last k column, decide relevance
    run = _reaches(qi, ki, first, **at) if causal else True

    @pl.when(run)
    def _compute():
        # Matmul inputs stay in their native dtype (bf16 rides the MXU at
        # full rate); preferred_element_type=f32 in _dot/_dot_t gives f32
        # accumulation, so only the elementwise softmax state is f32.
        q = q_ref[0]
        k = k_ref[0]
        s = _dot_t(q, k) * scale                      # [Bq, Bk] f32
        if causal:
            s = jnp.where(_band(qi, ki, first, **at), s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(m_prev - m_safe)
        l_ref[:] = l_ref[:] * corr + p.sum(-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + _dot(
            p.astype(v_ref.dtype), v_ref[0])
        m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # lse broadcast across the 128-lane dim (TPU block alignment)
        lse_ref[0] = jnp.broadcast_to(m_ref[:] + jnp.log(l),
                                      lse_ref.shape[1:])


def _call(kernel, first, *, grid, in_specs, out_specs, scratch_shapes,
          **kw):
    """``pl.pallas_call`` over ``grid``; with ``first`` (a banded call) the
    scalar is prefetched: the kernel's first ref and the index maps' last
    argument."""
    if first is None:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs,
                              scratch_shapes=scratch_shapes, **kw)
    call = pl.pallas_call(kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes), **kw)
    return functools.partial(call, first)


def _first_operand(first, window):
    """The prefetched operand of a banded call, int32 [1] (no key left out
    where ``first`` is None); None without a window."""
    if window is None:
        return None
    return jnp.asarray(0 if first is None else first, jnp.int32).reshape(1)


def _flash_fwd(q, k, v, *, causal, scale, block_q, block_k, window=None,
               first=None, interpret=False):
    """q, k: [BH, S, D], v: [BH, Sk, Dv] -> (out [BH, Sq, Dv], lse [BH, Sq,
    128]). ``window``, ``first``: the band (`flash_attention`).
    ``interpret`` runs the Pallas interpreter: only tests pass it."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    d_v = v.shape[-1]
    bq, bk = min(block_q, sq), min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0
    grid = (bh, sq // bq, sk // bk)
    at = dict(block_q=bq, block_k=bk, q_offset=sk - sq, window=window)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal, **at)

    def keys(b, i, j, *first):
        if window is not None:      # a step that does not run fetches none
            j = jnp.clip(j, *_band_blocks(i, first[0][0], **at))
        return b, j, 0

    out, lse = _call(
        kern, _first_operand(first, window),
        name="rt_flash_fwd",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bk, d), keys),
            pl.BlockSpec((1, bk, d_v), keys),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d_v), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j, *_: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d_v), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(*refs, scale, causal, **at):
    first, refs = _first_key(refs, at["window"])
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
     dk_acc, dv_acc) = refs
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _reaches(qi, ki, first, **at) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _dot_t(q, k) * scale                      # [Bq, Bk] f32
        if causal:
            s = jnp.where(_band(qi, ki, first, **at), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])          # [Bq, Bk] f32
        # p/ds are cast to the input dtype for their matmuls (standard
        # flash-bwd practice: bf16 MXU inputs, f32 accumulation).
        dv_acc[:] += _dot(p.astype(do.dtype).T, do)   # [Bk, D]
        dp = _dot_t(do, v)                            # [Bq, Bk]
        ds = (p * (dp - delta_ref[0][:, :1]) * scale).astype(q.dtype)
        dk_acc[:] += _dot(ds.T, q)                    # [Bk, D]

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, **at):
    first, refs = _first_key(refs, at["window"])
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _reaches(qi, ki, first, **at) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _dot_t(q, k) * scale
        if causal:
            s = jnp.where(_band(qi, ki, first, **at), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = _dot_t(do, v)
        ds = (p * (dp - delta_ref[0][:, :1]) * scale).astype(k.dtype)
        dq_acc[:] += _dot(ds, k)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _over_lanes(column):
    """[BH, S] -> [BH, S, 128]: the blocks the backward kernels read."""
    return jnp.broadcast_to(column[..., None], column.shape + (128,))


def _flash_bwd(res, g, *, causal, scale, block_q, block_k, window=None,
               first=None, interpret=False):
    """``res``: (q, k, v, out, the log-sum-exp as the forward kernel wrote
    it, [BH, Sq, 128], or as the column [BH, Sq] that is kept by name)."""
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    d_v = v.shape[-1]
    bq, bk = min(block_q, sq), min(block_k, sk)
    at = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
              q_offset=sk - sq, window=window)
    first = _first_operand(first, window)
    delta = _over_lanes(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1))
    if lse.ndim == 2:             # kept as a column (_flash_bhsd_fwd)
        lse = _over_lanes(lse)

    dkv = _call(
        functools.partial(_bwd_dkv_kernel, **at), first,
        name="rt_flash_dkv",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=(bh, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i, *_: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, d_v), lambda b, j, i, *_: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, d_v), lambda b, j, i, *_: (b, i, 0)),  # do
            pl.BlockSpec((1, bq, 128), lambda b, j, i, *_: (b, i, 0)),  # lse
            pl.BlockSpec((1, bq, 128), lambda b, j, i, *_: (b, i, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
            pl.BlockSpec((1, bk, d_v), lambda b, j, i, *_: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d_v), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    dk, dv = dkv

    dq = _call(
        functools.partial(_bwd_dq_kernel, **at), first,
        name="rt_flash_dq",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=(bh, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, bk, d_v), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, bq, d_v), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j, *_: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, causal, scale, block_q, block_k, window, first):
    out, _ = _flash_fwd(q, k, v, causal=causal, scale=scale,
                        block_q=block_q, block_k=block_k, window=window,
                        first=first)
    return out


def _worth_keeping(q, k, v, causal: bool, window=None) -> bool:
    """Whether a head's ``out`` [Sq, Dv] and log-sum-exp column are kept
    for the backward pass by name, from the kernel's shapes alone: the
    multiply-adds a second run of the forward executes (D + Dv a (query,
    key) pair it visits; under a window the band's pairs) over the bytes
    kept reach ``KEEP_FROM``."""
    (_, sq, d), sk, d_v = q.shape, k.shape[1], v.shape[2]
    pairs = sq * (sk - sq) + sq * (sq + 1) // 2 if causal else sq * sk
    if window is not None:
        # the queries from ``whole`` on have a whole window behind them
        whole = min(max(window - 1 - (sk - sq), 0), sq)
        pairs = whole * (sk - sq) + whole * (whole + 1) // 2 \
            + (sq - whole) * window
    return pairs * (d + d_v) >= KEEP_FROM * sq * (q.dtype.itemsize * d_v + 4)


def _flash_bhsd_fwd(q, k, v, causal, scale, block_q, block_k, window=None,
                    first=None):
    out, lse = _flash_fwd(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, window=window,
                          first=first)
    if _worth_keeping(q, k, v, causal, window):
        # the log-sum-exp's 128 lanes hold one number: the column is kept
        out, lse = checkpoint_name((out, lse[..., 0]), KEPT)
    return out, (q, k, v, out, lse, first)


def _flash_bhsd_bwd(causal, scale, block_q, block_k, window, res, g):
    *res, first = res
    # ``first`` is an index: it has no cotangent
    return _flash_bwd(res, g, causal=causal, scale=scale, block_q=block_q,
                      block_k=block_k, window=window, first=first) + (None,)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def _fit_block(seq: int, want: int) -> int:
    """Largest MXU-aligned block <= want that divides seq (or seq itself)."""
    if seq <= want:
        return seq
    b = (want // 128) * 128
    while b > 128 and seq % b:
        b -= 128
    return b if seq % b == 0 else seq


# Per-generation default (block_q, block_k). v5e measured: 512x1024 is ~4x
# the throughput of 128x128 (grid-step overhead amortizes over bigger MXU
# work, 67 TF/s fwd at S=16k vs 10 TF/s). Larger-VMEM generations take a
# wider kv block. autotune_blocks() refines these per (generation, seq)
# on the live chip and its results take precedence.
# Head width 256 (16 q heads on 2 kv heads, S = 8192, 2 rows; v5e, PR 37):
# fwd+bwd 43.8 ms at 512x1024 (113 TFLOP/s causal), 43.7 at 1024x512, 47.2 at
# 512x512, 49.5 at 256x2048, 51.2 at 256x1024, 57.5 at 256x512, 74.4 at
# 128x1024; 1024x1024 and 512x2048 do not fit VMEM at that width (the dkv
# kernel's stack). The v5e entry stands for both widths.
_GEN_BLOCKS = {
    "v3": (256, 512),
    "v4": (512, 1024),
    "v5e": (512, 1024),
    "v5p": (512, 1024),
    "v6e": (512, 2048),
}
# (generation, seq, head_dim, causal) -> (block_q, block_k)
_tuned_blocks: dict = {}


def _default_blocks(seq_q: int, seq_k: int, head_dim: int, causal: bool,
                    window: Optional[int] = None):
    gen = generation()
    want_q, want_k = _tuned_blocks.get(
        (gen, seq_k, head_dim, causal), _GEN_BLOCKS[gen])
    if window is not None:
        # a key block wider than the window's reach behind a query adds
        # pairs that the band masks
        want_k = min(want_k, max(128, -(-(window - 1) // 128) * 128))
    return _fit_block(seq_q, want_q), _fit_block(seq_k, want_k)


def autotune_blocks(seq: int, *, head_dim: int = 128, heads: int = 16,
                    batch: int = 8, causal: bool = True,
                    candidates=None) -> tuple:
    """Measure fwd+bwd flash throughput for candidate block shapes on the
    LIVE chip and cache the winner for (generation, seq, head_dim, causal)
    — the parameters block VMEM cost actually depends on.

    Measure at the REAL workload occupancy: callers should pass the
    model's heads/batch (grid size changes which block shape wins — the
    round-3 tuner measured a batch-2/heads-8 proxy for a batch-8/heads-16
    model and could crown a loser for the real shape). Timing is
    best-of-2 windows of 5 steps so one slow window can't crown a loser
    either.

    One-time cost per shape (~seconds); subsequent flash_attention calls
    with default blocks pick the tuned pair up automatically. A candidate
    the compiler refuses for VMEM is dropped and named on stderr; any
    other failure propagates.
    """
    gen = generation()
    key = (gen, seq, head_dim, causal)
    if key in _tuned_blocks:
        return _tuned_blocks[key]
    static = _GEN_BLOCKS[gen]
    if candidates is None:
        candidates = [(256, 512), (512, 512), (512, 1024), (512, 2048),
                      (1024, 1024)]
    if static not in candidates:
        candidates = [static] + list(candidates)
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (batch, seq, heads, head_dim), jnp.bfloat16)
    best, best_dt = None, float("inf")
    dropped = []
    for bq, bk in candidates:
        if bq > seq or bk > seq:
            continue

        def run(q, bq=bq, bk=bk):
            out = flash_attention(q, q, q, causal=causal,
                                  block_q=_fit_block(seq, bq),
                                  block_k=_fit_block(seq, bk))
            return jnp.sum(out * out)

        try:
            g = jax.jit(jax.grad(run))
            jax.block_until_ready(g(q))  # compile
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            dropped.append((bq, bk))  # does not fit this chip's VMEM
            continue
        jax.block_until_ready(g(q))  # settle
        dt = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(5):
                r = g(q)
            jax.block_until_ready(r)
            dt = min(dt, time.perf_counter() - t0)
        if dt < best_dt:
            best, best_dt = (bq, bk), dt
    if best is None:
        raise RuntimeError(
            f"flash autotune {key}: no candidate compiled (dropped for "
            f"VMEM: {dropped})")
    _tuned_blocks[key] = best
    print(f"[flash-autotune] {key} -> blocks {best}; dropped for VMEM: "
          f"{dropped}", file=sys.stderr, flush=True)
    return best


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None, first_key=None,
                    mesh=None, rules: LogicalRules = DEFAULT_RULES):
    """Fused attention; q, k: [B, S, H, D], v: [B, Sk, H, Dv] (a value
    width of its own, as expanded latent attention has: D 192, Dv 128) ->
    [B, Sq, H, Dv]. The queries are the last Sq of the Sk key positions.

    ``window`` (static; causal only): a query attends to the ``window``
    keys up to and with its own, by index, and a block of queries visits
    only the key blocks that band reaches. ``first_key`` (an int32 scalar,
    traced or not; with a window only): the keys before that index do not
    exist and no query attends to them, as before a prompt's position 0.

    Default block sizes come from the per-generation table (refined by
    autotune_blocks on the live chip); blocks shrink to fit/divide the
    sequence. Raises off-TPU. With a multi-device ``mesh`` the kernel runs
    on each device's shard, batch and heads split as ``rules`` say — pass
    the rules the rest of the program was sharded by, and the shard_map
    boundary moves nothing.
    """
    if not _on_tpu():
        raise RuntimeError(
            "flash_attention needs a TPU backend, found "
            f"{jax.default_backend()!r}; use mha(impl='auto') for a form "
            "that runs here")
    if window is None and first_key is not None or \
            window is not None and not causal:
        raise ValueError("a window is a causal band, and first_key one's "
                         f"lower end: causal={causal}, window={window}")
    if block_q is None or block_k is None:
        dq, dk = _default_blocks(q.shape[1], k.shape[1], q.shape[-1],
                                 causal, window)
        block_q = block_q if block_q is not None else dq
        block_k = block_k if block_k is not None else dk
    local = functools.partial(_flash_bshd, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              window=window)
    # a band's lower end rides as an operand that every device holds whole
    band = () if window is None else (_first_operand(first_key, window),)
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        # One device, or already inside a shard_map (ops/pipeline.py):
        # the operands are this device's own.
        return local(q, k, v, *band)
    # All mesh axes manual (Mosaic refuses anything less); axes the spec
    # does not name see replicated operands. The sequence stays whole.
    spec = rules.spec(("batch", None, "heads", None), mesh)
    head_axes = rules.spec(("heads",), mesh)
    head_shards = math.prod(
        mesh.shape[a] for ax in head_axes
        for a in ((ax,) if isinstance(ax, str) else ax))
    if k.shape[2] % head_shards:
        # Fewer kv heads than head shards (GQA/MQA under tp): one copy per
        # q head, which then splits the way q does.
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec) + (jax.sharding.PartitionSpec(),) * len(
            band),
        out_specs=spec, check_vma=False)(q, k, v, *band)


def _flash_bshd(q, k, v, first=None, *, causal, scale, block_q, block_k,
                window=None):
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale_ = scale if scale is not None else d ** -0.5

    def to_bhsd(x, s):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    out = _flash_bhsd(to_bhsd(q, sq), to_bhsd(k, sk), to_bhsd(v, sk),
                      causal, scale_, block_q, block_k, window, first)
    return out.reshape(b, h, sq, v.shape[-1]).transpose(0, 2, 1, 3)


@functools.cache
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"

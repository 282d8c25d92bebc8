"""The chunked gated delta rule as fused Pallas kernels for TPU (forward +
backward); ops/gated_delta.py holds the mathematics and picks the path.

Grid ``(batch, head group, chunk)``: batch and head groups are parallel, the
chunk index is the innermost, sequential dimension, and the float32 ``[dk,
dv]`` state of each of the group's heads lives in VMEM scratch across it (as
flash's running max and accumulator do). One grid step takes one chunk of
``HEADS`` value heads: their dependent chains (state -> v' -> state) are
independent of one another, so the scheduler overlaps them, and their ``[C,
C]`` arrays lie two beside each other on the 128 lanes (C = 64), so the VPU
works on full registers.

Operands are read where the convolution left them (head widths that are no
multiple of 128 lanes padded to the next one first:
``gated_delta_rule_kernels``): q, k ``[B, S, Hk*dk]``
and v ``[B, S, Hv*dv]`` in blocks of ``(C, heads*d)`` at the group's column,
a value head reading its key head's columns, so nothing is laid out as ``[B,
H, n, C, d]`` in HBM, q and k are not repeated and their unit length is
taken in VMEM (no float32 copy of them exists in HBM, forward or backward).
g and beta come as ``[B, Hv/heads, n, 2 heads, C]`` (a position a lane, 2
MB together at the benchmark's shape, laid out by XLA under the same
scope); their running sum and the turn to a position a sublane are the
kernels'. A chunk's ``decay``, ``A``, ``T = (I + A)^-1``, ``v'`` and scores
are made in VMEM and never written to HBM, but for what the backward reads
again: the forward run for a gradient also writes, in the inputs' dtype,
each chunk's starting state (``[B, Hv, n, dk, dv]``), ``T`` and ``v'``,
exactly the rounded values its own matmuls consumed. The backward is a
reverse scan over chunks with the state's gradient in scratch; it
recomputes the scores and ``K S``, inverts nothing, and sums dq and dk over
the value heads that share a key head before it takes them back through the
unit length. With ``W = T diag(beta e^G) K`` and ``U = T diag(beta) V`` of
the module's docstring, ``v' = U - W S = T (beta (V - e^G (K S)))``: one
product with ``T`` instead of two.

Precision as the jnp form: ``A`` and ``T`` float32, the inverse's small
blocks exact on the VPU and its merges float32 products at the highest
precision (``_product``), g's running sum, ``exp`` and the gates float32,
the state float32; the large products take operands in the inputs' dtype
and accumulate in float32.

The short causal convolution in front of the rule has kernels of its own at
the end of this file: one position on a carried tail (``rt_gdn_conv_step``)
and a whole sequence, forward and backward (``rt_gdn_conv_fwd``,
``rt_gdn_conv_bwd``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.gated_delta import EPS, KEPT
from ray_tpu.ops.gated_delta import _beside as _over_lanes

BASE = 8        # side of the blocks the VPU inverts row by row: a sublane tile
# Value heads a grid step. On a v5e at [2, 8192, 32 on 16 key heads, 128],
# bfloat16, the rule alone, forward / forward + backward (my chip run, PR
# 38, operands as 4-D arrays, so with XLA's 1.4 ms of copies to [B, S, H d]
# that the model does not pay): 4 heads 10.27 / 19.29 ms, 8 heads 10.25 /
# 18.28, 16 heads 10.20 / 17.97 (twice the code of 8 for 0.3 ms); 2 heads
# were 1.5 / 3.3 ms behind 4 in the first round. The jnp form: 14.4 / 39.1.
HEADS = 8

_F32 = jnp.float32


def _dot(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _dot_nt(a, b):
    """a @ b.T"""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _dot_tn(a, b):
    """a.T @ b"""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _dot_f32(a, b):
    """float32 a @ b at the highest precision (Mosaic's own passes)."""
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=_F32)


def _unit(x):
    """Rows of x [C, d] taken to unit length, float32 -> (rows, 1 / their
    length), as ops/gated_delta.py's ``unit``."""
    x = x.astype(_F32)
    inverse = lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + EPS)
    return x * inverse, inverse


def _split(x):
    """float32 -> three bfloat16 arrays whose sum is x to its last bit."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)


def _product(x, y, head_of):
    """x @ y, float32 at the highest precision: the six bfloat16 products hi
    hi, hi mid, mid hi, mid mid, hi lo, lo hi that XLA's HIGHEST takes,
    summed small to large. ``x``: a split [rows, m C], m heads beside each
    other on the lanes; ``y``: a split [C, m C], of which every head
    multiplies by its own [C, C] (the heads' blocks put on a diagonal). The
    pieces of ``x`` that meet one piece of ``y`` are stacked into one dot, so
    the MXU loads three sets of weights and not six: at 64 rows a dot the
    loads were most of a product's time (PERF.md, PR 38)."""
    n = x[0].shape[0]
    heads = y[0].shape[1] // y[0].shape[0]

    def diagonal(piece):    # [C, m C] -> [m C, m C]
        if heads == 1:
            return piece
        return jnp.concatenate(
            [jnp.where(head_of == h, piece, jnp.zeros_like(piece))
             for h in range(heads)], axis=0)

    by_hi = _dot(jnp.concatenate(x, axis=0), diagonal(y[0]))
    by_mid = _dot(jnp.concatenate(x[:2], axis=0), diagonal(y[1]))
    by_lo = _dot(x[0], diagonal(y[2]))
    return ((by_hi[2 * n:] + by_lo) + by_mid[n:]) \
        + (by_hi[n:2 * n] + by_mid[:n]) + by_hi[:n]


def _column_over_block(x, j, within):
    """x [BASE, W], its lanes in blocks of BASE: column j of every block,
    spread over its block's lanes (lane rotations: to the block's first
    lane, then doubling)."""
    column = jnp.where(within == j, x, 0.0)
    if j:
        column = pltpu.roll(column, x.shape[1] - j, axis=1)
    for reach in (1, 2, 4):
        column = column + pltpu.roll(column, reach, axis=1)
    return column


def _diagonal_blocks_inverse(a, cols):
    """(I + a's diagonal blocks of side BASE)^-1 as [C, m C] with zeros off
    those blocks: forward substitution over the rows inside a block, exact
    float32 on the VPU. The blocks of every head are first put on the same
    BASE sublanes (block r keeps its own lanes), so that one step of the
    substitution serves them all: (a column of ``a``, spread over its
    block's lanes) x (a row of the inverse, broadcast over the sublanes)."""
    c, width = a.shape
    block_of = cols[:BASE] >> int(math.log2(BASE))          # of the lane
    within = cols[:BASE] & (BASE - 1)
    packed = functools.reduce(jnp.add, [
        jnp.where(block_of == r, a[r * BASE:(r + 1) * BASE], 0.0)
        for r in range(c // BASE)])
    sublane = lax.broadcasted_iota(jnp.int32, (BASE, width), 0)
    t = (sublane == within).astype(_F32)
    for j in range(BASE - 1):       # row j of t is final when its turn comes
        t = t - _column_over_block(packed, j, within) * t[j:j + 1, :]
    return jnp.concatenate([jnp.where(block_of == r, t, 0.0)
                            for r in range(c // BASE)], axis=0)


def _unit_lower_inverse(a, rows, cols, head_of):
    """(I + a)^-1 for strictly lower triangular float32 ``a``, C a power of
    two from BASE = 8, as ops/gated_delta.py's: substitution inside the
    diagonal blocks of side BASE, then the doubling merge ``[[Ta, 0], [-Tb
    A21 Ta, Tb]]``, each level two whole-matrix products under a mask (a
    block diagonal times a block diagonal is one) of which only the lower
    blocks' rows are computed. ``a`` is [C, m C], m heads beside each other
    on the lanes; ``cols`` the column within a head. (The first merge on
    the VPU too, by the lane rotations of the substitution, was no faster:
    PERF.md, PR 38.)"""
    c = a.shape[0]
    t = _diagonal_blocks_inverse(a, cols)
    side = BASE
    while side < c:
        shift = int(math.log2(side))
        odd = [r for r in range(c // BASE) if (r * BASE // side) & 1]

        def lower_rows(x):      # the rows of each pair's lower block
            return jnp.concatenate(
                [x[r * BASE:(r + 1) * BASE] for r in odd], axis=0)

        def back(x, rest):      # ... put back among the rows of ``rest``
            return jnp.concatenate([
                x[odd.index(r) * BASE:(odd.index(r) + 1) * BASE]
                if r in odd else rest[r * BASE:(r + 1) * BASE]
                for r in range(c // BASE)], axis=0)

        a21 = lower_rows(jnp.where(
            (cols >> shift) == (rows >> shift) - 1, a, 0.0))
        inner = _product(_split(a21), _split(t), head_of)    # A21 Ta
        t21 = _product(_split(lower_rows(t)),
                       _split(back(inner, jnp.zeros_like(t))), head_of)
        t = back(lower_rows(t) - t21, t)
        side *= 2
    return t


def _beside(columns, head_of):
    """Per-head [C, 1] columns -> [C, m C], each over its head's lanes."""
    wide = columns[-1]
    for h in range(len(columns) - 2, -1, -1):
        wide = jnp.where(head_of == h, columns[h], wide)
    return wide


def _lane_packs(heads, c):
    """The step's heads in runs that fill the 128 lanes with their [C, C]
    arrays beside each other: (first head, how many, rows, column within a
    head, head of the lane), the last three [C, m C] int32."""
    pack = max(1, 128 // c)
    shift = int(math.log2(c))
    for first in range(0, heads, pack):
        m = min(pack, heads - first)
        rows = lax.broadcasted_iota(jnp.int32, (c, m * c), 0)
        lane = lax.broadcasted_iota(jnp.int32, (c, m * c), 1)
        yield first, m, rows, lane & (c - 1), lane >> shift


def _running(c, backward=False):
    """[C, C] of ones that takes a row of g to its running sum inside the
    chunk (``backward``: a row of gamma's gradient to g's)."""
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return (i >= j if backward else i <= j).astype(_F32)


def _gate_vectors(gates_ref, heads):
    """The step's gates [2 heads, C] (g of each head, then beta of each) ->
    gamma, g's running sum inside the chunk, and beta, with a position a
    lane [2 heads, C] and with a position a sublane [C, 2 heads]."""
    gates = gates_ref[...]
    c = gates.shape[1]
    summed = _dot_f32(gates, _running(c))
    is_g = lax.broadcasted_iota(jnp.int32, gates.shape, 0) < heads
    by_row = jnp.where(is_g, summed, gates)
    return by_row, by_row.T


def _decay(g_cols, g_rows, keep):
    """exp(gamma_i - gamma_j) where ``keep`` (i >= j), else 0."""
    return jnp.where(keep, jnp.exp(jnp.where(keep, g_cols - g_rows, 0.0)),
                     0.0)


def _exp_over_lanes(x, n):
    """exp of x [1, 1] as [1, n], for a product to take over the sublanes:
    Mosaic broadcasts one way a time, and folds two broadcasts into one
    unless something stands between them."""
    return jnp.exp(jnp.broadcast_to(x, (1, n)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, gates_ref, o_ref, *rest, heads, rep,
                dk, dv, scale, save, final):
    if save:
        states_ref, t_ref, fresh_ref, state_ref = rest
    elif final:
        final_ref, state_ref = rest
    else:
        (state_ref,) = rest
    c = v_ref.shape[0]
    dt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    by_row, by_col = _gate_vectors(gates_ref, heads)
    for first, m, rows, cols, head_of in _lane_packs(heads, c):
        pack = range(first, first + m)
        lanes = slice(first * c, (first + m) * c)
        qk, scores = {}, {}
        for j in sorted({i // rep for i in pack}):
            key = slice(j * dk, (j + 1) * dk)
            q = (_unit(q_ref[:, key])[0] * scale).astype(dt)
            k = _unit(k_ref[:, key])[0].astype(dt)
            qk[j] = jnp.concatenate([q, k], axis=0)
            scores[j] = _dot_nt(qk[j], k)                    # [Q K^T; K K^T]
        # the [C, C] arrays of the pack's heads beside each other
        g_cols = [by_col[:, i:i + 1] for i in pack]
        b_cols = [by_col[:, heads + i:heads + i + 1] for i in pack]
        g_rows = jnp.concatenate([by_row[i:i + 1] for i in pack], axis=1)
        decay = _decay(_beside(g_cols, head_of), g_rows, rows >= cols)
        k_k = jnp.concatenate([scores[i // rep][c:] for i in pack], axis=1)
        q_k = jnp.concatenate([scores[i // rep][:c] for i in pack], axis=1)
        a = jnp.where(rows > cols, k_k * _beside(b_cols, head_of) * decay,
                      0.0)
        t = _unit_lower_inverse(a, rows, cols, head_of).astype(dt)
        within = (q_k * decay).astype(dt)
        if save:
            t_ref[:, lanes] = t
        for at, i in enumerate(pack):
            val = slice(i * dv, (i + 1) * dv)
            own = slice(at * c, (at + 1) * c)
            g_col, b_col = g_cols[at], b_cols[at]
            g_last = g_col[c - 1:c, :]
            e = jnp.exp(g_col)
            state = state_ref[i]
            state_dt = state.astype(dt)
            seen = _dot(qk[i // rep], state_dt)              # [Q S; K S]
            resid = b_col * (v_ref[:, val].astype(_F32) - e * seen[c:])
            fresh = _dot(t[:, own], resid.astype(dt))        # v' [C, dv]
            fresh_dt = fresh.astype(dt)
            o = e * seen[:c] + _dot(within[:, own], fresh_dt)
            o_ref[:, val] = o.astype(o_ref.dtype)
            tail = (jnp.exp(g_last - g_col) * fresh).astype(dt)
            state_ref[i] = state * _exp_over_lanes(g_last, dv) \
                + _dot_tn(qk[i // rep][c:], tail)
            if save:
                states_ref[i] = state_dt
                fresh_ref[:, val] = fresh_dt

    if final:
        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _last():
            final_ref[...] = state_ref[...]


def _gates(g, beta, heads, c):
    """g, beta [B, S, Hv] float32 -> [B, Hv/heads, n, 2 heads, C]: a head
    group's chunk with a position a lane, g of each head then beta of each
    (the kernels turn it themselves for a position a sublane)."""
    b, s, hv = g.shape

    def rows(x):
        return jnp.transpose(x.reshape(b, s // c, c, hv // heads, heads),
                             (0, 3, 1, 4, 2))

    return jnp.concatenate([rows(g), rows(beta)], axis=3)


def _specs(heads, rep, dk, dv, c, chunk_of):
    """BlockSpecs of one grid step's operands; ``chunk_of`` maps the grid's
    innermost index to the chunk it stands for."""
    def at(f):
        return lambda b, h, n: f(b, h, chunk_of(n))

    key = pl.BlockSpec((None, c, heads // rep * dk),
                       at(lambda b, h, n: (b, n, h)))
    val = pl.BlockSpec((None, c, heads * dv), at(lambda b, h, n: (b, n, h)))
    gates = pl.BlockSpec((None, None, None, 2 * heads, c),
                         at(lambda b, h, n: (b, h, n, 0, 0)))
    states = pl.BlockSpec((None, heads, None, dk, dv),
                          at(lambda b, h, n: (b, h, n, 0, 0)))
    t = pl.BlockSpec((None, None, c, heads * c),
                     at(lambda b, h, n: (b, h, n, 0)))
    return key, val, gates, states, t


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(q, k, v, g, beta, *, hk, hv, heads, c, scale, save, interpret,
             final=False):
    """q, k [B, S, Hk*dk], v [B, S, Hv*dv], S a multiple of c; g, beta [B,
    S, Hv] float32; ``scale``: q's, on its unit length. -> o like v, and
    with ``save`` what the backward reads: (states, t, fresh); with
    ``final`` (and not ``save``) the state after the last chunk, float32
    [B, Hv, dk, dv]."""
    b, s, _ = v.shape
    dk, dv, rep = q.shape[-1] // hk, v.shape[-1] // hv, hv // hk
    groups, n = hv // heads, s // c
    key, val, gates, states_spec, t_spec = _specs(heads, rep, dk, dv, c,
                                                  lambda i: i)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [val]
    if save:
        out_shape += [
            jax.ShapeDtypeStruct((b, hv, n, dk, dv), v.dtype),
            jax.ShapeDtypeStruct((b, groups, s, heads * c), v.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype)]
        out_specs += [states_spec, t_spec, val]
    elif final:
        out_shape.append(jax.ShapeDtypeStruct((b, hv, dk, dv), _F32))
        out_specs.append(pl.BlockSpec((None, heads, dk, dv),
                                      lambda b, h, n: (b, h, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, rep=rep, dk=dk, dv=dv,
                          scale=scale, save=save, final=final),
        name="rt_gdn_fwd",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=(b, groups, n),
        in_specs=[key, key, val, gates],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, _gates(g, beta, heads, c))
    return (out[0], tuple(out[1:])) if save else \
        (out[0], out[1]) if final else out[0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, gates_ref, states_ref, t_ref, fresh_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dgates_ref, dstate_ref, *,
                heads, rep, dk, dv, scale):
    c = v_ref.shape[0]
    dt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    by_row, by_col = _gate_vectors(gates_ref, heads)
    last = lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    units, dq_sum, dk_sum = {}, {}, {}
    d_cols = [None] * (2 * heads)       # gamma's and beta's, [C, 1] each
    d_rows = [None] * heads             # gamma's part a lane a position
    for first, m, rows, cols, head_of in _lane_packs(heads, c):
        pack = range(first, first + m)
        lanes = slice(first * c, (first + m) * c)
        qk, gram = {}, {}
        for j in sorted({i // rep for i in pack}):
            key = slice(j * dk, (j + 1) * dk)
            units[j] = _unit(q_ref[:, key]) + _unit(k_ref[:, key])
            qk[j] = jnp.concatenate([
                (units[j][0] * scale).astype(dt),
                units[j][2].astype(dt)], axis=0)             # [2C, dk]
            gram[j] = _dot_nt(qk[j], qk[j])                  # [2C, 2C]
        g_cols = [by_col[:, i:i + 1] for i in pack]
        b_cols = [by_col[:, heads + i:heads + i + 1] for i in pack]
        g_rows = jnp.concatenate([by_row[i:i + 1] for i in pack], axis=1)
        b_rows = jnp.concatenate([by_row[heads + i:heads + i + 1]
                                  for i in pack], axis=1)
        g_wide, b_wide = _beside(g_cols, head_of), _beside(b_cols, head_of)
        decay = _decay(g_wide, g_rows, rows >= cols)
        decay_t = _decay(g_rows, g_wide, rows <= cols)
        q_k, k_q, k_k = (
            jnp.concatenate([gram[i // rep][x, y] for i in pack], axis=1)
            for x, y in ((slice(0, c), slice(c, 2 * c)),
                         (slice(c, 2 * c), slice(0, c)),
                         (slice(c, 2 * c), slice(c, 2 * c))))
        within_t = (k_q * decay_t).astype(dt)                # (Q K^T decay)^T
        t = t_ref[:, lanes]

        # o = e (Q S) + (Q K^T * decay) v';  S' = e_last S + K^T (tail v');
        # v' = T resid, so dA = -T^T (dv' resid^T) T^T = -dresid v'^T
        kept, by_fresh, by_fresh_t = [], [], []
        for at, i in enumerate(pack):
            val = slice(i * dv, (i + 1) * dv)
            own = slice(at * c, (at + 1) * c)
            k = qk[i // rep][c:]
            do, fresh = do_ref[:, val], fresh_ref[:, val]
            state, dstate = states_ref[i], dstate_ref[i]     # start; end's
            g_col = g_cols[at]
            g_last = g_col[c - 1:c, :]
            e, tail = jnp.exp(g_col), jnp.exp(g_last - g_col)
            seen = _dot(qk[i // rep], state)                 # [Q S; K S]
            from_state = _dot(k, dstate.astype(dt))          # K dS' [C, dv]
            dfresh = _dot(within_t[:, own], do) + tail * from_state
            dresid = _dot_tn(t[:, own], dfresh.astype(dt))
            both = jnp.concatenate([do, dresid.astype(dt)], axis=0)
            by_fresh.append(_dot_nt(both, fresh))            # [dP; -dA]
            by_fresh_t.append(_dot_nt(fresh, both))          # [dP^T, -dA^T]
            kept.append((val, own, do, fresh, state, dstate, g_col, g_last,
                         e, tail, seen, from_state, dresid))

        def beside(parts):
            return jnp.concatenate(parts, axis=1)

        dscores = beside([x[:c] for x in by_fresh]) * decay  # d(Q K^T)
        dscores_t = beside([x[:, :c] for x in by_fresh_t]) * decay_t
        da = jnp.where(rows > cols,
                       -beside([x[c:] for x in by_fresh]) * decay, 0.0)
        dkk = da * b_wide                                    # d(K K^T)
        dkk_t = jnp.where(rows < cols, -beside(
            [x[:, c:] for x in by_fresh_t]) * decay_t * b_rows, 0.0)
        through = dscores * q_k + dkk * k_k                  # d decay * decay
        of_beta = da * k_k
        down = -jnp.sum(through, axis=0, keepdims=True)      # [1, m C]
        dscores_dt = dscores.astype(dt)
        mixed = [jnp.concatenate([dscores_t[:, own], (dkk + dkk_t)[:, own]],
                                 axis=1).astype(dt)
                 for own in (x[1] for x in kept)]

        for at, i in enumerate(pack):
            (val, own, do, fresh, state, dstate, g_col, g_last, e, tail,
             seen, from_state, dresid) = kept[at]
            j = i // rep
            b_col = b_cols[at]
            q_s, k_s = seen[:c], seen[c:]
            do_f, fresh_f = do.astype(_F32), fresh.astype(_F32)
            e_do = (e * do_f).astype(dt)
            scaled = b_col * dresid                          # beta dresid
            dks = (-e * scaled).astype(dt)                   # d(K S)
            dv_ref[:, val] = scaled.astype(dv_ref.dtype)
            dq_i = _dot_nt(e_do, state) + _dot(dscores_dt[:, own],
                                               qk[j][c:])
            dk_i = _dot_nt(
                jnp.concatenate([(tail * fresh_f).astype(dt), dks], axis=1),
                jnp.concatenate([dstate.astype(dt), state], axis=1)) \
                + _dot(mixed[at], qk[j])
            dq_sum[j] = dq_i if j not in dq_sum else dq_sum[j] + dq_i
            dk_sum[j] = dk_i if j not in dk_sum else dk_sum[j] + dk_i
            e_last = jnp.exp(g_last)
            dstate_ref[i] = dstate * _exp_over_lanes(g_last, dv) \
                + _dot_tn(qk[j], jnp.concatenate([e_do, dks], axis=0))

            # the gates: beta, and gamma through e, tail, decay and e_last
            unseen = v_ref[:, val].astype(_F32) - e * k_s    # resid / beta
            dtail = jnp.sum(from_state * fresh_f, axis=1, keepdims=True) \
                * tail
            at_last = jnp.sum(dtail, axis=0, keepdims=True) + e_last \
                * jnp.sum(jnp.sum(dstate * state.astype(_F32), axis=1,
                                  keepdims=True), axis=0, keepdims=True)
            d_cols[i] = e * (jnp.sum(do_f * q_s, axis=1, keepdims=True)
                             - jnp.sum(scaled * k_s, axis=1, keepdims=True)) \
                - dtail + jnp.sum(through[:, own], axis=1, keepdims=True) \
                + jnp.where(last, at_last, 0.0)
            d_cols[heads + i] = \
                jnp.sum(dresid * unseen, axis=1, keepdims=True) \
                + jnp.sum(of_beta[:, own], axis=1, keepdims=True)
            d_rows[i] = down[:, own]

    for j, (q_unit, q_inverse, k_unit, k_inverse) in units.items():
        key = slice(j * dk, (j + 1) * dk)
        # through ``_unit``: d x = (d y - y (d y . y)) / |x|
        for out_ref, dy, y, inverse in (
                (dq_ref, dq_sum[j] * scale, q_unit, q_inverse),
                (dk_ref, dk_sum[j], k_unit, k_inverse)):
            along = jnp.sum(dy * y, axis=1, keepdims=True)
            out_ref[:, key] = (inverse * (dy - y * along)).astype(
                out_ref.dtype)

    # the gates' gradients go out as they came in: a position a lane
    lane = lax.broadcasted_iota(jnp.int32, (c, 2 * heads), 1)
    sublane = lax.broadcasted_iota(jnp.int32, (2 * heads, c), 0)
    columns = jnp.zeros((c, 2 * heads), _F32)
    for i, column in enumerate(d_cols):
        columns = jnp.where(lane == i, column, columns)
    dgates = columns.T
    for i, row in enumerate(d_rows):
        dgates = dgates + jnp.where(sublane == i, row, 0.0)
    summed = _dot_f32(dgates, _running(c, backward=True))    # gamma's -> g's
    dgates_ref[...] = jnp.where(sublane < heads, summed, dgates)


def _backward(q, k, v, g, beta, saved, do, *, hk, hv, heads, c, scale,
              interpret):
    """-> (dq, dk, dv, dg, dbeta), shaped and typed as the operands."""
    b, s, _ = v.shape
    dk, dv, rep = q.shape[-1] // hk, v.shape[-1] // hv, hv // hk
    groups, n = hv // heads, s // c
    key, val, gates, states_spec, t_spec = _specs(
        heads, rep, dk, dv, c, lambda i: n - 1 - i)
    dq, dk_, dv_, dgates = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, rep=rep, dk=dk, dv=dv,
                          scale=scale),
        name="rt_gdn_bwd",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=(b, groups, n),
        in_specs=[key, key, val, gates, states_spec, t_spec, val, val],
        out_specs=[key, key, val, gates],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b, groups, n, 2 * heads, c), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, _gates(g, beta, heads, c), *saved, do)

    def positions(x):       # [B, Hv/heads, n, heads, C] -> [B, S, Hv]
        return jnp.transpose(x, (0, 2, 4, 1, 3)).reshape(b, s, hv)

    return (dq, dk_, dv_, positions(dgates[..., :heads, :]),
            positions(dgates[..., heads:, :]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _rule(q, k, v, g, beta, hk, hv, heads, c, scale, interpret):
    return _forward(q, k, v, g, beta, hk=hk, hv=hv, heads=heads, c=c,
                    scale=scale, save=False, interpret=interpret)


def _rule_fwd(q, k, v, g, beta, hk, hv, heads, c, scale, interpret):
    o, saved = _forward(q, k, v, g, beta, hk=hk, hv=hv, heads=heads, c=c,
                        scale=scale, save=True, interpret=interpret)
    # All four of the kernel's outputs carry the name a remat'd layer's
    # policy keeps (models/transformer.py ``_stage_scan``): with one left
    # out the backward pass would run the whole kernel again for it (``o``
    # feeds the gated norm, whose backward the layer's tail recomputes). The
    # operands come out of the projections and the convolution, and are
    # recomputed with them.
    o, saved = checkpoint_name((o, saved), KEPT)
    return o, (q, k, v, g, beta, saved)


def _rule_bwd(hk, hv, heads, c, scale, interpret, res, do):
    *operands, saved = res
    return _backward(*operands, saved, do, hk=hk, hv=hv, heads=heads, c=c,
                     scale=scale, interpret=interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def heads_a_step(hk: int, hv: int) -> int:
    """Value heads a grid step: the most up to HEADS that divide ``hv`` and
    hold whole sets of the heads that share a key head (their dq and dk are
    summed in the step)."""
    rep = hv // hk
    fits = [g for g in range(rep, max(HEADS, rep) + 1, rep) if hv % g == 0]
    return fits[-1]


LANES = 128


def gated_delta_rule_kernels(q, k, v, g, beta, chunk, *, interpret=False,
                             final_state=False):
    """ops/gated_delta.py's ``gated_delta_rule`` by the kernels above. q, k
    [B, S, Hk, dk], Hk dividing Hv. A head width that is no multiple of
    LANES goes in with zeros up to the next one (a head's columns then
    start on a lane tile, as the kernels slice them): zeros add nothing to
    a head's length, to a product with it, nor to the state's other rows
    and columns, and q keeps the scale of its own width. Widths that are
    multiples are read where the convolution left them, no copy made.
    ``final_state``: also the state after position S - 1, float32 [B, Hv,
    dk, dv], by the forward kernel alone (no gradient). ``interpret`` runs
    the Pallas interpreter: only tests pass it."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    wide_k, wide_v = -(-dk // LANES) * LANES, -(-dv // LANES) * LANES
    pad = -s % chunk

    def widen(x, wide=0):   # positions to whole chunks, a head to ``wide``
        by = [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)
        if wide:
            by[-1] = (0, wide - x.shape[-1])
        return jnp.pad(x, by) if any(hi for _, hi in by) else x

    q, k, v = widen(q, wide_k), widen(k, wide_k), widen(v, wide_v)
    g, beta = widen(g), widen(beta)
    padded = s + pad
    operands = (q.reshape(b, padded, hk * wide_k),
                k.reshape(b, padded, hk * wide_k),
                v.reshape(b, padded, hv * wide_v), g.astype(_F32),
                beta.astype(_F32))
    heads, scale = heads_a_step(hk, hv), dk ** -0.5
    if final_state:
        o, state = _forward(*operands, hk=hk, hv=hv, heads=heads, c=chunk,
                            scale=scale, save=False, final=True,
                            interpret=interpret)
    else:
        o = _rule(*operands, hk, hv, heads, chunk, scale, interpret)
    o = o.reshape(b, padded, hv, wide_v)[:, :s, :, :dv]
    return (o, state[:, :, :dk, :dv]) if final_state else o


# ---------------------------------------------------------------------------
# one position on a carried state (serving's decode step)
# ---------------------------------------------------------------------------

STEP_BLOCK_BYTES = 4 << 20      # of the state a grid step holds


def _step_kernel(slot_ref, cols_ref, rows_ref, s_ref, o_ref, s_out_ref, *,
                 groups, r, dv, delta):
    """One row's ``groups`` runs of r heads. cols_ref [dk, groups 2r]: a
    run's k of each head, then its q of each, a key dim a sublane; rows_ref
    [3, groups, r dv]: v, exp(g) and beta over their heads' lanes; s_ref,
    s_out_ref [groups, dk, r dv]: the same block of the stack. ``delta``:
    what is written is ``beta (v - S^T k)``, the delta rule's correction;
    without it ``beta v`` (a state-space transition, beta its step size)."""
    del slot_ref
    dk, wide = s_ref.shape[1:]
    lane = lax.broadcasted_iota(jnp.int32, (dk, wide), 1)

    def beside(first):      # r columns, each over its head's dv lanes
        out = cols_ref[:, first + r - 1:first + r]
        for i in range(r - 2, -1, -1):
            out = jnp.where(lane < (i + 1) * dv,
                            cols_ref[:, first + i:first + i + 1], out)
        return out

    for i in range(groups):
        k, q = beside(2 * r * i), beside(2 * r * i + r)
        v, decay, beta = (rows_ref[n, i:i + 1, :] for n in range(3))
        state = s_ref[i].astype(_F32)
        if delta:
            seen = jnp.sum(state * k, axis=0, keepdims=True)     # S^T k
            fresh = beta * (v - decay * seen)
        else:
            fresh = beta * v
        state = decay * state + k * fresh
        s_out_ref[i] = state.astype(s_out_ref.dtype)
        o_ref[i:i + 1, :] = jnp.sum(state * q, axis=0, keepdims=True)


def _groups_a_step(total: int, group_bytes: int) -> int:
    """Runs of heads a grid step: all of a row's where they fit in
    STEP_BLOCK_BYTES, else the most that divide them into whole sublane
    tiles of the per-run operands."""
    if total * group_bytes <= STEP_BLOCK_BYTES:
        return total
    fits = [g for g in range(8, total, 8) if total % g == 0
            and g * group_bytes <= STEP_BLOCK_BYTES]
    return fits[-1] if fits else total


def step_kernel(states, slot, q, k, v, g, beta, *, delta=True,
                interpret=False):
    """ops/gated_delta.py's ``gated_delta_step_at`` as a kernel, and with
    ``delta=False`` ops/ssd.py's ``ssd_step_at`` (``rt_ssd_step``: the same
    step without the delta correction). states [slots, B, H / r, dk, r dv]
    float32; q, k [B, H, dk] float32 (the rule's at unit length,
    ``_step_operands``); v [B, H, dv]; g, beta [B, H]. -> (o [B, H, dv] in
    v's dtype, the stack: the kernel's output aliases ``states`` and a grid
    step reads and writes one row's block of slot ``slot``). The small
    operands are laid out by XLA under the caller's scope."""
    _, b, runs, dk, wide = states.shape
    h, dv = v.shape[1:]
    r = h // runs
    groups = _groups_a_step(runs, dk * wide * states.dtype.itemsize)
    cols = jnp.concatenate([x.reshape(b, runs, r, dk) for x in (k, q)],
                           axis=2)                   # [B, runs, 2r, dk]
    cols = jnp.swapaxes(
        cols.reshape(b, runs // groups, groups * 2 * r, dk), 2, 3)
    rows = jnp.stack([v.astype(_F32).reshape(b, runs, wide),
                      _over_lanes(jnp.exp(g.astype(_F32)), r, dv),
                      _over_lanes(beta.astype(_F32), r, dv)], axis=1)
    block = (None, None, groups, dk, wide)
    o, states = pl.pallas_call(
        functools.partial(_step_kernel, groups=groups, r=r, dv=dv,
                          delta=delta),
        name="rt_gdn_step" if delta else "rt_ssd_step",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, runs // groups),
            in_specs=[
                pl.BlockSpec((None, None, dk, groups * 2 * r),
                             lambda i, j, slot: (i, j, 0, 0)),
                pl.BlockSpec((None, 3, groups, wide),
                             lambda i, j, slot: (i, 0, j, 0)),
                pl.BlockSpec(block, lambda i, j, slot: (slot[0], i, j, 0,
                                                        0))],
            out_specs=[
                pl.BlockSpec((None, groups, wide),
                             lambda i, j, slot: (i, j, 0)),
                pl.BlockSpec(block, lambda i, j, slot: (slot[0], i, j, 0,
                                                        0))]),
        out_shape=[jax.ShapeDtypeStruct((b, runs, wide), _F32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=10 * groups * dk * wide * 4 + (8 << 20)),
        interpret=interpret,
    )(jnp.asarray(slot, jnp.int32).reshape(1), cols, rows, states)
    return o.reshape(b, h, dv).astype(v.dtype), states


CONV_BLOCK = 2048       # channels a grid step, at most


def _conv_step_kernel(slot_ref, x_ref, w_ref, t_ref, y_ref, t_out_ref):
    """x_ref [B, c]: the new inputs; w_ref [K, c] float32, or [K + 1, c]
    with the bias for its last row; t_ref, t_out_ref [K-1, B, c]: the same
    block of the stack, oldest position first."""
    del slot_ref
    kept = t_ref.shape[0]
    x = x_ref[...]
    y = x.astype(_F32) * w_ref[kept:kept + 1, :]
    if w_ref.shape[0] > kept + 1:
        y = y + w_ref[kept + 1:kept + 2, :]
    for j in range(kept):
        y = y + t_ref[j].astype(_F32) * w_ref[j:j + 1, :]
        t_out_ref[j] = t_ref[j + 1] if j + 1 < kept \
            else x.astype(t_out_ref.dtype)
    y_ref[...] = (y * jax.nn.sigmoid(y)).astype(y_ref.dtype)


def _taps(w, bias):
    """w [C, K], bias [C] or None -> [K, C] float32, or [K + 1, C] with the
    bias for a last row: the convolution kernels' one block of weights."""
    taps = w.astype(_F32).T
    if bias is None:
        return taps
    return jnp.concatenate([taps, bias.astype(_F32)[None]])


def conv_step_kernel(tails, slot, x, w, bias=None, *, interpret=False):
    """ops/gated_delta.py's ``conv_step_at`` as a kernel. tails [slots,
    K-1, B, C]; x [B, C]; w [C, K]; bias [C] or None. -> (y [B, C] in x's
    dtype, the stack: the kernel's output aliases ``tails``)."""
    _, kept, b, c = tails.shape
    taps = _taps(w, bias)
    fits = [n for n in range(128, min(c, CONV_BLOCK) + 1, 128) if c % n == 0]
    wide = fits[-1] if fits else c
    block = (None, kept, b, wide)
    y, tails = pl.pallas_call(
        _conv_step_kernel,
        name="rt_gdn_conv_step",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(c // wide,),
            in_specs=[
                pl.BlockSpec((b, wide), lambda i, slot: (0, i)),
                pl.BlockSpec((taps.shape[0], wide), lambda i, slot: (0, i)),
                pl.BlockSpec(block, lambda i, slot: (slot[0], 0, 0, i))],
            out_specs=[
                pl.BlockSpec((b, wide), lambda i, slot: (0, i)),
                pl.BlockSpec(block, lambda i, slot: (slot[0], 0, 0, i))]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(slot, jnp.int32).reshape(1), x, taps, tails)
    return y, tails


# ---------------------------------------------------------------------------
# the convolution over a whole sequence (training, a prompt of a block or
# more): one pass forward, one pass backward
# ---------------------------------------------------------------------------

# A grid step's block, on a v5e at [2, 8192, 8192] bfloat16, K = 4, forward /
# backward alone (my chip run, PR 59; the jnp form 1.99 / 8.21 ms; the bytes
# are 0.66 / 0.98 ms at 819 GB/s): rows 256 1.29 / 1.87 ms, 512 1.07 / 1.67,
# 1024 0.97 / 1.63, 2048 0.91 / 1.60 (fewer steps, fewer halos); at 512 rows
# 256 lanes 1.34 / 1.96, 1024 lanes 1.02 / 1.95, a tile of 16 rows 1.11 /
# 1.81, of 64 1.11 / 1.88. Both are bound by the vector ALUs' work, the
# backward at 48 operations a float32 register of x (PERF.md, PR 59).
CONV_ROWS_MOST = 2048   # positions a grid step, at most
CONV_LANES = 512        # channels a grid step
CONV_TILE = 32          # rows an inner step: two bfloat16 tiles
HALO = 16               # rows of the views beside a block (a bfloat16 tile)
_ROWS = 8               # a float32 tile: how far a shift reaches, at most
_LOG2_E = 1.4426950408889634


def _sigmoid(pre):
    """1 / (1 + e^-pre), float32: the EUP's reciprocal and two Newton steps
    (its 8 good bits to 16, to float32's last place). Mosaic's own ``divf``
    spends as much again on what ``1 + e^-pre`` cannot be: zero, negative,
    a NaN. The power of two is held at 2^115, so that the sum stays finite."""
    d = 1.0 + jnp.exp2(jnp.minimum(pre * -_LOG2_E, 115.0))
    r = pl.reciprocal(d, approx=True)
    r = r * (2.0 - d * r)
    return r * (2.0 - d * r)


def _shifted(ext, taps):
    """ext [_ROWS + n, c]: n rows behind the _ROWS before them -> [rows
    t - (K-1) + j of those n's t, for j = 0 .. K-1]."""
    return [ext[_ROWS:] if j == taps - 1 else
            pltpu.roll(ext, taps - 1 - j, axis=0)[_ROWS:]
            for j in range(taps)]


def _tap(w_ref, j, n):
    """Row j of w_ref [K or K + 1, _ROWS, c] (the taps, then the bias where
    there is one, float32, each over its sublanes) as [n, c]: read where it
    is used, so that no tap waits in registers."""
    return jnp.concatenate([w_ref[j]] * (n // _ROWS), axis=0)


def _pre_activation(parts, w_ref):
    """The taps' sum in the jnp form's order: j = 0 .. K-1, then the bias."""
    n = parts[0].shape[0]
    y = parts[0] * _tap(w_ref, 0, n)
    for j in range(1, len(parts)):
        y = y + parts[j] * _tap(w_ref, j, n)
    return y + _tap(w_ref, len(parts), n) if w_ref.shape[0] > len(parts) \
        else y


def _rows_before(before_ref):
    """before_ref [HALO, c], the rows of x that end where the block starts
    -> the last _ROWS of them, float32; zeros at a sequence's start, where
    the view holds other rows."""
    return jnp.where(pl.program_id(2) == 0, 0.0,
                     before_ref[HALO - _ROWS:, :].astype(_F32))


def _conv_fwd_kernel(x_ref, before_ref, w_ref, y_ref, *, taps, tile):
    """x_ref, y_ref [R, c]; before_ref: ``_rows_before``'s; w_ref:
    ``_tap``'s."""
    before = _rows_before(before_ref)

    def step(n, before):
        at = pl.multiple_of(n * tile, tile)
        x = x_ref[pl.ds(at, tile), :].astype(_F32)
        pre = _pre_activation(
            _shifted(jnp.concatenate([before, x], axis=0), taps), w_ref)
        y_ref[pl.ds(at, tile), :] = (pre * _sigmoid(pre)).astype(y_ref.dtype)
        return x[tile - _ROWS:]

    lax.fori_loop(0, x_ref.shape[0] // tile, step, before)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     w_ref, dx_ref, dw_ref, sums_ref, *, taps, tile):
    """x_ref, dy_ref, dx_ref [R, c]; before_ref, after_ref, dy_after_ref
    [HALO, c]: x's rows before the block, x's and dy's after it (past a
    sequence's end: no gradient comes from there); dw_ref [K + 1, c]:
    the taps' gradients and the bias's (its row is there with or without
    one), written once a channel block; sums_ref [K + 1, _ROWS, c]: their
    sums so far, a sublane a partial sum. The rows go last to first, so
    that a tile's dx finds the gate's gradient of the rows after it made."""
    rows = x_ref.shape[0]
    first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)
    last = (pl.program_id(1) == pl.num_programs(1) - 1) \
        & (pl.program_id(2) == pl.num_programs(2) - 1)

    @pl.when(first)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def gate(ext, dy):
        """ext [_ROWS + n, c] float32: n rows of x behind the _ROWS before
        them; dy [n, c] -> (d loss / d pre-activation [n, c] float32, the
        shifted x's it was made of)."""
        parts = _shifted(ext, taps)
        pre = _pre_activation(parts, w_ref)
        sig = _sigmoid(pre)
        return dy.astype(_F32) * (sig * (1.0 + pre * (1.0 - sig))), parts

    def fold(x):        # [n, c] -> [_ROWS, c]: partial sums, a sublane each
        return functools.reduce(
            jnp.add, [x[r:r + _ROWS] for r in range(0, x.shape[0], _ROWS)])

    def tile_of(ext, at, after):
        """One tile's sums and dx, from its rows of x behind the _ROWS
        before them -> its first rows' gate gradient."""
        g, parts = gate(ext, dy_ref[pl.ds(at, tile), :])
        for j, part in enumerate(parts):
            sums_ref[j] = sums_ref[j] + fold(g * part)
        sums_ref[taps] = sums_ref[taps] + fold(g)
        both = jnp.concatenate([g, after], axis=0)      # [tile + _ROWS, c]
        dx = g * _tap(w_ref, taps - 1, tile)
        for j in range(taps - 2, -1, -1):
            dx = dx + pltpu.roll(both, tile + _ROWS - (taps - 1 - j),
                                 axis=0)[:tile] * _tap(w_ref, j, tile)
        dx_ref[pl.ds(at, tile), :] = dx.astype(dx_ref.dtype)
        return g[:_ROWS]

    # the gate's gradient of the rows after the block, from its last rows on
    after, _ = gate(jnp.concatenate([
        x_ref[rows - HALO:, :].astype(_F32)[HALO - _ROWS:],
        after_ref[...].astype(_F32)[:_ROWS]], axis=0),
        dy_after_ref[...][:_ROWS])
    after = jnp.where(pl.program_id(2) == pl.num_programs(2) - 1, 0.0, after)

    def step(n, after):
        at = pl.multiple_of(rows - (n + 1) * tile, tile)
        ext = x_ref[pl.ds(at - HALO, HALO + tile), :].astype(_F32)
        return tile_of(ext[HALO - _ROWS:], at, after)

    after = lax.fori_loop(0, rows // tile - 1, step, after)
    tile_of(jnp.concatenate([_rows_before(before_ref),
                             x_ref[:tile, :].astype(_F32)], axis=0), 0, after)

    @pl.when(last)
    def _write():
        for j in range(taps + 1):
            dw_ref[j:j + 1, :] = jnp.sum(sums_ref[j], axis=0, keepdims=True)


def _conv_specs(rows, lanes, blocks, taps):
    """BlockSpecs over the grid (channel block, row of the batch, row
    block): a block [rows, lanes] of a [B, S, C] array, the HALO rows before
    it and the HALO rows after it (held inside the sequence at its ends,
    where the kernels do not read them), and the channel block's weights
    (``_tap``'s layout of ``taps`` [n, C])."""
    per = rows // HALO
    weights = pl.BlockSpec((taps.shape[0], _ROWS, lanes),
                           lambda c, b, i: (0, 0, c))
    block = pl.BlockSpec((None, rows, lanes), lambda c, b, i: (b, i, c))
    before = pl.BlockSpec(
        (None, HALO, lanes),
        lambda c, b, i: (b, jnp.maximum(i * per - 1, 0), c))
    after = pl.BlockSpec(
        (None, HALO, lanes),
        lambda c, b, i: (b, jnp.minimum((i + 1) * per, blocks * per - 1), c))
    return block, before, after, weights


_CONV_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _conv_block(s: int, c: int):
    """(rows, lanes) of a grid step for S = s positions (a multiple of
    CONV_ROWS) of c channels (a multiple of 128): the most rows up to
    CONV_ROWS_MOST that divide the sequence."""
    rows = CONV_ROWS_MOST
    while s % rows:
        rows //= 2
    return rows, CONV_LANES if c % CONV_LANES == 0 else 128


def _over_sublanes(taps):
    """[n, C] -> [n, _ROWS, C]: ``_tap``'s layout."""
    return jnp.broadcast_to(taps[:, None, :],
                            (taps.shape[0], _ROWS, taps.shape[1]))


def _conv_forward(x, taps, width, interpret):
    b, s, c = x.shape
    rows, lanes = _conv_block(s, c)
    block, before, _, weights = _conv_specs(rows, lanes, s // rows, taps)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, taps=width, tile=CONV_TILE),
        name="rt_gdn_conv_fwd",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=(c // lanes, b, s // rows),
        in_specs=[block, before, weights],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_CONV_PARAMS,
        interpret=interpret,
    )(x, x, _over_sublanes(taps))


def _conv_backward(x, taps, dy, width, interpret):
    """-> (dx like x, dtaps like taps: where they hold no bias its row of
    the kernel's sums is dropped)."""
    b, s, c = x.shape
    rows, lanes = _conv_block(s, c)
    block, before, after, weights = _conv_specs(rows, lanes, s // rows,
                                                 taps)
    sums = pl.BlockSpec((width + 1, lanes), lambda c, b, i: (0, c))
    dx, dtaps = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, taps=width, tile=CONV_TILE),
        name="rt_gdn_conv_bwd",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=(c // lanes, b, s // rows),
        in_specs=[block, before, after, block, after, weights],
        out_specs=[block, sums],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((width + 1, c), _F32)],
        scratch_shapes=[pltpu.VMEM((width + 1, _ROWS, lanes), _F32)],
        compiler_params=_CONV_PARAMS,
        interpret=interpret,
    )(x, x, x, dy, dy, _over_sublanes(taps))
    return dx, dtaps[:taps.shape[0]]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv(x, taps, width, interpret):
    """x [B, S, C], S a multiple of CONV_ROWS, C of 128; taps [K, C]
    float32, or [K + 1, C] with the bias last -> silu(the convolution),
    like x."""
    return _conv_forward(x, taps, width, interpret)


def _conv_fwd(x, taps, width, interpret):
    # nothing of the forward is kept: the backward makes the pre-activation
    # again from x, which the layer's remat makes again with the projection
    return _conv_forward(x, taps, width, interpret), (x, taps)


def _conv_bwd(width, interpret, res, dy):
    return _conv_backward(*res, dy, width, interpret)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_kernels(x, w, bias=None, *, interpret=False):
    """ops/gated_delta.py's ``causal_conv`` (inside its scope) by the two
    kernels above, for what ``conv_kernels_fit`` takes: x [B, S, C], S a
    multiple of CONV_ROWS and C of 128; w [C, K]; bias [C] or None. The
    taps go in as ``conv_step_kernel``'s, float32 with the bias for a last
    row; their gradient comes back through the same lines."""
    return _conv(x, _taps(w, bias), w.shape[1], interpret)

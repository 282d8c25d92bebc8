"""Attention of each query over a selection of its own, in one Pallas kernel
for TPU: the absorbed form of latent attention (models/latent.py) where a
learned indexer has picked ``topk`` cached rows a query.

A cached row ``[c_kv ; k_rope]`` is key and value of every head at once, so
what a query needs is its ``topk`` rows, once. The kernel keeps one batch
row's whole cache in VMEM (32,896 x 576 bfloat16 is 38 MB of a v5e core's
128 MiB), fetches a query's rows from there by dynamic sublane loads at the
positions ``at`` (read from SMEM), and attends over them in place: scores
``q @ rows^T`` in float32, softmax in float32 over the slots that are
``real``, ``p.astype(bf16) @ rows[:, :v]`` with a float32 accumulator. No
copy of the rows and no score goes to HBM.

A bfloat16 row is half of a packed sublane and cannot be loaded alone, so
the cache goes in as 32-bit words, ``pack``: word ``c`` of a row holds
columns ``c`` (low half) and ``c + half`` (high half) of its value part, and
likewise of its rope part, filled up to whole lane tiles; and a row's lane
tiles lie along the sublanes (``[T x tiles, 128]``: three sublanes a row of
576), so that a row is fetched by one load and one store (4.4 ns a row on a
v5e; with the tiles along the lanes, three of each, 6.1). In VMEM the
fetched words are read back a lane tile at a stride, split into two
bfloat16 halves with a shift and a mask, and each product is the sum of two
over the halves; the query is laid out the same way (``_halves``). The
order of columns is the kernel's own business: both products contract or
carry it.

``sparse_attend`` is the entry, over a cache that ``pack`` has laid out;
models/latent.py decides when it runs (``takes``: the sizes it can).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash import NEG_INF, _dot, _dot_t

LANES = 128
# Rows fetched between two tests of the loop's counter.
FETCH_UNROLL = 16
# VMEM beside the resident cache: the fetched words, their two halves, the
# scores and the blocks of q, real and o in flight.
WORK_VMEM_BYTES = 32 << 20
# What the resident cache may take of a core's VMEM (128 MiB on a v5e).
CACHE_VMEM_BYTES = 80 << 20


def _word_widths(width: int, v: int):
    """-> (words of the value part, words of the rope part, words a row
    filled up to whole lane tiles)."""
    words = width // 2
    return v // 2, (width - v) // 2, -(-words // LANES) * LANES


def _halves(x, v: int):
    """x [..., width] -> (low, high) [..., words]: the columns that ``pack``
    puts in the low and the high half of a row's words, zero where the
    words are fill."""
    vw, rw, words = _word_widths(x.shape[-1], v)
    fill = [(0, 0)] * (x.ndim - 1) + [(0, words - vw - rw)]
    return tuple(jnp.pad(jnp.concatenate(
        [x[..., lo:lo + vw], x[..., v + ro:v + ro + rw]], -1), fill)
        for lo, ro in ((0, 0), (vw, rw)))


def pack(keys, v: int):
    """keys [B, T, width] bfloat16 as the cache holds them -> what the
    kernel keeps in VMEM, [B, T' x tiles, 128] uint32: T filled up to whole
    sublane tiles, a row's words a lane tile a sublane. A pass over the
    cache: once for all the blocks of queries that read it."""
    b, t, _ = keys.shape
    low, high = (lax.bitcast_convert_type(h, jnp.uint16).astype(jnp.uint32)
                 for h in _halves(jnp.pad(keys, ((0, 0), (0, -t % 8), (0, 0))),
                                  v))
    return (low | (high << 16)).reshape(b, -1, LANES)


def takes(t: int, width: int, v: int, topk: int) -> bool:
    """Whether the kernel runs at these sizes: the widths split into halves
    of whole lane tiles, the selection into whole rounds of the fetch, and
    one batch row's cache of ``t`` rows may stay in VMEM."""
    if v % (2 * LANES) or (width - v) % 2 or topk % FETCH_UNROLL:
        return False
    return -(-t // 8) * 8 * _word_widths(width, v)[2] * 4 <= CACHE_VMEM_BYTES


def _kernel(at_ref, q_lo_ref, q_hi_ref, real_ref, keys_ref, o_ref,
            cache, rows, sem, *, scale, topk, vw, tiles):
    b = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _load():    # the batch row's cache, once for all of its queries
        copy = pltpu.make_async_copy(keys_ref.at[b], cache, sem)
        copy.start()
        copy.wait()

    def fetch(i, _):    # a row: ``tiles`` sublanes, one load and one store
        first = pl.multiple_of(i * FETCH_UNROLL, FETCH_UNROLL)
        for u in range(FETCH_UNROLL):
            rows[pl.ds((first + u) * tiles, tiles), :] = \
                cache[pl.ds(at_ref[0, first + u] * tiles, tiles), :]
        return 0

    lax.fori_loop(0, topk // FETCH_UNROLL, fetch, 0)
    # the rows' lane tiles side by side again: [topk, words]
    words = jnp.concatenate([rows[pl.ds(k, topk, stride=tiles), :]
                             for k in range(tiles)], -1)
    low = pltpu.bitcast(words << 16, jnp.float32).astype(jnp.bfloat16)
    high = pltpu.bitcast(words & jnp.uint32(0xFFFF0000),
                         jnp.float32).astype(jnp.bfloat16)
    s = (_dot_t(q_lo_ref[...], low) + _dot_t(q_hi_ref[...], high)) * scale
    s = jnp.where(real_ref[...] != 0, s, NEG_INF)       # [H, topk] float32
    e = jnp.exp(s - s.max(-1, keepdims=True))
    # a reciprocal a head and a multiply a score, not a division a score
    p = (e * (1.0 / e.sum(-1, keepdims=True))).astype(jnp.bfloat16)
    o_ref[:, :vw] = _dot(p, low[:, :vw]).astype(o_ref.dtype)
    o_ref[:, vw:] = _dot(p, high[:, :vw]).astype(o_ref.dtype)


def sparse_attend(q, packed, at, real, *, v: int, scale: float,
                  interpret: bool = False):
    """q [B, S, H, width] bfloat16 (a query against cached rows: the
    absorbed ``[q_latent ; q_rope]``), packed: ``pack`` of the cache
    [B, T, width], at [B, S, topk] int32 positions in the cache (a set:
    their order is free), real [B, S, topk] which of them count ->
    [B, S, H, v]: ``softmax(scale q . rows) @ rows[:, :v]`` over each
    query's real rows. A slot that is not real may point at any row."""
    b, s, h, width = q.shape
    topk = at.shape[-1]
    vw, _, words = _word_widths(width, v)
    tiles = words // LANES
    held = packed.shape[1]                  # sublanes: cache rows x tiles
    if not takes(held // tiles, width, v, topk):
        raise ValueError(f"rt_sparse_attend does not take {topk} of "
                         f"{held // tiles} rows of {width} with a value "
                         f"part of {v}")
    q_lo, q_hi = _halves(q, v)
    per_query = lambda *block: pl.BlockSpec(       # noqa: E731
        (None, None) + block, lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, topk=topk, vw=vw,
                          tiles=tiles),
        name="rt_sparse_attend",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=(b, s),
        in_specs=[
            pl.BlockSpec((None, 1, topk), lambda i, j: (i * s + j, 0, 0),
                         memory_space=pltpu.SMEM),
            per_query(h, words), per_query(h, words), per_query(1, topk),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=per_query(h, v),
        out_shape=jax.ShapeDtypeStruct((b, s, h, v), q.dtype),
        scratch_shapes=[pltpu.VMEM((held, LANES), jnp.uint32),
                        pltpu.VMEM((topk * tiles, LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA(())],
        # a batch row's queries follow one another: they share ``cache``
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=held * LANES * 4 + WORK_VMEM_BYTES),
        interpret=interpret,
    )(at.reshape(b * s, 1, topk), q_lo, q_hi,
      real.astype(jnp.int32)[:, :, None, :], packed)

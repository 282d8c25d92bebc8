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
576), so that a row is fetched by one load and one store. In VMEM the
fetched words are read back a lane tile at a stride, split into two
bfloat16 halves with a shift and a mask, and each product is the sum of two
over the halves; the query is laid out the same way (``_halves``). The
order of columns is the kernel's own business: both products contract or
carry it.

A query's work sits on two disjoint parts of the core. The fetch is scalar
work: a row's position read from SMEM, its address, one load, one store (in
a loop of its own, both scalar slots full for 2.75 bundles a row: 4.2-4.6 us
of a query's 9.9 on a v5e). The split (vector ALUs) and the products (MXUs)
are one basic block bound by the MXUs, which uses no scalar slot at all
(5,240 bundles a query at the dots3 block, 5,120 of them 1,280 ``vmatmul``
of 16 rows on four MXUs). So the queries of a batch row are
software-pipelined over two buffers of rows: query ``j`` reads the one that
query ``j - 1`` filled and fills the other with the rows of query ``j + 1``,
whose positions come as a second SMEM block of ``at`` (clamped at the row's
last query, which fetches its own rows again: nothing is fetched out of a
cache that is not loaded yet). A query's body is a loop over slices of
``FETCH_SLICE`` slots, and an iteration is one basic block that splits and
scores this query's slots of the slice and fetches the next query's rows of
the same slots, so that Mosaic's scheduler packs the fetch into the bundles
the products leave free: 6.9 us a query alone on a v5e. (The whole fetch
unrolled into a body with no loop ran 5.9 us, and cost every trace of a
program that holds the kernel 6 s on this sandbox and 20 s on the chip's
host, twice a program: a cell's set-up rose by half. A slice's fetch is
unrolled where the loop is lowered, 0.4 s.) Which buffer a query reads is
static where its body is compiled (two copies, by the parity of ``j``), so
that the fetch's stores are seen not to meet the split's loads. A batch
row's first query has nobody before it: its step loads the row's cache and
fetches its own rows, in a loop and beside nothing. What stays one after
another inside a query: the softmax needs the whole ``[H, topk]`` row of
scores (a slice's are kept in VMEM, as are the halves' value parts) before
the first value product, and the value products contract over all ``topk``
slots at once; a score is one contraction over a row's columns whatever the
slice, so every sum keeps the order it has with one query a call.

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
# Rows fetched between two tests of the loop's counter: of a batch row's
# first query, and of the next query beside a slice of this query's slots.
FETCH_UNROLL = 16
FETCH_SLICE = 256
# VMEM beside the resident cache: the two buffers of fetched words (3.1 MB
# each at the dots3 block), the scores (1 MB), the value parts of the two
# halves (1 MB each), a slice's halves and the blocks of at, q, real and o in
# flight.
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


def _kernel(at_ref, ahead_ref, q_lo_ref, q_hi_ref, real_ref, keys_ref, o_ref,
            cache, rows_even, rows_odd, scores, low_v, high_v, sem, *, scale,
            topk, vw, tiles, slots):
    b, j = pl.program_id(0), pl.program_id(1)

    def fetch(into, at, k, slot):   # a row: ``tiles`` sublanes, a scalar
        into[pl.ds(slot * tiles, tiles), :] = \
            cache[pl.ds(at[0, k] * tiles, tiles), :]    # read, a load, a store

    @pl.when(j == 0)
    def _first():   # the batch row's cache, once for all of its queries,
        copy = pltpu.make_async_copy(keys_ref.at[b], cache, sem)
        copy.start()
        copy.wait()

        def rows_of_first(i, _):    # and its first query's, beside nothing
            first = pl.multiple_of(i * FETCH_UNROLL, FETCH_UNROLL)
            for u in range(FETCH_UNROLL):
                fetch(rows_even, at_ref, first + u, first + u)
            return 0

        lax.fori_loop(0, topk // FETCH_UNROLL, rows_of_first, 0)

    def query(rows, ahead):
        def a_slice(c, _):
            # One basic block a slice: this query's slots split and scored,
            first = pl.multiple_of(c * slots, slots)
            mine = rows.at[pl.ds(first * tiles, slots * tiles)]
            # the rows' lane tiles side by side again: [slots, words]
            words = jnp.concatenate([mine[pl.ds(k, slots, stride=tiles), :]
                                     for k in range(tiles)], -1)
            low = pltpu.bitcast(words << 16, jnp.float32).astype(jnp.bfloat16)
            high = pltpu.bitcast(words & jnp.uint32(0xFFFF0000),
                                 jnp.float32).astype(jnp.bfloat16)
            scores[c] = (_dot_t(q_lo_ref[...], low)
                         + _dot_t(q_hi_ref[...], high)) * scale
            low_v[pl.ds(first, slots), :] = low[:, :vw]
            high_v[pl.ds(first, slots), :] = high[:, :vw]
            # and the next query's rows of the same slots fetched, unrolled
            # where the loop is lowered and written last: Mosaic's scheduler
            # then packs them into the bundles the products leave free (a
            # fetch written first ran first, beside nothing).
            theirs = ahead.at[pl.ds(first * tiles, slots * tiles)]
            lax.fori_loop(
                0, slots, lambda u, _: fetch(theirs, ahead_ref, first + u, u),
                None, unroll=True)
            return 0

        lax.fori_loop(0, topk // slots, a_slice, 0)
        s = jnp.concatenate([scores[c] for c in range(topk // slots)], -1)
        s = jnp.where(real_ref[...] != 0, s, NEG_INF)   # [H, topk] float32
        e = jnp.exp(s - s.max(-1, keepdims=True))
        # a reciprocal a head and a multiply a score, not a division a score
        p = (e * (1.0 / e.sum(-1, keepdims=True))).astype(jnp.bfloat16)
        o_ref[:, :vw] = _dot(p, low_v[...]).astype(o_ref.dtype)
        o_ref[:, vw:] = _dot(p, high_v[...]).astype(o_ref.dtype)

    # Which buffer a query reads is known where its body is compiled, so
    # that the fetch's stores and the split's loads are seen not to meet.
    pl.when(j % 2 == 0)(lambda: query(rows_even, rows_odd))
    pl.when(j % 2 == 1)(lambda: query(rows_odd, rows_even))


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
    slots = FETCH_SLICE if topk % FETCH_SLICE == 0 else topk
    if not takes(held // tiles, width, v, topk):
        raise ValueError(f"rt_sparse_attend does not take {topk} of "
                         f"{held // tiles} rows of {width} with a value "
                         f"part of {v}")
    q_lo, q_hi = _halves(q, v)
    per_query = lambda *block: pl.BlockSpec(       # noqa: E731
        (None, None) + block, lambda i, j: (i, j, 0, 0))
    # a query's positions, and those of the next of its batch row (the last
    # query's own again: nothing is fetched out of another row's cache)
    at_of = lambda ahead: pl.BlockSpec(             # noqa: E731
        (None, 1, topk),
        lambda i, j: (i * s + jnp.minimum(j + ahead, s - 1), 0, 0),
        memory_space=pltpu.SMEM)
    at = at.reshape(b * s, 1, topk)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, topk=topk, vw=vw,
                          tiles=tiles, slots=slots),
        name="rt_sparse_attend",   # rtcheck: allow-unminted-metric(a kernel's name in the device trace, not a metric)
        grid=(b, s),
        in_specs=[
            at_of(0), at_of(1),
            per_query(h, words), per_query(h, words), per_query(1, topk),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=per_query(h, v),
        out_shape=jax.ShapeDtypeStruct((b, s, h, v), q.dtype),
        scratch_shapes=[pltpu.VMEM((held, LANES), jnp.uint32),
                        pltpu.VMEM((topk * tiles, LANES), jnp.uint32),
                        pltpu.VMEM((topk * tiles, LANES), jnp.uint32),
                        pltpu.VMEM((topk // slots, h, slots), jnp.float32),
                        pltpu.VMEM((topk, vw), jnp.bfloat16),
                        pltpu.VMEM((topk, vw), jnp.bfloat16),
                        pltpu.SemaphoreType.DMA(())],
        # a batch row's queries follow one another: they share ``cache``,
        # and each finds its rows where the one before it put them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=held * LANES * 4 + WORK_VMEM_BYTES),
        interpret=interpret,
    )(at, at, q_lo, q_hi, real.astype(jnp.int32)[:, :, None, :], packed)

"""Latent attention: keys and values of all heads kept as one low-rank
latent a position (DeepSeek-V2's MLA), in two kinds of layer.

A "latent" layer attends to every earlier position or, where
``cfg.index_topk`` is set and more than that many keys stand, to the
``index_topk`` of them that a learned indexer scores highest (DeepSeek-V3.2's
sparse attention): ``I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])`` over
``index_heads`` small heads, the ``index_topk`` largest ``s <= t`` kept. A
"window" layer is the same mixer at widths of its own
(``cfg.window_latent``), without indexer, over the last ``cfg.window``
positions, the query's own included. Both can gate each head's output by
``sigmoid(w_g . x)`` (``attn_gate = "headwise"``) and scale the two latents
by ``sqrt(d_model / rank)`` (``lora_rescale``).

What is cached for a position is ``[c_kv ; k_rope]`` (``dims.cached`` wide:
the normed latent and the one rotated key all heads share) and, in an
indexed layer, the indexer's key ``kI``. The mixer makes a position's
entries, hands them to ``attend(new) -> (keys, key positions, kept)`` and
attends to what comes back: the sequence's own entries in the training
forward (key positions None: index i holds position i, and a gradient may
be taken), a cache with the entries written in prefill and decode
(models/generate.py). Nothing else differs between the three.

Two forms of one product. *Expanded*: every key's ``k_nope`` and ``v`` are
made from its latent (``W_ukv``), ``(nope + v)`` multiply-adds a head and
pair: for many queries over keys they share. *Absorbed*: ``W_uk`` is folded
into the query and ``W_uv`` applied to the attended latents, ``(2 kv_rank +
rope)`` a head and pair and nothing per key: for one query (a decode step),
and wherever each query has keys of its own (the selection), whose expanded
keys nothing could hold. Queries go through either in blocks, so that no
``[heads, queries, keys]`` score tensor is whole at once. Over the selection
the absorbed form runs in one of two ways, by what the code sees of its
input (``sparse_in_kernel``): where a block of queries fetches more rows
than the cache holds, on a TPU (every prefill block of a long prompt), in
the Pallas kernel ``rt_sparse_attend`` (ops/sparse_attend.py), which holds
one batch row's cache in VMEM, fetches each selected row from there once
(the next query's rows beside this query's products: 6.9 us a query of 128
heads over 2,048 rows on a v5e) and hands out the attended latents alone,
the scores float32 all the way;
otherwise (a decode step, whose one query a row fetches a sixteenth of the
cache; the CPU; small sizes) as a gather of the rows into a copy and three
``jnp`` passes over it, the kernel's reference in the tests. One algorithm,
two sizes: no knob chooses. Over the
sequence's own keys the expanded form is plain multi-head attention with k =
``[k_nope ; k_rope]`` (``nope + rope`` wide) and v (``v`` wide): there it
runs in the flash kernels, which take a value width of its own, or in query
blocks that the backward pass makes again, so that a latent layer trains at
8k and holds no block's scores across its backward.

A window layer's expanded form is banded causal attention, and its bound is
the layer's (``cfg.window``), never the mask's alone: ``attend`` hands a
window layer's many queries their keys in position order, ending with the
queries' own (a prefill chunk: the ``window - 1`` positions before it out of
the ring, then its own entries; the training forward: the sequence), so
that a query's window is a run of key indices. One algorithm at two sizes,
by what the code sees of its input (``window_in_kernel``): on a TPU a
prefill chunk goes through the flash forward kernel with that window
(ops/flash.py: a block of 512 queries visits the two key blocks of 512 its
band reaches, no score leaves VMEM, the keys before a prompt's position 0
left out by a prefetched scalar); everywhere else (the CPU, the training
forward, sizes the tiles do not divide) a block of queries takes the
``block + window - 1`` keys its band reaches out of the ordered keys and
masks those by position, the kernel's reference in the tests. A decode
step's one query is absorbed, over the ring as it lies.

The selection is a SET: ``select`` hands a query's ``index_topk`` key
positions to a gather and a softmax that sums over them, and nothing reads
their order. So no row of scores is sorted (XLA lowers ``lax.top_k`` on a
TPU to a full sort of the row, 32,896 keys padded to 65,536: 45 us a row).
The row's k-th largest score is found exactly, by bisection on the float32
bits folded into integer order (32 counts along the row, one loop), the
keys above it and the lowest-positioned of those equal to it are marked,
and the marks are compacted into ``index_topk`` slots a tile of 128 keys at
a time with three small matrix products: 3 us a row on a v5e at 32,896 keys
(``_top_set``). Exact in float32, to the last key and tie what
``lax.top_k`` selects.

Scopes in the compiled program: ``rt.mla.project`` (the projections and the
output), ``rt.dsa.index`` (the indexer's scores and the selection),
``rt.mla.sparse`` (the attention over the selection: the cache packed for
the kernel once a layer and chunk, the query absorbed, the kernel's Mosaic
call or the gather and the three passes, the values unabsorbed),
``rt.mla.window`` (a window layer's attention: the keys' expansion, the
layouts and the Mosaic call ``rt_flash_fwd``, or the banded blocks; a decode
step's absorbed attention over the ring), ``rt.mla.dense`` (a latent
layer's attention over all keys, where there are no more than
``index_topk``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.transformer import (LatentDims, TransformerConfig,
                                        _output_gate, _rmsnorm, _rope)
from ray_tpu.ops import sparse_attend
from ray_tpu.ops.flash import _on_tpu, flash_attention

PARAMS_KEY = {"latent": "mla", "window": "swa"}
# Queries a block: of the selection (the indexer's float32 scores of a block
# over every key are held, 128 x 32,896 x 4 B = 17 MB a row of the batch at
# 32k; off the kernel's path each query's index_topk rows of the cache too,
# 128 x 2,048 x 576 x 2 B = 302 MB a row) and of attention over shared keys.
SPARSE_QUERY_BLOCK = 128
DENSE_QUERY_BLOCK = 512
# A sequence over its own keys is filled up to a multiple of this for the
# flash kernels: the larger of their default blocks (ops/flash.py).
FLASH_MULTIPLE = 1024
# What the sizes of a window layer's chunk are multiples of where it goes
# through the flash forward kernel: a row of lanes, the least block.
KERNEL_TILE = 128
# Indexer heads scored at once: [B, group, block, keys] float32 is held.
INDEX_HEAD_GROUP = 16
# Keys a tile of the selection's compaction: one row of lanes.
SELECT_TILE = 128
LAYER_NORM_EPS = 1e-6
NEVER = jnp.iinfo(jnp.int32).max     # the position of a slot never written


def _indexed(cfg: TransformerConfig, kind: str) -> bool:
    return kind == "latent" and cfg.index_topk > 0


def latent_init(key, cfg: TransformerConfig, kind: str) -> dict:
    dims, d, pd = cfg.latent_dims(kind), cfg.d_model, cfg.param_dtype
    init = jax.nn.initializers.normal(0.02)
    ks = jax.random.split(key, 9)
    p = {
        "wdq": init(ks[0], (d, dims.q_rank), pd),
        "q_norm": jnp.ones((dims.q_rank,), pd),
        "wuq": init(ks[1], (dims.q_rank, dims.heads,
                            dims.nope + dims.rope), pd),
        "wdkv": init(ks[2], (d, dims.cached), pd),
        "kv_norm": jnp.ones((dims.kv_rank,), pd),
        "wukv": init(ks[3], (dims.kv_rank, dims.heads,
                             dims.nope + dims.v), pd),
        "wo": init(ks[4], (dims.heads, dims.v, d), pd),
    }
    if cfg.attn_gate == "headwise":
        p["wg"] = init(ks[5], (d, dims.heads), pd)
    if _indexed(cfg, kind):
        p["index"] = {
            "wq": init(ks[6], (dims.q_rank, cfg.index_heads,
                               cfg.index_head_dim), pd),
            "wk": init(ks[7], (d, cfg.index_head_dim), pd),
            "k_norm_w": jnp.ones((cfg.index_head_dim,), pd),
            "k_norm_b": jnp.zeros((cfg.index_head_dim,), pd),
            "ww": init(ks[8], (d, cfg.index_heads), pd),
        }
    return p


def latent_axes(cfg: TransformerConfig, kind: str, L) -> dict:
    """Logical axes of ``latent_init``'s tree (``L`` adds the stacked
    dims): heads over tp, the rest whole."""
    p = {"wdq": L("embed", None), "q_norm": L(None),
         "wuq": L(None, "heads", "kv"), "wdkv": L("embed", None),
         "kv_norm": L(None), "wukv": L(None, "heads", "kv"),
         "wo": L("heads", "kv", "embed")}
    if cfg.attn_gate == "headwise":
        p["wg"] = L("embed", "heads")
    if _indexed(cfg, kind):
        p["index"] = {"wq": L(None, None, None), "wk": L("embed", None),
                      "k_norm_w": L(None), "k_norm_b": L(None),
                      "ww": L("embed", None)}
    return p


def _layer_norm(x, w, b):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mean).mean(-1, keepdims=True)
    return ((x32 - mean) * lax.rsqrt(var + LAYER_NORM_EPS)).astype(x.dtype) \
        * w.astype(x.dtype) + b.astype(x.dtype)


def _block_queries(queries: int, block: int) -> int:
    """Queries that go through at once, of ``queries`` in blocks of
    ``block``: all of them where they are no more than a block or do not
    divide."""
    return queries if queries <= block or queries % block else block


def _over_query_blocks(fn, block: int, *per_query):
    """``fn(*blocks) -> tree of [B, block, ...]`` over blocks of the
    arrays' second dim (queries), one block at a time; the whole at once
    where the queries are no more than a block or do not divide."""
    s = per_query[0].shape[1]
    if _block_queries(s, block) == s:
        return fn(*per_query)
    n = s // block
    split = [jnp.moveaxis(a.reshape(a.shape[0], n, block, *a.shape[2:]),
                          1, 0) for a in per_query]
    out = lax.map(lambda blocks: fn(*blocks), split)
    return jax.tree.map(
        lambda a: jnp.moveaxis(a, 0, 1).reshape(
            a.shape[1], n * block, *a.shape[3:]), out)


def _softmax(scores, mask):
    """float32 softmax over the last dim of the masked scores; a row with
    no key (never under a causal mask that keeps the query's own position)
    would come out uniform."""
    return jax.nn.softmax(jnp.where(mask, scores.astype(jnp.float32), -1e30),
                          axis=-1)


def _mask(qpos, kpos, window: int):
    """[B, S, T]: key position <= query position, and inside the window."""
    q, k = qpos[:, :, None], kpos[:, None, :]
    return (k <= q) & (q - k < window) if window else (k <= q)


def _flash_own(impl: str, s: int) -> bool:
    """Whether a sequence of ``s`` positions over its own keys goes through
    the flash kernels: ``"flash"`` is the kernel or an error, ``"auto"``
    the kernel on a TPU from a length on that fills its blocks."""
    return impl == "flash" or (impl == "auto" and _on_tpu()
                               and s >= FLASH_MULTIPLE)


def window_in_kernel(dims: LatentDims, window: int, queries: int,
                     keys: int) -> bool:
    """Whether ``queries`` of a window layer over ``keys`` keys in position
    order, the last ``queries`` of them the queries' own, run in the flash
    forward kernel with a window: on a TPU, more than one query (a decode
    step is absorbed), just the ``window - 1`` keys before the first query
    that any query reaches (what a prefill chunk is handed; the training
    forward's own keys stay in the ``jnp`` form), and sizes the kernel's
    blocks and the chip's tiles divide. The ``jnp`` form otherwise."""
    return _on_tpu() and window > 0 and queries > 1 \
        and keys == queries + window - 1 \
        and not any(n % KERNEL_TILE for n in (
            queries, keys, dims.nope + dims.rope, dims.v))


def _with_lead(keys, kpos, queries: int, lead: int):
    """keys [B, T, ...] in position order, the last ``queries`` the
    queries' own, and their positions [b, T] -> the same with just ``lead``
    keys before the first query's: older ones cut off, the front filled
    with keys that do not exist."""
    extra = keys.shape[1] - queries - lead
    if extra >= 0:
        return keys[:, extra:], kpos[:, extra:]
    fill = ((0, 0), (-extra, 0))
    return jnp.pad(keys, fill + ((0, 0),) * (keys.ndim - 2)), \
        jnp.pad(kpos, fill, constant_values=NEVER)


def _expanded(dims, wukv, q_nope, q_rope, qpos, keys, kpos, window,
              own: str = ""):
    """Every key's k_nope and v from its latent; queries in blocks.
    ``own``: the keys are the queries' own sequence, position i at index i
    (the training forward), and a gradient may be taken, under the
    configuration's ``attn_impl``: the expanded form is plain multi-head
    attention with k = [k_nope ; k_rope] and a value width of its own, so
    it runs in the flash kernels (ops/flash.py: no score leaves the chip's
    fast memory, causal blocks above the diagonal skipped) or, where they
    do not run, in query blocks that a backward pass makes again.
    ``window``: the keys come in position order and end with the queries'
    own (a key that does not exist holds position ``NEVER``), so the band
    is one of indices: a prefill chunk's goes through the flash forward
    kernel with that window (`window_in_kernel`), and everywhere else a
    block of queries scores the ``block + window - 1`` keys its band
    reaches, not all of them."""
    r = dims.kv_rank
    s = q_nope.shape[1]
    in_kernel = not own and window_in_kernel(dims, window, s, keys.shape[1])
    if window and not in_kernel:
        keys, kpos = _with_lead(keys, kpos, s, window - 1)
    kv = jnp.einsum("btr,rhd->bthd", keys[..., :r], wukv)
    k_nope, v, k_rope = kv[..., :dims.nope], kv[..., dims.nope:], \
        keys[..., r:]
    scale = 1.0 / math.sqrt(dims.nope + dims.rope)
    if in_kernel or own and not window and _flash_own(own, s):
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None], k_nope.shape[:3] + (dims.rope,))], -1)
        if in_kernel:       # the keys that do not exist lead the others
            return flash_attention(
                q, k, v, causal=True, scale=scale, window=window,
                first_key=jnp.sum(kpos[0] == NEVER, dtype=jnp.int32))
        # up to a length the kernels' blocks divide (a multi-token-
        # prediction module runs S - 1 positions): the keys added lie
        # behind every query, the queries added are cut off again
        fill = ((0, 0), (0, -s % FLASH_MULTIPLE), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, fill) for a in (q, k, v))
        return flash_attention(q, k, v, causal=True, scale=scale)[:, :s]

    def attend(q_nope, q_rope, qpos, k_nope, k_rope, v, kpos):
        scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
                  + jnp.einsum("bshd,btd->bhst", q_rope, k_rope)) * scale
        w = _softmax(scores, _mask(qpos, kpos, window)[:, None])
        return jnp.einsum("bhst,bthd->bshd", w.astype(v.dtype), v)

    def block(q_nope, q_rope, qpos, *index):
        shared = k_nope, k_rope, v, kpos
        if window:      # the keys from window - 1 before the block's first
            span = q_nope.shape[1] + window - 1
            shared = [lax.dynamic_slice_in_dim(a, index[0][0, 0], span, 1)
                      for a in shared]
        return attend(q_nope, q_rope, qpos, *shared)

    # a window's block finds its keys by its first query's index
    index = (jnp.broadcast_to(jnp.arange(s), qpos.shape),) if window else ()
    # under a gradient a block's scores are made again, not kept
    return _over_query_blocks(jax.checkpoint(block) if own else block,
                              DENSE_QUERY_BLOCK, q_nope, q_rope, qpos,
                              *index)


def _absorb(dims, wukv, q_nope):
    """q_nope [B, S, H, nope] -> the query against latents [B, S, H, r]."""
    return jnp.einsum("bshd,rhd->bshr", q_nope, wukv[..., :dims.nope])


def _unabsorb(dims, wukv, o_latent):
    """Attended latents [B, S, H, r] -> values [B, S, H, v]."""
    return jnp.einsum("bshr,rhd->bshd", o_latent, wukv[..., dims.nope:])


def _absorbed(dims, wukv, q_nope, q_rope, qpos, keys, kpos, window):
    """The latents themselves as keys and values of every head."""
    r = dims.kv_rank
    latent, k_rope = keys[..., :r], keys[..., r:]
    scale = 1.0 / math.sqrt(dims.nope + dims.rope)
    scores = (jnp.einsum("bshr,btr->bhst", _absorb(dims, wukv, q_nope),
                         latent)
              + jnp.einsum("bshd,btd->bhst", q_rope, k_rope)) * scale
    w = _softmax(scores, _mask(qpos, kpos, window)[:, None])
    return _unabsorb(dims, wukv, jnp.einsum(
        "bhst,btr->bshr", w.astype(latent.dtype), latent))


def index_scores(qi, w, ki):
    """qi [B, S, J, D], w [B, S, J] float32, ki [B, T, D] -> ``sum_j w_j
    relu(qi_j . ki)`` [B, S, T] float32, INDEX_HEAD_GROUP heads at a
    time."""
    b, s, j, _ = qi.shape
    g = math.gcd(j, INDEX_HEAD_GROUP)

    def group(acc, q_w):
        q, wj = q_w                         # [B, S, g, D], [B, S, g]
        dots = jnp.einsum("bsjd,btd->bsjt", q, ki,
                          preferred_element_type=jnp.float32)
        return acc + jnp.einsum("bsjt,bsj->bst", jax.nn.relu(dots), wj), None

    groups = (jnp.moveaxis(qi.reshape(b, s, j // g, g, -1), 2, 0),
              jnp.moveaxis(w.reshape(b, s, j // g, g), 2, 0))
    return lax.scan(group, jnp.zeros((b, s, ki.shape[1]), jnp.float32),
                    groups)[0]


def _ordered_bits(x):
    """float32 -> uint32 whose integer order is the floats' total order
    (``-inf`` below every finite value, ``-0.0`` below ``+0.0``)."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


_LEAST_KEY = 0x007FFFFF         # _ordered_bits(-inf): a masked key


def _kth_largest(keys, k: int):
    """keys [..., T] uint32, T >= k -> the k-th largest of each row [...]:
    the largest v with ``count(keys >= v) >= k``, a bit a pass from the
    top (32 counts along the row; no order among the keys is made)."""
    def bit(i, thr):
        cand = thr | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = (keys >= cand[..., None]).sum(-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


def _running_count(mask):
    """mask [..., SELECT_TILE] of 0 / 1 -> how many are set up to and with
    each lane (int32), as a product with a triangle of ones: 0 / 1 in
    bfloat16 and sums of at most SELECT_TILE of them in float32 are
    exact."""
    lane = jnp.arange(SELECT_TILE)
    upto = (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16)
    return jnp.einsum("...l,lm->...m", mask.astype(jnp.bfloat16), upto,
                      preferred_element_type=jnp.float32).astype(jnp.int32)


def _top_set(scores, topk: int):
    """scores [B, S, T] float32 -> (at [B, S, topk] int32, real [B, S,
    topk]): the positions ``lax.top_k(scores, topk)`` returns, as a set,
    ascending; ``-inf`` is never selected, and the slots a row does not
    fill are not real and point at key 0."""
    b, s, t = scores.shape
    tiles = -(-max(t, topk) // SELECT_TILE)
    keys = _ordered_bits(jnp.pad(
        scores, ((0, 0), (0, 0), (0, tiles * SELECT_TILE - t)),
        constant_values=-jnp.inf)).reshape(b, s, tiles, SELECT_TILE)
    thr = _kth_largest(keys.reshape(b, s, -1), topk)[..., None, None]
    above, tie = keys > thr, keys == thr
    # the k-th value's ties, lowest positions first, fill what is left
    tie_upto = _running_count(tie)
    tie_before = jnp.cumsum(tie_upto[..., -1], -1) - tie_upto[..., -1]
    need = topk - above.sum((-1, -2), dtype=jnp.int32)
    chosen = (above | (tie & (tie_before[..., None] + tie_upto
                              <= need[..., None, None]))) \
        & (keys > _LEAST_KEY)
    # compaction: slot j holds the (j + 1)-th chosen key of the row. Its
    # tile, by the running sum of the tiles' counts; that tile's lanes
    # fetched by a one-hot product, each chosen lane reading its rank in
    # the row modulo the tile (no two alike within a tile, and 0 / 1 and
    # ranks up to SELECT_TILE are exact in bfloat16); the lane that reads
    # j's.
    upto = _running_count(chosen)
    count = upto[..., -1]                               # [B, S, tiles]
    end = jnp.cumsum(count, -1)
    start = end - count
    rank = jnp.where(chosen, (start[..., None] + upto) % SELECT_TILE + 1, 0)
    slot = jnp.arange(topk)
    # summed over the tiles with the slots along the lanes: 9 x faster on
    # the chip than with the tiles along them
    tile = (end[..., :, None] <= slot).sum(-2)
    here = tile[..., None] == jnp.arange(tiles)
    fetched = jnp.einsum("bsjt,bstl->bsjl", here.astype(jnp.bfloat16),
                         rank.astype(jnp.bfloat16),
                         preferred_element_type=jnp.bfloat16)
    wanted = ((slot + 1) % SELECT_TILE + 1).astype(jnp.bfloat16)
    lane = ((fetched == wanted[:, None]) * jnp.arange(SELECT_TILE)).sum(-1)
    real = slot < end[..., -1:]
    return jnp.where(real, tile * SELECT_TILE + lane, 0).astype(jnp.int32), \
        real


def select(topk: int, qi, w, ki, qpos, kpos):
    """-> (positions in the keys [B, S, topk] int32, which of them are
    real [B, S, topk]): the ``topk`` keys ``s <= t`` of largest index
    score; a query with fewer keys than that selects them all, and the
    rest of its row is marked not real. What is guaranteed is the set
    ``{at[real]}``: it is the set ``lax.top_k`` of the masked float32
    scores returns (ties at the k-th value go to the lowest positions;
    ``-0.0`` counts as under ``+0.0``). The order within a row is free
    (ascending positions today) and nothing downstream reads it; a slot
    that is not real holds position 0. The module comment has the cost."""
    scores = jnp.where(_mask(qpos, kpos, 0), index_scores(qi, w, ki),
                       -jnp.inf)
    return _top_set(scores, topk)


def sparse_in_kernel(dims: LatentDims, topk: int, queries: int,
                     keys: int) -> bool:
    """Whether ``queries`` a call, each over a selection of ``topk`` of
    ``keys`` cached rows, run in ``rt_sparse_attend``: on a TPU, where a
    block of them fetches more rows than the cache holds (so that holding
    one batch row's cache in VMEM pays: a prefill block, never a decode
    step) and the kernel takes the sizes. The ``jnp`` form otherwise."""
    return _on_tpu() \
        and _block_queries(queries, SPARSE_QUERY_BLOCK) * topk > keys \
        and sparse_attend.takes(keys, dims.cached, dims.kv_rank, topk)


def _sparse(cfg, dims, wukv, q_nope, q_rope, qpos, keys, kpos, index_keys,
            index_query):
    """Each query over its own selection, absorbed, in blocks of queries.
    -> (o [B, S, H, v], the last query's selection)."""
    r = dims.kv_rank
    scale = 1.0 / math.sqrt(dims.nope + dims.rope)
    gather = jax.vmap(lambda rows, at: rows[at])    # over the batch
    packed = None
    if sparse_in_kernel(dims, cfg.index_topk, q_nope.shape[1],
                        keys.shape[1]):
        with jax.named_scope("rt.mla.sparse"):
            packed = sparse_attend.pack(keys, r)    # once for every block

    def block(q_nope, q_rope, qpos, qi, w):
        with jax.named_scope("rt.dsa.index"):
            at, real = select(cfg.index_topk, qi, w, index_keys, qpos, kpos)
        with jax.named_scope("rt.mla.sparse"):
            q_latent = _absorb(dims, wukv, q_nope)
            if packed is not None:
                o_latent = sparse_attend.sparse_attend(
                    jnp.concatenate([q_latent, q_rope], -1), packed, at,
                    real, v=r, scale=scale)
            else:
                rows = gather(keys, at)             # [B, S, topk, cached]
                latent, k_rope = rows[..., :r], rows[..., r:]
                scores = (jnp.einsum("bshr,bskr->bshk", q_latent, latent)
                          + jnp.einsum("bshd,bskd->bshk", q_rope, k_rope)) \
                    * scale
                p = _softmax(scores, real[:, :, None, :])
                o_latent = jnp.einsum("bshk,bskr->bshr",
                                      p.astype(latent.dtype), latent)
            o = _unabsorb(dims, wukv, o_latent)
        return o, at, real

    o, at, real = _over_query_blocks(block, SPARSE_QUERY_BLOCK, q_nope,
                                     q_rope, qpos, *index_query)
    return o, {"selected": at[:, -1], "selected_real": real[:, -1]}


def latent_mix(cfg: TransformerConfig, layer, h, positions, attend):
    """The latent-attention mixer over the normed input ``h`` [B, S, E] of
    the layer's kind (``mla``: a latent layer, ``swa``: a window layer).
    -> (o [B, S, E], what ``attend`` kept, taps: an indexed layer's
    selection for its last query, ``selected`` [B, topk] key positions and
    ``selected_real``, where it selected; a window layer's ``window_keys``
    [B], the keys its last query attended to)."""
    kind = "latent" if "mla" in layer else "window"
    p, dims, dt = layer[PARAMS_KEY[kind]], cfg.latent_dims(kind), cfg.dtype
    window = cfg.window if kind == "window" else 0
    r, eps = dims.kv_rank, cfg.norm_eps
    wukv = p["wukv"].astype(dt)
    with jax.named_scope("rt.mla.project"):
        c_q = _rmsnorm(h @ p["wdq"].astype(dt), p["q_norm"], eps)
        down = h @ p["wdkv"].astype(dt)
        c_kv = _rmsnorm(down[..., :r], p["kv_norm"], eps)
        if cfg.lora_rescale:
            c_q = c_q * jnp.asarray(math.sqrt(cfg.d_model / dims.q_rank), dt)
            c_kv = c_kv * jnp.asarray(math.sqrt(cfg.d_model / r), dt)
        k_rope = _rope(down[:, :, None, r:], positions, dims.rope_theta)
        q = jnp.einsum("bsr,rhd->bshd", c_q, p["wuq"].astype(dt))
        q_nope = q[..., :dims.nope]
        q_rope = _rope(q[..., dims.nope:], positions, dims.rope_theta)
        new = {"latent": jnp.concatenate([c_kv, k_rope[:, :, 0]], -1)}
    if "index" in p:
        with jax.named_scope("rt.dsa.index"):
            ix = p["index"]
            qi = _rope(jnp.einsum("bsr,rjd->bsjd", c_q, ix["wq"].astype(dt)),
                       positions, dims.rope_theta, dims.rope)
            ki = _layer_norm(h @ ix["wk"].astype(dt), ix["k_norm_w"],
                             ix["k_norm_b"])
            new["index"] = _rope(ki[:, :, None], positions, dims.rope_theta,
                                 dims.rope)[:, :, 0]
            w = (h @ ix["ww"].astype(dt)).astype(jnp.float32) \
                * (cfg.index_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    keys, kpos, kept = attend(new)
    own = kpos is None          # the training forward: the sequence's own
    if own:
        kpos = positions
    cached = keys["latent"]
    taps = {}
    if "index" in p and cached.shape[1] > cfg.index_topk:
        o, taps = _sparse(cfg, dims, wukv, q_nope, q_rope, positions, cached,
                          kpos, keys["index"], (qi, w))
    else:
        form = _expanded if h.shape[1] > 1 else _absorbed
        if own and form is _expanded:
            form = partial(_expanded, own=cfg.attn_impl)
        with jax.named_scope("rt.mla.window" if window else "rt.mla.dense"):
            o = form(dims, wukv, q_nope, q_rope, positions, cached, kpos,
                     window)
        if window:      # how many keys the last query's window held
            taps = {"window_keys": _mask(positions[:, -1:], kpos,
                                         window).sum(-1)[:, 0]}
    with jax.named_scope("rt.mla.project"):
        if "wg" in p:
            o = _output_gate(o, h @ p["wg"].astype(dt))
        return jnp.einsum("bshd,hde->bse", o, p["wo"].astype(dt)), kept, taps


def ring_positions(last, rows: int):
    """The positions a ring of ``rows`` slots holds once position ``last``
    is written (slot = position mod rows) -> [rows] int32; a slot nothing
    was written to reads as a position no query reaches."""
    slot = jnp.arange(rows)
    held = last - (last - slot) % rows
    return jnp.where(held >= 0, held, NEVER)

"""Autoregressive generation with a KV cache for the flagship Transformer.

The reference has no in-tree LM inference; serving there means wrapping an
external model in Ray Serve. Here decode is a first-class TPU program
(completing the LM story: train with jax_step, serve with serve/ + this):

- The KV cache is ONE stacked array pair [loop steps x L, B, T_max, KVH, D]
  matching the layer-stacked parameter layout: one slot a layer, and for a
  looped stack (``cfg.loop_steps`` passes over the one set of weights) one
  slot for every (pass t, layer l), slot ``t * L + l``. The passes share
  weights, not activations: layer l's keys and values of pass t are
  projections of pass t's state, so a pass-t query sees pass-t keys only.
  Decode scans over (layers, layer index), and over the passes around
  that, with one compiled layer body and CARRIES the stacked cache through
  both levels: a layer writes its new key and value at [slot, :, pos] and
  then reads its slot's written prefix back from the updated carry. Write
  first, read second: a read of the pre-update stack after the write would
  make XLA keep two buffers and copy 2 GB a token. The cache is never a
  scanned input or output inside the decode loop, so the token loop updates
  one buffer in place (what a step writes is a few positions a slot, not
  the whole cache).
- `generate` runs the whole decode loop INSIDE jit via lax.scan: static
  shapes (cache padded to max length, attention masked by position), PRNG
  threaded through the scan — zero host round-trips per token.
- The token loop runs in SEGMENTS: consecutive lax.scans over the one
  carried cache, same carry and same body, each compiled for a static
  ``extent``: the positions its last step will have written, rounded up to
  the block a step writes. A step's attention reads ``[B, extent, KVH, D]``
  of its slot, not all of T_max (`_decode_segments`; the slice fuses into
  the two attention fusions, no slab is written out). A short loop, or one
  whose steps are a small part of the cache, stays one segment of T_max.
- Prefill and decode run the training forward's one layer
  (transformer._layer_apply) and hand it only the attention step: prefill
  keeps each layer's rotated K/V as scan outputs (once a call; a looped
  stack's prefill writes them into the carried cache slot by slot, since
  the scan outputs of a pass, stacked over the passes, would hold a
  pass's slots twice); decode steps attend over the cache with a position
  mask. S=1 queries are bandwidth-bound, so a masked position costs what a
  read one costs: where the cache is most of what a step reads (a slot for
  every (pass, layer): 9.7 GB a token at 16 x 384 beside 19.9 GB of
  weights) the never-written tail of T_max is a third of the cache's
  bytes, which is what the segments' extents are for.

GQA (n_kv_heads < n_heads) is supported; pp_stages>1 is not (decode
pipelining is a different schedule than GPipe microbatching).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.latent import ring_positions, sparse_in_kernel
from ray_tpu.models.transformer import (TransformerConfig, _attention,
                                        _head, _layer_apply,
                                        _over_loop_steps)
from ray_tpu.ops.attention import auto_path
from ray_tpu.util import events

LATENT_KINDS = ("latent", "window")


def _by_kind(cfg: TransformerConfig) -> bool:
    """A stack of latent-attention layers, served from a cache by kind."""
    return bool(cfg.layer_types) and \
        set(cfg.layer_types) <= set(LATENT_KINDS)


def _refuse_recurrent(cfg: TransformerConfig) -> None:
    if _by_kind(cfg):
        return
    if "linear" in cfg.layer_types:
        raise NotImplementedError(
            "generate serves softmax-attention layers only: this "
            "configuration has gated-delta-rule layers, whose recurrent "
            "state [B, Hv, dk, dv] and convolution tail would have to live "
            "beside the keys and values, and the cache here holds one kind "
            "(ROADMAP.md R8)")
    if cfg.layer_types:
        raise NotImplementedError(
            "generate serves one stack of like softmax-attention layers, or "
            "a pattern of latent and window layers; this pattern "
            f"{cfg.layer_types} is not served")


def cache_slots(cfg: TransformerConfig) -> int:
    """One slot for every (loop step, layer)."""
    return cfg.loop_steps * cfg.n_layers


def window_rows(cfg: TransformerConfig) -> int:
    """Positions a window layer's cache holds: the window rounded up to the
    block a step writes. A ring: position p lives in slot ``p mod rows``."""
    return -(-cfg.window // _WRITE_ROWS) * _WRITE_ROWS


def _lead_slots(cfg: TransformerConfig, kind: str) -> int:
    """The kind's slots that the leading dense layers (of the period's
    first kind) hold: its first."""
    return cfg.first_dense_layers if cfg.kinds[0] == kind else 0


def kind_slots(cfg: TransformerConfig) -> Dict[str, int]:
    """Cache slots by layer kind: a layer of a kind has one."""
    return {kind: _lead_slots(cfg, kind)
            + cfg.periods * cfg.layer_types.count(kind)
            for kind in LATENT_KINDS}


def _slot(cfg: TransformerConfig, kind: str, period, j: int):
    """The slot, among its kind's, of the layer at position ``j`` of
    period ``period`` (the leading dense layers hold their kind's
    first)."""
    return _lead_slots(cfg, kind) + period * cfg.layer_types.count(kind) \
        + cfg.layer_types[:j].count(kind)


def cache_shapes(cfg: TransformerConfig, batch: int, max_len: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """The cache's arrays. A stack by kind holds, a latent layer, the
    latent and shared key of every position (``latent``) and the indexer's
    key (``index``), and a window layer a ring of `window_rows` positions
    (``window``): [slots of the kind, B, positions, 1, width]."""
    if not _by_kind(cfg):
        shape = (cache_slots(cfg), batch, max_len, cfg.kv_heads,
                 cfg.head_dim)
        return {"k": shape, "v": shape}
    slots, shapes = kind_slots(cfg), {}
    if slots["latent"]:
        shapes["latent"] = (slots["latent"], batch, max_len, 1,
                            cfg.latent.cached)
        if cfg.index_topk:
            shapes["index"] = (slots["latent"], batch, max_len, 1,
                               cfg.index_head_dim)
    if slots["window"]:
        shapes["window"] = (slots["window"], batch, window_rows(cfg), 1,
                            cfg.window_latent.cached)
    return shapes


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Zeros of `cache_shapes` (kv dtype = compute dtype): the
    [loop steps x L, B, T, KVH, D] pair, or the arrays by kind."""
    if _by_kind(cfg):
        return {name: jnp.zeros(shape, cfg.dtype) for name, shape
                in cache_shapes(cfg, batch, max_len).items()}
    shape = (cache_slots(cfg), batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _cached_attention(cfg: TransformerConfig, q, k_cache, v_cache, pos):
    """q [B, 1, H, D] against a slot's first positions [B, extent, KVH, D],
    of them those <= pos."""
    b, _, h, d = q.shape
    t = k_cache.shape[1]
    kvh = k_cache.shape[2]
    group = h // kvh
    qg = q.reshape(b, 1, kvh, group, d)
    scores = jnp.einsum("bokgd,btkd->bkgt", qg, k_cache) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    mask = (jnp.arange(t) <= pos)[None, None, None, :]
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgt,btkd->bkgd", w, v_cache)
    return o.reshape(b, 1, h, d)


# Positions written at once. The TPU compiler lays the whole stack out for
# its smallest write: an update of fewer positions than one tile has
# sublanes (8) puts the batch, not the positions, in the tile, and
# attention then re-lays out a layer's slab on every step (on a v5e 0.52 s
# of a 3.82 s call at [24,32,640,8,128], against 0.07 s).
_WRITE_ROWS = 8


def _write_position(cache, l, pos, new):
    """cache [slots, B, T, KVH, D] with new [B, 1, KVH, D] at [l, :, pos]: the
    block of _WRITE_ROWS positions that holds ``pos`` is read, gets the new
    row and is written back where it was. Nothing reads the stack between
    that read and the write, so the update is in place."""
    n = min(_WRITE_ROWS, cache.shape[2])
    start = jnp.minimum(pos // n * n, cache.shape[2] - n)
    at = (l, 0, start, 0, 0)
    old = lax.dynamic_slice(cache, at,
                            (1, cache.shape[1], n) + cache.shape[3:])
    row = (jnp.arange(n) == pos - start)[None, None, :, None, None]
    return lax.dynamic_update_slice(cache, jnp.where(row, new[None], old),
                                    at)


# The token loop's segments (`_decode_segments`): equal, at most
# _MAX_SEGMENTS of them (each is a compiled copy of the loop's body), none
# shorter than _MIN_SEGMENT_STEPS steps, and one alone where the loop's
# steps are under 1 / _MIN_NEW_PART of the cache: the never-written tail
# that one loop reads is then under an eighth of the cache's reads. On a
# v5e a call of 16 x (128 + 256) over 192 slots took 11.774 s in 1 segment,
# 10.923 in 4 x 64 steps, 10.791 in 8 x 32 and 10.833 in 16 x 16 (which
# compiled in 14.5 s against 7.0); one of 32 x (512 + 128) over 24 slots
# 3.360 s in 1, 3.338 in 4 x 32 and 3.332 in 8 x 16.
_MAX_SEGMENTS = 8
_MIN_SEGMENT_STEPS = 32
_MIN_NEW_PART = 4


def _decode_segments(prompt: int, new: int) -> List[Tuple[int, int]]:
    """[(steps, extent)] of the token loop of ``new`` steps after a prompt
    of ``prompt`` positions, step i writing position ``prompt + i``: the
    segments' steps sum to ``new`` and a segment's extent, the cache
    positions its steps' attention reads, is the position its last step
    writes + 1, rounded up to the block `_write_position` writes and no
    more than the cache's ``prompt + new``."""
    n = max(1, min(_MAX_SEGMENTS, new // _MIN_SEGMENT_STEPS))
    if new * _MIN_NEW_PART < prompt + new:
        n = 1
    segments, done = [], 0
    for j in range(n):
        steps = new // n + (j < new % n)
        done += steps
        extent = -(-(prompt + done) // _WRITE_ROWS) * _WRITE_ROWS
        segments.append((steps, min(extent, prompt + new)))
    return segments


def _slot_prefix(stack, slot, extent):
    """stack [slots, B, T, KVH, D] -> the slot's first positions
    [B, extent, KVH, D]."""
    return lax.dynamic_slice(
        stack, (slot, 0, 0, 0, 0), (1, stack.shape[1], extent)
        + stack.shape[3:])[0]


def _write_prompt(cache, l, new):
    """cache [slots, B, T, KVH, D] with new [B, S, KVH, D] at [l, :, :S],
    a row of the batch at a time, in a loop of its own: the TPU compiler
    lays a loop's carry out by what the loop's body does with it. Written
    straight from the layer's body the stack takes the layout attention
    wants of its k and v (heads before positions), the decode loop wants
    positions before heads, and the compiler copies the whole stack from
    the one to the other: at [192, 16, 384, 16, 128] two copies of 4.8 GB
    beside the stack, 18.85 of the 15.75 GiB a v5e has. This loop's body
    holds no matmul, so its carry keeps the default layout, which is the
    decode loop's, and what is laid out anew is the layer's own 8 MB."""
    def row(b, cache):
        return lax.dynamic_update_slice(
            cache, lax.dynamic_slice_in_dim(new, b, 1)[None],
            (l, b, 0, 0, 0))
    return lax.fori_loop(0, new.shape[0], row, cache)


def _slots_of_pass(cfg: TransformerConfig, t):
    """The cache slots of loop step ``t``'s layers, in layer order."""
    return t * cfg.n_layers + jnp.arange(cfg.n_layers)


def _over_the_slots(cfg: TransformerConfig, params, x, positions, cache,
                    attend_at):
    """The trunk over a CARRIED cache: every loop step's pass over the
    layers, layer l of step t owning slot ``t * L + l``. ``attend_at(
    cache_k, cache_v, slot)`` gives the layer its attention step, which
    hands back the two stacks with the slot written. -> (x, cache, exit
    distribution or None)."""
    def layers(x, kv, t):
        def step(carry, layer_and_slot):
            x, kv = carry
            layer, slot = layer_and_slot
            return _layer_apply(cfg, layer, x, positions,
                                attend_at(*kv, slot))[:2], None

        (x, kv), _ = lax.scan(step, (x, kv),
                              (params["layers"], _slots_of_pass(cfg, t)))
        return x, kv

    x, (cache_k, cache_v), exits = _over_loop_steps(
        cfg, params, layers, x, (cache["k"], cache["v"]))
    return x, {"k": cache_k, "v": cache_v}, exits


# A stack by kind (latent and window layers, models/latent.py). The prompt
# goes through the whole stack in chunks of queries, each chunk writing its
# entries into the carried cache and attending to what stands: at 32,768
# positions one row's queries of 128 heads x 192 are 1.6 GB and the expert
# layer's buffer grows with the tokens of a step.
PREFILL_CHUNK = 2048


def prefill_chunk(prompt: int, most: int = PREFILL_CHUNK) -> int:
    """Queries a chunk: the largest divisor of the prompt up to ``most``."""
    return max(c for c in range(1, min(most, prompt) + 1) if prompt % c == 0)


def _slot_rows(stack, slot):
    """stack [slots, B, T, 1, W] -> the slot's [B, T, W]."""
    return lax.dynamic_index_in_dim(stack, slot, 0, keepdims=False)[:, :, 0]


def _over_the_kinds(cfg: TransformerConfig, params, x, positions, cache,
                    attend_at):
    """The trunk over a CARRIED cache by kind: the leading dense layers,
    then the periods. ``attend_at(kind, cache, slot)`` gives a layer its
    ``attend(new) -> (keys, key positions, cache)``, which hands back the
    cache with the slot written. -> (x, cache, [rows routed to held
    experts, rows dropped] summed over the layers, taps: the indexed
    layers' selections and the window layers' key counts, ``lead`` stacked over the leading layers and
    ``periods`` a list over the period's positions, stacked over the
    periods)."""
    kinds = cfg.layer_types

    def run(kind, slot, layer, x, cache, counts):
        x, cache, stats = _layer_apply(cfg, layer, x, positions,
                                       attend_at(kind, cache, slot))
        stats = stats or {}
        if "rows_here" in stats:
            counts = counts + jnp.stack([stats["rows_here"],
                                         stats["rows_dropped"]])
        return (x, cache, counts), {k: v for k, v in stats.items()
                                    if k.startswith(("selected", "window"))}

    carry, lead_taps = (x, cache, jnp.zeros((2,), jnp.int32)), None
    if cfg.first_dense_layers:
        carry, lead_taps = lax.scan(
            lambda carry, at: run(kinds[0], at[1], at[0], *carry),
            carry,
            (params["dense_layers"], jnp.arange(cfg.first_dense_layers)))

    def period(carry, layers_and_index):
        layers, i = layers_and_index
        taps = []
        for j, (kind, layer) in enumerate(zip(kinds, layers)):
            carry, tap = run(kind, _slot(cfg, kind, i, j), layer, *carry)
            taps.append(tap)
        return carry, taps

    (x, cache, counts), taps = lax.scan(
        period, carry, (params["layers"], jnp.arange(cfg.periods)))
    return x, cache, counts, {"lead": lead_taps, "periods": taps}


def _write_chunk_at(cfg: TransformerConfig, start, chunk: int):
    """``attend_at`` of a prefill chunk of ``chunk`` positions from
    ``start``. A latent layer writes the chunk's entries at their positions
    and attends to its whole slot (the mask leaves out what is not written
    yet); a window layer attends to its ring as it stood and the chunk's
    own entries, then writes the chunk's last `window_rows` into the
    ring."""
    def attend_at(kind, cache, slot):
        def latent(new):
            out = dict(cache)
            for name, rows in new.items():
                out[name] = lax.dynamic_update_slice(
                    cache[name], rows[None, :, :, None, :],
                    (slot, 0, start, 0, 0))
            keys = {name: _slot_rows(out[name], slot) for name in new}
            return keys, jnp.arange(cache["latent"].shape[2])[None], out

        def window(new):
            rows = window_rows(cfg)
            ring = _slot_rows(cache["window"], slot)
            keys = {"latent": jnp.concatenate([ring, new["latent"]], 1)}
            kpos = jnp.concatenate([ring_positions(start - 1, rows),
                                    start + jnp.arange(chunk)])[None]
            tail = min(chunk, rows)
            at = (start + chunk - tail + jnp.arange(tail)) % rows
            ring = ring.at[:, at].set(new["latent"][:, chunk - tail:])
            return keys, kpos, dict(cache, window=lax.dynamic_update_slice(
                cache["window"], ring[None, :, :, None, :],
                (slot, 0, 0, 0, 0)))

        return latent if kind == "latent" else window
    return attend_at


def _write_and_read_at(cfg: TransformerConfig, pos, extent: int):
    """``attend_at`` of a decode step at position ``pos``: write the entry,
    then read the slot from the UPDATED stack (as `decode_step_and_exits`):
    a latent layer its first ``extent`` positions, a window layer its
    ring."""
    def attend_at(kind, cache, slot):
        def latent(new):
            out = dict(cache)
            for name, row in new.items():
                out[name] = _write_position(cache[name], slot, pos,
                                            row[:, :, None, :])
            keys = {name: _slot_prefix(out[name], slot, extent)[:, :, 0]
                    for name in new}
            return keys, jnp.arange(extent)[None], out

        def window(new):
            rows = window_rows(cfg)
            stack = _write_position(cache["window"], slot, pos % rows,
                                    new["latent"][:, :, None, :])
            return ({"latent": _slot_rows(stack, slot)},
                    ring_positions(pos, rows)[None],
                    dict(cache, window=stack))

        return latent if kind == "latent" else window
    return attend_at


def prefill_and_taps(params, tokens, cfg: TransformerConfig, max_len: int,
                     chunk: Optional[int] = None):
    """A stack by kind: the prompt [B, S] through the trunk in chunks of
    ``chunk`` queries (`prefill_chunk` of S where None; S is a multiple)
    -> (last-position logits [B, vocab], filled cache, taps of the last
    chunk as `_over_the_kinds` gives them, with ``moe_rows``: [rows routed
    to held experts, rows dropped] over the whole prompt)."""
    b, s = tokens.shape
    chunk = chunk or prefill_chunk(s)
    if s % chunk:
        raise ValueError(f"a prompt of {s} is not a multiple of the chunk "
                         f"{chunk}")
    embed = params["embed"].astype(cfg.dtype)

    def step(carry, c):
        cache, counts = carry
        start = c * chunk
        positions = start + jnp.broadcast_to(jnp.arange(chunk), (b, chunk))
        x = embed[lax.dynamic_slice_in_dim(tokens, start, chunk, axis=1)]
        x, cache, n, taps = _over_the_kinds(
            cfg, params, x, positions, cache,
            _write_chunk_at(cfg, start, chunk))
        return (cache, counts + n), (x[:, -1], taps)

    (cache, counts), (last, taps) = lax.scan(
        step, (init_cache(cfg, b, max_len), jnp.zeros((2,), jnp.int32)),
        jnp.arange(s // chunk))
    taps = dict(jax.tree.map(lambda a: a[-1], taps), moe_rows=counts)
    return _head(params, last[-1][:, None], cfg)[:, 0], cache, taps


def decode_step_and_taps(params, token, pos, cache, cfg: TransformerConfig,
                         *, extent: Optional[int] = None):
    """A stack by kind, one token for the whole batch (as
    `decode_step_and_exits`) -> (logits [B, vocab], updated cache, taps
    with ``moe_rows``)."""
    if extent is None:
        extent = cache["latent"].shape[2] if "latent" in cache else 0
    x = params["embed"].astype(cfg.dtype)[token][:, None, :]
    positions = jnp.full((x.shape[0], 1), pos)
    x, cache, counts, taps = _over_the_kinds(
        cfg, params, x, positions, cache,
        _write_and_read_at(cfg, pos, extent))
    return _head(params, x, cfg)[:, 0], cache, dict(taps, moe_rows=counts)


def prefill_and_exits(params, tokens, cfg: TransformerConfig, max_len: int,
                      mesh=None):
    """Run the prompt through the trunk, returning (last-position logits
    [B, vocab], filled cache, the last position's exit distribution [B,
    loop_steps] or None where there is no loop). tokens [B, S], S <=
    max_len."""
    if cfg.pp_stages > 1:
        raise NotImplementedError("decode with pp_stages>1 is not supported")
    _refuse_recurrent(cfg)
    if _by_kind(cfg):
        logits, cache, _ = prefill_and_taps(params, tokens, cfg, max_len)
        return logits, cache, None
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.loop_steps == 1:
        # One pass: each layer's K and V are the layer scan's outputs,
        # stacked as the cache. Of a looped stack those outputs would be
        # stacked once more over the passes, a pass's slots (2.4 GB of 9.7)
        # held twice; there the cache is the loops' carry, below.
        pad = ((0, 0), (0, max_len - s), (0, 0), (0, 0))

        def attend(q, k, v):
            # The training forward's attention; the layer's rotated K and
            # V are kept, so the cache matches the forward bit for bit.
            return (_attention(cfg, q, k, v, mesh),
                    {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)})

        def step(carry, layer):
            return _layer_apply(cfg, layer, carry, positions, attend)[:2]

        x, cache = lax.scan(step, x, params["layers"])
        return _head(params, x[:, -1:], cfg)[:, 0], cache, None

    def write_at(cache_k, cache_v, slot):
        def attend(q, k, v):
            # the same attention and the same K and V; each goes straight
            # into its slot of the carried cache
            return _attention(cfg, q, k, v, mesh), (
                _write_prompt(cache_k, slot, k),
                _write_prompt(cache_v, slot, v))
        return attend

    x, cache, exits = _over_the_slots(cfg, params, x, positions,
                                      init_cache(cfg, b, max_len), write_at)
    return _head(params, x[:, -1:], cfg)[:, 0], cache, exits[:, -1]


def prefill(params, tokens, cfg: TransformerConfig, max_len: int,
            mesh=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """``prefill_and_exits`` without the exits: (logits, cache)."""
    return prefill_and_exits(params, tokens, cfg, max_len, mesh)[:2]


def decode_step_and_exits(params, token, pos, cache,
                          cfg: TransformerConfig, *,
                          extent: Optional[int] = None):
    """One token for the whole batch: token [B] int32, pos scalar int32.
    -> (logits [B, vocab], updated cache, exit distribution [B,
    loop_steps] or None where there is no loop). Attention reads the
    first ``extent`` positions of a slot (static; the caller's word that
    ``pos < extent``), all of them where it is None."""
    _refuse_recurrent(cfg)
    if _by_kind(cfg):
        logits, cache, _ = decode_step_and_taps(params, token, pos, cache,
                                                cfg, extent=extent)
        return logits, cache, None
    extent = cache["k"].shape[2] if extent is None else extent
    x = params["embed"].astype(cfg.dtype)[token][:, None, :]   # [B, 1, E]
    positions = jnp.full((x.shape[0], 1), pos)

    def write_and_read_at(cache_k, cache_v, slot):
        def attend(q, k, v):
            # Write, then read the slot from the UPDATED stack: a read of
            # the old stack after the write would make XLA keep two
            # buffers and copy.
            with jax.named_scope("rt.loop.cache"):
                stack_k = _write_position(cache_k, slot, pos, k)
                stack_v = _write_position(cache_v, slot, pos, v)
                o = _cached_attention(
                    cfg, q, _slot_prefix(stack_k, slot, extent),
                    _slot_prefix(stack_v, slot, extent), pos)
            return o, (stack_k, stack_v)
        return attend

    x, cache, exits = _over_the_slots(cfg, params, x, positions, cache,
                                      write_and_read_at)
    return (_head(params, x, cfg)[:, 0], cache,
            None if exits is None else exits[:, 0])


def decode_step(params, token, pos, cache, cfg: TransformerConfig, *,
                extent: Optional[int] = None):
    """``decode_step_and_exits`` without the exits: (logits, cache)."""
    return decode_step_and_exits(params, token, pos, cache, cfg,
                                 extent=extent)[:2]


def _sample(logits, key, temperature: float, top_k: Optional[int]):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        thresh = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < thresh, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def _expected_exit_step(exits):
    """exits [B, loop_steps] -> sum over the rows of ``sum_t (t + 1)
    p_t``: the loop steps these tokens would have run at a threshold that
    follows the gate."""
    return jnp.sum(exits * jnp.arange(1, exits.shape[-1] + 1,
                                      dtype=exits.dtype))


def generate_and_cache(params, prompt, cfg: TransformerConfig, *,
                       max_new_tokens: int, temperature: float = 0.0,
                       top_k: Optional[int] = None, seed: int = 0,
                       mesh=None
                       ) -> Tuple[jnp.ndarray, Dict[str, Any], Dict[str, Any]]:
    """prompt [B, S] int32 -> (generated tokens [B, max_new_tokens],
    stats, the cache as the call's last step left it: what a caller that
    holds the call to a reference reads, and the token loop's carry, so
    handing it back costs no copy). ``stats`` is ``{}`` but for a looped
    stack: there
    ``exit_steps_sum``, the sum over the generated tokens of the exit
    gate's expected loop step ``sum_t (t + 1) p_t``, and ``exit_tokens``,
    their count: a few floats, accumulated in the decode loop's carry; and
    for a stack by kind, whose expert layers must drop nothing:
    ``moe_rows_here`` and ``moe_rows_dropped``, summed over the call.

    The whole decode loop runs inside the caller's jit scope (wrap with
    jax.jit(partial(generate, ...)) or call under jit), one lax.scan for
    each of `_decode_segments`' segments: no per-token host round trips.
    """
    _refuse_recurrent(cfg)
    b, s = prompt.shape
    max_len = s + max_new_tokens
    looped, by_kind = cfg.loop_steps > 1, _by_kind(cfg)
    rows = None
    with jax.named_scope("rt.generate.prefill"):
        if by_kind:
            logits, cache, taps = prefill_and_taps(params, prompt, cfg,
                                                   max_len)
            exits, rows = None, taps["moe_rows"]
        else:
            logits, cache, exits = prefill_and_exits(params, prompt, cfg,
                                                     max_len, mesh=mesh)
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    first = _sample(logits, sub, temperature, top_k)

    def step(extent, carry, _):
        token, pos, cache, key, exits, steps_sum, rows = carry
        if looped:      # ``exits`` came with the logits ``token`` is from
            steps_sum = steps_sum + _expected_exit_step(exits)
        if by_kind:
            logits, cache, taps = decode_step_and_taps(
                params, token, pos, cache, cfg, extent=extent)
            rows = rows + taps["moe_rows"]
        else:
            logits, cache, exits = decode_step_and_exits(
                params, token, pos, cache, cfg, extent=extent)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sub, temperature, top_k)
        return (nxt, pos + 1, cache, key, exits, steps_sum, rows), token

    carry = (first, jnp.asarray(s, jnp.int32), cache, key, exits,
             jnp.zeros((), jnp.float32) if looped else None, rows)
    tokens = []
    with jax.named_scope("rt.generate.decode"):
        for steps, extent in _decode_segments(s, max_new_tokens):
            carry, emitted = lax.scan(partial(step, extent), carry, None,
                                      length=steps)
            tokens.append(emitted)
    steps_sum, rows = carry[-2:]
    tokens = jnp.concatenate(tokens)
    stats = {"exit_steps_sum": steps_sum,
             "exit_tokens": jnp.asarray(b * max_new_tokens, jnp.float32)} \
        if looped else {}
    if by_kind:
        stats = {"moe_rows_here": rows[0], "moe_rows_dropped": rows[1]}
    return jnp.transpose(tokens, (1, 0)), stats, carry[2]


def generate_with_stats(params, prompt, cfg: TransformerConfig, *,
                        max_new_tokens: int, temperature: float = 0.0,
                        top_k: Optional[int] = None, seed: int = 0,
                        mesh=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """``generate_and_cache`` without the cache: (tokens [B,
    max_new_tokens], stats)."""
    return generate_and_cache(
        params, prompt, cfg, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, seed=seed, mesh=mesh)[:2]


def generate(params, prompt, cfg: TransformerConfig, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             seed: int = 0, mesh=None) -> jnp.ndarray:
    """``generate_with_stats`` without the stats: the tokens."""
    return generate_with_stats(
        params, prompt, cfg, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, seed=seed, mesh=mesh)[0]


def call_span(cfg: TransformerConfig, rows: int, prompt: int,
              new: int) -> events.span:
    """The flight-recorder span the caller of a compiled ``generate`` opens
    around one call, from its dispatch to its tokens on the host.
    ``sp.set(exit_steps_mean=...)`` puts a looped stack's exit counter
    (``exit_steps_sum / exit_tokens`` of ``generate_with_stats``) on it
    once it is fetched with the tokens. ``cache_positions_read`` is the sum
    over the call's decode steps of their segment's extent, what a slot's
    attention reads, ``cache_positions_needed`` that of ``pos + 1``, what
    it has to."""
    segments = _decode_segments(prompt, new)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    shapes = cache_shapes(cfg, rows, prompt + new)
    attrs = dict(
        rows=rows, prompt=prompt, new=new, loop_steps=cfg.loop_steps,
        attention_path="latent" if _by_kind(cfg)
        else auto_path(prompt, prompt, cfg.head_dim)
        if cfg.attn_impl == "auto" else cfg.attn_impl,
        cache_slots=sum(shape[0] for shape in shapes.values())
        if _by_kind(cfg) else cache_slots(cfg),
        cache_bytes=sum(math.prod(shape) for shape in shapes.values())
        * itemsize,
        decode_segments=len(segments),
        cache_positions_read=sum(n * extent for n, extent in segments),
        cache_positions_needed=new * prompt + new * (new + 1) // 2)
    if _by_kind(cfg):
        # a latent layer's queries, each over the keys up to its own: all
        # of them scored by the indexer, index_topk of them attended to
        layers = rows * kind_slots(cfg)["latent"]
        k = cfg.index_topk if 0 < cfg.index_topk < prompt + new else 0
        total = prompt + new - 1        # queries at positions 0 .. total-1
        causal = total * (total + 1) // 2
        few = min(total, k)             # positions with no more than k keys
        attrs.update(
            {"cache_bytes_" + name: math.prod(shape) * itemsize
             for name, shape in shapes.items()},
            prefill_chunks=prompt // prefill_chunk(prompt),
            index_topk=cfg.index_topk,
            keys_scored=layers * causal if k else 0,
            keys_attended=layers * (few * (few + 1) // 2 + (total - few) * k
                                    if k else causal),
            # the prompt's queries, where a block of them over the whole
            # cache went through rt_sparse_attend (no decode step does)
            sparse_kernel_queries=layers * prompt if k and sparse_in_kernel(
                cfg.latent_dims("latent"), k, prefill_chunk(prompt),
                prompt + new) else 0)
    return events.span("generate.call", **attrs)

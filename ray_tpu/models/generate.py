"""Autoregressive generation through a cache, for every stack `generate`
serves (the reference has no in-tree LM inference; here decode is a TPU
program: train with jax_step, serve with serve/ and this).

One cache, one trunk, two makers of a layer's attention step.

- The cache is a dict of arrays by layer kind, and `cache_shapes` alone
  knows them: a softmax layer (kind ``"full"``) has a slot of ``k`` and of
  ``v`` [slots, B, T_max, KVH, D], a latent layer one of ``latent`` (and of
  the indexer's ``index``) over every position, a window layer one of
  ``window``, a ring of `window_rows` positions, a layer with a linear mixer
  (kind ``"linear"``: the gated delta rule or, by ``cfg.linear_transition``,
  a state-space recurrence) one of ``state``, the recurrent state after the
  last position in float32 whatever the compute dtype (the rule's packed so
  that its minor dimension fills the TPU's lanes, ops/gated_delta.py
  ``pack_state``), and one of ``tail``, the convolution's last K - 1
  inputs: neither grows with the positions, and a step rewrites the whole
  of both. A layer of two mixers (kind ``"parallel"``: softmax attention and
  the linear mixer side by side) has a slot of each of its mixers' arrays,
  ``k``, ``v``, ``state`` and ``tail`` (`CACHES_OF`). A looped stack
  (``cfg.loop_steps`` passes over the one set of weights) has a slot for
  every (pass t, layer l), slot ``t * L + l``: the passes share weights, not
  activations, so a pass-t query sees pass-t keys only.
- `_over_the_layers` is the trunk of prefill and decode alike: the leading
  dense layers and then the periods of ``cfg.kinds``, under
  ``transformer._over_loop_steps`` for the passes, each layer the training
  forward's own (``transformer._layer_apply``) and handed only its attention
  step ``attend_at(kind, cache, slots)``. The cache is the CARRY of every
  level (passes, periods, and the token loop around them), never a scanned
  input or output: a layer writes its slot and reads it back from the
  updated carry, so the token loop updates one buffer in place. Write first,
  read second: a read of the pre-update stack after the write would make
  XLA keep two buffers and copy the cache a token.
- `_write_chunk_at` makes the attention step of a prefill chunk,
  `_write_and_read_at` that of a decode step; each has the four kinds, and
  a parallel layer's is the pair of its softmax and its linear part, the
  second made from the cache the first wrote. A linear layer's is the rule
  itself (``transformer._gated_delta_mix``'s
  ``rule``): the chunked rule from a zero state, whose final state and last
  inputs prefill writes, and one position of the recurrence on the carried
  state, read from its slot and written back to it, no second copy.
  `prefill_and_taps` and `decode_step_and_taps` are the two entry points
  (``prefill``, ``prefill_and_exits``, ``decode_step`` and
  ``decode_step_and_exits`` are views of them). The prompt of a stack by
  kind goes through in chunks of queries, each attending to what the cache
  holds; a softmax layer attends to its chunk's own keys, the training
  forward's attention, and a linear layer starts from a zero state, so a
  stack that has either goes through in one chunk.

Served: one stack of like softmax layers (looped or not), a pattern of
latent and window layers, a pattern of softmax, linear and parallel layers.
Not served: a linear mixer beside latent or window layers, or under a mesh
of several devices (`_refuse_unserved`).
- `generate` runs the whole decode loop INSIDE jit via lax.scan: static
  shapes (cache padded to max length, attention masked by position), PRNG
  threaded through the scan, no host round trip a token. The token loop
  runs in SEGMENTS: consecutive scans over the one carried cache, same
  carry and same body, each compiled for a static ``extent``: the positions
  its last step will have written, rounded up to the block a step writes. A
  step's attention reads ``[B, extent, ...]`` of its slot, not all of T_max
  (`_decode_segments`; the slice fuses into the attention fusions, no slab
  is written out). S=1 queries are bandwidth-bound, so a masked position
  costs what a read one costs: where the cache is most of what a step reads
  (9.7 GB a token at 16 x 384 over 192 slots beside 19.9 GB of weights) the
  never-written tail of T_max is a third of the cache's bytes. A short
  loop, or one whose steps are a small part of the cache, stays one segment.

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

from ray_tpu.models.latent import (NEVER, ring_positions, sparse_in_kernel,
                                   window_in_kernel)
from ray_tpu.models.transformer import (TransformerConfig, _attention,
                                        _head, _layer_apply,
                                        _over_loop_steps, _rule_operands,
                                        _state_space_operands)
from ray_tpu.ops import gated_delta, ssd
from ray_tpu.ops.attention import auto_path
from ray_tpu.util import events

LATENT_KINDS = ("latent", "window")


RECURRENT_KINDS = ("full", "linear", "parallel")
# The cache kinds a layer kind has a slot of: a kind's own but for a layer
# of two mixers, which has one of each mixer's.
CACHES_OF = {"full": ("full",), "linear": ("linear",),
             "parallel": ("full", "linear"), "latent": ("latent",),
             "window": ("window",)}
# the cache's arrays over positions, [slots, B, T, ...]: what a decode
# step's ``extent`` cuts
BY_POSITION = ("k", "v", "latent", "index")
# An expert layer's counters that a call sums, in the order of ``moe_rows``
# (models/moe.py: pairs routed to held experts, those the buffer dropped,
# the buffer's rows passed over outside the grouped matmuls).
MOE_ROWS = ("rows_here", "rows_dropped", "rows_walked")


def _refuse_unserved(cfg: TransformerConfig, mesh=None) -> None:
    kinds = set(cfg.layer_types)
    linear = kinds & {"linear", "parallel"}
    if linear and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "generate serves a linear mixer (the gated delta rule, a "
            "state-space recurrence) on one device: the step's kernels "
            "cannot be partitioned, and the state's layout over a mesh is "
            "not built (ROADMAP.md R8)")
    if kinds <= set(LATENT_KINDS) or kinds <= set(RECURRENT_KINDS):
        return
    if linear:
        raise NotImplementedError(
            "generate serves linear and parallel layers beside softmax "
            f"layers only: this pattern {cfg.layer_types} has them beside "
            "latent or window layers, whose prompt goes through in chunks "
            "of queries, and a linear mixer's prefill starts from a zero "
            "state: a chunked prefill over a carried state is not built "
            "(ROADMAP.md R8)")
    raise NotImplementedError(
        "generate serves one stack of like softmax-attention layers, a "
        "pattern of softmax, linear and parallel layers, or a pattern of "
        f"latent and window layers; this pattern {cfg.layer_types} is not "
        "served")


def cache_slots(cfg: TransformerConfig) -> int:
    """One slot for every (loop step, layer)."""
    return cfg.loop_steps * cfg.n_layers


def window_rows(cfg: TransformerConfig) -> int:
    """Positions a window layer's cache holds: the window rounded up to the
    block a step writes. A ring: position p lives in slot ``p mod rows``."""
    return -(-cfg.window // _WRITE_ROWS) * _WRITE_ROWS


def _layers_with(kinds, cache: str) -> int:
    """How many layers of ``kinds`` have a slot of the cache kind."""
    return sum(cache in CACHES_OF[kind] for kind in kinds)


def _lead_slots(cfg: TransformerConfig, cache: str) -> int:
    """The cache kind's slots that the leading dense layers (of the
    period's first kind) hold: its first."""
    return cfg.first_dense_layers * _layers_with(cfg.kinds[:1], cache)


def kind_slots(cfg: TransformerConfig) -> Dict[str, int]:
    """Cache slots by cache kind: a layer has one a loop step of each of
    its `CACHES_OF` (a parallel layer one of ``"full"`` and one of
    ``"linear"``)."""
    return {cache: cfg.loop_steps * (
        _lead_slots(cfg, cache)
        + cfg.periods * _layers_with(cfg.kinds, cache))
        for cache in ("full", "linear") + LATENT_KINDS}


def _slots_of_pass(cfg: TransformerConfig, t):
    """The periods of loop step ``t``, in the order they run, numbered
    over all the passes. A plain stack's period is one layer: its layers'
    cache slots ``t * L + l``."""
    return t * cfg.periods + jnp.arange(cfg.periods)


def _slots(cfg: TransformerConfig, period, j: int) -> Dict[str, Any]:
    """{cache kind: the slot, among that kind's} of the layer at position
    ``j`` of period ``period`` (as `_slots_of_pass` numbers them; the
    leading dense layers hold their kinds' first)."""
    return {cache: _lead_slots(cfg, cache)
            + period * _layers_with(cfg.kinds, cache)
            + _layers_with(cfg.kinds[:j], cache)
            for cache in CACHES_OF[cfg.kinds[j]]}


def cache_shapes(cfg: TransformerConfig, batch: int, max_len: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """The cache's arrays, [slots of the kind, B, positions, heads, width]:
    a softmax layer holds the rotated keys and the values of every position
    (``k``, ``v``), a latent layer the latent and shared key of every
    position (``latent``) and the indexer's key (``index``), a window layer
    a ring of `window_rows` positions (``window``); a linear mixer holds no
    positions: the rule's packed state [slots, B, Hv / r, dk, r dv]
    (``state``: r = ``gated_delta.state_pack`` heads beside each other on
    the minor dimension, 2 x 192 = 384 = 3 x 128 lanes, so nothing is
    padded) and the convolution's last K - 1 inputs, oldest first, [slots,
    K - 1, B, 2 kd + vd] (``tail``: positions before rows, the only array
    here whose second dimension is not the batch, so that a tile holds rows
    x channels and the 3 positions pad nothing). A state-space mixer's
    (``linear_transition`` "ssd") are the same two arrays: ``state`` [slots,
    B, H, N, P], a head's [state width, head width] as ``ops/ssd.py`` hands
    it back (the transpose of the papers' [P, N]: P = 128 is the lanes,
    nothing to pack), and ``tail`` over the H P + 2 G N channels of [x | B |
    C]. A parallel layer has all four of ``k``, ``v``, ``state``,
    ``tail``."""
    slots, shapes = kind_slots(cfg), {}
    if slots["full"]:
        shapes["k"] = shapes["v"] = (slots["full"], batch, max_len,
                                     cfg.kv_heads, cfg.head_dim)
    if slots["latent"]:
        shapes["latent"] = (slots["latent"], batch, max_len, 1,
                            cfg.latent.cached)
        if cfg.index_topk:
            shapes["index"] = (slots["latent"], batch, max_len, 1,
                               cfg.index_head_dim)
    if slots["window"]:
        shapes["window"] = (slots["window"], batch, window_rows(cfg), 1,
                            cfg.window_latent.cached)
    if slots["linear"]:
        hv, dv = cfg.linear_value_heads, cfg.linear_value_dim
        r = 1 if cfg.linear_transition == "ssd" \
            else gated_delta.state_pack(hv, dv)
        shapes["state"] = (slots["linear"], batch, hv // r,
                           cfg.linear_key_dim, r * dv)
        shapes["tail"] = (
            slots["linear"], cfg.linear_conv_kernel - 1, batch,
            2 * cfg.linear_key_heads * cfg.linear_key_dim + hv * dv)
    return shapes


def cache_dtype(cfg: TransformerConfig, name: str):
    """The recurrent state is float32 whatever the compute dtype (every
    step adds to it: bfloat16's 8 bits would be lost under what it holds);
    every other array is in the compute dtype."""
    return jnp.float32 if name == "state" else cfg.dtype


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Zeros of `cache_shapes`, each in its `cache_dtype`."""
    return {name: jnp.zeros(shape, cache_dtype(cfg, name)) for name, shape
            in cache_shapes(cfg, batch, max_len).items()}


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
    holds no matmul, so its carry keeps the default layout, which is that
    decode loop's, and what is laid out anew is the layer's own 8 MB.
    Where the decode loop wants the other (8 KV heads under 32 query heads,
    [24, 32, 640, 8, 128]) the stack is copied once a call, 2 x 1.0 GB,
    11 ms of a 3,372 ms call on a v5e: ROADMAP.md S11."""
    def row(b, cache):
        return lax.dynamic_update_slice(
            cache, lax.dynamic_slice_in_dim(new, b, 1)[None],
            (l, b, 0, 0, 0))
    return lax.fori_loop(0, new.shape[0], row, cache)


def _write_slot(stack, slot, new):
    """stack [slots, ...] with new [...] as the whole of ``slot``."""
    return lax.dynamic_update_slice(stack, new[None].astype(stack.dtype),
                                    (slot,) + (0,) * new.ndim)


def _slot_rows(stack, slot):
    """stack [slots, B, T, 1, W] -> the slot's [B, T, W]."""
    return lax.dynamic_index_in_dim(stack, slot, 0, keepdims=False)[:, :, 0]


def _over_the_layers(cfg: TransformerConfig, params, x, positions, cache,
                     attend_at):
    """The trunk over the CARRIED cache: every loop step's pass over the
    leading dense layers and then the periods of ``cfg.kinds`` (a plain
    stack's period is its one ``"full"`` layer). ``attend_at(kind, cache,
    slots)`` gives a layer its attention step (``transformer._layer_apply``'s
    ``attend`` of that kind), which hands back the cache with the slot
    written. -> (x, cache, exit distribution or None where there is no
    loop, the expert layers' ``MOE_ROWS`` summed over the layers, taps: the
    indexed layers' selections and the window layers' key counts, ``lead``
    stacked over the leading layers and ``periods`` a list
    over the period's positions, stacked over the periods).

    Only latent kinds have taps and only a stack of like ``"full"`` layers
    loops (``TransformerConfig`` refuses a loop over a pattern or an expert
    layer), so the taps ride out on the carry of ``_over_loop_steps``, whose
    scanned step may not change its carry's shape and has no outputs of its
    own: a looped stack's are as empty as they went in. The rows' counter
    rides with them; where no layer adds to it, it is a loop invariant that
    XLA drops."""
    kinds = cfg.kinds
    periods = params["layers"] if cfg.layer_types else (params["layers"],)

    def run(kind, slots, layer, x, cache, counts):
        x, cache, stats = _layer_apply(cfg, layer, x, positions,
                                       attend_at(kind, cache, slots))
        stats = stats or {}
        if "rows_here" in stats:
            counts = counts + jnp.stack([stats[name] for name in MOE_ROWS])
        return (x, cache, counts), {k: v for k, v in stats.items()
                                    if k.startswith(("selected", "window"))}

    def stack(x, carry, t):
        cache, counts, _ = carry
        carry, lead_taps = (x, cache, counts), None
        if cfg.first_dense_layers:
            carry, lead_taps = lax.scan(
                lambda carry, at: run(
                    kinds[0], dict.fromkeys(CACHES_OF[kinds[0]], at[1]),
                    at[0], *carry),
                carry,
                (params["dense_layers"], jnp.arange(cfg.first_dense_layers)))

        def period(carry, layers_and_index):
            layers, i = layers_and_index
            taps = []
            for j, (kind, layer) in enumerate(zip(kinds, layers)):
                carry, tap = run(kind, _slots(cfg, i, j), layer, *carry)
                taps.append(tap)
            return carry, taps

        (x, cache, counts), taps = lax.scan(
            period, carry, (periods, _slots_of_pass(cfg, t)))
        return x, (cache, counts, {"lead": lead_taps, "periods": taps})

    x, (cache, counts, taps), exits = _over_loop_steps(
        cfg, params, stack, x,
        (cache, jnp.zeros((len(MOE_ROWS),), jnp.int32),
         {"lead": None, "periods": [{} for _ in kinds]}))
    return x, cache, exits, counts, taps


# A stack by kind (latent and window layers, models/latent.py) takes the
# prompt in chunks of queries, each chunk writing its entries into the
# carried cache and attending to what stands: at 32,768 positions one row's
# queries of 128 heads x 192 are 1.6 GB and the expert layer's buffer grows
# with the tokens of a step.
PREFILL_CHUNK = 2048


def prefill_chunk(prompt: int, most: int = PREFILL_CHUNK) -> int:
    """Queries a chunk: the largest divisor of the prompt up to ``most``."""
    return max(c for c in range(1, min(most, prompt) + 1) if prompt % c == 0)


def _write_chunk_at(cfg: TransformerConfig, start, chunk: int, mesh=None):
    """``attend_at`` of a prefill chunk of ``chunk`` positions from
    ``start``. A softmax layer runs the training forward's attention over
    the chunk's own keys (the whole prompt: `prefill_and_taps`) and keeps
    the rotated K and V it ran on, so the cache matches the forward bit for
    bit; a latent layer writes the chunk's entries at their positions and
    attends to its whole slot (the mask leaves out what is not written
    yet); a window layer attends to keys in position order, the
    ``window - 1`` positions before the chunk read out of its ring as it
    stood and then the chunk's own entries (so that the band is one of
    indices: models/latent.py ``_expanded``), then writes the chunk's last
    `window_rows` into the ring; a linear layer runs the chunked rule from
    a zero state (the whole prompt too) and writes the state it ends in and
    the convolution's last K - 1 inputs (a state-space mixer the chunked
    scan of ops/ssd.py, the same way); a parallel layer's is the pair of
    its softmax part and, from the cache that part wrote, its linear
    part."""
    def attend_at(kind, cache, slots):
        if kind == "parallel":
            return (attend_at("full", cache, slots),
                    lambda cache: attend_at("linear", cache, slots))
        slot = slots[kind]

        def full(q, k, v):
            return _attention(cfg, q, k, v, mesh), dict(
                cache, k=_write_prompt(cache["k"], slot, k),
                v=_write_prompt(cache["v"], slot, v))

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
            # the window - 1 positions before the chunk, out of the ring,
            # then the chunk's own: position order (those before position
            # 0 do not exist)
            before = start - (cfg.window - 1) + jnp.arange(cfg.window - 1)
            keys = {"latent": jnp.concatenate(
                [ring[:, before % rows], new["latent"]], 1)}
            kpos = jnp.concatenate([jnp.where(before >= 0, before, NEVER),
                                    start + jnp.arange(chunk)])[None]
            tail = min(chunk, rows)
            at = (start + chunk - tail + jnp.arange(tail)) % rows
            ring = ring.at[:, at].set(new["latent"][:, chunk - tail:])
            return keys, kpos, dict(cache, window=lax.dynamic_update_slice(
                cache["window"], ring[None, :, :, None, :],
                (slot, 0, 0, 0, 0)))

        def written(u, state):
            """The cache with the chunk's final state and the
            convolution's last K - 1 inputs in the slot."""
            kept = cfg.linear_conv_kernel - 1       # zeros before position 0
            tail = jnp.swapaxes(
                jnp.pad(u, ((0, 0), (kept, 0), (0, 0)))[:, chunk:], 0, 1)
            return dict(
                cache, tail=_write_slot(cache["tail"], slot, tail),
                state=_write_slot(cache["state"], slot, state))

        def linear(u, ba, p):
            qkv = gated_delta.causal_conv(u, p["conv"])
            with jax.named_scope("rt.gdn.scan"):
                operands = _rule_operands(cfg, p, qkv, ba)
            o, state = gated_delta.gated_delta_rule(*operands,
                                                    final_state=True)
            return o, written(u, gated_delta.pack_state(state))

        def state_space(u, dt, p):
            xbc = gated_delta.causal_conv(u, p["conv"], p["conv_bias"],
                                          scope="rt.ssd.conv")
            with jax.named_scope("rt.ssd.scan"):
                y, state = ssd.ssd_scan(
                    *_state_space_operands(cfg, p, xbc, dt),
                    final_state=True)
            return y, written(u, state)

        if kind == "linear" and cfg.linear_transition == "ssd":
            return state_space
        return {"full": full, "latent": latent, "window": window,
                "linear": linear}[kind]
    return attend_at


def _write_and_read_at(cfg: TransformerConfig, pos, extent: int):
    """``attend_at`` of a decode step at position ``pos``: write the entry,
    then read the slot from the UPDATED stack (a read of the old stack
    after the write would make XLA keep two buffers and copy): a softmax
    and a latent layer its first ``extent`` positions, a window layer its
    ring. A linear layer's entry is made FROM what its slot holds: the new
    column is convolved against the tail, one position of the rule taken on
    the state, and both written back where they were read, each by one
    operation on the stack (``gated_delta.conv_step_at``,
    ``gated_delta_step_at``, or ``ssd.ssd_step_at`` for a state-space
    mixer: on a TPU kernels whose output is the stack they read). A
    parallel layer's is the pair of the two, the second made from the
    cache the first wrote."""
    def attend_at(kind, cache, slots):
        if kind == "parallel":
            return (attend_at("full", cache, slots),
                    lambda cache: attend_at("linear", cache, slots))
        slot = slots[kind]

        def full(q, k, v):
            with jax.named_scope("rt.loop.cache"):
                stack_k = _write_position(cache["k"], slot, pos, k)
                stack_v = _write_position(cache["v"], slot, pos, v)
                o = _cached_attention(
                    cfg, q, _slot_prefix(stack_k, slot, extent),
                    _slot_prefix(stack_v, slot, extent), pos)
            return o, dict(cache, k=stack_k, v=stack_v)

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

        def linear(u, ba, p):
            qkv, tails = gated_delta.conv_step_at(cache["tail"], slot,
                                                  u[:, 0], p["conv"])
            with jax.named_scope("rt.gdn.step"):
                operands = [x[:, 0] for x in
                            _rule_operands(cfg, p, qkv[:, None], ba)]
            o, states = gated_delta.gated_delta_step_at(cache["state"], slot,
                                                        *operands)
            return o[:, None], dict(cache, tail=tails, state=states)

        def state_space(u, dt, p):
            xbc, tails = gated_delta.conv_step_at(
                cache["tail"], slot, u[:, 0], p["conv"], p["conv_bias"],
                scope="rt.ssd.conv")
            with jax.named_scope("rt.ssd.step"):
                x, b, c, g, dt, skip = _state_space_operands(
                    cfg, p, xbc[:, None], dt)
                y, states = ssd.ssd_step_at(
                    cache["state"], slot, x[:, 0], b[:, 0], c[:, 0],
                    g[:, 0], dt[:, 0], skip)
            return y[:, None], dict(cache, tail=tails, state=states)

        if kind == "linear" and cfg.linear_transition == "ssd":
            return state_space
        return {"full": full, "latent": latent, "window": window,
                "linear": linear}[kind]
    return attend_at


def _last_exits(exits):
    """The exit distribution [B, S, loop_steps] -> the last position's [B,
    loop_steps]; None where there is no loop."""
    return None if exits is None else exits[:, -1]


def prefill_and_taps(params, tokens, cfg: TransformerConfig, max_len: int,
                     chunk: Optional[int] = None, mesh=None):
    """The prompt [B, S] (S <= max_len) through the trunk in chunks of
    ``chunk`` queries (S is a multiple; where None, `prefill_chunk` of S)
    -> (last-position logits [B, vocab], filled cache, taps of the last
    chunk as `_over_the_layers` gives them, with ``moe_rows``: the expert
    layers' ``MOE_ROWS`` over the whole prompt, and ``exits``: the last
    position's exit distribution [B, loop_steps], None where there is no
    loop)."""
    if cfg.pp_stages > 1:
        raise NotImplementedError("decode with pp_stages>1 is not supported")
    _refuse_unserved(cfg, mesh)
    b, s = tokens.shape
    # A softmax layer's prefill attends to its chunk's own keys and no
    # others, and a linear layer's starts from a zero state, so a stack
    # with either goes through in one chunk: the prompt.
    whole = bool(set(cfg.kinds) & set(RECURRENT_KINDS))
    chunk = chunk or (s if whole else prefill_chunk(s))
    if s % chunk:
        raise ValueError(f"a prompt of {s} is not a multiple of the chunk "
                         f"{chunk}")
    if whole and chunk != s:
        raise ValueError("a stack with softmax or linear layers takes its "
                         f"prompt in one chunk, not {s} in chunks of {chunk}")
    embed = params["embed"].astype(cfg.dtype)

    def step(carry, c):
        cache, counts = carry
        start = c * chunk
        positions = start + jnp.broadcast_to(jnp.arange(chunk), (b, chunk))
        x = embed[lax.dynamic_slice_in_dim(tokens, start, chunk, axis=1)]
        x, cache, exits, n, taps = _over_the_layers(
            cfg, params, x, positions, cache,
            _write_chunk_at(cfg, start, chunk, mesh))
        return (cache, counts + n), (x[:, -1], _last_exits(exits), taps)

    (cache, counts), last = lax.scan(
        step, (init_cache(cfg, b, max_len),
               jnp.zeros((len(MOE_ROWS),), jnp.int32)),
        jnp.arange(s // chunk))
    x, exits, taps = jax.tree.map(lambda a: a[-1], last)
    return (_head(params, x[:, None], cfg)[:, 0], cache,
            dict(taps, moe_rows=counts, exits=exits))


def decode_step_and_taps(params, token, pos, cache, cfg: TransformerConfig,
                         *, extent: Optional[int] = None):
    """One token for the whole batch: token [B] int32, pos scalar int32.
    -> (logits [B, vocab], updated cache, taps with ``moe_rows`` and
    ``exits`` as `prefill_and_taps` gives them). Attention reads the first
    ``extent`` positions of a slot (static; the caller's word that ``pos <
    extent``), all of them where it is None; a window layer reads its
    ring."""
    _refuse_unserved(cfg)
    if extent is None:
        extent = max((a.shape[2] for name, a in cache.items()
                      if name in BY_POSITION), default=0)
    x = params["embed"].astype(cfg.dtype)[token][:, None, :]   # [B, 1, E]
    positions = jnp.full((x.shape[0], 1), pos)
    x, cache, exits, counts, taps = _over_the_layers(
        cfg, params, x, positions, cache,
        _write_and_read_at(cfg, pos, extent))
    return (_head(params, x, cfg)[:, 0], cache,
            dict(taps, moe_rows=counts, exits=_last_exits(exits)))


def prefill_and_exits(params, tokens, cfg: TransformerConfig, max_len: int,
                      mesh=None):
    """``prefill_and_taps`` with the exits for the taps: (logits, cache,
    the last position's exit distribution or None)."""
    logits, cache, taps = prefill_and_taps(params, tokens, cfg, max_len,
                                           mesh=mesh)
    return logits, cache, taps["exits"]


def prefill(params, tokens, cfg: TransformerConfig, max_len: int,
            mesh=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """``prefill_and_taps`` without the taps: (logits, cache)."""
    return prefill_and_taps(params, tokens, cfg, max_len, mesh=mesh)[:2]


def decode_step_and_exits(params, token, pos, cache,
                          cfg: TransformerConfig, *,
                          extent: Optional[int] = None):
    """``decode_step_and_taps`` with the exits for the taps: (logits,
    cache, exit distribution [B, loop_steps] or None)."""
    logits, cache, taps = decode_step_and_taps(params, token, pos, cache,
                                               cfg, extent=extent)
    return logits, cache, taps["exits"]


def decode_step(params, token, pos, cache, cfg: TransformerConfig, *,
                extent: Optional[int] = None):
    """``decode_step_and_taps`` without the taps: (logits, cache)."""
    return decode_step_and_taps(params, token, pos, cache, cfg,
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
    follows the gate; 0 where there is no loop (None)."""
    if exits is None:
        return 0.0
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
    handing it back costs no copy). ``stats`` holds, of a looped stack,
    ``exit_steps_sum``, the sum over the generated tokens of the exit
    gate's expected loop step ``sum_t (t + 1) p_t``, and ``exit_tokens``,
    their count; and of a stack with expert layers, which must drop
    nothing, ``moe_rows_here``, ``moe_rows_dropped`` and
    ``moe_rows_walked`` (``MOE_ROWS``), summed over the call: a few
    numbers, accumulated in the decode loop's carry.

    The whole decode loop runs inside the caller's jit scope (wrap with
    jax.jit(partial(generate, ...)) or call under jit), one lax.scan for
    each of `_decode_segments`' segments: no per-token host round trips.
    """
    b, s = prompt.shape
    with jax.named_scope("rt.generate.prefill"):
        logits, cache, taps = prefill_and_taps(
            params, prompt, cfg, s + max_new_tokens, mesh=mesh)
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    first = _sample(logits, sub, temperature, top_k)

    def step(extent, carry, _):
        token, pos, cache, key, exits, steps_sum, rows = carry
        # ``exits`` came with the logits ``token`` is from
        steps_sum = steps_sum + _expected_exit_step(exits)
        logits, cache, taps = decode_step_and_taps(
            params, token, pos, cache, cfg, extent=extent)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sub, temperature, top_k)
        return (nxt, pos + 1, cache, key, taps["exits"], steps_sum,
                rows + taps["moe_rows"]), token

    carry = (first, jnp.asarray(s, jnp.int32), cache, key, taps["exits"],
             jnp.zeros((), jnp.float32), taps["moe_rows"])
    tokens = []
    with jax.named_scope("rt.generate.decode"):
        for steps, extent in _decode_segments(s, max_new_tokens):
            carry, emitted = lax.scan(partial(step, extent), carry, None,
                                      length=steps)
            tokens.append(emitted)
    _, _, cache, _, exits, steps_sum, rows = carry
    stats = {}
    if exits is not None:
        stats.update(
            exit_steps_sum=steps_sum,
            exit_tokens=jnp.asarray(b * max_new_tokens, jnp.float32))
    if cfg.num_experts:
        stats.update({"moe_" + name: rows[i]
                      for i, name in enumerate(MOE_ROWS)})
    return jnp.transpose(jnp.concatenate(tokens), (1, 0)), stats, cache


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
    it has to. Of a stack with linear mixers the cache's bytes by what
    holds them (``cache_bytes_state``, ``_tail``, ``_kv``), its slots by
    cache kind and ``mixers_a_layer`` (2 where a layer holds softmax
    attention and a linear mixer side by side)."""
    segments = _decode_segments(prompt, new)
    shapes = cache_shapes(cfg, rows, prompt + new)
    nbytes = {name: math.prod(shape)
              * jnp.dtype(cache_dtype(cfg, name)).itemsize
              for name, shape in shapes.items()}
    by_kind = "latent" in shapes or "window" in shapes
    attrs = dict(
        rows=rows, prompt=prompt, new=new, loop_steps=cfg.loop_steps,
        attention_path="latent" if by_kind
        else auto_path(prompt, prompt, cfg.head_dim)
        if cfg.attn_impl == "auto" else cfg.attn_impl,
        # a softmax layer's slot is its pair of k and v, a linear layer's
        # its state and tail
        cache_slots=sum(shape[0] for name, shape in shapes.items()
                        if name not in ("v", "tail")),
        cache_bytes=sum(nbytes.values()),
        decode_segments=len(segments),
        cache_positions_read=sum(n * extent for n, extent in segments),
        cache_positions_needed=new * prompt + new * (new + 1) // 2)
    if "state" in shapes:
        slots = kind_slots(cfg)
        attrs.update(
            cache_bytes_state=nbytes["state"], cache_bytes_tail=nbytes["tail"],
            cache_bytes_kv=nbytes.get("k", 0) + nbytes.get("v", 0),
            linear_slots=slots["linear"], full_slots=slots["full"],
            mixers_a_layer=max(len(CACHES_OF[kind]) for kind in cfg.kinds))
    if by_kind:
        # a latent layer's queries, each over the keys up to its own: all
        # of them scored by the indexer, index_topk of them attended to
        layers = rows * kind_slots(cfg)["latent"]
        k = cfg.index_topk if 0 < cfg.index_topk < prompt + new else 0
        chunk = prefill_chunk(prompt)
        total = prompt + new - 1        # queries at positions 0 .. total-1
        causal = total * (total + 1) // 2
        few = min(total, k)             # positions with no more than k keys
        attrs.update(
            {"cache_bytes_" + name: n for name, n in nbytes.items()},
            prefill_chunks=prompt // chunk,
            index_topk=cfg.index_topk,
            keys_scored=layers * causal if k else 0,
            keys_attended=layers * (few * (few + 1) // 2 + (total - few) * k
                                    if k else causal),
            # the prompt's queries, where a block of them over the whole
            # cache went through rt_sparse_attend (no decode step does)
            sparse_kernel_queries=layers * prompt if k and sparse_in_kernel(
                cfg.latent_dims("latent"), k, chunk, prompt + new) else 0,
            # and a window layer's, where a chunk of them went through the
            # flash forward kernel with a window
            window_kernel_queries=rows * kind_slots(cfg)["window"] * prompt
            if "window" in shapes and window_in_kernel(
                cfg.latent_dims("window"), cfg.window, chunk,
                chunk + cfg.window - 1) else 0)
    return events.span("generate.call", **attrs)

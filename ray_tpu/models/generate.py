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

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.transformer import (TransformerConfig, _attention,
                                        _head, _layer_apply,
                                        _over_loop_steps)
from ray_tpu.util import events


def _refuse_recurrent(cfg: TransformerConfig) -> None:
    if "linear" in cfg.layer_types:
        raise NotImplementedError(
            "generate serves softmax-attention layers only: this "
            "configuration has gated-delta-rule layers, whose recurrent "
            "state [B, Hv, dk, dv] and convolution tail would have to live "
            "beside the keys and values, and the cache here holds one kind "
            "(ROADMAP.md R8)")
    if cfg.layer_types:
        raise NotImplementedError(
            "generate scans one stack of like layers; a layer pattern "
            "(layer_types) is not served yet (ROADMAP.md R5)")


def cache_slots(cfg: TransformerConfig) -> int:
    """One slot for every (loop step, layer)."""
    return cfg.loop_steps * cfg.n_layers


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """[loop steps x L, B, T, KVH, D] zeros pair (kv dtype = compute
    dtype)."""
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


def prefill_and_exits(params, tokens, cfg: TransformerConfig, max_len: int,
                      mesh=None):
    """Run the prompt through the trunk, returning (last-position logits
    [B, vocab], filled cache, the last position's exit distribution [B,
    loop_steps] or None where there is no loop). tokens [B, S], S <=
    max_len."""
    if cfg.pp_stages > 1:
        raise NotImplementedError("decode with pp_stages>1 is not supported")
    _refuse_recurrent(cfg)
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


def generate_with_stats(params, prompt, cfg: TransformerConfig, *,
                        max_new_tokens: int, temperature: float = 0.0,
                        top_k: Optional[int] = None, seed: int = 0,
                        mesh=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """prompt [B, S] int32 -> (generated tokens [B, max_new_tokens],
    stats). ``stats`` is ``{}`` but for a looped stack: there
    ``exit_steps_sum``, the sum over the generated tokens of the exit
    gate's expected loop step ``sum_t (t + 1) p_t``, and ``exit_tokens``,
    their count: a few floats, accumulated in the decode loop's carry.

    The whole decode loop runs inside the caller's jit scope (wrap with
    jax.jit(partial(generate, ...)) or call under jit), one lax.scan for
    each of `_decode_segments`' segments: no per-token host round trips.
    """
    _refuse_recurrent(cfg)
    b, s = prompt.shape
    max_len = s + max_new_tokens
    looped = cfg.loop_steps > 1
    with jax.named_scope("rt.generate.prefill"):
        logits, cache, exits = prefill_and_exits(params, prompt, cfg,
                                                 max_len, mesh=mesh)
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    first = _sample(logits, sub, temperature, top_k)

    def step(extent, carry, _):
        token, pos, cache, key, exits, steps_sum = carry
        if looped:      # ``exits`` came with the logits ``token`` is from
            steps_sum = steps_sum + _expected_exit_step(exits)
        logits, cache, exits = decode_step_and_exits(
            params, token, pos, cache, cfg, extent=extent)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sub, temperature, top_k)
        return (nxt, pos + 1, cache, key, exits, steps_sum), token

    carry = (first, jnp.asarray(s, jnp.int32), cache, key, exits,
             jnp.zeros((), jnp.float32) if looped else None)
    tokens = []
    with jax.named_scope("rt.generate.decode"):
        for steps, extent in _decode_segments(s, max_new_tokens):
            carry, emitted = lax.scan(partial(step, extent), carry, None,
                                      length=steps)
            tokens.append(emitted)
    steps_sum = carry[-1]
    tokens = jnp.concatenate(tokens)
    stats = {"exit_steps_sum": steps_sum,
             "exit_tokens": jnp.asarray(b * max_new_tokens, jnp.float32)} \
        if looped else {}
    return jnp.transpose(tokens, (1, 0)), stats   # [B, max_new_tokens]


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
    slots = cache_slots(cfg)
    segments = _decode_segments(prompt, new)
    return events.span(
        "generate.call", rows=rows, prompt=prompt, new=new,
        loop_steps=cfg.loop_steps, cache_slots=slots,
        cache_bytes=2 * slots * rows * (prompt + new) * cfg.kv_heads
        * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize,
        decode_segments=len(segments),
        cache_positions_read=sum(n * extent for n, extent in segments),
        cache_positions_needed=new * prompt + new * (new + 1) // 2)

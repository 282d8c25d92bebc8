"""Autoregressive generation with a KV cache for the flagship Transformer.

The reference has no in-tree LM inference; serving there means wrapping an
external model in Ray Serve. Here decode is a first-class TPU program
(completing the LM story: train with jax_step, serve with serve/ + this):

- The KV cache is ONE stacked array pair [L, B, T_max, KVH, D] matching the
  layer-stacked parameter layout. Decode scans over (layers, layer index)
  with one compiled layer body and CARRIES the stacked cache: a layer
  writes its new key and value at [l, :, pos] and then reads its slab back
  from the updated carry. Write first, read second: a read of the
  pre-update stack after the write would make XLA keep two buffers and
  copy 2 GB a token. The cache is never a scanned input or output inside
  the decode loop, so the token loop updates one buffer in place (what a
  step writes is a few positions a layer, not the whole cache).
- `generate` runs the whole decode loop INSIDE jit via lax.scan: static
  shapes (cache padded to max length, attention masked by position), PRNG
  threaded through the scan — zero host round-trips per token.
- Prefill and decode run the training forward's one layer
  (transformer._layer_apply) and hand it only the attention step: prefill
  keeps each layer's rotated K/V as scan outputs (once a call); decode
  steps attend over the cache with a position mask (S=1 queries are
  bandwidth-bound; masking the padded tail costs nothing against reading
  the cache itself).

GQA (n_kv_heads < n_heads) is supported; pp_stages>1 is not (decode
pipelining is a different schedule than GPipe microbatching).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.transformer import (TransformerConfig, _attention,
                                        _head, _layer_apply)


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


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """[L, B, T, KVH, D] zeros pair (kv dtype = compute dtype)."""
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _cached_attention(cfg: TransformerConfig, q, k_cache, v_cache, pos):
    """q [B, 1, H, D] against cache [B, T, KVH, D], positions <= pos."""
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
    """cache [L, B, T, KVH, D] with new [B, 1, KVH, D] at [l, :, pos]: the
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


def prefill(params, tokens, cfg: TransformerConfig, max_len: int,
            mesh=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Run the prompt through the trunk, returning (last-position logits
    [B, vocab], filled cache). tokens [B, S], S <= max_len."""
    if cfg.pp_stages > 1:
        raise NotImplementedError("decode with pp_stages>1 is not supported")
    _refuse_recurrent(cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = params["embed"].astype(cfg.dtype)[tokens]
    pad = ((0, 0), (0, max_len - s), (0, 0), (0, 0))

    def attend(q, k, v):
        # The training forward's attention; the layer's rotated K and V
        # are kept, so the cache matches the forward bit for bit.
        return (_attention(cfg, q, k, v, mesh),
                {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)})

    def step(carry, layer):
        return _layer_apply(cfg, layer, carry, positions, attend)[:2]

    x, cache = lax.scan(step, x, params["layers"])
    return _head(params, x[:, -1:], cfg)[:, 0], cache


def decode_step(params, token, pos, cache, cfg: TransformerConfig):
    """One token for the whole batch: token [B] int32, pos scalar int32.
    -> (logits [B, vocab], updated cache)."""
    _refuse_recurrent(cfg)
    x = params["embed"].astype(cfg.dtype)[token][:, None, :]   # [B, 1, E]
    positions = jnp.full((x.shape[0], 1), pos)

    def step(carry, layer_and_index):
        x, cache_k, cache_v = carry
        layer, l = layer_and_index

        def attend(q, k, v):
            # Write, then read the slab from the UPDATED stack: a read of
            # the old stack after the write would make XLA keep two
            # buffers and copy.
            stack_k = _write_position(cache_k, l, pos, k)
            stack_v = _write_position(cache_v, l, pos, v)
            o = _cached_attention(
                cfg, q,
                lax.dynamic_index_in_dim(stack_k, l, 0, keepdims=False),
                lax.dynamic_index_in_dim(stack_v, l, 0, keepdims=False),
                pos)
            return o, (stack_k, stack_v)

        x, (cache_k, cache_v), _ = _layer_apply(cfg, layer, x, positions,
                                                attend)
        return (x, cache_k, cache_v), None

    (x, cache_k, cache_v), _ = lax.scan(
        step, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    return _head(params, x, cfg)[:, 0], {"k": cache_k, "v": cache_v}


def _sample(logits, key, temperature: float, top_k: Optional[int]):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        thresh = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < thresh, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def generate(params, prompt, cfg: TransformerConfig, *,
             max_new_tokens: int, temperature: float = 0.0,
             top_k: Optional[int] = None, seed: int = 0,
             mesh=None) -> jnp.ndarray:
    """prompt [B, S] int32 -> generated tokens [B, max_new_tokens].

    The whole decode loop is ONE lax.scan inside the caller's jit scope
    (wrap with jax.jit(partial(generate, ...)) or call under jit): no
    per-token host round trips.
    """
    _refuse_recurrent(cfg)
    b, s = prompt.shape
    max_len = s + max_new_tokens
    with jax.named_scope("rt.generate.prefill"):
        logits, cache = prefill(params, prompt, cfg, max_len, mesh=mesh)
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    first = _sample(logits, sub, temperature, top_k)

    def step(carry, _):
        token, pos, cache, key = carry
        logits, cache = decode_step(params, token, pos, cache, cfg)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sub, temperature, top_k)
        return (nxt, pos + 1, cache, key), token

    with jax.named_scope("rt.generate.decode"):
        (_, _, _, _), tokens = lax.scan(
            step, (first, jnp.asarray(s, jnp.int32), cache, key),
            None, length=max_new_tokens)
    return jnp.transpose(tokens, (1, 0))   # [B, max_new_tokens]

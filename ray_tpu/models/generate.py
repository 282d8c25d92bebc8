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
- Prefill reuses the training forward structure, collecting per-layer K/V
  as scan outputs (once a call); decode steps attend over the cache with a
  position mask (S=1 queries are bandwidth-bound; masking the padded tail
  costs nothing against reading the cache itself).

GQA (n_kv_heads < n_heads) is supported; pp_stages>1 is not (decode
pipelining is a different schedule than GPipe microbatching).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import dataclasses

from ray_tpu.models.transformer import (TransformerConfig, _layer_apply,
                                        _rmsnorm, _rope)


def _inference_cfg(cfg: TransformerConfig) -> TransformerConfig:
    """Dropless MoE at inference: capacity dropping is a training
    throughput trade; S=1 decode never drops, so prefill must not either
    or cached and uncached passes diverge."""
    if cfg.num_experts and cfg.moe_capacity_factor is None:
        return dataclasses.replace(cfg, moe_capacity_factor=1e9)
    return cfg


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """[L, B, T, KVH, D] zeros pair (kv dtype = compute dtype)."""
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _project_kv(cfg: TransformerConfig, layer, h, positions):
    a = layer["attn"]
    dt = cfg.dtype
    k = jnp.einsum("bse,ehd->bshd", h, a["wk"].astype(dt))
    v = jnp.einsum("bse,ehd->bshd", h, a["wv"].astype(dt))
    return _rope(k, positions, cfg.rope_theta), v


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


def _decode_layer(cfg: TransformerConfig, layer, l, cache_k, cache_v, x,
                  pos):
    """One layer, one token: x [B, 1, E]; cache_k/v the whole stack
    [L, B, T, KVH, D], of which layer ``l`` gets position ``pos`` written
    and is then attended over. -> (x, cache_k, cache_v)."""
    dt = cfg.dtype
    h = _rmsnorm(x, layer["ln1"])
    a = layer["attn"]
    positions = jnp.full((x.shape[0], 1), pos)
    q = jnp.einsum("bse,ehd->bshd", h, a["wq"].astype(dt))
    q = _rope(q, positions, cfg.rope_theta)
    k_new, v_new = _project_kv(cfg, layer, h, positions)
    # Write, then read the slab from the UPDATED stack: a read of the old
    # stack after the write would make XLA keep two buffers and copy.
    cache_k = _write_position(cache_k, l, pos, k_new)
    cache_v = _write_position(cache_v, l, pos, v_new)
    o = _cached_attention(
        cfg, q, lax.dynamic_index_in_dim(cache_k, l, 0, keepdims=False),
        lax.dynamic_index_in_dim(cache_v, l, 0, keepdims=False), pos)
    o = jnp.einsum("bshd,hde->bse", o, a["wo"].astype(dt))
    x = x + o
    h = _rmsnorm(x, layer["ln2"])
    if cfg.num_experts:
        from ray_tpu.models.moe import moe_apply
        y = moe_apply(cfg, layer["moe"], h)
    else:
        m = layer["mlp"]
        gate = jax.nn.silu(h @ m["w1"].astype(dt))
        up = h @ m["w3"].astype(dt)
        y = (gate * up) @ m["w2"].astype(dt)
    return x + y, cache_k, cache_v


def prefill(params, tokens, cfg: TransformerConfig, max_len: int,
            mesh=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Run the prompt through the trunk, returning (last-position logits
    [B, vocab], filled cache). tokens [B, S], S <= max_len."""
    if cfg.pp_stages > 1:
        raise NotImplementedError("decode with pp_stages>1 is not supported")
    cfg = _inference_cfg(cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = params["embed"].astype(cfg.dtype)[tokens]

    def step(carry, layer):
        # return_kv hands back the layer's already-computed rotated K/V —
        # cache matches the forward bit-for-bit at zero extra FLOPs.
        out, (k, v) = _layer_apply(cfg, mesh, layer, carry, positions,
                                   return_kv=True)
        pad = max_len - s
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return out, {"k": k, "v": v}

    x, cache = lax.scan(step, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"])
    head = (params["embed"].T if cfg.tied_embeddings else params["lm_head"])
    logits = (x[:, -1:] @ head.astype(cfg.dtype)).astype(jnp.float32)
    return logits[:, 0], cache


def decode_step(params, token, pos, cache, cfg: TransformerConfig):
    """One token for the whole batch: token [B] int32, pos scalar int32.
    -> (logits [B, vocab], updated cache)."""
    cfg = _inference_cfg(cfg)
    x = params["embed"].astype(cfg.dtype)[token][:, None, :]   # [B, 1, E]

    def step(carry, layer_and_index):
        x, cache_k, cache_v = carry
        layer, l = layer_and_index
        return _decode_layer(cfg, layer, l, cache_k, cache_v, x, pos), None

    (x, cache_k, cache_v), _ = lax.scan(
        step, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    cache = {"k": cache_k, "v": cache_v}
    x = _rmsnorm(x, params["final_norm"])
    head = (params["embed"].T if cfg.tied_embeddings else params["lm_head"])
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    return logits[:, 0], cache


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
    cfg = _inference_cfg(cfg)
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

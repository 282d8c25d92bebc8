"""Flagship decoder-only Transformer LM (llama-style), pure-functional.

Design notes (TPU-first):
- Params are a pytree of jnp arrays; layers are *stacked* on a leading dim
  and applied with `lax.scan` so XLA compiles one layer body regardless of
  depth; `jax.checkpoint` remats each layer (HBM <-> FLOPs trade).
- Every weight carries logical axis names (transformer_logical_axes) mapped
  to mesh axes by parallel/sharding.py: tp shards heads/mlp/vocab, fsdp
  shards the embed dim (ZeRO-3), sp shards the sequence (ring/Ulysses
  attention), pp splits the layer stack into stages (ops/pipeline.py).
- Compute dtype bfloat16 (MXU native), params float32.

The reference has no in-tree LM; its model-parallel story is external
(SURVEY.md §2d). This model is the vehicle for the framework's TP/PP/SP/EP
strategies and the bench flagship.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import mha
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses import ulysses_attention
from ray_tpu.parallel.sharding import DEFAULT_RULES, LogicalRules


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None      # None -> = n_heads (MHA)
    d_ff: Optional[int] = None            # None -> 4 * d_model (SwiGLU 2/3)
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16             # compute dtype
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"               # auto|reference|blockwise|flash|ring|ulysses
    causal: bool = True                   # False: bidirectional (ViT/BERT)
    remat: bool = True
    pp_stages: int = 1                    # >1: split layers into pipeline stages
    num_microbatches: int = 1             # pipeline microbatches
    # MoE (0 = dense)
    num_experts: int = 0
    # None -> moe_apply's training default (1.25). Inference sets a huge
    # factor (dropless): capacity dropping is a TRAINING throughput trade;
    # at decode S=1 every token always fits, so prefill must match or
    # cached and uncached forward passes diverge (models/generate.py).
    moe_capacity_factor: Optional[float] = None
    expert_top_k: int = 1
    tied_embeddings: bool = False

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def layers_per_stage(self) -> int:
        assert self.n_layers % self.pp_stages == 0
        return self.n_layers // self.pp_stages


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: TransformerConfig) -> Dict[str, Any]:
    d, h, hk, hd, f = (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                       cfg.ff_dim)
    ks = jax.random.split(key, 8)
    init = jax.nn.initializers.normal(0.02)
    pd = cfg.param_dtype
    layer = {
        "attn": {
            "wq": init(ks[0], (d, h, hd), pd),
            "wk": init(ks[1], (d, hk, hd), pd),
            "wv": init(ks[2], (d, hk, hd), pd),
            "wo": init(ks[3], (h, hd, d), pd),
        },
        "ln1": jnp.ones((d,), pd),
        "ln2": jnp.ones((d,), pd),
    }
    if cfg.num_experts:
        ek = jax.random.split(ks[4], 4)
        e = cfg.num_experts
        layer["moe"] = {
            "router": init(ek[0], (d, e), pd),
            "w1": init(ek[1], (e, d, f), pd),
            "w3": init(ek[2], (e, d, f), pd),
            "w2": init(ek[3], (e, f, d), pd),
        }
    else:
        layer["mlp"] = {
            "w1": init(ks[5], (d, f), pd),
            "w3": init(ks[6], (d, f), pd),
            "w2": init(ks[7], (f, d), pd),
        }
    return layer


def transformer_init(key, cfg: TransformerConfig) -> Dict[str, Any]:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    init = jax.nn.initializers.normal(0.02)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys)
    if cfg.pp_stages > 1:
        stacked = jax.tree.map(
            lambda a: a.reshape((cfg.pp_stages, cfg.layers_per_stage)
                                + a.shape[1:]), stacked)
    params = {
        "embed": init(k_emb, (cfg.vocab_size, cfg.d_model), cfg.param_dtype),
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = init(k_head, (cfg.d_model, cfg.vocab_size),
                                 cfg.param_dtype)
    return params


def transformer_logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Pytree mirroring params: per-leaf logical dim names (see
    parallel/sharding.py DEFAULT_RULES)."""
    stage = ("stage", "layers") if cfg.pp_stages > 1 else ("layers",)
    def L(*axes):  # layer leaf: leading stacked dim(s)
        return stage + axes
    layer = {
        "attn": {
            "wq": L("embed", "heads", "kv"),
            "wk": L("embed", "heads", "kv"),
            "wv": L("embed", "heads", "kv"),
            "wo": L("heads", "kv", "embed"),
        },
        "ln1": L("embed"),
        "ln2": L("embed"),
    }
    if cfg.num_experts:
        layer["moe"] = {
            "router": L("embed", None),
            "w1": L("expert", "embed", "expert_mlp"),
            "w3": L("expert", "embed", "expert_mlp"),
            "w2": L("expert", "expert_mlp", "embed"),
        }
    else:
        layer["mlp"] = {
            "w1": L("embed", "mlp"),
            "w3": L("embed", "mlp"),
            "w2": L("mlp", "embed"),
        }
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("embed",),
    }
    if not cfg.tied_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, positions, theta: float):
    """x: [B, S, H, D]; rotate pairs (d, d + D/2)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (jnp.log(theta) / half))
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg: TransformerConfig, q, k, v, mesh,
               rules: LogicalRules = DEFAULT_RULES):
    impl = cfg.attn_impl
    if impl == "ring":
        return ring_attention(q, k, v, mesh, causal=cfg.causal)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, mesh, causal=cfg.causal)
    return mha(q, k, v, causal=cfg.causal, impl=impl, mesh=mesh,
               rules=rules)


def _feed_forward(cfg: TransformerConfig, layer, h):
    if cfg.num_experts:
        from ray_tpu.models.moe import moe_apply
        return moe_apply(cfg, layer["moe"], h)
    dt = cfg.dtype
    m = layer["mlp"]
    gate = jax.nn.silu(h @ m["w1"].astype(dt))
    up = h @ m["w3"].astype(dt)
    return (gate * up) @ m["w2"].astype(dt)


def _layer_apply(cfg: TransformerConfig, layer, x, positions, attend):
    """One block. ``attend(q, k, v) -> (o, kept)`` is all that differs
    between training, prefill and decode (models/generate.py): what
    attention does with the rotated k and v, and what it keeps of them.
    -> (x, kept)."""
    dt = cfg.dtype
    h = _rmsnorm(x, layer["ln1"])
    a = layer["attn"]
    q = jnp.einsum("bse,ehd->bshd", h, a["wq"].astype(dt))
    k = jnp.einsum("bse,ehd->bshd", h, a["wk"].astype(dt))
    v = jnp.einsum("bse,ehd->bshd", h, a["wv"].astype(dt))
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    o, kept = attend(q, k, v)
    o = jnp.einsum("bshd,hde->bse", o, a["wo"].astype(dt))
    x = x + o
    h = _rmsnorm(x, layer["ln2"])
    return x + _feed_forward(cfg, layer, h), kept


def _stage_apply(cfg: TransformerConfig, mesh, stage_layers, x, positions,
                 rules: LogicalRules = DEFAULT_RULES):
    """Apply a stack of layers (leading dim = layers) with lax.scan.
    ``rules``: what the caller sharded params and batch by over ``mesh``."""
    body = partial(
        _layer_apply, cfg,
        attend=lambda q, k, v: (_attention(cfg, q, k, v, mesh, rules), None))
    if cfg.remat:
        body = jax.checkpoint(body)

    def step(carry, layer):
        return body(layer, carry, positions)

    out, _ = lax.scan(step, x, stage_layers)
    return out


def _head(params, x, cfg: TransformerConfig):
    """Final norm + (tied or untied) head: x [..., E] -> float32 logits."""
    x = _rmsnorm(x, params["final_norm"])
    head = (params["embed"].T if cfg.tied_embeddings else params["lm_head"])
    return (x @ head.astype(cfg.dtype)).astype(jnp.float32)


def _next_token_loss(logits, tokens, mask=None):
    """Cross-entropy of logits[:, :-1] against tokens[:, 1:], mean over
    the positions ``mask`` keeps (all when None)."""
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        mask = mask[:, 1:]
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def transformer_apply(params, tokens, cfg: TransformerConfig, *,
                      mesh=None, positions=None,
                      rules: LogicalRules = DEFAULT_RULES):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (compute in cfg.dtype,
    logits float32)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.pp_stages > 1:
        if mesh is None:
            raise ValueError("pp_stages>1 requires a mesh")
        from ray_tpu.ops.pipeline import pipeline_apply
        m = cfg.num_microbatches
        assert b % m == 0, f"batch {b} % microbatches {m} != 0"
        mb = b // m
        xs = x.reshape(m, mb, s, cfg.d_model)
        # positions are identical across batch rows; a [1, S] row broadcasts
        # against any local microbatch slice inside shard_map
        pos_s = positions[:1]

        def stage_fn(stage_layers, act):
            return _stage_apply(cfg, mesh, stage_layers, act, pos_s, rules)

        x = pipeline_apply(stage_fn, params["layers"], xs, mesh,
                           num_microbatches=m)
        x = x.reshape(b, s, cfg.d_model)
    else:
        x = _stage_apply(cfg, mesh, params["layers"], x, positions, rules)
    return _head(params, x, cfg)


def transformer_loss(params, batch, cfg: TransformerConfig, *, mesh=None,
                     rules: LogicalRules = DEFAULT_RULES):
    """batch: {"tokens": [B, S]} next-token cross-entropy (mean over
    non-final positions)."""
    tokens = batch["tokens"]
    logits = transformer_apply(params, tokens, cfg, mesh=mesh, rules=rules)
    return _next_token_loss(logits, tokens, batch.get("mask"))


# ---------------------------------------------------------------------------
# MPMD pipeline partitioning (train/pipeline.py)
# ---------------------------------------------------------------------------
#
# With cfg.pp_stages > 1 the stacked layer tree is [P, layers_per_stage,
# ...]; partition p applies slice p with the SAME _stage_apply scan the
# single-process model uses, so a pipeline of P partitions is numerically
# identical to the pp_stages=1 forward (layer order preserved). Partition
# 0 additionally owns the embedding; the last partition owns final_norm +
# lm_head and computes the loss.

def transformer_partition_params(params, cfg: TransformerConfig,
                                 part: int) -> Dict[str, Any]:
    """Slice the full init tree down to what partition ``part`` owns."""
    P = cfg.pp_stages
    if P < 2:
        raise ValueError("partitioning requires cfg.pp_stages >= 2")
    if cfg.tied_embeddings:
        # Tied embeddings would put one weight on two stages (grads would
        # need a cross-stage reduction the schedule does not express).
        raise ValueError("MPMD pipeline requires tied_embeddings=False")
    sub: Dict[str, Any] = {
        "layers": jax.tree.map(lambda a: a[part], params["layers"])}
    if part == 0:
        sub["embed"] = params["embed"]
    if part == P - 1:
        sub["final_norm"] = params["final_norm"]
        sub["lm_head"] = params["lm_head"]
    return sub


def transformer_stage_forward(stage_params, x, positions,
                              cfg: TransformerConfig, *, part: int,
                              mesh=None):
    """Forward one partition: tokens [B, S] int for partition 0 (embed
    lookup included), activations [B, S, D] otherwise."""
    if part == 0:
        x = stage_params["embed"].astype(cfg.dtype)[x]
    return _stage_apply(cfg, mesh, stage_params["layers"], x, positions)


def transformer_stage_loss(stage_params, x, tokens,
                           cfg: TransformerConfig, *, mesh=None):
    """Last partition: its layer slice, then final norm + head +
    next-token cross-entropy (same reduction as transformer_loss)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = transformer_stage_forward(stage_params, x, positions, cfg,
                                  part=cfg.pp_stages - 1, mesh=mesh)
    return _next_token_loss(_head(stage_params, x, cfg), tokens)


def transformer_num_params(cfg: TransformerConfig) -> int:
    d, f, v = cfg.d_model, cfg.ff_dim, cfg.vocab_size
    per_layer = d * cfg.n_heads * cfg.head_dim * 2 \
        + d * cfg.kv_heads * cfg.head_dim * 2 + 2 * d
    if cfg.num_experts:
        per_layer += d * cfg.num_experts + cfg.num_experts * 3 * d * f
    else:
        per_layer += 3 * d * f
    total = v * d + cfg.n_layers * per_layer + d
    if not cfg.tied_embeddings:
        total += d * v
    return total

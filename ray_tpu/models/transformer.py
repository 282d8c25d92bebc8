"""Flagship decoder-only Transformer LM, pure-functional: the llama block
(RMSNorm / SwiGLU / RoPE / GQA) and, by configuration, the hybrid block of
Qwen3-Next (a pattern of gated-delta-rule and gated full-attention layers
over an expert layer without a capacity per expert, with a shared expert),
the post-normed hybrid block of OLMo (the same rule with beta up to 2 beside
plain softmax layers, a norm on each sublayer's output only, q and k normed
over the whole projection, no rotation), the parallel block of Falcon-H1
(softmax attention and a state-space mixer, Mamba-2's SSD, read one normed
input side by side and both join the residual) or a looped stack (the layers run ``loop_steps`` times over one set of
weights, a norm on each sublayer's output, an exit gate after each pass).

Design notes (TPU-first):
- Params are a pytree of jnp arrays; layers are *stacked* on a leading dim
  and applied with `lax.scan` so XLA compiles one layer body regardless of
  depth; `jax.checkpoint` remats each layer (HBM <-> FLOPs trade). A remat'd
  layer keeps its input and what its mixer's Pallas forward kernel hands
  the backward kernels under a checkpoint name, so that kernel is not run
  again for the backward: the gated delta rule's four outputs
  (ops/gated_delta.py ``KEPT``: 603,979,776 bytes a layer at 2 rows x 8,192
  in bfloat16), and flash attention's ``out`` and log-sum-exp column where
  a kept byte spares enough of a second run (ops/flash.py ``KEPT``,
  ``KEEP_FROM``: 136,314,880 bytes a latent layer at 2 rows x 8,192 x 32
  heads, nothing at S = 2,048 with heads of 128); XLA's attention forms and
  the rule's jnp form keep nothing else.
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

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import flash, gated_delta, ssd
from ray_tpu.ops.attention import mha
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses import ulysses_attention
from ray_tpu.parallel.sharding import DEFAULT_RULES, LogicalRules


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """Widths of one kind of latent-attention layer (models/latent.py)."""
    heads: int
    q_rank: int
    kv_rank: int
    nope: int                             # a head's q/k dims without RoPE
    rope: int                             # ... with RoPE (the key's shared)
    v: int
    rope_theta: float = 10000.0

    @property
    def cached(self) -> int:
        """Width of a cached position: the latent and the shared key."""
        return self.kv_rank + self.rope


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
    # Expert layer (0 = dense), models/moe.py. ``num_experts`` is the
    # router's width; this program holds ``experts_held`` of them from
    # ``first_expert`` on (None: all) and computes their part of the result.
    num_experts: int = 0
    experts_held: Optional[int] = None
    first_expert: int = 0
    expert_top_k: int = 1
    norm_topk_prob: bool = False          # top-k weights divided by their sum
    expert_ff: Optional[int] = None       # None -> ff_dim
    shared_expert_ff: int = 0             # 0 = no shared expert
    tied_embeddings: bool = False
    router_scoring: str = "softmax"       # | "sigmoid": selection by score
    #                                       + a correction bias, weights by
    #                                       the score alone
    routed_scaling_factor: float = 1.0    # on the routed experts' weights
    shared_expert_gate: bool = True       # sigmoid(w_g . x) on the shared
    # what a step adds to or takes from a sigmoid router's correction bias
    # by each expert's load (models/moe.py balance_bias)
    router_bias_update_rate: float = 0.001
    # Hybrid block. ``layer_types``: one period of "full" | "linear" |
    # "parallel" (softmax attention and the linear mixer side by side on one
    # normed input) | "latent" | "window", () = every layer full attention;
    # after
    # ``first_dense_layers`` leading layers of the period's first kind, with
    # a dense MLP whatever ``num_experts`` says, n_layers is a multiple of
    # its length.
    layer_types: Tuple[str, ...] = ()
    first_dense_layers: int = 0
    norm_eps: float = 1e-6                # RMSNorm epsilon
    # Latent attention (models/latent.py): "latent" layers attend to every
    # earlier position or, where ``index_topk`` is set and there are more,
    # to the ``index_topk`` that a learned indexer of ``index_heads`` heads
    # scores highest; "window" layers to the last ``window`` positions, the
    # query's own included.
    latent: Optional[LatentDims] = None
    window_latent: Optional[LatentDims] = None
    window: int = 0
    index_heads: int = 0
    index_head_dim: int = 128
    index_topk: int = 0
    attn_gate: str = ""                   # "headwise": sigmoid(w_g . x), one
    #                                       scalar a head, on a latent layer
    lora_rescale: bool = False            # latents times sqrt(d / rank)
    head_width: Optional[int] = None      # None -> d_model / n_heads
    partial_rotary_factor: float = 1.0    # share of a head RoPE rotates
    qk_norm: bool = False                 # RMSNorm of q and k per head
    qk_norm_whole: bool = False           # ... over the whole projection, all
    #                                       heads together (OLMo 2 / 3)
    attn_output_gate: bool = False        # wq also gives a sigmoid gate
    norm_plus_one: bool = False           # norms scale by 1 + w, w from 0
    linear_key_heads: int = 0             # gated delta rule (ops/gated_delta)
    linear_value_heads: int = 0
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    linear_conv_kernel: int = 4
    linear_beta_scale: float = 1.0        # beta = scale x sigmoid(b): 2 lets
    #                                       a state's eigenvalue go negative
    # The linear mixer's transition: "delta", the gated delta rule, or
    # "ssd", Mamba-2's state-space recurrence (ops/ssd.py), its dual: the
    # key heads are the groups that share B and C, ``linear_key_dim`` the
    # state's width, ``linear_value_dim`` a head's.
    linear_transition: str = "delta"
    # Looped stack: the n_layers run ``loop_steps`` times over the one set
    # of weights, the final norm and the exit gate (Linear(d_model -> 1),
    # sigmoid) after every pass. A position leaves the loop at the first
    # pass whose cumulative exit probability reaches the threshold.
    loop_steps: int = 1
    early_exit_threshold: float = 1.0
    sandwich_norm: bool = False           # a norm on each sublayer's output
    post_norm_only: bool = False          # ... and none on its input: ``x +
    #                                       norm(mixer(x))`` (OLMo 2 / 3)
    # Multi-token prediction (DeepSeek-V3, arXiv:2412.19437 section 2.2):
    # ``mtp_layers`` modules (0 or 1) after the stack, ``params["mtp"]``,
    # each one block of the period's last kind over ``W_eh [norm(Emb(t_{i+1}
    # )) ; norm(h_i)]``, read by the model's own head: a second loss, of
    # ``t_{i+2}``, added with weight ``mtp_loss_weight``. Training only:
    # ``generate`` serves the main stack and does not read the module.
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3

    def __post_init__(self):
        if set(self.layer_types) - {"full", "linear", "parallel", "latent",
                                    "window"}:
            raise ValueError(f"layer_types {self.layer_types}: each is "
                             "'full', 'linear', 'parallel', 'latent' or "
                             "'window'")
        if self.linear_transition not in ("delta", "ssd"):
            raise ValueError(f"linear_transition {self.linear_transition!r}"
                             ": 'delta' or 'ssd'")
        if self.layer_types and \
                (self.n_layers - self.first_dense_layers) \
                % len(self.layer_types):
            raise ValueError(
                f"n_layers {self.n_layers} less the {self.first_dense_layers}"
                " leading dense layers is not a multiple of the period "
                f"{len(self.layer_types)}")
        if self.first_dense_layers and not (self.layer_types
                                            and self.num_experts):
            raise ValueError("first_dense_layers needs a layer pattern and "
                             "an expert layer to precede")
        if "latent" in self.layer_types and self.latent is None:
            raise ValueError("latent layers need their widths (latent)")
        if "window" in self.layer_types and \
                not (self.window_latent and self.window):
            raise ValueError("window layers need their widths "
                             "(window_latent) and a window")
        if self.index_topk and not self.index_heads:
            raise ValueError("index_topk needs index_heads")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"router_scoring {self.router_scoring!r}: "
                             "'softmax' or 'sigmoid'")
        if self.qk_norm and self.qk_norm_whole:
            raise ValueError("qk_norm (per head) and qk_norm_whole (over "
                             "the projection) are two norms of one place")
        if self.sandwich_norm and self.post_norm_only:
            raise ValueError("sandwich_norm norms a sublayer's input too, "
                             "post_norm_only does not: one of the two")
        if self.attn_gate not in ("", "headwise"):
            raise ValueError(f"attn_gate {self.attn_gate!r}: '' or "
                             "'headwise'")
        if {"linear", "parallel"} & set(self.layer_types) \
                and not (self.linear_key_heads and self.linear_value_heads):
            raise ValueError("linear and parallel layers need "
                             "linear_key_heads and linear_value_heads")
        if self.layer_types and self.pp_stages > 1:
            raise ValueError("a layer pattern with pp_stages > 1 is not "
                             "supported")
        if self.mtp_layers not in (0, 1):
            raise ValueError(
                f"mtp_layers {self.mtp_layers}: 0 or 1 (a module of depth k "
                "reads the module before it, and one depth is built)")
        if self.mtp_layers and self.pp_stages > 1:
            raise ValueError(
                "mtp_layers with pp_stages > 1 is not supported: the module "
                "reads the last stage's output and the first stage's "
                "embedding, and the pipeline's schedule has no such edge")
        if self.mtp_layers and self.loop_steps > 1:
            raise ValueError("mtp_layers with loop_steps > 1 is not "
                             "supported: a looped stack has no loss")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps}: at least 1")
        if self.loop_steps > 1 and self.pp_stages > 1:
            raise ValueError(
                "loop_steps > 1 with pp_stages > 1 is not supported: the "
                "last stage's output would have to go back to the first, "
                "and the pipeline's schedule has no such edge")
        if self.loop_steps > 1 and self.layer_types:
            raise ValueError(
                "loop_steps > 1 with a layer pattern (layer_types) is not "
                "supported: a looped stack is one stack of like layers")
        if self.loop_steps > 1 and self.num_experts:
            raise ValueError(
                "loop_steps > 1 with an expert layer is not supported: the "
                "expert layers' counters of several passes have no place "
                "in the step's metrics")
        if self.early_exit_threshold < 1:
            raise NotImplementedError(
                f"early_exit_threshold {self.early_exit_threshold} under "
                "1: rows of one batch would leave the loop at different "
                "steps, so a step would no longer cost every row the same "
                "(the batcher and generate assume it does) and the cache "
                "slots of the skipped steps would stay unwritten; every "
                "position runs all loop_steps here")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """One period's layer kinds; the leading dense layers are of the
        first."""
        return self.layer_types or ("full",)

    @property
    def periods(self) -> int:
        return (self.n_layers - self.first_dense_layers) \
            // max(len(self.layer_types), 1)

    def latent_dims(self, kind: str) -> LatentDims:
        return self.latent if kind == "latent" else self.window_latent

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def expert_ff_dim(self) -> int:
        return self.expert_ff if self.expert_ff is not None else self.ff_dim

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

def _layer_norms(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The block's norm scales: on each sublayer's input (``ln1``, ``ln2``)
    unless ``post_norm_only``, on its output (``ln1_post``, ``ln2_post``)
    under that or ``sandwich_norm``."""
    before = () if cfg.post_norm_only else ("ln1", "ln2")
    after = ("ln1_post", "ln2_post") \
        if cfg.sandwich_norm or cfg.post_norm_only else ()
    return before + after


def _linear_mixer_init(key, cfg: TransformerConfig) -> Dict[str, Any]:
    """The linear mixer of ``cfg.linear_transition`` under its own name:
    ``{"gdn": ...}``, the gated delta rule's, or ``{"ssm": ...}``, the
    state-space mixer's."""
    gk = jax.random.split(key, 5)
    init = jax.nn.initializers.normal(0.02)
    d, pd, hv = cfg.d_model, cfg.param_dtype, cfg.linear_value_heads
    kd = cfg.linear_key_heads * cfg.linear_key_dim
    vd = hv * cfg.linear_value_dim
    if cfg.linear_transition == "ssd":
        return {"ssm": {
            # flat, as published: [z | x | B | C | dt]
            "in_proj": init(gk[0], (d, 2 * vd + 2 * kd + hv), pd),
            "conv": init(gk[2], (vd + 2 * kd, cfg.linear_conv_kernel), pd),
            "conv_bias": jnp.zeros((vd + 2 * kd,), pd),
            "dt_bias": jnp.ones((hv,), pd),
            "A_log": jnp.log(jax.random.uniform(
                gk[3], (hv,), pd, minval=1.0, maxval=16.0)),
            "D": jnp.ones((hv,), pd),
            "norm": jnp.ones((vd,), pd),
            "out": init(gk[4], (vd, d), pd),
        }}
    return {"gdn": {
        # flat: [all q | all k | all v | all z] and [all b | all a]
        "in_qkvz": init(gk[0], (d, 2 * kd + 2 * vd), pd),
        "in_ba": init(gk[1], (d, 2 * hv), pd),
        "conv": init(gk[2], (2 * kd + vd, cfg.linear_conv_kernel), pd),
        "dt_bias": jnp.ones((hv,), pd),
        "A_log": jnp.log(jax.random.uniform(
            gk[3], (hv,), pd, minval=1e-3, maxval=16.0)),
        "norm": jnp.ones((cfg.linear_value_dim,), pd),
        "out": init(gk[4], (vd, d), pd),
    }}


def _layer_init(key, cfg: TransformerConfig, kind: str = "full",
                dense: bool = False) -> Dict[str, Any]:
    """``dense``: a dense MLP whatever ``cfg.num_experts`` says."""
    d, h, hk, hd, f = (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                       cfg.ff_dim)
    ks = jax.random.split(key, 8)
    init = jax.nn.initializers.normal(0.02)
    pd = cfg.param_dtype
    norm = jnp.zeros if cfg.norm_plus_one else jnp.ones
    layer = {name: norm((d,), pd) for name in _layer_norms(cfg)}
    if kind in ("full", "parallel"):
        gate = 2 if cfg.attn_output_gate else 1   # per head: query, gate
        layer["attn"] = {
            "wq": init(ks[0], (d, h, gate * hd), pd),
            "wk": init(ks[1], (d, hk, hd), pd),
            "wv": init(ks[2], (d, hk, hd), pd),
            "wo": init(ks[3], (h, hd, d), pd),
        }
        if cfg.qk_norm:
            layer["attn"]["q_norm"] = norm((hd,), pd)
            layer["attn"]["k_norm"] = norm((hd,), pd)
        if cfg.qk_norm_whole:
            layer["attn"]["q_norm"] = norm((h * hd,), pd)
            layer["attn"]["k_norm"] = norm((hk * hd,), pd)
    elif kind in ("latent", "window"):
        from ray_tpu.models.latent import PARAMS_KEY, latent_init
        layer[PARAMS_KEY[kind]] = latent_init(ks[0], cfg, kind)
    if kind in ("linear", "parallel"):
        # a parallel layer's from a key of its own: ks[0] is its wq's
        layer.update(_linear_mixer_init(
            jax.random.fold_in(key, 1) if kind == "parallel" else ks[0],
            cfg))
    if cfg.num_experts and not dense:
        ek = jax.random.split(ks[4], 8)
        e, ef, sf = cfg.held, cfg.expert_ff_dim, cfg.shared_expert_ff
        layer["moe"] = {
            "router": init(ek[0], (d, cfg.num_experts), pd),
            "w1": init(ek[1], (e, d, ef), pd),
            "w3": init(ek[2], (e, d, ef), pd),
            "w2": init(ek[3], (e, ef, d), pd),
        }
        if cfg.router_scoring == "sigmoid":
            # the correction bias of aux-loss-free balancing: trained by
            # the balancer, not the loss, from zero
            layer["moe"]["router_bias"] = jnp.zeros((cfg.num_experts,), pd)
        if sf:
            layer["moe"]["shared"] = {
                "w1": init(ek[4], (d, sf), pd),
                "w3": init(ek[5], (d, sf), pd),
                "w2": init(ek[6], (sf, d), pd),
            }
            if cfg.shared_expert_gate:
                layer["moe"]["shared"]["gate"] = init(ek[7], (d,), pd)
    else:
        layer["mlp"] = {
            "w1": init(ks[5], (d, f), pd),
            "w3": init(ks[6], (d, f), pd),
            "w2": init(ks[7], (f, d), pd),
        }
    return layer


def transformer_init(key, cfg: TransformerConfig) -> Dict[str, Any]:
    """``params["layers"]``: the layer tree stacked on a leading dim, or,
    under a layer pattern, a tuple of such stacks, one a position of the
    period, each stacked over the periods; the leading dense layers are a
    stack of their own before it, ``params["dense_layers"]``."""
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    init = jax.nn.initializers.normal(0.02)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    lead = cfg.first_dense_layers
    dense_layers = jax.vmap(lambda k: _layer_init(
        k, cfg, cfg.kinds[0], dense=True))(layer_keys[:lead]) \
        if lead else None
    layer_keys = layer_keys[lead:]
    if cfg.layer_types:
        period = len(cfg.layer_types)
        stacked = tuple(
            jax.vmap(lambda k, kind=kind: _layer_init(k, cfg, kind))(
                layer_keys[i::period])
            for i, kind in enumerate(cfg.layer_types))
    else:
        stacked = jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys)
    if cfg.pp_stages > 1:
        stacked = jax.tree.map(
            lambda a: a.reshape((cfg.pp_stages, cfg.layers_per_stage)
                                + a.shape[1:]), stacked)
    norm = jnp.zeros if cfg.norm_plus_one else jnp.ones
    params = {
        "embed": init(k_emb, (cfg.vocab_size, cfg.d_model), cfg.param_dtype),
        "layers": stacked,
        "final_norm": norm((cfg.d_model,), cfg.param_dtype),
    }
    if lead:
        params["dense_layers"] = dense_layers
    if not cfg.tied_embeddings:
        params["lm_head"] = init(k_head, (cfg.d_model, cfg.vocab_size),
                                 cfg.param_dtype)
    if cfg.loop_steps > 1:
        params["exit_gate"] = {
            "w": init(jax.random.fold_in(k_head, 1), (cfg.d_model,),
                      cfg.param_dtype),
            "b": jnp.zeros((), cfg.param_dtype)}
    if cfg.mtp_layers:
        k_proj, k_block = jax.random.split(jax.random.fold_in(k_head, 2))
        d, pd = cfg.d_model, cfg.param_dtype
        params["mtp"] = {
            "enorm": norm((d,), pd), "hnorm": norm((d,), pd),
            "eh_proj": init(k_proj, (2 * d, d), pd),      # [Emb ; h] -> d
            "block": _layer_init(k_block, cfg, cfg.kinds[-1]),
            "final_norm": norm((d,), pd)}
    return params


def fold_multipliers(params, cfg: TransformerConfig, *, embedding=1.0,
                     lm_head=1.0, attention_in=1.0, key=1.0,
                     attention_out=1.0, ssm_in=1.0, ssm=(1.0,) * 5,
                     ssm_out=1.0, mlp=(1.0, 1.0)) -> Dict[str, Any]:
    """A parameter tree as published with muP multipliers (Falcon-H1's:
    constants on the inputs and outputs of linear maps) -> the tree this
    program runs, each constant folded into the matrix it scales, so that
    the forward pass carries none: ``embedding`` into the embedding,
    ``lm_head`` into the untied head; ``attention_in`` into wq, wk, wv,
    ``key`` into wk besides, ``attention_out`` into wo; ``ssm_in`` and the
    five ``ssm`` constants of the packed projection's segments [z | x | B |
    C | dt] into its columns, ``ssm_out`` into the mixer's output
    projection; ``mlp[0]`` into the gate's matrix, ``mlp[1]`` into the
    down projection. Products in float32, rounded once to the parameter's
    dtype."""
    if cfg.tied_embeddings and (embedding != 1.0 or lm_head != 1.0):
        raise ValueError("a tied embedding holds one matrix for two "
                         "multipliers: untie it")
    hv, vd = cfg.linear_value_heads, \
        cfg.linear_value_heads * cfg.linear_value_dim
    gn = cfg.linear_key_heads * cfg.linear_key_dim
    columns = ssm_in * jnp.concatenate([
        jnp.full((w,), m, jnp.float32)
        for w, m in zip((vd, vd, gn, gn, hv), ssm)])

    def scaled(a, m):
        return (a.astype(jnp.float32) * m).astype(a.dtype)

    def layer(stack):
        stack = dict(stack)
        if "attn" in stack:
            a = stack["attn"]
            stack["attn"] = dict(
                a, wq=scaled(a["wq"], attention_in),
                wk=scaled(a["wk"], attention_in * key),
                wv=scaled(a["wv"], attention_in),
                wo=scaled(a["wo"], attention_out))
        if "ssm" in stack:
            m = stack["ssm"]
            stack["ssm"] = dict(m, in_proj=scaled(m["in_proj"], columns),
                                out=scaled(m["out"], ssm_out))
        if "mlp" in stack:
            f = stack["mlp"]
            stack["mlp"] = dict(f, w1=scaled(f["w1"], mlp[0]),
                                w2=scaled(f["w2"], mlp[1]))
        return stack

    layers = params["layers"]
    out = dict(params, embed=scaled(params["embed"], embedding),
               layers=tuple(layer(stack) for stack in layers)
               if cfg.layer_types else layer(layers))
    if "lm_head" in params:
        out["lm_head"] = scaled(params["lm_head"], lm_head)
    return out


def transformer_logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Pytree mirroring params: per-leaf logical dim names (see
    parallel/sharding.py DEFAULT_RULES)."""
    stage = ("stage", "layers") if cfg.pp_stages > 1 else ("layers",)

    def stacked(*axes):  # layer leaf: leading stacked dim(s)
        return stage + axes

    def layer_axes(kind: str, dense: bool = False,
                   L=stacked) -> Dict[str, Any]:
        layer = {name: L("embed") for name in _layer_norms(cfg)}
        if kind in ("full", "parallel"):
            layer["attn"] = {
                "wq": L("embed", "heads", "kv"),
                "wk": L("embed", "heads", "kv"),
                "wv": L("embed", "heads", "kv"),
                "wo": L("heads", "kv", "embed"),
            }
            if cfg.qk_norm or cfg.qk_norm_whole:
                layer["attn"]["q_norm"] = L(None)
                layer["attn"]["k_norm"] = L(None)
        elif kind in ("latent", "window"):
            from ray_tpu.models.latent import PARAMS_KEY, latent_axes
            layer[PARAMS_KEY[kind]] = latent_axes(cfg, kind, L)
        if kind in ("linear", "parallel"):
            # the packed projections keep their columns whole: q, k, v and
            # z blocks of unequal width do not split over tp
            if cfg.linear_transition == "ssd":
                layer["ssm"] = {
                    "in_proj": L("embed", None), "conv": L(None, None),
                    "conv_bias": L(None), "dt_bias": L(None),
                    "A_log": L(None), "D": L(None), "norm": L(None),
                    "out": L(None, "embed"),
                }
            else:
                layer["gdn"] = {
                    "in_qkvz": L("embed", None), "in_ba": L("embed", None),
                    "conv": L(None, None), "dt_bias": L(None),
                    "A_log": L(None), "norm": L(None),
                    "out": L(None, "embed"),
                }
        if cfg.num_experts and not dense:
            layer["moe"] = {
                "router": L("embed", None),
                "w1": L("expert", "embed", "expert_mlp"),
                "w3": L("expert", "embed", "expert_mlp"),
                "w2": L("expert", "expert_mlp", "embed"),
            }
            if cfg.router_scoring == "sigmoid":
                layer["moe"]["router_bias"] = L(None)
            if cfg.shared_expert_ff:
                layer["moe"]["shared"] = {
                    "w1": L("embed", "mlp"), "w3": L("embed", "mlp"),
                    "w2": L("mlp", "embed"),
                }
                if cfg.shared_expert_gate:
                    layer["moe"]["shared"]["gate"] = L("embed")
        else:
            layer["mlp"] = {
                "w1": L("embed", "mlp"),
                "w3": L("embed", "mlp"),
                "w2": L("mlp", "embed"),
            }
        return layer

    axes = {
        "embed": ("vocab", "embed"),
        "layers": tuple(layer_axes(kind) for kind in cfg.layer_types)
        if cfg.layer_types else layer_axes("full"),
        "final_norm": ("embed",),
    }
    if cfg.first_dense_layers:
        axes["dense_layers"] = layer_axes(cfg.kinds[0], dense=True)
    if not cfg.tied_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.loop_steps > 1:
        axes["exit_gate"] = {"w": ("embed",), "b": ()}
    if cfg.mtp_layers:
        axes["mtp"] = {
            "enorm": ("embed",), "hnorm": ("embed",),
            "eh_proj": (None, "embed"),
            "block": layer_axes(cfg.kinds[-1], L=lambda *axes: axes),
            "final_norm": ("embed",)}
    return axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _norm(cfg: TransformerConfig, x, w):
    """RMSNorm over the last dim with the configuration's scale: ``w``, or
    ``1 + w`` (``norm_plus_one``)."""
    return _rmsnorm(x, 1.0 + w if cfg.norm_plus_one else w,
                    cfg.norm_eps)


def _rope(x, positions, theta: float, rotary_dim: Optional[int] = None):
    """x: [B, S, H, D]; rotate pairs (d, d + R/2) of the first R =
    ``rotary_dim`` dims (all of them when None); the rest pass."""
    d = x.shape[-1]
    if rotary_dim == 0:             # partial_rotary_factor 0: no rotation
        return x
    if rotary_dim is not None and rotary_dim < d:
        return jnp.concatenate(
            [_rope(x[..., :rotary_dim], positions, theta),
             x[..., rotary_dim:]], -1)
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
    """-> (y, stats): ``stats`` is the expert layer's counters (None for
    the dense MLP)."""
    if "moe" in layer:
        from ray_tpu.models.moe import moe_apply
        return moe_apply(cfg, layer["moe"], h)
    dt = cfg.dtype
    m = layer["mlp"]
    gate = jax.nn.silu(h @ m["w1"].astype(dt))
    up = h @ m["w3"].astype(dt)
    return (gate * up) @ m["w2"].astype(dt), None


def _full_attention_mix(cfg: TransformerConfig, a, h, positions, attend):
    """Softmax attention over the block's (normed) input ``h``: projections,
    q/k norm (per head, or over the whole projection) and output gate where
    configured, RoPE, ``attend``, ``wo``."""
    dt = cfg.dtype
    q = jnp.einsum("bse,ehd->bshd", h, a["wq"].astype(dt))
    k = jnp.einsum("bse,ehd->bshd", h, a["wk"].astype(dt))
    v = jnp.einsum("bse,ehd->bshd", h, a["wv"].astype(dt))
    if cfg.attn_output_gate:
        q, gate = jnp.split(q, 2, axis=-1)
    if cfg.qk_norm:
        q, k = _norm(cfg, q, a["q_norm"]), _norm(cfg, k, a["k_norm"])
    if cfg.qk_norm_whole:
        q, k = (_norm(cfg, x.reshape(x.shape[:2] + (-1,)), w).reshape(x.shape)
                for x, w in ((q, a["q_norm"]), (k, a["k_norm"])))
    q = _rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
    k = _rope(k, positions, cfg.rope_theta, cfg.rotary_dim)
    o, kept = attend(q, k, v)
    if cfg.attn_output_gate:
        o = _output_gate(o, gate)
    return jnp.einsum("bshd,hde->bse", o, a["wo"].astype(dt)), kept


def _output_gate(o, gate):
    """o [B, S, H, D] times ``sigmoid(gate)``: element-wise where the gate
    has o's shape, head-wise where it is [B, S, H], one scalar a head."""
    if gate.ndim == o.ndim - 1:
        gate = gate[..., None]
    return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


def _gated_delta_mix(cfg: TransformerConfig, p, h, rule):
    """The gated delta rule over the block's (normed) input ``h`` [B, S,
    E]: packed projections, ``rule``, gated norm, output projection.
    ``rule(u, ba, p) -> (o [B, S, Hv, dv], kept)`` is all that differs
    between training, prefill and decode, as ``attend`` is for a softmax
    layer: the short causal convolution of ``u`` [B, S, 2 kd + vd] (the
    projection's [q | k | v] columns), ``_rule_operands`` and the rule over
    them: the chunked rule from a zero state (``_whole_rule``, and prefill,
    which keeps the final state and the last inputs), or one position on a
    carried state against a carried tail (models/generate.py)."""
    dt, f32 = cfg.dtype, jnp.float32
    b, s, _ = h.shape
    hv, dv = cfg.linear_value_heads, cfg.linear_value_dim
    conv = p["conv"].shape[0]               # 2 kd + vd
    with jax.named_scope("rt.gdn.proj"):
        qkvz = h @ p["in_qkvz"].astype(dt)
        ba = (h @ p["in_ba"].astype(dt)).astype(f32)
    o, kept = rule(qkvz[..., :conv], ba, p)
    z = qkvz[..., conv:].reshape(b, s, hv, dv)
    with jax.named_scope("rt.gdn.proj"):
        o = _rmsnorm(o, p["norm"]) * jax.nn.silu(z.astype(f32)).astype(dt)
        return o.reshape(b, s, hv * dv) @ p["out"].astype(dt), kept


def _rule_operands(cfg: TransformerConfig, p, qkv, ba):
    """The convolved [q | k | v] [B, S, 2 kd + vd] and the float32 [b | a]
    [B, S, 2 Hv] -> the rule's (q, k [B, S, Hk, dk], v [B, S, Hv, dv], g,
    beta [B, S, Hv] float32). q, k go to unit length inside the rule; a key
    head serves Hv / Hk value heads."""
    f32 = jnp.float32
    b, s, _ = qkv.shape
    hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    kd = hk * dk
    q = qkv[..., :kd].reshape(b, s, hk, dk)
    k = qkv[..., kd:2 * kd].reshape(b, s, hk, dk)
    v = qkv[..., 2 * kd:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    if cfg.linear_beta_scale != 1.0:
        beta = cfg.linear_beta_scale * beta
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"].astype(f32))
    return q, k, v, g, beta


def _whole_rule(cfg: TransformerConfig, mesh=None,
                rules: LogicalRules = DEFAULT_RULES):
    """``_gated_delta_mix``'s ``rule`` over the sequence's own positions
    from a zero state, nothing kept: the training forward's.
    ``mesh``/``rules``: what the layer's input is laid out by (the rule's
    kernels run per shard)."""
    def rule(u, ba, p):
        qkv = gated_delta.causal_conv_over(mesh, rules, u, p["conv"])
        with jax.named_scope("rt.gdn.scan"):
            operands = _rule_operands(cfg, p, qkv, ba)
        return gated_delta.gated_delta_rule_over(mesh, rules,
                                                 *operands), None
    return rule


def _state_space_mix(cfg: TransformerConfig, p, h, rule):
    """Mamba-2's mixer over the block's (normed) input ``h`` [B, S, E]: the
    packed projection [z | x | B | C | dt], ``rule``, the gate, a norm over
    each group's share of the gated values, the output projection.
    ``rule(u, dt, p) -> (y [B, S, H, P], kept)`` is all that differs
    between training, prefill and decode, as for ``_gated_delta_mix``: the
    convolution (with its bias) of ``u`` [B, S, H P + 2 G N], the
    projection's [x | B | C] columns, ``_state_space_operands`` and the
    recurrence over them (ops/ssd.py), skip included."""
    dt, f32 = cfg.dtype, jnp.float32
    b, s, _ = h.shape
    hv, grp = cfg.linear_value_heads, cfg.linear_key_heads
    vd = hv * cfg.linear_value_dim
    conv = p["conv"].shape[0]               # H P + 2 G N
    with jax.named_scope("rt.ssd.proj"):
        zxbcdt = h @ p["in_proj"].astype(dt)
    y, kept = rule(zxbcdt[..., vd:vd + conv],
                   zxbcdt[..., vd + conv:].astype(f32), p)
    with jax.named_scope("rt.ssd.proj"):
        y = y.reshape(b, s, vd) * jax.nn.silu(
            zxbcdt[..., :vd].astype(f32)).astype(dt)
        y = _rmsnorm(y.reshape(b, s, grp, vd // grp),
                     p["norm"].reshape(grp, vd // grp), cfg.norm_eps)
        return y.reshape(b, s, vd) @ p["out"].astype(dt), kept


def _state_space_operands(cfg: TransformerConfig, p, xbc, dt):
    """The convolved [x | B | C] [B, S, H P + 2 G N] and the float32 step
    size's pre-activation [B, S, H] -> ``ssd.ssd_scan``'s operands (x [B, S,
    H, P], B, C [B, S, G, N], g, dt [B, S, H] float32, D [H])."""
    f32 = jnp.float32
    b, s, _ = xbc.shape
    hv, grp = cfg.linear_value_heads, cfg.linear_key_heads
    n, vd = cfg.linear_key_dim, hv * cfg.linear_value_dim
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
    return (xbc[..., :vd].reshape(b, s, hv, cfg.linear_value_dim),
            xbc[..., vd:vd + grp * n].reshape(b, s, grp, n),
            xbc[..., vd + grp * n:].reshape(b, s, grp, n),
            -jnp.exp(p["A_log"].astype(f32)) * dt, dt, p["D"])


def _whole_scan(cfg: TransformerConfig, mesh=None,
                rules: LogicalRules = DEFAULT_RULES):
    """``_state_space_mix``'s ``rule`` over the sequence's own positions
    from a zero state, nothing kept: the training forward's.
    ``mesh``/``rules``: as ``_whole_rule``'s (the convolution's kernels run
    per shard)."""
    def rule(u, dt, p):
        xbc = gated_delta.causal_conv_over(mesh, rules, u, p["conv"],
                                           p["conv_bias"],
                                           scope="rt.ssd.conv")
        with jax.named_scope("rt.ssd.scan"):
            return ssd.ssd_scan(
                *_state_space_operands(cfg, p, xbc, dt)), None
    return rule


def _layer_apply(cfg: TransformerConfig, layer, x, positions, attend):
    """One block: ``x + mixer(norm(x))``, then ``+ feed_forward(norm(.))``.
    The mixer is the layer's own: softmax attention where it holds
    ``attn``, the gated delta rule where it holds ``gdn``, the state-space
    mixer where it holds ``ssm``, latent attention where it holds ``mla`` or
    ``swa`` (models/latent.py); a layer that holds ``attn`` AND a linear
    mixer (kind ``"parallel"``) feeds both the one normed input and adds
    both to the residual, and its ``attend`` is a pair: the softmax part,
    and ``rule_after(kept) -> rule``, the linear mixer's part once the
    softmax part has kept what it keeps (the cache it wrote). Where the layer
    holds ``ln1_post`` and ``ln2_post`` each sublayer's output is normed
    before it joins the residual (sandwich norm), and where it holds no
    ``ln1`` and ``ln2`` its input goes in as it is (``post_norm_only``).
    ``attend`` is all that differs between training, prefill and decode
    (models/generate.py): ``attend(q, k, v) -> (o, kept)`` is what softmax
    attention does with the rotated k and v, and what it keeps of them;
    ``attend(new) -> (keys, key positions, kept)`` is what a latent layer's
    new cached entries join (key positions None: the sequence's own, index
    i at position i), and what is kept of them; ``attend(u, ba, p) -> (o,
    kept)`` is the gated delta rule's convolution and recurrence
    (``_gated_delta_mix``'s ``rule``). -> (x, kept, stats: the expert
    layer's counters and a latent layer's selection, or None)."""
    h = _norm(cfg, x, layer["ln1"]) if "ln1" in layer else x
    taps, o = {}, 0
    linear = next((name for name in ("gdn", "ssm") if name in layer), None)
    if "attn" in layer:
        if linear:
            attend, rule_after = attend
        # the gated variant's device time is found by this scope
        with jax.named_scope("rt.attn.gated") if cfg.attn_output_gate \
                else contextlib.nullcontext():
            o, kept = _full_attention_mix(cfg, layer["attn"], h, positions,
                                          attend)
        if linear:
            attend = rule_after(kept)
    if linear:
        mix = _gated_delta_mix if linear == "gdn" else _state_space_mix
        m, kept = mix(cfg, layer[linear], h, attend)
        o = o + m
    elif "attn" not in layer:
        from ray_tpu.models.latent import latent_mix
        o, kept, taps = latent_mix(cfg, layer, h, positions, attend)
    if "ln1_post" in layer:
        o = _norm(cfg, o, layer["ln1_post"])
    x = x + o
    y, stats = _feed_forward(
        cfg, layer, _norm(cfg, x, layer["ln2"]) if "ln2" in layer else x)
    if "ln2_post" in layer:
        y = _norm(cfg, y, layer["ln2_post"])
    if taps:
        stats = {**(stats or {}), **taps}
    return x + y, kept, stats


def _layer_bodies(cfg: TransformerConfig, mesh, rules: LogicalRules):
    """{kind: ``body(layer, x, positions) -> (x, kept, stats)``}: the
    training forward's ``_layer_apply`` of each kind of the period, over
    the sequence's own keys, remat'd where the configuration says."""
    def softmax(q, k, v):
        return _attention(cfg, q, k, v, mesh, rules), None

    def whole(new):             # a latent layer's keys: the sequence's own
        return new, None, None

    rule = (_whole_scan if cfg.linear_transition == "ssd"
            else _whole_rule)(cfg, mesh, rules)
    attends = {"full": softmax, "latent": whole, "window": whole,
               "linear": rule, "parallel": (softmax, lambda kept: rule)}

    def body_of(kind: str):
        body = partial(_layer_apply, cfg, attend=attends[kind])
        if cfg.remat:
            # what the delta rule's and flash's forward kernels name for
            # their backward kernels outlives the forward pass (flash names
            # its own only from a size on: ops/flash.py KEEP_FROM); a layer
            # that holds nothing under either name is remat'd whole as by a
            # bare jax.checkpoint
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.save_only_these_names(
                    gated_delta.KEPT, flash.KEPT))
        return body

    return {kind: body_of(kind) for kind in cfg.kinds}


def _stage_scan(cfg: TransformerConfig, mesh, stage_layers, x, positions,
                rules: LogicalRules = DEFAULT_RULES, dense_layers=None):
    """Apply a stack of layers (leading dim = layers, or under a layer
    pattern a tuple of stacks with leading dim = periods, after the stack
    of leading ``dense_layers``) with lax.scan. ``rules``: what the caller
    sharded params and batch by over ``mesh``.
    -> (x, the expert layers' stats stacked over the scan, or None)."""
    kinds = cfg.kinds
    bodies = _layer_bodies(cfg, mesh, rules)
    if dense_layers is not None:
        x, _ = lax.scan(
            lambda carry, layer: (bodies[kinds[0]](layer, carry,
                                                   positions)[0], None),
            x, dense_layers)

    def step(carry, period):
        stats = []
        for kind, layer in zip(kinds, period):
            carry, _, layer_stats = bodies[kind](layer, carry, positions)
            stats.append(layer_stats)
        return carry, stats if cfg.num_experts else None

    return lax.scan(step, x, stage_layers if cfg.layer_types
                    else (stage_layers,))


def _stage_apply(cfg: TransformerConfig, mesh, stage_layers, x, positions,
                 rules: LogicalRules = DEFAULT_RULES):
    return _stage_scan(cfg, mesh, stage_layers, x, positions, rules)[0]


def exit_distribution(gates):
    """gates [T, ...]: each pass's exit probability given that the position
    is still in the loop -> [..., T], the probability of leaving after
    pass t: ``gate_t * prod_{j<t}(1 - gate_j)``, and for the last pass what
    is left, so that the T of them sum to 1."""
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)
    stay = jnp.concatenate([jnp.ones_like(gates[:1]), stay])
    p = jnp.concatenate([gates[:-1] * stay[:-1], stay[-1:]])
    return jnp.moveaxis(p, 0, -1)


def _over_loop_steps(cfg: TransformerConfig, params, stack, x, carry=None):
    """``stack(x, carry, t) -> (x, carry)`` is one pass over the layers;
    ``t`` counts the passes. Runs it ``cfg.loop_steps`` times over the one
    set of weights. A looped stack's state is normed by the final norm at
    the end of EVERY pass (the normed state goes on to the next, and
    ``_head`` does not norm it again) and read by the exit gate.
    -> (x, carry, exit distribution [..., loop_steps] float32, or None
    where there is no loop)."""
    if cfg.loop_steps == 1:
        return *stack(x, carry, 0), None

    def step(state, t):
        x, carry = stack(*state, t)
        x = _norm(cfg, x, params["final_norm"])
        gate = params["exit_gate"]
        z = x.astype(jnp.float32) @ gate["w"].astype(jnp.float32)
        return (x, carry), jax.nn.sigmoid(z + gate["b"].astype(jnp.float32))

    (x, carry), gates = lax.scan(step, (x, carry),
                                 jnp.arange(cfg.loop_steps))
    return x, carry, exit_distribution(gates)


def _head(params, x, cfg: TransformerConfig):
    """Final norm + (tied or untied) head: x [..., E] -> float32 logits.
    A looped stack hands over its state normed (``_over_loop_steps``)."""
    if cfg.loop_steps == 1:
        x = _norm(cfg, x, params["final_norm"])
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


def _hidden_and_stats(params, tokens, cfg: TransformerConfig, mesh,
                      positions, rules: LogicalRules):
    """-> (the stack's output [B, S, E] as ``_head`` takes it, expert-layer
    stats or None, exit distribution or None)."""
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
        x, stats, exits = x.reshape(b, s, cfg.d_model), None, None
    else:
        x, stats, exits = _over_loop_steps(
            cfg, params, lambda x, _, t: _stage_scan(
                cfg, mesh, params["layers"], x, positions, rules,
                params.get("dense_layers")), x)
    return x, stats, exits


def _logits_and_stats(params, tokens, cfg: TransformerConfig, mesh,
                      positions, rules: LogicalRules):
    """-> (logits, expert-layer stats or None, exit distribution or
    None)."""
    x, stats, exits = _hidden_and_stats(params, tokens, cfg, mesh, positions,
                                        rules)
    return _head(params, x, cfg), stats, exits


def _mtp_loss(params, h, tokens, cfg: TransformerConfig, mesh,
              rules: LogicalRules, mask=None):
    """The multi-token-prediction module's loss. ``h`` [B, S, E]: the main
    stack's output before the final norm. For i = 0 .. S-2: ``u_i = W_eh
    [norm_e(Emb(t_{i+1})) ; norm_h(h_i)]``, one block over u at positions
    i, the module's own final norm, the model's own head: logits of
    ``t_{i+2}``. ``Emb`` and the head are the main model's arrays, so each
    gets the sum of both losses' gradients. -> (mean cross-entropy over i =
    0 .. S-3, the block's expert-layer stats or None). The logits are made
    again in the backward pass and not kept beside the main head's. Scopes
    ``rt.mtp.combine`` (the two norms and ``W_eh``) inside ``rt.mtp`` (the
    whole module)."""
    m, dt = params["mtp"], cfg.dtype
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s - 1), (b, s - 1))
    with jax.named_scope("rt.mtp"):
        with jax.named_scope("rt.mtp.combine"):
            e = _norm(cfg, params["embed"].astype(dt)[tokens[:, 1:]],
                      m["enorm"])
            u = jnp.concatenate([e, _norm(cfg, h[:, :-1], m["hnorm"])], -1) \
                @ m["eh_proj"].astype(dt)
        body = _layer_bodies(cfg, mesh, rules)[cfg.kinds[-1]]
        v, _, stats = body(m["block"], u, positions)

        @jax.checkpoint
        def head_loss(head_params, v):
            return _next_token_loss(
                _head(head_params, v, cfg), tokens[:, 1:],
                None if mask is None else mask[:, 1:])

        head_params = {k: params[k] for k in ("embed", "lm_head")
                       if k in params}
        head_params["final_norm"] = m["final_norm"]
        return head_loss(head_params, v), stats


def _refuse_looped_loss(cfg: TransformerConfig) -> None:
    if cfg.loop_steps > 1:
        raise NotImplementedError(
            "no loss over a looped stack: the published objective weights "
            "each loop step's next-token loss by the exit gate's "
            "distribution and adds an entropy term whose weight no key of "
            "the configuration gives, and each step's loss needs the head "
            "applied to that step's state; the last step's loss alone "
            "would train no gate")


def transformer_apply(params, tokens, cfg: TransformerConfig, *,
                      mesh=None, positions=None,
                      rules: LogicalRules = DEFAULT_RULES):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (compute in cfg.dtype,
    logits float32); of a looped stack, the last pass's."""
    return _logits_and_stats(params, tokens, cfg, mesh, positions, rules)[0]


def transformer_apply_and_exits(params, tokens, cfg: TransformerConfig, *,
                                mesh=None, positions=None,
                                rules: LogicalRules = DEFAULT_RULES):
    """-> (logits as ``transformer_apply``, the exit distribution [B, S,
    loop_steps] float32: for each position the probability of leaving the
    loop after each pass, summing to 1; None where there is no loop)."""
    logits, _, exits = _logits_and_stats(params, tokens, cfg, mesh,
                                         positions, rules)
    return logits, exits


def transformer_loss_and_stats(params, batch, cfg: TransformerConfig, *,
                               mesh=None,
                               rules: LogicalRules = DEFAULT_RULES):
    """batch: {"tokens": [B, S]} -> (the loss; the step's counters, ``{}``
    for a dense model without a module). The loss is the next-token
    cross-entropy, mean over non-final positions, and with a
    multi-token-prediction module ``loss_main + mtp_loss_weight x
    loss_mtp``, both among the counters. The expert layers' (the module's
    among them), as scalars: ``moe_rows_here``, ``moe_rows_dropped`` and
    ``moe_rows_walked`` (the buffers' rows that the layers passed over
    outside their grouped matmuls: the blocks that held a routed row)
    summed over the layers, ``moe_load_max`` and ``moe_load_mean`` the
    fullest held expert's rows and the mean, over layers and experts; of a
    router with a correction bias also ``moe_count_max_over_mean`` (the
    fullest published expert's pairs over the mean, the worst layer's) and,
    not a scalar, ``moe_counts``: each router's pairs of every published
    expert, [periods, E] a stack and [E] the module's, in a tree that holds
    them where ``params`` holds the ``router_bias`` they move
    (train/jax_step.py)."""
    _refuse_looped_loss(cfg)
    tokens, mask = batch["tokens"], batch.get("mask")
    x, stats, _ = _hidden_and_stats(params, tokens, cfg, mesh, None, rules)
    loss = _next_token_loss(_head(params, x, cfg), tokens, mask)
    out, module = {}, None
    if cfg.mtp_layers:
        loss_mtp, module = _mtp_loss(params, x, tokens, cfg, mesh, rules,
                                     mask)
        out.update(loss_main=loss, loss_mtp=loss_mtp)
        loss = loss + cfg.mtp_loss_weight * loss_mtp
    if stats is None:
        return loss, out
    # the module's block is one more layer: a stack of one
    stacks = list(stats) + ([jax.tree.map(lambda a: a[None], module)]
                            if module else [])
    load = jnp.concatenate([s["load"] for s in stacks])     # [layers, held]
    out.update(
        moe_rows_here=sum(s["rows_here"].sum() for s in stacks),
        moe_rows_dropped=sum(s["rows_dropped"].sum() for s in stacks),
        moe_rows_walked=sum(s["rows_walked"].sum() for s in stacks),
        moe_load_max=load.max(), moe_load_mean=load.mean())
    if "counts" in stacks[0]:
        counts = jnp.concatenate([s["counts"] for s in stacks])
        out["moe_count_max_over_mean"] = (
            counts.max(-1) / counts.astype(jnp.float32).mean(-1)).max()
        at_bias = lambda s: {"moe": {"router_bias": s["counts"]}}
        layers = [at_bias(s) for s in stats]
        out["moe_counts"] = {"layers": tuple(layers) if cfg.layer_types
                             else layers[0]}
        if module:
            out["moe_counts"]["mtp"] = {"block": at_bias(module)}
    return loss, out


def transformer_loss(params, batch, cfg: TransformerConfig, *, mesh=None,
                     rules: LogicalRules = DEFAULT_RULES):
    """The loss alone (see ``transformer_loss_and_stats``)."""
    return transformer_loss_and_stats(params, batch, cfg, mesh=mesh,
                                      rules=rules)[0]


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
    """The parameters this program holds (under a share, its own experts
    and its own slice of the vocabulary)."""
    shapes = jax.eval_shape(lambda: transformer_init(jax.random.PRNGKey(0),
                                                     cfg))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

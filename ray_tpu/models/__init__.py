"""Model zoo, TPU-first.

Flagship: decoder-only Transformer LM, one block whose layers are, by
configuration: softmax attention (RMSNorm / RoPE, whole or partial / GQA;
optionally per-head q/k norm, an output gate, any head width), the gated
delta rule (linear attention with a short causal convolution) or a
state-space recurrence (Mamba-2's SSD), alone or beside softmax attention
on one normed input, over a SwiGLU
MLP or a top-k expert layer with no capacity per expert that holds a share
of the experts, and a shared expert; the stack run once, or ``loop_steps``
times over the one set of weights with sandwich norms and an exit gate; or
latent attention (models/latent.py: keys and values as one low-rank latent
a position, in full layers with a learned sparse indexer and in window
layers, a head-wise output gate) after leading dense layers, over expert
layers routed by sigmoid with a correction bias; a block's norms on its
sublayers' inputs, on inputs and outputs, or on the outputs only.
``generate`` serves, from one cache by layer kind, the softmax stacks (keys
and values), the latent stacks (latents, indexer keys, window rings; the
prompt in chunks) and patterns of gated-delta-rule and softmax layers (a
float32 recurrent state and the convolution's last inputs beside the keys
and values); a layer may hold two mixers side by side, softmax attention
and a linear one (the rule, or Mamba-2's state-space recurrence,
ops/ssd.py), and then has a slot of both caches.
Pure-functional params pytree with logical-axis
annotations so one definition runs under any MeshSpec (dp/fsdp/tp/pp/sp/ep).
Plus ResNet-50 (the north-star image benchmark, BASELINE.json) and an MLP.

Role parity: the reference's model code lives in RLlib's catalog (reference
rllib/models/catalog.py:197) and in user-provided torch modules for
ray.train; here models are jax pytrees + pure apply fns, jit/pjit-ready.
"""

from ray_tpu.models.transformer import (
    LatentDims,
    TransformerConfig,
    transformer_init,
    transformer_apply,
    transformer_apply_and_exits,
    transformer_loss,
    transformer_loss_and_stats,
    transformer_logical_axes,
)
from ray_tpu.models.generate import (decode_step, generate,
                                     generate_and_cache,
                                     generate_with_stats, init_cache,
                                     prefill)
from ray_tpu.models.resnet import resnet50_init, resnet50_apply, resnet_loss
from ray_tpu.models.mlp import mlp_init, mlp_apply
from ray_tpu.models.vit import ViTConfig, vit_init, vit_apply, vit_loss

__all__ = [
    "LatentDims", "TransformerConfig", "transformer_init",
    "transformer_apply",
    "transformer_apply_and_exits", "transformer_loss", "transformer_loss_and_stats",
    "transformer_logical_axes",
    "generate", "generate_and_cache", "generate_with_stats", "prefill",
    "decode_step",
    "init_cache",
    "resnet50_init", "resnet50_apply", "resnet_loss",
    "mlp_init", "mlp_apply",
    "ViTConfig", "vit_init", "vit_apply", "vit_loss",
]

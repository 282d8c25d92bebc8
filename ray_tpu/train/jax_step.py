"""Compiled train-step factories: model + mesh + optax -> sharded pjit step.

The GSPMD recipe (scaling-book): place params with explicit NamedShardings
(logical axes -> mesh axes), let jit propagate shardings through optimizer
state and activations, and let XLA insert the DP psum / FSDP
all-gather+reduce-scatter / TP collectives. This replaces the reference's
entire process-group + DDP/FSDP-wrapper surface (reference
python/ray/train/torch/config.py:113, train_loop_utils.py:23-96) with
compilation.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.moe import balance_bias
from ray_tpu.models.resnet import resnet50, resnet_loss
from ray_tpu.models.transformer import (TransformerConfig,
                                        _refuse_looped_loss,
                                        transformer_init,
                                        transformer_logical_axes,
                                        transformer_loss_and_stats)
from ray_tpu.parallel.sharding import (DEFAULT_RULES, LogicalRules,
                                       batch_sharding, pytree_shardings,
                                       replicated, shard_pytree)
from ray_tpu.util import events


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any  # scalar int32 array


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt_state, s.step), None),
    lambda _, c: TrainState(*c),
)


def step_span(step: int) -> events.span:
    """The flight-recorder span a training loop opens around one step, from
    its dispatch to its metrics on the host. ``sp.set(**counters)`` puts
    the step's own counters (the ``moe_*`` keys of its metrics) on it once
    they are fetched, so they cost no transfer of their own."""
    return events.span("train.step", step=step)


def _init_opt_state(tx: optax.GradientTransformation, params, mesh: Mesh):
    """tx.init under jit, every moment placed like its parameter and the
    rest replicated (ZeRO under fsdp). Left to itself jit puts these zeros
    — they depend on no input — on the default device: the whole optimizer
    state sat on chip 0 until the first step moved it."""
    shardings = optax.tree_map_params(
        tx, lambda _, p: p.sharding, jax.eval_shape(tx.init, params), params,
        transform_non_params=lambda _: replicated(mesh))
    return jax.jit(tx.init, out_shardings=shardings)(params)


def _balance_routers(updates, counts, cfg: TransformerConfig):
    """``updates`` with every router's correction bias moved by its
    layer's load (models/moe.py ``balance_bias``) in place of whatever the
    optimizer gave it: no gradient reaches the bias, and neither AdamW nor
    its weight decay is to move it. ``counts``: the step's ``moe_counts``,
    a tree that holds each router's counts where ``updates`` holds its
    ``router_bias``."""
    moved = dict(jax.tree_util.tree_leaves_with_path(
        jax.tree.map(partial(balance_bias, cfg), counts)))
    return jax.tree_util.tree_map_with_path(
        lambda path, u: moved[path].astype(u.dtype) if path in moved else u,
        updates)


def make_lm_train_step(cfg: TransformerConfig, mesh: Mesh,
                       tx: Optional[optax.GradientTransformation] = None,
                       rules: LogicalRules = DEFAULT_RULES,
                       learning_rate: float = 3e-4):
    """Returns (init_fn(key) -> TrainState on-mesh,
               step_fn(state, batch) -> (state, metrics) jitted,
               place_batch)."""
    _refuse_looped_loss(cfg)      # here, not at the first step's trace
    if tx is None:
        tx = optax.adamw(learning_rate, weight_decay=0.01)
    axes = transformer_logical_axes(cfg)

    def init_fn(key) -> TrainState:
        # Initialised under jit straight into its shardings: every device
        # makes only its own shard, instead of the whole f32 tree landing
        # on the default device before being spread out.
        init = partial(transformer_init, cfg=cfg)
        params = jax.jit(init, out_shardings=pytree_shardings(
            jax.eval_shape(init, key), mesh, axes, rules))(key)
        return TrainState(params, _init_opt_state(tx, params, mesh),
                          jax.device_put(jnp.zeros((), jnp.int32),
                                         replicated(mesh)))

    def loss_fn(params, batch):
        return transformer_loss_and_stats(params, batch, cfg, mesh=mesh,
                                          rules=rules)

    @partial(jax.jit, donate_argnums=(0,))
    def step_fn(state: TrainState, batch) -> Tuple[TrainState, dict]:
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        # a sigmoid router's bias is the balancer's, by the step's counts
        counts = stats.pop("moe_counts", None)
        if counts is not None:
            updates = _balance_routers(updates, counts, cfg)
        params = optax.apply_updates(state.params, updates)
        if counts is not None:
            at = {path for path, _ in
                  jax.tree_util.tree_leaves_with_path(counts)}
            stats["router_bias_abs_mean"] = jnp.abs(jnp.concatenate([
                b.reshape(-1) for path, b in
                jax.tree_util.tree_leaves_with_path(params)
                if path in at])).mean()
        gnorm = optax.global_norm(grads)
        # ``stats``: the expert layers' counters (``moe_*``) and a
        # multi-token-prediction module's two losses, none for a dense
        # model without one
        return (TrainState(params, opt_state, state.step + 1),
                {"loss": loss, "grad_norm": gnorm, "step": state.step + 1,
                 **stats})

    def place_batch(batch):
        return jax.tree.map(
            lambda x: jax.device_put(x, batch_sharding(mesh, x.ndim, rules)),
            batch)

    return init_fn, step_fn, place_batch


def make_resnet_train_step(mesh: Mesh, *, num_classes: int = 1000,
                           image_size: int = 224,
                           tx: Optional[optax.GradientTransformation] = None,
                           learning_rate: float = 0.1,
                           rules: LogicalRules = DEFAULT_RULES):
    """ResNet-50 data-parallel train step: params replicated, batch sharded
    over (dp, fsdp); XLA inserts the gradient psum (DDP-equivalent)."""
    if tx is None:
        tx = optax.sgd(learning_rate, momentum=0.9, nesterov=True)
    model = resnet50(num_classes)

    def init_fn(key) -> TrainState:
        variables = model.init(
            key, jnp.zeros((1, image_size, image_size, 3), jnp.float32),
            train=True)
        variables = jax.device_put(variables, replicated(mesh))
        opt_state = _init_opt_state(tx, variables["params"], mesh)
        return TrainState(variables, opt_state,
                          jax.device_put(jnp.zeros((), jnp.int32),
                                         replicated(mesh)))

    def loss_fn(params, batch_stats, images, labels):
        logits, new_stats = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        return resnet_loss(logits, labels), (logits, new_stats["batch_stats"])

    @partial(jax.jit, donate_argnums=(0,))
    def step_fn(state: TrainState, batch) -> Tuple[TrainState, dict]:
        variables = state.params
        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(variables["params"],
                                   variables["batch_stats"],
                                   batch["image"], batch["label"])
        updates, opt_state = tx.update(grads, state.opt_state,
                                       variables["params"])
        new_params = optax.apply_updates(variables["params"], updates)
        acc = (logits.argmax(-1) == batch["label"]).mean()
        new_vars = {"params": new_params, "batch_stats": new_stats}
        return (TrainState(new_vars, opt_state, state.step + 1),
                {"loss": loss, "accuracy": acc})

    def place_batch(batch):
        return jax.tree.map(
            lambda x: jax.device_put(x, batch_sharding(mesh, x.ndim, rules)),
            batch)

    return init_fn, step_fn, place_batch


def make_vit_train_step(cfg, mesh: Mesh, *,
                        tx: Optional[optax.GradientTransformation] = None,
                        learning_rate: float = 3e-4,
                        rules: LogicalRules = DEFAULT_RULES):
    """ViT train step on the shared transformer substrate: encoder layers
    shard by the SAME logical-axis rules as the LM (fsdp/tp apply), batch
    over the data axes; gradient psum inserted by XLA."""
    from ray_tpu.models.vit import vit_init, vit_loss

    if tx is None:
        tx = optax.adamw(learning_rate, weight_decay=0.05)
    enc = cfg.encoder_config()

    def init_fn(key) -> TrainState:
        params = vit_init(key, cfg)
        layer_axes = transformer_logical_axes(enc)["layers"]
        axes = {
            "patch_proj": (None, "embed"),
            "cls": (None, None, "embed"),
            "pos": (None, None, "embed"),
            "layers": layer_axes,
            "ln_f": (None,),
            "head": ("embed", None),
        }
        params = shard_pytree(params, mesh, axes, rules)
        return TrainState(params, _init_opt_state(tx, params, mesh),
                          jax.device_put(jnp.zeros((), jnp.int32),
                                         replicated(mesh)))

    def loss_fn(params, batch):
        return vit_loss(params, batch, cfg, mesh=mesh)

    @partial(jax.jit, donate_argnums=(0,))
    def step_fn(state: TrainState, batch) -> Tuple[TrainState, dict]:
        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (TrainState(params, opt_state, state.step + 1),
                {"loss": loss, "accuracy": acc})

    def place_batch(batch):
        return jax.tree.map(
            lambda x: jax.device_put(x, batch_sharding(mesh, x.ndim, rules)),
            batch)

    return init_fn, step_fn, place_batch

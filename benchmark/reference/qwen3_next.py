"""Plain reference for the ``qwen3_next`` family (Qwen3-Next-80B-A3B):
forward and next-token loss in straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no chunked form of
the recurrence, no sorting of routed rows, and nothing imported from
``ray_tpu``. ``jax.checkpoint`` around a block of positions, a block of
queries and an expert changes no value: it lets ``loss_and_grads`` run at
the cell's own size beside the system's parameters. It follows Hugging Face ``modeling_qwen3_next.py``
(n = RMSNorm in float32 with scale ``1 + w``, eps ``rms_norm_eps``):

    layer i:  x = x + mixer_i(n(x));  x = x + moe(n(x))
    mixer_i is gated attention where (i + 1) % full_attention_interval == 0,
    else the gated delta rule.

- Gated attention: ``wq`` gives, per head, ``head_dim`` query and ``head_dim``
  gate values; ``q = n(query)``, ``k = n(wk x)`` per head; RoPE
  (``rotate_half``) on the first ``partial_rotary_factor * head_dim`` dims;
  causal softmax attention scaled by ``head_dim ** -0.5``, a KV head shared
  by ``H / KVH`` query heads; ``wo (attn * sigmoid(gate))``. Scores are made
  a block of queries at a time so that S = 8192 fits beside the system.
- Gated delta rule: q, k (``linear_num_key_heads`` x ``linear_key_head_dim``),
  v, z (``linear_num_value_heads`` x ``linear_value_head_dim``) from
  ``in_qkvz``, b, a from ``in_ba``; [q, k, v] through a causal depthwise
  convolution of width ``linear_conv_kernel_dim`` (no bias) and SiLU; q, k
  L2-normalised (eps 1e-6), each repeated to its value heads, q scaled by
  ``dk ** -0.5``; ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a +
  dt_bias)``; then the recurrence AS WRITTEN, one position after another:
  ``S = exp(g_t) S; S = S + k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t``;
  ``out (RMSNorm_w(o) * silu(z))``, that norm per head with plain scale w.
- Expert layer: ``p = softmax(router x)`` over all published experts, the
  ``num_experts_per_tok`` largest, divided by their sum (``norm_topk_prob``);
  the sum over the experts HELD HERE (``num_experts`` of them from
  ``first_expert`` on: the chip's share, ``benchmark/configs``) of ``p_e W2_e
  (silu(W1_e x) * W3_e x)``, computed densely for every token and masked,
  plus ``sigmoid(w_g . x) * shared(x)``. What the absent experts would add is
  left out; with every expert held this is the published layer.
- ``logits = lm_head n(x_L)``; loss = mean next-token cross-entropy.

Departures from the published model, each also under the configuration
file's ``assumed``:
- ``in_proj_qkvz`` is laid out flat, [all q | all k | all v | all z], and
  ``in_proj_ba`` as [all b | all a]; the checkpoint interleaves them per key
  head. The same mathematics on a permutation of the columns.
- the multi-token-prediction module is left out (no key of the published
  ``config.json`` describes it);
- the router's auxiliary loss is left out (``output_router_logits`` is false
  in the published defaults).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 1024
RECURRENCE_BLOCK = 64


class Weights(NamedTuple):
    """``layer(i)`` -> dict. Every layer: ``ln1``, ``ln2`` [d]; ``router``
    [d, E]; ``w1``, ``w3`` [held, d, f], ``w2`` [held, f, d]; ``shared_w1``,
    ``shared_w3`` [d, fs], ``shared_w2`` [fs, d], ``shared_gate`` [d].
    Full attention: ``wq`` [d, H*2*hd] (per head query then gate), ``wk``,
    ``wv`` [d, KVH*hd], ``wo`` [H*hd, d], ``q_norm``, ``k_norm`` [hd].
    Gated delta rule: ``in_qkvz`` [d, 2*Hk*dk + 2*Hv*dv], ``in_ba`` [d,
    2*Hv], ``conv`` [2*Hk*dk + Hv*dv, K], ``dt_bias``, ``A_log`` [Hv],
    ``norm`` [dv], ``out`` [Hv*dv, d]."""
    embed: jax.Array
    layer: Callable[[int], dict]
    n_layers: int
    final_norm: jax.Array
    lm_head: jax.Array


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _partial_rope(x, theta, factor):
    """x [B, S, H, hd]: rotate_half over the first ``factor * hd`` dims."""
    rot = int(x.shape[-1] * factor)
    half = rot // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


@partial(jax.checkpoint, static_argnums=(3, 4))
def _attend_block(q, k, v, lo, hi):
    """Causal softmax attention of the queries ``lo:hi``. Under
    ``jax.checkpoint``: a gradient keeps q, k and v, not the scores."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) \
        * hd ** -0.5
    causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                      v[:, :hi])


def _gated_attention(n, w, c):
    b, s, _ = n.shape
    heads, kvh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    qg = (n @ w["wq"]).reshape(b, s, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (n @ w["wk"]).reshape(b, s, kvh, hd)
    v = (n @ w["wv"]).reshape(b, s, kvh, hd)
    factor = float(c["partial_rotary_factor"])
    q = _partial_rope(_rmsnorm(q, 1.0 + w["q_norm"], eps), theta, factor)
    k = _partial_rope(_rmsnorm(k, 1.0 + w["k_norm"], eps), theta, factor)
    k = jnp.repeat(k, heads // kvh, axis=2)
    v = jnp.repeat(v, heads // kvh, axis=2)
    out = [_attend_block(q, k, v, lo, min(s, lo + QUERY_BLOCK))
           for lo in range(0, s, QUERY_BLOCK)]
    attn = jnp.concatenate(out, axis=1) * jax.nn.sigmoid(gate)
    return attn.reshape(b, s, heads * hd) @ w["wo"]


def _recurrence(q, k, v, g, beta):
    """q, k [B, S, Hv, dk], v [B, S, Hv, dv], g, beta [B, S, Hv] -> o [B, S,
    Hv, dv]: one position after another from a zero state. The positions
    are walked in blocks of ``RECURRENCE_BLOCK`` under ``jax.checkpoint``,
    the same operations in the same order, so that a gradient keeps one
    state a block and not one a position (17 GB a layer at S = 8192). A
    length that is no multiple is filled up with positions that leave the
    state as it is (k = 0, beta = 0, g = 0) and cut again."""
    b, s, hv, dk = q.shape
    dv = v.shape[-1]

    def position(state, xs):            # state [B, Hv, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        delta = beta_t[..., None] * (v_t - seen)
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return lax.scan(position, state, xs)

    fill = -s % RECURRENCE_BLOCK

    def blocks(x):                      # [B, S, ...] -> [S/blk, blk, B, ...]
        x = jnp.pad(x, ((0, 0), (0, fill)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, RECURRENCE_BLOCK) + x.shape[1:])

    _, o = lax.scan(block, jnp.zeros((b, hv, dk, dv), jnp.float32),
                    tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:s], 0, 1)


def _gated_delta_rule(n, w, c):
    b, s, _ = n.shape
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    width = c["linear_conv_kernel_dim"]
    kd, vd = hk * dk, hv * dv
    qkvz, ba = n @ w["in_qkvz"], n @ w["in_ba"]
    qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * w["conv"][:, j]
                          for j in range(width)))
    q = qkv[..., :kd].reshape(b, s, hk, dk)
    k = qkv[..., kd:2 * kd].reshape(b, s, hk, dk)
    v = qkv[..., 2 * kd:].reshape(b, s, hv, dv)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q), hv // hk, axis=2) * dk ** -0.5
    k = jnp.repeat(unit(k), hv // hk, axis=2)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., hv:] + w["dt_bias"])

    o = _recurrence(q, k, v, g, beta)
    o = _rmsnorm(o, w["norm"], float(c["rms_norm_eps"])) \
        * jax.nn.silu(z.reshape(b, s, hv, dv))
    return o.reshape(b, s, vd) @ w["out"]


def _experts(n, w, c):
    b, s, d = n.shape
    x = n.reshape(b * s, d)
    probs = jax.nn.softmax(x @ w["router"], axis=-1)
    top_p, top_e = lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    first = int(c.get("first_expert", 0))

    @jax.checkpoint
    def expert(e, w1, w3, w2):
        share = jnp.sum(jnp.where(top_e == e + first, top_p, 0.0), -1)
        return share[:, None] * ((jax.nn.silu(x @ w1) * (x @ w3)) @ w2)

    def one(acc, ew):
        return acc + expert(*ew), None

    held = w["w1"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(x),
                         (jnp.arange(held), w["w1"], w["w3"], w["w2"]))
    shared = (jax.nn.silu(x @ w["shared_w1"]) * (x @ w["shared_w3"])) \
        @ w["shared_w2"]
    shared = jax.nn.sigmoid(x @ w["shared_gate"])[:, None] * shared
    return (routed + shared).reshape(b, s, d)


def _layer(x, w, c, full):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        eps = float(c["rms_norm_eps"])
        n = _rmsnorm(x, 1.0 + w["ln1"], eps)
        x = x + (_gated_attention if full else _gated_delta_rule)(n, w, c)
        return x + _experts(_rmsnorm(x, 1.0 + w["ln2"], eps), w, c)


def _head(x, final_norm, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, 1.0 + final_norm.astype(jnp.float32), eps) \
            @ lm_head.astype(jnp.float32)


_layer_jit = jax.jit(_layer, static_argnums=(2, 3))
_head_jit = jax.jit(_head, static_argnums=3)


def is_full_attention(i: int, config: dict) -> bool:
    return (i + 1) % config["full_attention_interval"] == 0


def _static(config: dict) -> tuple:
    """The configuration's numbers as a hashable argument of ``jit``."""
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, bool))))


def forward(weights: Weights, tokens, config: dict):
    """tokens [B, S] int -> logits [B, S, vocab] float32."""
    x = weights.embed[tokens].astype(jnp.float32)
    for i in range(weights.n_layers):
        x = _layer_jit(x, weights.layer(i), _static(config),
                       is_full_attention(i, config))
    return _head_jit(x, weights.final_norm, weights.lm_head,
                     float(config["rms_norm_eps"]))


def loss(weights: Weights, tokens, config: dict, rows_per_pass: int = 1):
    """Mean next-token cross-entropy over every non-final position, a few
    rows at a time so that the float32 logits fit beside the system."""
    @jax.jit
    def nll_sum(logits, targets):
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.take_along_axis(logp, targets[:, 1:, None], -1).sum()

    total, count = 0.0, 0
    for lo in range(0, tokens.shape[0], rows_per_pass):
        chunk = tokens[lo:lo + rows_per_pass]
        total += float(nll_sum(forward(weights, chunk, config), chunk))
        count += chunk.shape[0] * (chunk.shape[1] - 1)
    return total / count


def _nll_sum(x, final_norm, lm_head, tokens, eps):
    logits = _head(x, final_norm, lm_head, eps)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).sum()


def _layer_back(x, w, c, full, dy):
    _, vjp = jax.vjp(lambda x, w: _layer(x, w, c, full), x, w)
    return vjp(dy)                                      # (dx, dw)


_head_back_jit = jax.jit(jax.value_and_grad(_nll_sum, argnums=(0, 1, 2)),
                         static_argnums=4)
_layer_back_jit = jax.jit(_layer_back, static_argnums=(2, 3))


def loss_and_grads(weights: Weights, tokens, config: dict):
    """-> (the loss of ``loss``, its gradient as ``Weights`` of float32),
    at any size: a row at a time, forward keeping each layer's input, then
    back a layer at a time (``jax.vjp`` of the same ``_layer``)."""
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    embed, final_norm, lm_head = f32((weights.embed, weights.final_norm,
                                      weights.lm_head))
    eps = float(config["rms_norm_eps"])
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    total, grads = 0.0, None
    for r in range(tokens.shape[0]):
        row = jnp.asarray(tokens[r:r + 1])
        xs = [embed[row]]
        for i in range(weights.n_layers - 1):
            xs.append(_layer_jit(xs[-1], weights.layer(i), _static(config),
                                 is_full_attention(i, config)))
        last = weights.n_layers - 1
        x = _layer_jit(xs[-1], weights.layer(last), _static(config),
                       is_full_attention(last, config))
        nll, (dx, d_norm, d_head) = _head_back_jit(x, final_norm, lm_head,
                                                   row, eps)
        del x
        layers = [None] * weights.n_layers
        for i in reversed(range(weights.n_layers)):
            dx, layers[i] = _layer_back_jit(
                xs.pop(), f32(weights.layer(i)), _static(config),
                is_full_attention(i, config), dx)
        got = {"embed": jnp.zeros_like(embed).at[row[0]].add(dx[0]),
               "final_norm": d_norm, "lm_head": d_head, "layers": layers}
        total += float(nll)
        grads = got if grads is None else jax.tree.map(jnp.add, grads, got)
        del got
    grads = jax.tree.map(lambda g: g / count, grads)
    return total / count, Weights(
        embed=grads["embed"], layer=grads["layers"].__getitem__,
        n_layers=weights.n_layers, final_norm=grads["final_norm"],
        lm_head=grads["lm_head"])


def loss_of_arrays(layers: list, embed, final_norm, lm_head, tokens,
                   config: dict):
    """The same loss as one differentiable function of plain arrays
    (``layers``: one dict a layer), for ``jax.grad`` in the CPU tests."""
    x = embed[tokens].astype(jnp.float32)
    for i, w in enumerate(layers):
        x = _layer(x, w, config, is_full_attention(i, config))
    logits = _head(x, final_norm, lm_head, float(config["rms_norm_eps"]))
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

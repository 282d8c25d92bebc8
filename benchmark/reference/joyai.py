"""Plain reference for the ``joyai`` family (JoyAI-LLM-Flash, a
DeepSeek-V3-shaped stack): forward, both training losses, their gradient
and the router's bias update in straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``. No kernel, no absorbed form, no
sorting of routed rows, and nothing imported from ``ray_tpu``. Written from
the published ``config.json`` and DeepSeek-V3's report (arXiv:2412.19437);
``jax.checkpoint`` around a block of queries and an expert changes no value:
it lets ``loss_and_grads`` run at the cell's own size beside the system's
parameters. n = RMSNorm in float32, eps ``rms_norm_eps``.

    layer i:  x = x + mla_i(n(x));  x = x + ffn_i(n(x))
    ffn_i is a dense SwiGLU of ``intermediate_size`` where i <
    ``first_k_dense_replace``, else the expert layer.

- Latent attention (section 2.1.1), H heads: ``c_q = n(W_dq x)``
  (``q_lora_rank``), ``q = W_uq c_q``, per head ``[q_nope (qk_nope_head_dim)
  ; q_rope (qk_rope_head_dim)]``; ``[c_kv ; k_r] = W_dkv x``
  (``kv_lora_rank`` + ``qk_rope_head_dim``), ``c_kv = n(c_kv)``, ``W_ukv
  c_kv`` per head ``[k_nope ; v (v_head_dim)]``; RoPE (``rope_theta``, no
  scaling) on ``q_rope`` and on the one ``k_r`` every head shares; ``k =
  [k_nope ; k_r]``; causal softmax attention scaled by ``(qk_nope_head_dim +
  qk_rope_head_dim) ** -0.5``; ``W_o`` over the heads' values. Scores are
  made a block of queries at a time.
- Expert layer (section 2.1.2): ``s = sigmoid(W_r x)`` over all
  ``n_routed_experts_published`` experts; the ``num_experts_per_tok`` of
  largest ``s + bias`` are taken, weighted by ``s`` alone, divided by their
  sum (``norm_topk_prob``), times ``routed_scaling_factor``; the sum over
  the experts HELD HERE (``n_routed_experts`` of them from ``first_expert``
  on: the chip's share) of ``g_e W2_e (silu(W1_e x) * W3_e x)``, computed
  densely for every token and masked, plus one shared expert of the same
  width on every token. What the absent experts would add is left out; with
  every expert held this is the published layer. ``n_group`` = ``topk_group``
  = 1: no group limits the choice.
- ``logits = W_head n(x_L)``; ``L_main`` = mean next-token cross-entropy.
- The multi-token-prediction module (section 2.2, depth 1), with ``h_i`` the
  stack's output at position i BEFORE the final norm and t the tokens: for i
  = 0 .. S-2, ``u_i = W_eh [n_e(Emb(t_{i+1})) ; n_h(h_i)]``; ``v =
  Block(u)``, one expert layer's block with weights, router and bias of its
  own at positions i; ``logits_i = W_head n_f'(v_i)`` predicts ``t_{i+2}``;
  ``L_mtp`` = mean cross-entropy over i = 0 .. S-3. ``Emb`` and ``W_head``
  are the main model's. ``L = L_main + mtp_loss_weight x L_mtp``.
- The bias (section 2.1.2, "auxiliary-loss-free"): no gradient reaches it;
  after a step, with ``c_e`` the step's (token, expert) pairs of expert e
  over all published experts of one layer, ``b_e += router_bias_update_rate
  x sign(mean(c) - c_e)``.

Assumed, each also under the configuration file's ``assumed``:
``mtp_loss_weight`` 0.3 and ``router_bias_update_rate`` 0.001 (DeepSeek-V3's
values; no key of the config gives them); the concatenation's order (the
embedding's half first, as the released DeepSeek-V3 checkpoints have it);
RoPE by rotate-half (pairs j, j + d/2) where the checkpoint interleaves: a
column permutation of seeded weights.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 1024


class Weights(NamedTuple):
    """``layer(i)`` -> dict. Every layer: ``ln1``, ``ln2`` [d]; ``wdq`` [d,
    rq], ``q_norm`` [rq], ``wuq`` [rq, H*(nope+rope)], ``wdkv`` [d, rkv +
    rope], ``kv_norm`` [rkv], ``wukv`` [rkv, H*(nope+v)], ``wo`` [H*v, d].
    A dense layer: ``w1``, ``w3`` [d, f], ``w2`` [f, d]. An expert layer:
    ``router`` [d, E], ``router_bias`` [E], ``w1``, ``w3`` [held, d, fe],
    ``w2`` [held, fe, d], ``shared_w1``, ``shared_w3`` [d, fe],
    ``shared_w2`` [fe, d]. ``mtp``: ``enorm``, ``hnorm``, ``final_norm``
    [d], ``eh_proj`` [2d, d], ``block``: an expert layer's dict."""
    embed: jax.Array
    layer: Callable[[int], dict]
    n_layers: int
    final_norm: jax.Array
    lm_head: jax.Array
    mtp: dict


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, S, H, r]: rotate_half over all r dims, position = index."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.checkpoint, static_argnums=(3, 4))
def _attend_block(q, k, v, lo, hi):
    """Causal softmax attention of the queries ``lo:hi``. Under
    ``jax.checkpoint``: a gradient keeps q, k and v, not the scores."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) \
        * q.shape[-1] ** -0.5
    causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                      v[:, :hi])


def _latent_attention(n, w, c):
    b, s, _ = n.shape
    heads, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    c_q = _rmsnorm(n @ w["wdq"], w["q_norm"], eps)
    q = (c_q @ w["wuq"]).reshape(b, s, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    down = n @ w["wdkv"]
    c_kv = _rmsnorm(down[..., :r], w["kv_norm"], eps)
    k_r = _rope(down[:, :, None, r:], theta)             # one for all heads
    kv = (c_kv @ w["wukv"]).reshape(b, s, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, heads, rope))], -1)
    out = [_attend_block(q, k, kv[..., nope:], lo, min(s, lo + QUERY_BLOCK))
           for lo in range(0, s, QUERY_BLOCK)]
    return jnp.concatenate(out, axis=1).reshape(b, s, heads * vd) @ w["wo"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _experts(n, w, c):
    """-> (the held experts' part + the shared expert [B, S, d], the (token,
    expert) pairs of every published expert [E] int32)."""
    b, s, d = n.shape
    x = n.reshape(b * s, d)
    scores = jax.nn.sigmoid(x @ w["router"])
    _, top_e = lax.top_k(scores + w["router_bias"], c["num_experts_per_tok"])
    gate = jnp.take_along_axis(scores, top_e, axis=-1)
    if c["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * float(c["routed_scaling_factor"])
    first = int(c.get("first_expert", 0))

    @jax.checkpoint
    def expert(e, w1, w3, w2):
        share = jnp.sum(jnp.where(top_e == e + first, gate, 0.0), -1)
        return share[:, None] * _swiglu(x, w1, w3, w2)

    def one(acc, ew):
        return acc + expert(*ew), None

    held = w["w1"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(x),
                         (jnp.arange(held), w["w1"], w["w3"], w["w2"]))
    shared = _swiglu(x, w["shared_w1"], w["shared_w3"], w["shared_w2"])
    counts = jnp.bincount(top_e.reshape(-1), length=w["router"].shape[1])
    return (routed + shared).reshape(b, s, d), counts


def _layer(x, w, c):
    """-> (the block's output, the router's counts [E]; None for a dense
    layer, which holds no ``router``)."""
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        eps = float(c["rms_norm_eps"])
        x = x + _latent_attention(_rmsnorm(x, w["ln1"], eps), w, c)
        n = _rmsnorm(x, w["ln2"], eps)
        if "router" not in w:
            return x + _swiglu(n, w["w1"], w["w3"], w["w2"]), None
        y, counts = _experts(n, w, c)
        return x + y, counts


def _nll_sum(x, final_norm, lm_head, targets, eps):
    """x [B, T, d] -> the summed cross-entropy of ``targets`` [B, T]."""
    with jax.default_matmul_precision("highest"):
        logits = _rmsnorm(x, final_norm.astype(jnp.float32), eps) \
            @ lm_head.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).sum()


def _mtp(h, embed, lm_head, m, tokens, c):
    """The module over the stack's output ``h`` [B, S, d] (before the final
    norm) -> (summed cross-entropy of t_{i+2} over i = 0 .. S-3, counts)."""
    c = dict(c)
    eps = float(c["rms_norm_eps"])
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        e = _rmsnorm(embed.astype(f32)[tokens[:, 1:]],
                     m["enorm"].astype(f32), eps)
        g = _rmsnorm(h[:, :-1], m["hnorm"].astype(f32), eps)
        u = jnp.concatenate([e, g], -1) @ m["eh_proj"].astype(f32)
    v, counts = _layer(u, m["block"], c)
    return _nll_sum(v[:, :-1], m["final_norm"], lm_head, tokens[:, 2:],
                    eps), counts


def _static(config: dict) -> tuple:
    """The configuration's numbers as a hashable argument of ``jit``."""
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, bool))))


_layer_jit = jax.jit(_layer, static_argnums=2)


def stack(weights: Weights, tokens, config: dict):
    """tokens [B, S] -> (the stack's output before the final norm, the
    expert layers' counts {layer index: [E]})."""
    x = weights.embed[tokens].astype(jnp.float32)
    counts = {}
    for i in range(weights.n_layers):
        x, c = _layer_jit(x, weights.layer(i), _static(config))
        if c is not None:
            counts[i] = c
    return x, counts


def forward(weights: Weights, tokens, config: dict):
    """tokens [B, S] int -> the main head's logits [B, S, vocab] float32."""
    x, _ = stack(weights, tokens, config)
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, weights.final_norm.astype(jnp.float32),
                        float(config["rms_norm_eps"])) \
            @ weights.lm_head.astype(jnp.float32)


def bias_delta(counts, config: dict):
    """counts [E] -> what a step adds to that router's bias."""
    counts = jnp.asarray(counts, jnp.float32)
    return float(config["router_bias_update_rate"]) \
        * jnp.sign(counts.mean() - counts)


def _tail(h, embed, final_norm, lm_head, m, tokens, c, n_main, n_mtp):
    """The two losses of one row's worth of the stack's output, each over
    its own count of positions in the whole batch -> (their weighted sum,
    (main, module, the module's counts))."""
    cd = dict(c)
    main = _nll_sum(h[:, :-1], final_norm, lm_head, tokens[:, 1:],
                    float(cd["rms_norm_eps"])) / n_main
    module, counts = _mtp(h, embed, lm_head, m, tokens, c)
    module = module / n_mtp
    return main + float(cd["mtp_loss_weight"]) * module, \
        (main, module, counts)


def _layer_back(x, w, c, dy):
    _, vjp, _ = jax.vjp(lambda x, w: _layer(x, w, c), x, w, has_aux=True)
    return vjp(dy)                                      # (dx, dw)


_tail_back_jit = jax.jit(
    jax.value_and_grad(_tail, argnums=(0, 1, 2, 3, 4), has_aux=True),
    static_argnums=(6, 7, 8))
_layer_back_jit = jax.jit(_layer_back, static_argnums=2)


def loss_and_grads(weights: Weights, tokens, config: dict):
    """-> ({"loss", "loss_main", "loss_mtp"}, the gradient of ``loss`` as
    ``Weights`` of float32, the step's counts {layer index | "mtp": [E]}),
    at any size: a row at a time, forward keeping each layer's input, the
    two heads and the module back, then back a layer at a time (``jax.vjp``
    of the same ``_layer``)."""
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    embed, final_norm, lm_head, mtp = f32(
        (weights.embed, weights.final_norm, weights.lm_head, weights.mtp))
    static = _static(config)
    rows, s = tokens.shape
    n_main, n_mtp = rows * (s - 1), rows * (s - 2)
    main = module = 0.0
    grads, counts = None, {}

    def count(key, c):
        counts[key] = c if key not in counts else counts[key] + c

    for r in range(rows):
        row = jnp.asarray(tokens[r:r + 1])
        xs = [embed[row]]
        for i in range(weights.n_layers):
            x, c = _layer_jit(xs[-1], weights.layer(i), static)
            xs.append(x)
            if c is not None:
                count(i, c)
        (_, (l_main, l_mtp, c)), (dx, d_embed, d_norm, d_head, d_mtp) = \
            _tail_back_jit(xs.pop(), embed, final_norm, lm_head, mtp, row,
                           static, n_main, n_mtp)
        count("mtp", c)
        layers = [None] * weights.n_layers
        for i in reversed(range(weights.n_layers)):
            dx, layers[i] = _layer_back_jit(xs.pop(), f32(weights.layer(i)),
                                            static, dx)
        got = {"embed": d_embed.at[row[0]].add(dx[0]),
               "final_norm": d_norm, "lm_head": d_head, "layers": layers,
               "mtp": d_mtp}
        main, module = main + float(l_main), module + float(l_mtp)
        grads = got if grads is None else jax.tree.map(jnp.add, grads, got)
        del got
    losses = {"loss_main": main, "loss_mtp": module,
              "loss": main + float(config["mtp_loss_weight"]) * module}
    return losses, Weights(
        embed=grads["embed"], layer=grads["layers"].__getitem__,
        n_layers=weights.n_layers, final_norm=grads["final_norm"],
        lm_head=grads["lm_head"], mtp=grads["mtp"]), counts


def losses_of_arrays(layers: list, embed, final_norm, lm_head, mtp, tokens,
                     config: dict):
    """The same losses as one differentiable function of plain arrays
    (``layers``: one dict a layer) -> (loss, (main, module)), for
    ``jax.grad`` in the CPU tests."""
    x = embed[tokens].astype(jnp.float32)
    for w in layers:
        x, _ = _layer(x, w, config)
    b, s = tokens.shape
    total, (main, module, _) = _tail(
        x, embed, final_norm, lm_head, mtp, tokens, _static(config),
        b * (s - 1), b * (s - 2))
    return total, (main, module)

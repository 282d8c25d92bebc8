"""Plain reference for the ``olmo_hybrid`` family (Olmo-Hybrid-7B, Allen
Institute for AI: three gated-delta-rule layers and one softmax layer a
period, post-normed): the served forward pass in straight ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``, a Python ``for`` over
the layers and a loop over POSITIONS for the rule (``lax.scan``, one
position a step: no chunks, no WY transform). No kernel, no cache, and
nothing imported from ``ray_tpu`` or from another family's rule (RMSNorm, the
rounding of activations and the comparisons of logits and tokens are the
llama and ouro references' own). n = RMSNorm at ``rms_norm_eps`` with a
scale vector, no bias anywhere; ``h`` is a block's input, which no norm
touches:

    x = embed[tokens]
    for l in 0..L-1:
        x = x + n(mixer_l(x); ln1_post_l)         # the norm on the OUTPUT
        x = x + n(W2_l (silu(W1_l x) * W3_l x); ln2_post_l)
    logits = lm_head . n(x; final_norm)

Linear layer (``layer_types[l] == "linear_attention"``; H =
``linear_num_value_heads`` = ``linear_num_key_heads``, dk =
``linear_key_head_dim``, dv = ``linear_value_head_dim``, K =
``linear_conv_kernel_dim``):

    q = Wq h, k = Wk h  [H dk];  v = Wv h, z = Wg h  [H dv];  b = Wb h, a = Wa h  [H]
    [q; k; v]_t <- silu(sum_{j<K} conv[:, j] [q; k; v]_{t-K+1+j})   # depthwise,
                                                  causal, zeros before t = 0
    per head: q <- q / sqrt(|q|^2 + 1e-6) * dk^-0.5,  k <- k / sqrt(|k|^2 + 1e-6)
    beta = 2 sigmoid(b)  (``linear_allow_neg_eigval``);  g = -exp(A_log) softplus(a + dt_bias)
    S_0 = 0 [dk, dv];  S_t = e^{g_t} S_{t-1};  S_t += k_t (beta_t (v_t - S_t^T k_t))^T;  o_t = S_t^T q_t
    o <- n_dv(o; norm) * silu(z);   y = Wo o

Full layer (every fourth): ``q, k, v = W h`` (``num_attention_heads`` heads
of ``hidden_size / num_attention_heads`` on ``num_key_value_heads``), q and
k each RMS-normed over the WHOLE projection (all heads together), no
rotation (``rope_parameters.rope_theta`` is null), causal softmax scaled by
``hd ** -0.5``, ``Wo``.

**As recalled, not fetched** (there is no network here; the configuration
file lists the same under ``assumed``): the norm on each sublayer's output
only and the q/k norm over the whole projection are OLMo 2 / 3's, which
``modeling_olmo_hybrid.py`` is recalled to keep; no rotation is read off the
null ``rope_theta``; the 1e-6 under the root of a head's squared length is
the Flash Linear Attention library's ``l2norm``. The rule, the convolution,
the gated norm and beta's factor 2 are the published ``config.json``'s keys
as Gated DeltaNet defines them.

What the serving app judges the system by is this same code run once more
with ``dtype=bfloat16`` (its activations rounded, the state and every
accumulation still float32): the floor of what rounding does to the model a
seed drew, to its logits and to the state and the convolution's inputs a
cache would hold (``forward_and_cache``). Its control is this same code
over ``int8_weights``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.llama import _rmsnorm, _rounder
# the serving comparisons, under this family's name too
from benchmark.reference.llama import (compare_logits,  # noqa: F401
                                       token_deficit)
from benchmark.reference.ouro import (errors_a_position,  # noqa: F401
                                      over_floor, token_deficit_over_floor)

UNIT_EPS = 1e-6         # under the root of a head's squared length
VOCAB_BLOCK = 16384     # columns of the head a product (float32 beside the
#                         program's weights on the chip)


class Weights(NamedTuple):
    """``layer(i)`` returns layer i's matrices as a dict. Every layer:
    ``w1`` (gate) and ``w3`` (up) [d, ff], ``w2`` (down) [ff, d], the two
    output norms' scales ``ln1_post``, ``ln2_post`` [d]. A full layer:
    ``wq`` [d, H*hd], ``wk``/``wv`` [d, KVH*hd], ``wo`` [H*hd, d],
    ``q_norm`` [H*hd], ``k_norm`` [KVH*hd]. A linear layer: ``wq``/``wk``
    [d, H*dk], ``wv``/``wg`` [d, H*dv], ``wb``/``wa`` [d, H], ``conv``
    [2 H dk + H dv, K] over [q; k; v] (column K-1 weighs the current
    position), ``A_log``, ``dt_bias`` [H], ``norm`` [dv], ``wo`` [H*dv, d].
    ``kinds[i]`` is "linear" or "full"."""
    embed: jax.Array            # [vocab, d]
    layer: Callable[[int], dict]
    kinds: tuple
    final_norm: jax.Array       # [d]
    lm_head: jax.Array          # [d, vocab]

    @property
    def n_layers(self) -> int:
        return len(self.kinds)


def layer_kinds(config: dict) -> tuple:
    names = {"linear_attention": "linear", "full_attention": "full"}
    return tuple(names[t] for t in config["layer_types"])


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + UNIT_EPS)


def _delta_rule(q, k, v, g, beta):
    """q, k [B, S, H, dk] (unit length, q scaled), v [B, S, H, dv], g, beta
    [B, S, H] -> (o [B, S, H, dv], the state after the last position
    [B, H, dk, dv]): the recurrence, one position a step."""
    b, _, h, dk = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        fresh = beta_t[..., None] * (v_t - seen)
        state = state + k_t[..., :, None] * fresh[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    state, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _linear_mixer(h, w, *, heads, dk, dv, dtype=None):
    """-> (y [B, S, d], the state after the last position [B, H, dk, dv],
    the convolution's last K - 1 inputs [B, K-1, 2 H dk + H dv])."""
    rnd = _rounder(dtype)
    b, s, _ = h.shape
    u = jnp.concatenate([rnd(h @ w[n]) for n in ("wq", "wk", "wv")], -1)
    z = rnd(h @ w["wg"]).reshape(b, s, heads, dv)
    beta = 2.0 * jax.nn.sigmoid(rnd(h @ w["wb"]))
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(rnd(h @ w["wa"])
                                               + w["dt_bias"])
    width = w["conv"].shape[1]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    qkv = rnd(jax.nn.silu(sum(padded[:, j:j + s] * w["conv"][:, j]
                              for j in range(width))))
    kd = heads * dk
    q = rnd(_unit(qkv[..., :kd].reshape(b, s, heads, dk)) * dk ** -0.5)
    k = rnd(_unit(qkv[..., kd:2 * kd].reshape(b, s, heads, dk)))
    v = qkv[..., 2 * kd:].reshape(b, s, heads, dv)
    o, state = _delta_rule(q, k, v, g, beta)
    o = rnd(rnd(_rmsnorm(rnd(o), w["norm"], 1e-6)) * jax.nn.silu(z))
    return rnd(o.reshape(b, s, heads * dv) @ w["wo"]), state, \
        padded[:, s:]


def _full_mixer(h, w, *, heads, kv_heads, eps, dtype=None):
    rnd = _rounder(dtype)
    b, s, _ = h.shape
    hd = w["wq"].shape[1] // heads
    q = rnd(_rmsnorm(rnd(h @ w["wq"]), w["q_norm"], eps))
    k = rnd(_rmsnorm(rnd(h @ w["wk"]), w["k_norm"], eps))
    q = q.reshape(b, s, heads, hd)
    k = jnp.repeat(k.reshape(b, s, kv_heads, hd), heads // kv_heads, axis=2)
    v = jnp.repeat(rnd(h @ w["wv"]).reshape(b, s, kv_heads, hd),
                   heads // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = rnd(jnp.einsum("bhqk,bkhd->bqhd",
                          rnd(jax.nn.softmax(scores, axis=-1)), v))
    return rnd(attn.reshape(b, s, heads * hd) @ w["wo"])


def _layer(x, w, *, kind, heads, kv_heads, linear_heads, dk, dv, eps,
           dtype=None):
    """One block -> (x, the linear layer's final state and last inputs, or
    (None, None))."""
    rnd = _rounder(dtype)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        state = tail = None
        if kind == "linear":
            y, state, tail = _linear_mixer(x, w, heads=linear_heads, dk=dk,
                                           dv=dv, dtype=dtype)
        else:
            y = _full_mixer(x, w, heads=heads, kv_heads=kv_heads, eps=eps,
                            dtype=dtype)
        x = rnd(x + rnd(_rmsnorm(y, w["ln1_post"], eps)))
        gated = rnd(jax.nn.silu(rnd(x @ w["w1"])) * rnd(x @ w["w3"]))
        m = rnd(gated @ w["w2"])
        return rnd(x + rnd(_rmsnorm(m, w["ln2_post"], eps))), state, tail


def _final_norm(x, final_norm, *, eps, dtype=None):
    return _rounder(dtype)(_rmsnorm(x, final_norm.astype(jnp.float32), eps))


def _unembed(x, columns):
    with jax.default_matmul_precision("highest"):
        return x @ columns.astype(jnp.float32)


def forward_and_cache(weights: Weights, tokens, config: dict, eps=None,
                      dtype=None, rows_a_pass: int = 2):
    """tokens [B, S] int -> (logits [B, S, vocab] float32, what a cache
    would hold of the linear layers after position S - 1, in layer order:
    ``{"state": [linear layers, B, H, dk, dv], "tail": [linear layers, B,
    K-1, 2 H dk + H dv]}``, float32). ``eps``: RMSNorm's epsilon where it is
    not the configuration's published one. ``dtype``: the same code with
    every activation (the result of each matmul, norm, convolution, softmax,
    product and residual sum) rounded to that type, the state and every
    accumulation still in float32: what rounding alone does to this model's
    logits, states and tails, the floor the serving app judges the system
    against. ``rows_a_pass`` rows at a time, so that the float32 pass fits
    beside the program's weights on the chip."""
    eps = float(config["rms_norm_eps"] if eps is None else eps)
    layer = jax.jit(_layer, static_argnames=(
        "kind", "heads", "kv_heads", "linear_heads", "dk", "dv", "eps",
        "dtype"))
    sizes = dict(
        heads=config["num_attention_heads"],
        kv_heads=config.get("num_key_value_heads")
        or config["num_attention_heads"],
        linear_heads=config["linear_num_value_heads"],
        dk=config["linear_key_head_dim"], dv=config["linear_value_head_dim"])
    norm = jax.jit(_final_norm, static_argnames=("eps", "dtype"))
    unembed = jax.jit(_unembed)
    vocab = weights.lm_head.shape[1]
    logits, states, tails = [], [], []
    for lo in range(0, tokens.shape[0], rows_a_pass):
        x = weights.embed[tokens[lo:lo + rows_a_pass]].astype(jnp.float32)
        row_states, row_tails = [], []
        for i, kind in enumerate(weights.kinds):
            x, state, tail = layer(x, weights.layer(i), kind=kind, eps=eps,
                                   dtype=dtype, **sizes)
            if kind == "linear":
                row_states.append(state)
                row_tails.append(tail)
        x = norm(x, weights.final_norm, eps=eps, dtype=dtype)
        logits.append(jnp.concatenate(
            [unembed(x, weights.lm_head[:, at:at + VOCAB_BLOCK])
             for at in range(0, vocab, VOCAB_BLOCK)], axis=-1))
        states.append(jnp.stack(row_states))
        tails.append(jnp.stack(row_tails))
    return jnp.concatenate(logits), {"state": jnp.concatenate(states, 1),
                                     "tail": jnp.concatenate(tails, 1)}


def forward(weights: Weights, tokens, config: dict, eps=None, dtype=None):
    """The logits of ``forward_and_cache``."""
    return forward_and_cache(weights, tokens, config, eps, dtype)[0]


def int8_weights(weights: Weights) -> Weights:
    """The control: the same weights rounded to 8 bits (absmax per output
    channel, symmetric) and handed back as the values they then are, one
    layer at a time; vectors (norms' scales, ``A_log``, ``dt_bias``) as they
    are, the convolution's K taps a channel among the matrices. The nearest
    precision under the served bfloat16 that a later PR could be tempted
    by; ``correct`` has to refuse it."""
    def q(w):
        if w.ndim < 2:
            return w
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(w / scale) * scale
    return weights._replace(
        embed=q(weights.embed.T).T, lm_head=q(weights.lm_head),
        layer=lambda i: {k: q(v.T).T if k == "conv" else q(v)
                         for k, v in weights.layer(i).items()})


def cache_errors(got: dict, reference: dict):
    """What a cache holds of the linear layers against what the reference's
    layers made: ``{"state": [slots, B, H, dk, dv], "tail": [slots, B, K-1,
    C]}`` each -> [slots, 2]: the rms error of a slot's state (0) and of
    its tail (1). The first layers' are a projection and a convolution away
    from the embedding: nothing has amplified anything yet."""
    def rms(name):
        err = (jnp.asarray(got[name], jnp.float32)
               - jnp.asarray(reference[name], jnp.float32)) ** 2
        return jnp.sqrt(jnp.mean(err.reshape(err.shape[0], -1), axis=1))
    return jnp.stack([rms("state"), rms("tail")], axis=1)

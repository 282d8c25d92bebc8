"""Plain reference for the llama family (Mistral-7B, InternLM2): forward,
next-token loss and the serving comparison, in straight ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``. No kernel, no cache,
no scan, no remat, and nothing imported from ``ray_tpu``: it follows the
published block (Hugging Face ``modeling_mistral.py`` /
``modeling_internlm2.py``):

    h  = x + Wo . attention(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))
    y  = h + W2 . (silu(W1 n2(h)) * W3 n2(h))          n = RMSNorm
    logits = lm_head . n_f(y_L)

RoPE rotates the pairs (i, i + hd/2) ("rotate_half"), keys and values of a
KV head are shared by ``H / KVH`` query heads, attention is causal with no
window, scores are scaled by ``hd ** -0.5``.

Weights are handed over as plain matrices (see ``Weights``); the apps
convert the system's own parameter tree, so the same numbers go through
both. They may be stored in bfloat16: the reference upcasts, and so sees
exactly the values the system serves.

RMSNorm's epsilon is the published ``rms_norm_eps`` of the configuration.
The program fixes its own at 1e-6 (``models/transformer.py``) against the
published 1e-5, and the reference is not bent to it: the training app
judges the loss against this reference as it is (the difference fits inside
its tolerance), and the serving app, whose comparison on logits is finer,
runs ``forward`` at the program's epsilon to judge the arithmetic apart
from that known difference, holds the program to the two epsilons it may
have, and reports the distance between the two references
(``program_eps_gap``).

What the serving app judges the system's logits by is this same code run
once more with ``dtype=bfloat16`` (its activations rounded, nothing else
changed): the floor of what rounding does to the model a seed drew. Its
control is this same code over ``int8_weights``.

Departures from the published models:
- InternLM2's checkpoint packs q, k and v into one ``wqkv``; the block is
  the same mathematics with three projections (``assumed`` in its file).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class Weights(NamedTuple):
    """``layer(i)`` returns layer i's matrices as a dict: ``wq`` [d, H*hd],
    ``wk``/``wv`` [d, KVH*hd], ``wo`` [H*hd, d], ``w1`` (gate) and ``w3``
    (up) [d, ff], ``w2`` (down) [ff, d], ``ln1``/``ln2`` [d]."""
    embed: jax.Array            # [vocab, d]
    layer: Callable[[int], dict]
    n_layers: int
    final_norm: jax.Array       # [d]
    lm_head: jax.Array          # [d, vocab]


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x [B, S, H, hd], positions 0..S-1."""
    hd = x.shape[-1]
    half = hd // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                / half))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rounder(dtype):
    """Activations kept in ``dtype``: each result is rounded to it and
    carried on in float32 (None: nothing is rounded)."""
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(jnp.float32)


def _layer(x, w, *, heads, kv_heads, theta, eps, dtype=None):
    rnd = _rounder(dtype)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, d = x.shape
        hd = w["wq"].shape[1] // heads
        n = rnd(_rmsnorm(x, w["ln1"], eps))
        q = rnd(_rope(rnd(n @ w["wq"]).reshape(b, s, heads, hd), theta))
        k = rnd(_rope(rnd(n @ w["wk"]).reshape(b, s, kv_heads, hd), theta))
        v = rnd(n @ w["wv"]).reshape(b, s, kv_heads, hd)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        attn = rnd(jnp.einsum("bhqk,bkhd->bqhd",
                              rnd(jax.nn.softmax(scores, axis=-1)), v))
        h = rnd(x + rnd(attn.reshape(b, s, heads * hd) @ w["wo"]))
        n = rnd(_rmsnorm(h, w["ln2"], eps))
        gated = rnd(jax.nn.silu(rnd(n @ w["w1"])) * rnd(n @ w["w3"]))
        return rnd(h + rnd(gated @ w["w2"]))


def _head(x, final_norm, lm_head, *, eps, dtype=None):
    rnd = _rounder(dtype)
    with jax.default_matmul_precision("highest"):
        return rnd(_rmsnorm(x, final_norm.astype(jnp.float32), eps)) \
            @ lm_head.astype(jnp.float32)


def forward(weights: Weights, tokens, config: dict, eps=None, dtype=None):
    """tokens [B, S] int -> logits [B, S, vocab] float32. ``eps``: RMSNorm's
    epsilon where it is not the configuration's published one. ``dtype``:
    the same code with every activation (the result of each matmul, norm,
    rotation, softmax, product and residual sum) rounded to that type,
    accumulations still in float32: what rounding alone does to this
    model's logits, the floor the serving app judges the system against."""
    eps = float(config["rms_norm_eps"] if eps is None else eps)
    layer = jax.jit(_layer, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "dtype"))
    x = weights.embed[tokens].astype(jnp.float32)
    for i in range(weights.n_layers):
        x = layer(x, weights.layer(i),
                  heads=config["num_attention_heads"],
                  kv_heads=config.get("num_key_value_heads")
                  or config["num_attention_heads"],
                  theta=float(config["rope_theta"]), eps=eps, dtype=dtype)
    return jax.jit(_head, static_argnames=("eps", "dtype"))(
        x, weights.final_norm, weights.lm_head, eps=eps, dtype=dtype)


def int8_weights(weights: Weights) -> Weights:
    """The control: the same weights rounded to 8 bits (absmax per output
    channel, symmetric) and handed back as the values they then are, one
    layer at a time. The nearest precision under the served bfloat16 that a
    later PR could be tempted by; ``correct`` has to refuse it."""
    def q(w):
        if w.ndim < 2:
            return w
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(w / scale) * scale
    return Weights(embed=q(weights.embed.T).T,
                   layer=lambda i: {k: q(v) for k, v in
                                    weights.layer(i).items()},
                   n_layers=weights.n_layers, final_norm=weights.final_norm,
                   lm_head=q(weights.lm_head))


def loss(weights: Weights, tokens, config: dict, rows_per_pass: int = 2):
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


def compare_logits(system, reference) -> dict:
    """The serving comparison: error of ``system`` logits against
    ``reference`` over every compared position, as shares of the
    reference's own standard deviation (random weights give logits of
    std ~1.3; an absolute bound would hide that scale)."""
    system = jnp.asarray(system, jnp.float32)
    reference = jnp.asarray(reference, jnp.float32)
    err = system - reference
    std = float(jnp.std(reference))
    return {"rms_over_std": float(jnp.sqrt(jnp.mean(err ** 2))) / std,
            "max_over_std": float(jnp.max(jnp.abs(err))) / std,
            "reference_std": std, "n_logits": int(err.size)}


def token_deficit(reference, tokens) -> dict:
    """Greedy tokens against the reference's logits at the positions that
    predicted them (``reference`` [B, T, vocab], ``tokens`` [B, T]): how far
    below the reference's best logit each chosen token's logit lies, as a
    share of the logits' standard deviation. 0 where the token is the
    reference's argmax; a near-tie that the system's rounding decided the
    other way lies within its error of 0; a token from a wrong loop, cache
    or sampler lies ~4 std below (the best of 32,768 near-normal logits)."""
    reference = jnp.asarray(reference, jnp.float32)
    chosen = jnp.take_along_axis(reference, jnp.asarray(tokens)[..., None],
                                 -1)[..., 0]
    deficit = (reference.max(-1) - chosen) / jnp.std(reference)
    return {"token_deficit_over_std": float(deficit.max()),
            "token_mismatches": int((deficit > 0).sum()),
            "tokens_checked": int(deficit.size)}

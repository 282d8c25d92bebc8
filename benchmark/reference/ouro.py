"""Plain reference for the ``ouro`` family (Ouro-2.6B, ByteDance, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): the served
forward pass in straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, a Python ``for`` over the loop
steps and the layers. No kernel, no cache, no scan, and nothing imported
from ``ray_tpu`` (RMSNorm, RoPE, the rounding of activations and the
comparisons of logits and tokens are the llama reference's own,
``benchmark/reference/llama.py``). T = ``total_ut_steps``, L =
``num_hidden_layers``, n = RMSNorm at ``rms_norm_eps`` with a scale vector,
no bias but the gate's:

    x = embed[tokens]
    for t in 0..T-1:                      # the SAME L layers' weights every t
        for l in 0..L-1:
            h = n(x; ln1_l)
            a = Wo_l . attention(rope(Wq_l h), rope(Wk_l h), Wv_l h)
            x = x + n(a; ln1_post_l)      # sandwich norm: on the OUTPUT too
            h = n(x; ln2_l)
            x = x + n(W2_l (silu(W1_l h) * W3_l h); ln2_post_l)
        x = n(x; final_norm)              # at the end of EVERY loop step;
        lam_t = sigmoid(x . w_gate + b_gate)     # the normed state goes on
    p_t = lam_t prod_{j<t} (1 - lam_j)  for t < T-1;   p_{T-1} = the rest
    logits = lm_head . x                  # the last loop step's normed state

RoPE rotates the pairs (i, i + hd/2) of all ``head_dim`` dims
("rotate_half"), keys and values of a KV head are shared by ``H / KVH`` query
heads (1 here), attention is causal with no window and scaled by ``hd **
-0.5``; a step-t query sees the keys and values that step t's own state
gave, and no other step's. Scores are made a block of queries at a time, so
that a long sequence fits beside the system. ``early_exit_threshold`` is 1
as published: every position runs all T steps, and the exit distribution is
reported, not acted on.

**As recalled, not fetched** (there is no network here; the configuration
file lists the same under ``assumed``): the four norms a layer; the final
norm after every loop step with the normed state carried on; the gate as
``Linear(hidden -> 1)`` with a bias, read from the normed state; the exit
distribution's form; that keys and values are kept per (loop step, layer).
The widths, ``total_ut_steps`` and ``early_exit_threshold`` are the
published ``config.json``'s.

What the serving app judges the system by is this same code run once more
with ``dtype=bfloat16`` (its activations rounded, nothing else changed): the
floor of what rounding does to the model a seed drew, to its logits, its
exit distribution and the keys and values a cache would hold
(``forward_and_cache``), each read one place at a time (``over_floor``). Its
control is this same code over ``int8_weights``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.llama import _rmsnorm, _rope, _rounder
# the serving comparisons, under this family's name too
from benchmark.reference.llama import (compare_logits,  # noqa: F401
                                       token_deficit)

QUERY_BLOCK = 1024


class Weights(NamedTuple):
    """``layer(i)`` returns layer i's matrices as a dict: ``wq`` [d, H*hd],
    ``wk``/``wv`` [d, KVH*hd], ``wo`` [H*hd, d], ``w1`` (gate) and ``w3``
    (up) [d, ff], ``w2`` (down) [ff, d], and the four norms' scales
    ``ln1``, ``ln1_post``, ``ln2``, ``ln2_post`` [d]."""
    embed: jax.Array            # [vocab, d]
    layer: Callable[[int], dict]
    n_layers: int
    final_norm: jax.Array       # [d]
    lm_head: jax.Array          # [d, vocab]
    gate_w: jax.Array           # [d]
    gate_b: jax.Array           # []


def _attention(q, k, v, rnd):
    """Causal softmax attention, [B, S, H, hd] each, a block of queries at
    a time."""
    s, hd = q.shape[1], q.shape[-1]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(s, lo + QUERY_BLOCK)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) \
            * hd ** -0.5
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        out.append(rnd(jnp.einsum(
            "bhqk,bkhd->bqhd", rnd(jax.nn.softmax(scores, axis=-1)),
            v[:, :hi])))
    return jnp.concatenate(out, axis=1)


def _layer(x, w, *, heads, kv_heads, theta, eps, dtype=None):
    rnd = _rounder(dtype)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, _ = x.shape
        hd = w["wq"].shape[1] // heads
        n = rnd(_rmsnorm(x, w["ln1"], eps))
        q = rnd(_rope(rnd(n @ w["wq"]).reshape(b, s, heads, hd), theta))
        k = rnd(_rope(rnd(n @ w["wk"]).reshape(b, s, kv_heads, hd), theta))
        v = rnd(n @ w["wv"]).reshape(b, s, kv_heads, hd)
        group = heads // kv_heads
        attn = _attention(q, jnp.repeat(k, group, axis=2),
                          jnp.repeat(v, group, axis=2), rnd)
        a = rnd(attn.reshape(b, s, heads * hd) @ w["wo"])
        h = rnd(x + rnd(_rmsnorm(a, w["ln1_post"], eps)))
        n = rnd(_rmsnorm(h, w["ln2"], eps))
        gated = rnd(jax.nn.silu(rnd(n @ w["w1"])) * rnd(n @ w["w3"]))
        m = rnd(gated @ w["w2"])
        return rnd(h + rnd(_rmsnorm(m, w["ln2_post"], eps))), k, v


def _end_of_step(x, final_norm, gate_w, gate_b, *, eps, dtype=None):
    """-> (the normed state, the gate's exit probability [B, S])."""
    rnd = _rounder(dtype)
    with jax.default_matmul_precision("highest"):
        x = rnd(_rmsnorm(x, final_norm.astype(jnp.float32), eps))
        return x, jax.nn.sigmoid(x @ gate_w.astype(jnp.float32)
                                 + gate_b.astype(jnp.float32))


def _unembed(x, lm_head):
    with jax.default_matmul_precision("highest"):
        return x @ lm_head.astype(jnp.float32)


def exit_distribution(gates: list):
    """gates: T arrays [B, S] -> [B, S, T], summing to 1 over T."""
    stay, out = jnp.ones_like(gates[0]), []
    for lam in gates[:-1]:
        out.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(out + [stay], axis=-1)


def forward_and_cache(weights: Weights, tokens, config: dict, eps=None,
                      dtype=None, passes_kept: int = 0):
    """tokens [B, S] int -> (logits [B, S, vocab] float32, exit distribution
    [B, S, T] float32, what a cache would hold of the first ``passes_kept``
    loop steps: the rotated keys and the values of every (loop step t, layer
    l), ``{"k", "v"}`` each [passes_kept * L, B, S, KVH, hd] float32 in
    slot order ``t * L + l``). ``eps``: RMSNorm's epsilon where it is not
    the configuration's published one. ``dtype``: the same code with every
    activation (the result of each matmul, norm, rotation, softmax, product
    and residual sum) rounded to that type, accumulations still in float32:
    what rounding alone does to this model's logits, keys and values, the
    floor the serving app judges the system against."""
    eps = float(config["rms_norm_eps"] if eps is None else eps)
    layer = jax.jit(_layer, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "dtype"))
    end = jax.jit(_end_of_step, static_argnames=("eps", "dtype"))
    x = weights.embed[tokens].astype(jnp.float32)
    gates, keys, values = [], [], []
    for t in range(config["total_ut_steps"]):
        for i in range(weights.n_layers):
            x, k, v = layer(x, weights.layer(i),
                            heads=config["num_attention_heads"],
                            kv_heads=config.get("num_key_value_heads")
                            or config["num_attention_heads"],
                            theta=float(config["rope_theta"]), eps=eps,
                            dtype=dtype)
            if t < passes_kept:
                keys.append(k)
                values.append(v)
        x, lam = end(x, weights.final_norm, weights.gate_w, weights.gate_b,
                     eps=eps, dtype=dtype)
        gates.append(lam)
    cache = {"k": jnp.stack(keys), "v": jnp.stack(values)} if keys else {}
    return (jax.jit(_unembed)(x, weights.lm_head), exit_distribution(gates),
            cache)


def forward_and_exits(weights: Weights, tokens, config: dict, eps=None,
                      dtype=None):
    """The logits and the exit distribution of ``forward_and_cache``."""
    return forward_and_cache(weights, tokens, config, eps, dtype)[:2]


def forward(weights: Weights, tokens, config: dict, eps=None, dtype=None):
    """The logits of ``forward_and_exits``."""
    return forward_and_exits(weights, tokens, config, eps, dtype)[0]


def int8_weights(weights: Weights) -> Weights:
    """The control: the same weights rounded to 8 bits (absmax per output
    channel, symmetric) and handed back as the values they then are, one
    layer at a time; norms' scales and the gate as they are. The nearest
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
        layer=lambda i: {k: q(v) for k, v in weights.layer(i).items()})


def errors_a_position(got, reference):
    """[..., n] each -> the rms over the last dim of ``got - reference``,
    one number a position (the leading dims, flattened)."""
    err = jnp.asarray(got, jnp.float32) - jnp.asarray(reference, jnp.float32)
    return jnp.sqrt(jnp.mean(err ** 2, axis=-1)).reshape(-1)


def over_floor(errors, floor_errors) -> dict:
    """Errors over their own floors, one for one (arrays of one shape): the
    ``typical`` ratio, their geometric mean, and the ``worst``. A looped
    stack of random weights amplifies a rounding by orders of magnitude at
    some positions and hardly at others, the system's and the reference's
    alike; the ratio of the two rms errors over all positions together is
    the ratio at the few worst ones and swings with the seed (0.76 .. 2.65
    over 12 sound seeds), the mean of the positions' own ratios does not.
    The mean alone would let one position pass that is off a thousandfold
    (it moves the mean of 32 by 1.24 x): the worst ratio is held as well."""
    import numpy as np      # plain host arithmetic: the benchmark's own
    #                         process judges with this and opens no backend
    ratio = (np.asarray(errors, np.float64)
             / np.asarray(floor_errors, np.float64)).reshape(-1)
    return {"typical": float(np.exp(np.mean(np.log(ratio)))),
            "worst": float(np.max(ratio))}


def cache_errors(got: dict, reference: dict, prompt: int):
    """What a cache holds against what the reference's layers made: ``got``
    and ``reference`` ``{"k", "v"}`` of [slots, B, S', KVH, hd] each (the
    first slots and positions that both hold are compared) -> [slots, 2, 2]:
    the rms error of a slot's keys (0) and values (1) over the prompt's
    positions (0, written by ``prefill``) and over those after it (1, by
    ``decode_step``; NaN where there are none). The keys and values of the
    first loop step's first layers are a norm and one matmul away from the
    embedding: nothing has amplified anything yet, so what weights of
    fewer bits do to them stands clear of what rounded activations do, on
    every seed; at the logits, 192 layers on, it does not."""
    out = []
    for name in ("k", "v"):
        a, b = got[name], reference[name]
        slots, s = min(a.shape[0], b.shape[0]), min(a.shape[2], b.shape[2])
        err = (jnp.asarray(a[:slots, :, :s], jnp.float32)
               - jnp.asarray(b[:slots, :, :s], jnp.float32)) ** 2
        out.append(jnp.stack([
            jnp.sqrt(jnp.mean(err[:, :, :prompt], axis=(1, 2, 3, 4))),
            jnp.sqrt(jnp.mean(err[:, :, prompt:], axis=(1, 2, 3, 4)))],
            axis=-1))
    return jnp.stack(out, axis=1)


def token_deficits(reference, tokens):
    """``reference`` [B, T, vocab], ``tokens`` [B, T] -> [B, T]: how far
    under the reference's best logit each token's logit lies."""
    reference = jnp.asarray(reference, jnp.float32)
    chosen = jnp.take_along_axis(reference, jnp.asarray(tokens)[..., None],
                                 -1)[..., 0]
    return reference.max(-1) - chosen


def token_deficit_over_floor(reference, floor, tokens) -> float:
    """The widest gap of a token under the reference's best, each over what
    rounding alone does to the logits AT ITS OWN POSITION (the rms over the
    vocabulary of ``floor``, the reference with its activations rounded on
    the same sequences, less ``reference``). A near-tie that the system's
    rounding decided the other way lies within a few such errors of 0,
    whatever the position amplifies; a token that is not the model's lies
    the logits' own spread under the best, tens to hundreds of them."""
    return float(jnp.max(
        token_deficits(reference, tokens).reshape(-1)
        / errors_a_position(floor, reference)))


def compare_exits(system, reference) -> dict:
    """Exit distributions [..., T]: how far the system's rows are from
    summing to 1, and its largest and its rms distance from the
    reference's (probabilities: absolute)."""
    system = jnp.asarray(system, jnp.float32)
    err = system - jnp.asarray(reference, jnp.float32)
    return {"exit_rows_off_one": float(jnp.max(jnp.abs(
                system.sum(-1) - 1.0))),
            "exit_gap": float(jnp.max(jnp.abs(err))),
            "exit_rms": float(jnp.sqrt(jnp.mean(err ** 2)))}

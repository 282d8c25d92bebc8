"""Plain reference for the ``falcon_h1`` family (Falcon-H1, Technology
Innovation Institute: every block holds softmax attention AND a Mamba-2
state-space mixer, both fed the one normed input, their outputs summed into
the residual; muP multipliers on the linear maps): the served forward pass in
straight ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
a Python ``for`` over the layers and a loop over POSITIONS for the
state-space recurrence (``lax.scan``, one position a step: no chunks). No
kernel, no cache, no batching, and nothing imported from ``ray_tpu`` (RMSNorm,
rotate-half RoPE and the comparisons of logits and tokens are the llama and
ouro references' own; the rounding of activations is this file's). Every multiplier is
applied WHERE THE PUBLISHED CODE APPLIES IT, on the activation (the program
folds them into its matrices: ``ray_tpu.models.transformer
.fold_multipliers``). n = RMSNorm at ``rms_norm_eps`` with a scale vector;
no bias but the convolution's:

    x = embedding_multiplier * embed[tokens]
    for l in 0..L-1:
        h = n(x; ln1_l)                                   # input_layernorm
        x = x + attention_out_multiplier * Attn_l(attention_in_multiplier * h)
              + ssm_out_multiplier * SSD_l(ssm_in_multiplier * h)
        y = n(x; ln2_l)                                   # pre_ff_layernorm
        x = x + mlp_multipliers[1] * W2_l (W3_l y * silu(mlp_multipliers[0] * W1_l y))
    logits = lm_head_multiplier * (lm_head . n(x; final_norm))

Attn: ``q = Wq h`` (``num_attention_heads`` x ``head_dim``), ``k =
key_multiplier * Wk h``, ``v = Wv h`` (``num_key_value_heads`` x
``head_dim``); rotate-half RoPE over all of a head's dims at ``rope_theta``;
causal softmax(q k^T / sqrt(head_dim)) v, a KV head shared by H / KVH query
heads in a row; ``Wo``.

SSD (H = ``mamba_n_heads`` heads of P = ``mamba_d_head``, ``mamba_d_ssm`` = H
P; G = ``mamba_n_groups`` groups of N = ``mamba_d_state``; K =
``mamba_d_conv``):

    p = (W_in u) * mup           # [z | x | B | C | dt] = H P + H P + G N + G N + H columns;
                                 # mup scales the five segments by ssm_multipliers[0..4]
    [x | B | C]_t <- silu(conv_bias + sum_{j<K} conv[:, j] [x | B | C]_{t-K+1+j})
                                 # depthwise, causal, zeros before t = 0
    dt = softplus(dt + dt_bias) [H];  A = -exp(A_log) [H]
    per head h, its group g = h // (H / G):
        S_0 = 0 [P, N];  S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T;  y_t = S_t C_t + D x_t
    y <- n_group(y * silu(z); norm)      # ``mamba_norm_before_gate`` false: the gate first, then
                                         # RMSNorm over each of the G groups' H P / G values
    out = W_out y

**As recalled, not fetched** (there is no network here; the configuration
file lists the same under ``assumed``): the segment order of ``in_proj`` and
of ``mup``, the gate before the grouped norm and its groups, the multipliers'
places, no clamp on dt, ``mamba_use_mlp`` read by no path (the block always
has its SwiGLU), attention in every layer (``attn_layer_indices`` null).

What the serving app judges the system by is this same code run once more
with ``dtype=bfloat16`` (its activations rounded, the state and every
accumulation still float32): the floor of what rounding does to the model a
seed drew, to its logits and to what a cache would hold (``forward_and_cache``:
each layer's S, the convolution's last K - 1 inputs, the rotated keys and
the values). Its control is this same code over ``int8_weights``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.llama import _rmsnorm, _rope
# the serving comparisons, under this family's name too
from benchmark.reference.llama import (compare_logits,  # noqa: F401
                                       token_deficit)
from benchmark.reference.ouro import (errors_a_position,  # noqa: F401
                                      over_floor, token_deficit_over_floor)

VOCAB_BLOCK = 16384     # columns of the head a product (float32 beside the
#                         program's weights on the chip)


def _rounder(dtype):
    """Activations kept in ``dtype``: each result is rounded to it and
    carried on in float32 (None: nothing is rounded). By
    ``lax.reduce_precision``, which the compiler has to keep: a pair of
    converts, float32 -> bfloat16 -> float32, is one it may drop where it
    fuses (XLA's ``allow_excess_precision``), and on a TPU the floor of
    this family's state, a sum over positions of products of two rounded
    values, then read a sixth of what the same pass reads on a CPU (my
    chip run, PR 55)."""
    if dtype is None:
        return lambda x: x
    info = jnp.finfo(dtype)
    return lambda x: jax.lax.reduce_precision(
        x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


class Weights(NamedTuple):
    """``layer(i)`` returns layer i's arrays as a dict, as published (no
    multiplier in them): ``wq`` [d, H*hd], ``wk``/``wv`` [d, KVH*hd], ``wo``
    [H*hd, d]; ``w_in`` [d, 2 H P + 2 G N + H] over [z | x | B | C | dt],
    ``conv`` [H P + 2 G N, K] over [x | B | C] (column K-1 weighs the
    current position), ``conv_bias`` [H P + 2 G N], ``dt_bias``, ``A_log``,
    ``D`` [H], ``norm`` [H P], ``w_out`` [H P, d]; ``w1`` (gate) and ``w3``
    (up) [d, ff], ``w2`` (down) [ff, d]; the two input norms' scales
    ``ln1``, ``ln2`` [d]."""
    embed: jax.Array            # [vocab, d]
    layer: Callable[[int], dict]
    n_layers: int
    final_norm: jax.Array       # [d]
    lm_head: jax.Array          # [d, vocab]


def sizes(config: dict) -> dict:
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    if h * p != config["mamba_d_ssm"]:
        raise ValueError(f"mamba_d_ssm {config['mamba_d_ssm']} is not "
                         f"mamba_n_heads x mamba_d_head = {h * p}")
    return dict(heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                hd=config["head_dim"], ssm_heads=h, p=p,
                groups=config["mamba_n_groups"], n=config["mamba_d_state"],
                theta=float(config["rope_theta"]))


def multipliers(config: dict) -> tuple:
    """The published scalars, hashable (a static argument of the jitted
    layer): (attention_in, key, attention_out, ssm_in, ssm_multipliers x 5,
    ssm_out, mlp gate, mlp down)."""
    return (float(config["attention_in_multiplier"]),
            float(config["key_multiplier"]),
            float(config["attention_out_multiplier"]),
            float(config["ssm_in_multiplier"]),
            tuple(float(m) for m in config["ssm_multipliers"]),
            float(config["ssm_out_multiplier"]),
            *(float(m) for m in config["mlp_multipliers"]))


def mup_vector(ssm_multipliers, *, ssm_heads, p, groups, n):
    """[2 H P + 2 G N + H]: ``ssm_multipliers[i]`` over segment i of
    [z | x | B | C | dt]."""
    widths = (ssm_heads * p, ssm_heads * p, groups * n, groups * n,
              ssm_heads)
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(widths, ssm_multipliers)])


def _recurrence(x, b, c, dt, a, d):
    """x [B, S, H, P], b, c [B, S, H, N] (a group's copy a head), dt [B, S,
    H], a, d [H] -> (y [B, S, H, P], S after the last position [B, H, P,
    N]): one position a step."""
    bsz, _, h, p = x.shape

    def step(state, at):
        x_t, b_t, c_t, dt_t = at
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) + d[:, None] * x_t

    state, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt)))
    return jnp.moveaxis(y, 0, 1), state


def _ssd_mixer(u, w, ssm_multipliers, *, ssm_heads, p, groups, n, eps,
               dtype=None):
    """-> (out [B, S, d], S after the last position [B, H, P, N], the
    convolution's last K - 1 inputs [B, K-1, H P + 2 G N])."""
    rnd = _rounder(dtype)
    bsz, s, _ = u.shape
    di, gn = ssm_heads * p, groups * n
    proj = rnd((u @ w["w_in"]) * mup_vector(
        ssm_multipliers, ssm_heads=ssm_heads, p=p, groups=groups, n=n))
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * gn], \
        proj[..., 2 * di + 2 * gn:]
    width = w["conv"].shape[1]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = rnd(jax.nn.silu(w["conv_bias"] + sum(
        padded[:, j:j + s] * w["conv"][:, j] for j in range(width))))
    x = xbc[..., :di].reshape(bsz, s, ssm_heads, p)
    b, c = (jnp.repeat(v.reshape(bsz, s, groups, n), ssm_heads // groups,
                       axis=2)
            for v in (xbc[..., di:di + gn], xbc[..., di + gn:]))
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y, state = _recurrence(x, b, c, dt, -jnp.exp(w["A_log"]), w["D"])
    y = rnd(rnd(y).reshape(bsz, s, di) * jax.nn.silu(z))
    y = rnd(_rmsnorm(y.reshape(bsz, s, groups, di // groups),
                     w["norm"].reshape(groups, di // groups), eps))
    return rnd(y.reshape(bsz, s, di) @ w["w_out"]), state, padded[:, s:]


def _attention_mixer(u, w, key_multiplier, *, heads, kv_heads, hd, theta,
                     dtype=None):
    """-> (out [B, S, d], the rotated keys and the values [B, S, KVH, hd])."""
    rnd = _rounder(dtype)
    bsz, s, _ = u.shape
    q = rnd(_rope(rnd(u @ w["wq"]).reshape(bsz, s, heads, hd), theta))
    k = rnd(_rope(rnd((u @ w["wk"]) * key_multiplier).reshape(
        bsz, s, kv_heads, hd), theta))
    v = rnd(u @ w["wv"]).reshape(bsz, s, kv_heads, hd)
    kk, vv = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = rnd(jnp.einsum("bhqk,bkhd->bqhd",
                          rnd(jax.nn.softmax(scores, axis=-1)), vv))
    return rnd(attn.reshape(bsz, s, heads * hd) @ w["wo"]), k, v


def _layer(x, w, *, mult, heads, kv_heads, hd, ssm_heads, p, groups, n,
           theta, eps, dtype=None):
    """One block -> (x, what a cache would hold of it: S, the convolution's
    last inputs, the rotated keys, the values)."""
    rnd = _rounder(dtype)
    attn_in, key, attn_out, ssm_in, ssm, ssm_out, gate, down = mult
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        h = rnd(_rmsnorm(x, w["ln1"], eps))
        a, k, v = _attention_mixer(
            rnd(h * attn_in), w, key, heads=heads, kv_heads=kv_heads, hd=hd,
            theta=theta, dtype=dtype)
        m, state, tail = _ssd_mixer(
            rnd(h * ssm_in), w, ssm, ssm_heads=ssm_heads, p=p, groups=groups,
            n=n, eps=eps, dtype=dtype)
        x = rnd(x + rnd(rnd(a * attn_out) + rnd(m * ssm_out)))
        y = rnd(_rmsnorm(x, w["ln2"], eps))
        gated = rnd(rnd(y @ w["w3"])
                    * jax.nn.silu(rnd((y @ w["w1"]) * gate)))
        return rnd(x + rnd((gated @ w["w2"]) * down)), \
            {"state": state, "tail": tail, "k": k, "v": v}


def _final_norm(x, final_norm, *, eps, dtype=None):
    return _rounder(dtype)(_rmsnorm(x, final_norm.astype(jnp.float32), eps))


def _unembed(x, columns):
    with jax.default_matmul_precision("highest"):
        return x @ columns.astype(jnp.float32)


def forward_and_cache(weights: Weights, tokens, config: dict, eps=None,
                      dtype=None, rows_a_pass: int = 2):
    """tokens [B, S] int -> (logits [B, S, vocab] float32, what a cache
    would hold after position S - 1, in layer order, float32: ``{"state":
    [L, B, H, P, N], "tail": [L, B, K-1, H P + 2 G N], "k", "v": [L, B, S,
    KVH, hd]}``). ``eps``: RMSNorm's epsilon where it is not the
    configuration's published one. ``dtype``: the same code with every
    activation (the result of each matmul, norm, convolution, softmax,
    product and residual sum) rounded to that type, the state and every
    accumulation still in float32: the floor the serving app judges the
    system against. ``rows_a_pass`` rows at a time and a layer's weights
    upcast at a time, so that the float32 pass fits beside the program's
    weights on the chip."""
    eps = float(config["rms_norm_eps"] if eps is None else eps)
    layer = jax.jit(_layer, static_argnames=(
        "mult", "heads", "kv_heads", "hd", "ssm_heads", "p", "groups", "n",
        "theta", "eps", "dtype"))
    norm = jax.jit(_final_norm, static_argnames=("eps", "dtype"))
    unembed = jax.jit(_unembed)
    rnd = _rounder(dtype)
    fixed = dict(sizes(config), mult=multipliers(config), eps=eps,
                 dtype=dtype)
    vocab = weights.lm_head.shape[1]
    logits, kept = [], []
    for lo in range(0, tokens.shape[0], rows_a_pass):
        x = rnd(weights.embed[tokens[lo:lo + rows_a_pass]].astype(jnp.float32)
                * float(config["embedding_multiplier"]))
        rows = []
        for i in range(weights.n_layers):
            x, cached = layer(x, weights.layer(i), **fixed)
            # one layer's float32 weights at a time: the next are not made
            # while this layer still runs on them
            x.block_until_ready()
            rows.append(cached)
        x = norm(x, weights.final_norm, eps=eps, dtype=dtype)
        logits.append(float(config["lm_head_multiplier"]) * jnp.concatenate(
            [unembed(x, weights.lm_head[:, at:at + VOCAB_BLOCK])
             for at in range(0, vocab, VOCAB_BLOCK)], axis=-1))
        kept.append({name: jnp.stack([r[name] for r in rows])
                     for name in rows[0]})
    return jnp.concatenate(logits), {
        name: jnp.concatenate([k[name] for k in kept], axis=1)
        for name in kept[0]}


def forward(weights: Weights, tokens, config: dict, eps=None, dtype=None):
    """The logits of ``forward_and_cache``."""
    return forward_and_cache(weights, tokens, config, eps, dtype)[0]


def int8_weights(weights: Weights) -> Weights:
    """The control: the same weights rounded to 8 bits (absmax per output
    channel, symmetric) and handed back as the values they then are, one
    layer at a time; vectors (norms' scales, ``A_log``, ``dt_bias``, ``D``,
    the convolution's bias) as they are, the convolution's K taps a channel
    among the matrices. The nearest precision under the served bfloat16
    that a later PR could be tempted by; ``correct`` has to refuse it."""
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


def cache_errors(got: dict, reference: dict, prompt: int) -> dict:
    """What a cache holds against what the reference's layers made, each
    ``{"state", "tail", "k", "v"}`` as ``forward_and_cache`` lays them out
    -> the rms error a slot: ``{"state": [L], "tail": [L], "kv": [L, 2, 2]:
    keys and values, over the prompt's positions (written by ``prefill``)
    and over those after it (by ``decode_step``; NaN where there are
    none)}``. The first layer's are a norm, a projection and a convolution
    away from the embedding: nothing has amplified anything yet."""
    def err2(name):
        return (jnp.asarray(got[name], jnp.float32)
                - jnp.asarray(reference[name], jnp.float32)) ** 2

    def rms(name):
        e = err2(name)
        return jnp.sqrt(jnp.mean(e.reshape(e.shape[0], -1), axis=1))

    kv = jnp.stack([jnp.stack([
        jnp.sqrt(jnp.mean(err2(name)[:, :, :prompt], axis=(1, 2, 3, 4))),
        jnp.sqrt(jnp.mean(err2(name)[:, :, prompt:], axis=(1, 2, 3, 4)))],
        axis=-1) for name in ("k", "v")], axis=1)
    return {"state": rms("state"), "tail": rms("tail"), "kv": kv}

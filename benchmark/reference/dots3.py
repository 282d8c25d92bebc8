"""Plain reference for the ``dots3`` family (dots3-note-prev's language
model): the forward pass in straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, the expanded form. No cache, no
chunks, no absorption, no kernel, and nothing imported from
``ray_tpu.models``. ``x`` is a position's residual, RMSNorm ``n`` with the
configuration's epsilon, no biases, RoPE by rotate-half on the rope dims.

Full layer (``layer_types[l] == "full_attention"``), ``h = n1(x)``:

    c_q = n(W_dq h) * sqrt(d / q_rank)          [c_kv ; k_r] = W_dkv h
    c_kv = n(c_kv) * sqrt(d / kv_rank)          k_r = RoPE(k_r), one a position
    [q_nope_i ; q_rope_i] = W_uq c_q            [k_nope_i ; v_i] = W_ukv c_kv
    logits_i[t, s] = (q_nope_i[t] . k_nope_i[s] + RoPE(q_rope_i)[t] . k_r[s])
                     / sqrt(nope + rope)
    indexer: qI_j = RoPE64(W_qI c_q), kI = RoPE64(LayerNorm(W_kI h)),
             w = W_w h / sqrt(heads_I * dim_I)
             I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])
             S_t = the index_topk positions s <= t of largest I[t, s]
                   (all of them while t < index_topk)
    a_i[t] = softmax over S_t of logits_i[t, .] . v_i
    o = W_o concat_i(sigmoid(W_g h)_i * a_i)

Sliding layer: the same without indexer at the ``swa_*`` widths, keys
``0 <= t - s < sliding_window_size``.

Feed-forward: the first ``first_k_dense_replace`` layers SwiGLU of
``intermediate_size``; every other layer ``s = sigmoid(W_r h)`` in float32,
the ``num_experts_per_tok`` experts of largest ``s + b``, weights ``s_e /
sum_selected(s) * routed_scaling_factor``, of which this member of the
expert group adds the ``experts_held`` it holds from ``first_expert`` on,
plus one ungated shared SwiGLU expert on every token.

Sized for one chip beside the system's own 8 GB of weights: a row at a time,
a layer at a time, each weight cast up where it is used, attention by groups
of heads over blocks of queries against the keys the block can reach (a
head group's keys and values of every position are materialised, the
expanded form; no [heads, S, S] tensor is; the host waits for a group's last
block before it asks for the next group's keys, since a buffer is held from
the asking on and the host runs ahead of the chip),
experts one at a time over the positions routed to them.

``forward(..., dtype=bfloat16)`` is the same code with every activation
rounded to that type: the floor the app judges the system's logits by. Its
matmuls then run at the type itself with float32 accumulation, which gives
what ``highest`` gives on operands that the type holds exactly, at a sixth
of the passes. (The two passes are two sets of programs to compile, a
minute or two more on a chip's host. One set handed a flag "round or not",
``highest`` throughout, was tried in PR 45: on the chip its floor read 0 at
some position and the check divided by it, where the CPU had rounded as
asked; cause not found, not kept.) ``int8_weights`` is the precision
control.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.llama import (compare_logits,  # noqa: F401
                                       token_deficit)
from benchmark.reference.ouro import (errors_a_position,  # noqa: F401
                                      over_floor)

LAYER_NORM_EPS = 1e-6
F32 = jnp.float32


class Weights(NamedTuple):
    """``layer(i)`` returns layer i's arrays: ``ln1``/``ln2`` [d]; ``attn``:
    ``wdq`` [d, q_rank], ``q_norm``, ``wuq`` [q_rank, H, nope + rope],
    ``wdkv`` [d, kv_rank + rope], ``kv_norm``, ``wukv`` [kv_rank, H, nope +
    v], ``wo`` [H, v, d], ``wg`` [d, H] and, in a full layer, ``index``:
    ``wq`` [q_rank, J, D], ``wk`` [d, D], ``k_norm_w``/``k_norm_b`` [D],
    ``ww`` [d, J]; and ``mlp`` (``w1``, ``w3`` [d, f], ``w2`` [f, d]) or
    ``moe``: ``router`` [d, E], ``router_bias`` [E], ``shared`` (an
    ``mlp``) and ``expert``, which returns held expert e's ``(w1, w3,
    w2)`` (sliced out one at a time: 32 of them are 1.5 GB a layer)."""
    embed: jax.Array
    layer: Callable[[int], dict]
    n_layers: int
    final_norm: jax.Array
    lm_head: jax.Array


def dims_of(config: dict, kind: str) -> dict:
    """A layer kind's widths from the published keys."""
    pre = "" if kind == "full_attention" else "swa_"
    return dict(
        heads=config[pre + "num_attention_heads"],
        q_rank=config[pre + "q_lora_rank"],
        kv_rank=config[pre + "kv_lora_rank"],
        nope=config[pre + "qk_nope_head_dim"],
        rope=config[pre + "qk_rope_head_dim"], v=config[pre + "v_head_dim"],
        theta=float(config[pre + "rope_theta"]),
        window=0 if kind == "full_attention"
        else config["sliding_window_size"])


def _rounder(dtype):
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(F32)


def _mm(dtype):
    """einsum at float32 ``highest``, or at ``dtype`` with float32
    accumulation on operands that hold it exactly."""
    if dtype is None:
        return partial(jnp.einsum, precision="highest")
    return lambda spec, a, b: jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype), preferred_element_type=F32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _layer_norm(x, w, b):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) \
        * w.astype(F32) + b.astype(F32)


def _rope(x, positions, theta, rotary=None):
    """x [S, ..., D], positions [S]: rotate-half over the first ``rotary``
    dims (all when None)."""
    d = x.shape[-1]
    if rotary is not None and rotary < d:
        return jnp.concatenate(
            [_rope(x[..., :rotary], positions, theta), x[..., rotary:]], -1)
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    angles = positions.astype(F32)[:, None] * inv_freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("z", "eps", "rescale", "dtype", "d"))
def _latents(x, positions, ln1, a, *, z, eps, rescale, dtype, d):
    """A block of positions [P, d] -> per position: the two latents, the
    rotated shared key, every head's output gate and, in a full layer, the
    indexer's key and head weights. (The normed input itself, 0.67 GB at
    32k positions, is not kept for the gate's sake.)"""
    z = dict(z)
    rnd, mm = _rounder(dtype), _mm(dtype)
    h = rnd(_rmsnorm(x, ln1, eps))
    c_q = rnd(_rmsnorm(rnd(mm("pe,er->pr", h, a["wdq"])), a["q_norm"], eps))
    down = rnd(mm("pe,er->pr", h, a["wdkv"]))
    c_kv = rnd(_rmsnorm(down[:, :z["kv_rank"]], a["kv_norm"], eps))
    if rescale:
        c_q = rnd(c_q * math.sqrt(d / z["q_rank"]))
        c_kv = rnd(c_kv * math.sqrt(d / z["kv_rank"]))
    out = {"c_q": c_q, "c_kv": c_kv,
           "k_r": rnd(_rope(down[:, z["kv_rank"]:], positions, z["theta"])),
           "gate": jax.nn.sigmoid(rnd(mm("pe,eh->ph", h, a["wg"])))}
    if "index" in a:
        ix = a["index"]
        ki = rnd(_layer_norm(rnd(mm("pe,ed->pd", h, ix["wk"])),
                             ix["k_norm_w"], ix["k_norm_b"]))
        out["k_i"] = rnd(_rope(ki, positions, z["theta"], z["rope"]))
        out["w_i"] = rnd(mm("pe,ej->pj", h, ix["ww"])
                         * (ix["ww"].shape[1] ** -0.5
                            * ix["wk"].shape[1] ** -0.5))
    return out


def _rows(a, lo, n: int):
    """``a[lo:lo + n]`` with ``lo`` a traced number: one program for every
    block (a slice at a Python number is a program of its own to compile)."""
    return jax.lax.dynamic_slice_in_dim(a, lo, n, axis=0)


@partial(jax.jit, static_argnames=("block", "topk", "theta", "rotary",
                                   "dtype"))
def _select(c_q, w_i, positions, k_i, wq, lo, *, block, topk, theta, rotary,
            dtype):
    """The block of ``block`` queries from ``lo`` on -> (the positions S_t
    [block, topk], which of them are real): ``lax.top_k`` of the float32
    index scores under the causal mask."""
    rnd, mm = _rounder(dtype), _mm(dtype)
    c_q, w_i, qpos = (_rows(x, lo, block) for x in (c_q, w_i, positions))
    q_i = rnd(_rope(rnd(mm("qr,rjd->qjd", c_q, wq)), qpos, theta, rotary))
    dots = jax.nn.relu(mm("qjd,sd->qjs", q_i, k_i))
    scores = jnp.einsum("qjs,qj->qs", dots, w_i, precision="highest")
    causal = jnp.arange(k_i.shape[0])[None, :] <= qpos[:, None]
    top, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    return at, top > -jnp.inf


@partial(jax.jit, static_argnames=("z", "dtype"))
def _keys_values(c_kv, wukv, *, z, dtype):
    """A head group's k_nope and v of every position, from the latent."""
    z = dict(z)
    kv = _rounder(dtype)(_mm(dtype)("sr,rhd->shd", c_kv, wukv))
    return kv[..., :z["nope"]], kv[..., z["nope"]:]


@partial(jax.jit, static_argnames=("z", "dtype", "block", "keys"))
def _attend(c_q, gate, positions, selected, k_nope, v, k_r, wuq, wo, lo,
            klo, *, z, dtype, block, keys):
    """The block of ``block`` queries from ``lo`` on x a group of heads,
    over the ``keys`` keys from ``klo`` on -> its part of ``o`` [block, d]:
    softmax over the keys the block's rows of ``selected`` (positions, which
    are real) name, or where there is no selection over the causal keys
    inside the window; gated (``gate``: the group's columns), through the
    group's rows of W_o."""
    z = dict(z)
    rnd, mm = _rounder(dtype), _mm(dtype)
    c_q, gate, qpos = (_rows(x, lo, block) for x in (c_q, gate, positions))
    k_nope, v, k_r = (_rows(x, klo, keys) for x in (k_nope, v, k_r))
    if selected is not None:
        at, real = (_rows(x, lo, block) for x in selected)
        mask = jnp.zeros((block, keys), bool).at[
            jnp.arange(block)[:, None], at - klo].max(real)
    else:
        q, k = qpos[:, None], klo + jnp.arange(keys)[None, :]
        mask = (k <= q) & (q - k < z["window"]) if z["window"] else k <= q
    q = rnd(mm("qr,rhd->qhd", c_q, wuq))
    q_nope = q[..., :z["nope"]]
    q_rope = rnd(_rope(q[..., z["nope"]:], qpos, z["theta"]))
    scores = rnd((mm("qhd,shd->hqs", q_nope, k_nope)
                  + mm("qhd,sd->hqs", q_rope, k_r))
                 / math.sqrt(z["nope"] + z["rope"]))
    p = rnd(jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1))
    a = rnd(mm("hqs,shd->qhd", p, v))
    return mm("qhd,hde->qe", rnd(a * gate[..., None]), wo)


@partial(jax.jit, static_argnames=("dtype",))
def _residual(x, o, *, dtype):
    """``x + o`` (``o`` padded to whole blocks of queries)."""
    rnd = _rounder(dtype)
    return rnd(x + rnd(o[:x.shape[0]]))


@partial(jax.jit, donate_argnums=0)
def _add_rows(total, part, lo):
    """``total[lo:lo + len(part)] += part``, in place where the backend
    lets a buffer be given away."""
    at = (lo, 0)
    return jax.lax.dynamic_update_slice(
        total, jax.lax.dynamic_slice(total, at, part.shape) + part, at)


def _swiglu(h, m, rnd, mm):
    gate = rnd(jax.nn.silu(rnd(mm("pe,ef->pf", h, m["w1"]))))
    up = rnd(mm("pe,ef->pf", h, m["w3"]))
    return rnd(mm("pf,fe->pe", rnd(gate * up), m["w2"]))


@partial(jax.jit, static_argnames=("eps", "dtype"))
def _dense_ffn(x, ln2, m, *, eps, dtype):
    rnd, mm = _rounder(dtype), _mm(dtype)
    return rnd(x + _swiglu(rnd(_rmsnorm(x, ln2, eps)), m, rnd, mm))


@partial(jax.jit, static_argnames=("eps", "dtype", "k", "scaling",
                                   "norm_topk"))
def _route(x, ln2, router, bias, *, eps, dtype, k, scaling, norm_topk):
    """-> (the normed input [P, d], every published expert's weight a
    position [P, E], 0 where it is not among the position's k)."""
    rnd, mm = _rounder(dtype), _mm(dtype)
    h = rnd(_rmsnorm(x, ln2, eps))
    s = jax.nn.sigmoid(mm("pe,ex->px", h, router))
    _, chosen = jax.lax.top_k(s + bias.astype(F32), k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        picked = picked / picked.sum(-1, keepdims=True)
    weights = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(picked * scaling)
    return h, weights


@partial(jax.jit, static_argnames=("dtype",), donate_argnums=0)
def _one_expert(acc, h, rows, weight, w1, w3, w2, *, dtype):
    """``acc[rows] += weight * swiglu(h[rows])``: one expert over the
    positions routed to it (``rows`` padded with weight 0)."""
    rnd, mm = _rounder(dtype), _mm(dtype)
    y = _swiglu(h[rows], {"w1": w1, "w3": w3, "w2": w2}, rnd, mm)
    return acc.at[rows].add(y * weight[:, None])


@partial(jax.jit, static_argnames=("dtype",))
def _shared_and_residual(x, acc, h, m, *, dtype):
    rnd, mm = _rounder(dtype), _mm(dtype)
    return rnd(x + rnd(rnd(acc) + _swiglu(h, m, rnd, mm)))


@partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(x, final_norm, lm_head, *, eps, dtype):
    rnd, mm = _rounder(dtype), _mm(dtype)
    return mm("pe,ev->pv", rnd(_rmsnorm(x, final_norm, eps)), lm_head)


def _blocks(n: int, block: int):
    return [(lo, min(lo + block, n)) for lo in range(0, n, block)]


def _row(weights: Weights, tokens, config: dict, *, eps, dtype, keep_from,
         sizes: dict) -> dict:
    """One row [S] through the stack."""
    s = int(tokens.shape[0])
    d = config["hidden_size"]
    positions = jnp.arange(s)
    x = _rounder(dtype)(weights.embed[tokens].astype(F32))
    held, first = config["n_routed_experts"], config.get("first_expert", 0)
    out = {"latent": [], "index": [], "window": [], "selected": [],
           "selected_real": [], "moe_rows_here": 0, "routed_inputs": [],
           "routed_weights": []}
    route_from = max(0, keep_from + 1 - ROUTING_POSITIONS)
    for i in range(weights.n_layers):
        w = weights.layer(i)
        kind = config["layer_types"][i]
        z = dims_of(config, kind)
        zkey = tuple(sorted(z.items()))
        a = w["attn"]
        per = [_latents(x[lo:hi], positions[lo:hi], w["ln1"], a, z=zkey,
                        eps=eps, rescale=bool(config.get(
                            "apply_mla_qkv_lora_rescale")), dtype=dtype, d=d)
               for lo, hi in _blocks(s, sizes["positions"])]
        per = {k: jnp.concatenate([b[k] for b in per]) for k in per[0]}
        entry = jnp.concatenate([per["c_kv"], per["k_r"]], -1)
        # the queries' arrays padded to whole blocks: a padded query sees
        # every key, and its row is dropped at the end
        blocks = (sizes["queries"], sizes["index_queries"])
        padded = -(-s // max(blocks)) * max(blocks)
        assert padded % min(blocks) == 0, blocks
        qs = {k: jnp.pad(per.pop(k), ((0, padded - s), (0, 0)))
              for k in ("c_q", "gate", "w_i") if k in per}
        qs["positions"] = jnp.arange(padded)
        selected = None
        if kind == "full_attention":
            out["latent"].append(entry)
            out["index"].append(per["k_i"])
            topk = config["index_topk"]
            if s > topk:
                picks = [_select(qs["c_q"], qs["w_i"], qs["positions"],
                                 per["k_i"], a["index"]["wq"], lo,
                                 block=blocks[1], topk=topk,
                                 theta=z["theta"], rotary=z["rope"],
                                 dtype=dtype)
                         for lo in range(0, padded, blocks[1])]
                selected = (jnp.concatenate([p[0] for p in picks]),
                            jnp.concatenate([p[1] for p in picks]))
                del picks
                out["selected"].append(selected[0][keep_from:s])
                out["selected_real"].append(selected[1][keep_from:s])
        else:
            out["window"].append(entry)

        def keys_of(lo):
            """(first, count) of the keys a block of queries is given: in a
            sliding layer a stretch of one length that holds its windows;
            in a full layer all up to its last query, rounded up to
            KEYS_STEP, or every key where that is less than half a step
            more (a stretch of another length is another program to
            compile, a quarter of a minute on a chip's host: at 32,896
            positions there are two)."""
            hi = min(lo + blocks[0], s)
            if z["window"]:
                count = min(s, blocks[0] + z["window"] - 1)
                return max(0, hi - count), count
            count = -(-hi // KEYS_STEP) * KEYS_STEP
            return 0, s if count + KEYS_STEP // 2 > s else count

        o = jnp.zeros((padded, d), F32)
        hg = sizes["heads"]
        for g in range(0, z["heads"], hg):
            k_nope, v = _keys_values(per["c_kv"], a["wukv"][:, g:g + hg],
                                     z=zkey, dtype=dtype)
            gate, wuq, wo = (qs["gate"][:, g:g + hg], a["wuq"][:, g:g + hg],
                             a["wo"][g:g + hg])
            for lo in range(0, padded, blocks[0]):
                klo, keys = keys_of(lo)
                o = _add_rows(o, _attend(
                    qs["c_q"], gate, qs["positions"], selected, k_nope, v,
                    per["k_r"], wuq, wo, lo, klo, z=zkey, dtype=dtype,
                    block=blocks[0], keys=keys), lo)
            # a group's keys and values go before the next group's come:
            # the host runs ahead of the chip, and what it has asked for is
            # held from the asking on
            o.block_until_ready()
            k_nope = v = None
        x = _residual(x, o, dtype=dtype)
        del per, qs, o, k_nope, v, selected, gate, wuq, wo
        if "mlp" in w:
            x = jnp.concatenate([
                _dense_ffn(x[lo:hi], w["ln2"], w["mlp"], eps=eps, dtype=dtype)
                for lo, hi in _blocks(s, sizes["positions"])])
            continue
        m = w["moe"]
        blocks, tail_h, tail_w = [], [], []
        for lo, hi in _blocks(s, sizes["positions"]):
            h, weight = _route(
                x[lo:hi], w["ln2"], m["router"], m["router_bias"], eps=eps,
                dtype=dtype, k=config["num_experts_per_tok"],
                scaling=float(config.get("routed_scaling_factor", 1.0)),
                norm_topk=bool(config.get("norm_topk_prob")))
            out["moe_rows_here"] += int(
                (weight[:, first:first + held] > 0).sum())
            if hi > route_from:
                tail_h.append(h[max(route_from - lo, 0):])
                tail_w.append(weight[max(route_from - lo, 0):])
            acc = jnp.zeros_like(h)
            routed = np.asarray(weight[:, first:first + held])
            for e in range(held):
                rows = np.nonzero(routed[:, e])[0]
                pad = -len(rows) % ROUTED_ROWS_PAD      # few shapes to compile
                acc = _one_expert(
                    acc, h, np.pad(rows, (0, pad)),
                    np.pad(routed[rows, e], (0, pad)), *m["expert"](e),
                    dtype=dtype)
            blocks.append(_shared_and_residual(x[lo:hi], acc, h,
                                               m["shared"], dtype=dtype))
        x = jnp.concatenate(blocks)
        out["routed_inputs"].append(jnp.concatenate(tail_h))
        out["routed_weights"].append(jnp.concatenate(tail_w))
        del blocks, m, w, a
    out["logits"] = _head(x[keep_from:], weights.final_norm, weights.lm_head,
                          eps=eps, dtype=dtype)
    return out


# A full layer's block of queries is given its keys up to a multiple of this.
KEYS_STEP = 16384
# An expert's routed positions are padded (weight 0) to a multiple of this.
ROUTED_ROWS_PAD = 128
# The expert layers' inputs and routing weights are handed back for the
# last this many positions up to ``keep_from``.
ROUTING_POSITIONS = 256
# Blocks that fit one chip beside the system at the published widths; the
# tests run the same code with the whole of a toy sequence in one block.
# 16 x 2,056 positions are the 32,768 + 128 of the cell: no shorter last
# block, which would be a second program of every kind to compile.
CHIP_SIZES = {"positions": 2056, "index_queries": 32, "queries": 64,
              "heads": 8}


def forward_and_details(weights: Weights, tokens, config: dict, eps=None,
                        dtype=None, keep_from: int = 0,
                        sizes: dict = CHIP_SIZES) -> dict:
    """tokens [B, S] -> ``logits`` [B, S - keep_from, vocab] of the
    positions from ``keep_from`` on, and what the layers made on the way:
    ``latent`` [full layers, B, S, kv_rank + rope] (``[c_kv ; k_r]``, what
    a cache would hold of a full layer) and ``index`` [full layers, B, S,
    D] (the indexer's keys), ``window`` [sliding layers, B, S, ...] (the
    same of the sliding layers), ``selected`` / ``selected_real`` [full
    layers, B, S - keep_from, topk] (S_t of the kept positions; empty
    while S <= index_topk), ``moe_rows_here`` (pairs routed to held
    experts), ``routed_inputs`` [expert layers, B, n, d] and
    ``routed_weights`` [expert layers, B, n, published experts] (the
    expert layers' normed inputs and every expert's weight, 0 where not
    chosen, of the last ROUTING_POSITIONS positions up to ``keep_from`` and
    those after it)."""
    eps = float(config["rms_norm_eps"] if eps is None else eps)
    rows = [_row(weights, tokens[b], config, eps=eps, dtype=dtype,
                 keep_from=keep_from, sizes=sizes)
            for b in range(tokens.shape[0])]
    out = {"logits": jnp.stack([r["logits"] for r in rows]),
           "moe_rows_here": sum(r["moe_rows_here"] for r in rows)}
    for name in ("latent", "index", "window", "selected", "selected_real",
                 "routed_inputs", "routed_weights"):
        out[name] = jnp.stack([jnp.stack(r[name]) for r in rows], axis=1) \
            if rows[0][name] else None
    return out


def forward(weights: Weights, tokens, config: dict, eps=None, dtype=None):
    """tokens [B, S] -> float32 logits [B, S, vocab]."""
    return forward_and_details(weights, tokens, config, eps, dtype)["logits"]


def quantize(w):
    """A matrix rounded to 8 bits (absmax per output channel, the last dim;
    symmetric) as the float32 values it then is; a vector as it is."""
    if w.ndim < 2:
        return w
    w = w.astype(F32)
    scale = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)),
                    keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


def int8_weights(weights: Weights) -> Weights:
    """The control: the same weights through ``quantize``, one layer at a
    time. Norm scales and the router's bias stay."""
    def layer(i: int) -> dict:
        one = dict(weights.layer(i))
        moe = one.get("moe")
        if moe:
            one["moe"] = {k: v for k, v in moe.items() if k != "expert"}
        one = jax.tree.map(quantize, one)
        if moe:
            one["moe"]["expert"] = lambda e: tuple(
                quantize(w) for w in moe["expert"](e))
        return one

    return weights._replace(
        embed=quantize(weights.embed.T).T, lm_head=quantize(weights.lm_head),
        layer=layer)


def selection_overlap(got, got_real, want, want_real) -> float:
    """The share of the reference's selected positions (``want`` [...,
    topk] where ``want_real``) that ``got`` holds too."""
    got, want = np.asarray(got), np.asarray(want)
    got_real, want_real = np.asarray(got_real), np.asarray(want_real)
    hit = total = 0
    for g, gr, w, wr in zip(got.reshape(-1, got.shape[-1]),
                            got_real.reshape(-1, got.shape[-1]),
                            want.reshape(-1, want.shape[-1]),
                            want_real.reshape(-1, want.shape[-1])):
        mine, theirs = set(g[gr].tolist()), set(w[wr].tolist())
        hit += len(mine & theirs)
        total += len(theirs)
    return hit / max(total, 1)


def rms(got, reference) -> float:
    err = jnp.asarray(got, F32) - jnp.asarray(reference, F32)
    return float(jnp.sqrt(jnp.mean(err ** 2)))

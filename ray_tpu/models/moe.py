"""The expert layer: top-k routing with no capacity per expert over a share
of the experts, with an optional shared expert.

The router scores every published expert (``cfg.num_experts`` wide, in
float32: softmax, or sigmoid with a correction bias that enters the
selection and not the weights), keeps the ``expert_top_k`` largest and,
under ``norm_topk_prob``, divides them by their sum. This program holds
``cfg.held`` experts from ``cfg.first_expert`` on: the (token, expert) pairs
that fall on them are sorted by expert, their rows gathered, run through the
experts as one grouped matmul a weight (``lax.ragged_dot``), weighted and
added back to their tokens. No one-hot dispatch tensor, no capacity per
expert: an expert takes as many rows as are routed to it. What the experts
held elsewhere would add is left out, and nothing stands in for them or for
their exchange: under expert parallelism that partial result is what this
member of the group contributes (the all-to-all itself is not built; the
``expert`` logical axis still places the weights over an ``ep`` mesh axis,
where GSPMD gathers them). The shared expert, ``swiglu(x)``, gated by
``sigmoid(w_g . x)`` where it holds a ``gate``, is computed for every
token.

The buffer of routed rows is static: ``BUFFER_OVER_MEAN`` x the mean of the
pairs routed here (tokens x top_k x held / experts), and never more than
the most any routing can send (tokens x min(top_k, held)). A program that
holds a quarter of the experts or more therefore never drops a pair; one
that holds a smaller share drops, AND counts, what a router sends past 4 x
its mean: at a sixteenth with top-10, the pairs of a router collapsed onto
three or more of this share's experts at once. The counters go out with the
result: ``rows_here`` (pairs routed to held experts), ``rows_dropped``,
``rows_walked`` (rows of the blocks walked: ``rows_here - rows_dropped``
rounded up to whole blocks, at least one and at most the layout), ``load``
(rows of each held expert) and, of a router with a correction bias,
``counts``: the pairs of each of the ``cfg.num_experts`` published
experts, which is what the balancer moves the bias by (``balance_bias``).

Memory follows the buffer; time follows the rows that were routed: the
grouped matmuls cost by their groups' sizes, and every other pass over the
buffer (rows in, mask, weight, cast, rows added back to their tokens, and
the transposes of all of them) walks it a block of ``walk_block`` rows at a
time, from the first block to the last that holds a routed row, forward in
a ``lax.while_loop`` and backward in another (``rows_in``, ``rows_out``: a
loop of a trip count known only on the device has no transpose of its own).
A buffer no larger than one block, a decode step's, is passed over whole
with no loop.

The bias is trained by no gradient (it enters a top-k's selection alone):
after the optimizer's update each expert's bias goes up by
``cfg.router_bias_update_rate`` where the expert took fewer pairs than the
mean over the experts this step, and down where it took more (DeepSeek-V3's
aux-loss-free balancing, arXiv:2412.19437 section 2.1.2), over this
program's own tokens: a deployment sums the counts over its data-parallel
group first, and no exchange runs here.

Scopes ``rt.moe.route``, ``rt.moe.experts``, ``rt.moe.shared`` name the three
parts in the compiled program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# The step's memory follows the buffer's rows, filled or not; its time
# follows the rows that were routed (the walk, below). Measured on one chip's
# sixteenth of 512 experts at 16,384 tokens (PERF.md, PR 37), when the row
# gathers and scatter-adds still cost by the buffer's rows: 1.5 x the mean
# dropped rows from the third step of a router trained from random weights;
# 4 x never did in 46 runs of 45 s (four layers' rows together at most
# 64,077 of 163,840); the full 16 x took 811 ms a step for 633 and 15.2 GB
# for 14.0, and a step stalled for seconds in three of nine runs at that
# size.
BUFFER_OVER_MEAN = 4


# A step of this few tokens or fewer (a decode step's rows) gets the most
# any routing can send: the mean of so few draws says nothing about their
# largest, and in serving a dropped row is a wrong answer.
FEW_TOKENS = 64

# The buffer's arrays are laid out at a multiple of this many rows, whatever
# it takes: the TPU's grouped matmul (``lax.ragged_dot``) computes wrong
# values and gradients over a row count that 8 does not divide (measured on
# a v5e, PR 47: 32,764 rows, what 2 x 8,191 tokens of a multi-token-
# prediction module ask for, read a sum of squares of 187,224 for 2,882,809
# and a weight gradient wholly off; 32,760 and 32,768 rows were right).
ROW_MULTIPLE = 8


def buffer_rows(cfg, tokens: int) -> int:
    """Rows of the routed-pairs buffer for ``tokens`` tokens."""
    most = tokens * min(cfg.expert_top_k, cfg.held)
    mean = -(-tokens * cfg.expert_top_k * cfg.held // cfg.num_experts)
    return most if tokens <= FEW_TOKENS \
        else min(most, BUFFER_OVER_MEAN * mean)


def route(cfg, moe_params, x):
    """x [N, D] -> (weights [N, k] float32, experts [N, k] int32). Softmax
    scoring: the k largest probabilities. Sigmoid scoring: the k experts of
    largest ``sigmoid + router_bias``, weighted by the sigmoid alone (the
    bias corrects the load, not the mixture), times
    ``routed_scaling_factor``."""
    logits = (x @ moe_params["router"].astype(x.dtype)).astype(jnp.float32)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_e = lax.top_k(
            scores + moe_params["router_bias"].astype(jnp.float32),
            cfg.expert_top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    else:
        top_p, top_e = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg.expert_top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    if cfg.routed_scaling_factor != 1.0:
        top_p = top_p * cfg.routed_scaling_factor
    return top_p, top_e


# Rows moved an iteration of the walk, as bytes of a float32 [block, D].
# Measured on a v5e over 2-16 MiB at the three cells' shapes (PERF.md, PR 53
# and 54): the layer's time is flat within 4 % and least about here.
BLOCK_BYTES = 8 << 20


def walk_block(laid: int, d: int) -> int:
    """Rows of one block of the walk over a buffer laid out at ``laid`` rows
    of ``d``: the whole buffer where it is no larger than ``BLOCK_BYTES``,
    else the largest power of two within ``BLOCK_BYTES`` that divides it
    (``ROW_MULTIPLE`` divides every layout)."""
    most = max(ROW_MULTIPLE, BLOCK_BYTES // (4 * d))
    block = 1 << (most.bit_length() - 1)
    if laid <= block:
        return laid
    while laid % block:
        block //= 2
    return block


def blocks_walked(count, laid: int, block: int):
    """Blocks of the buffer that the walk passes over: the first, and every
    other that holds one of the buffer's first ``count`` rows (a static 1
    for a buffer of one block)."""
    return 1 if block == laid \
        else jnp.maximum(1, (count + block - 1) // block)


def _walk(body, init, count, laid: int, block: int):
    """``body(first row, live [block, 1], carry) -> carry`` over the blocks
    walked, ``live`` the rows before ``count``. The first block is passed
    over outside the loop, whatever it holds: a buffer of one block, a
    decode step's, has no loop at all, and what a grouped matmul wrote is
    read by an instruction under the layer's scope and not by a loop's
    operands alone, so that a trace's reduction by scope still finds whose
    kernel it is (benchmark/trace_scopes.py). The body must update its
    carry in place (a dynamic_update_slice, a scatter): the carry is the
    size of the buffer or of the tokens, and a copy of it an iteration
    costs more than the pass the walk replaces."""
    def step(i, carry):
        at = i * block
        return body(at, (at + jnp.arange(block) < count)[:, None], carry)
    first = step(0, init)
    return first if block == laid else lax.fori_loop(
        1, blocks_walked(count, laid, block), step, first)


def _take(a, at, block: int):
    return lax.dynamic_slice_in_dim(a, at, block)


def _gathered(src, token, count, block: int):
    """-> [laid, D]: row i is ``src[token[i]]`` for i < count, zero from
    there on."""
    def body(at, live, out):
        rows = src.at[_take(token, at, block)].get(mode="promise_in_bounds")
        return lax.dynamic_update_slice_in_dim(
            out, jnp.where(live, rows, 0), at, 0)
    laid = token.shape[0]
    return _walk(body, jnp.zeros((laid, src.shape[1]), src.dtype), count,
                 laid, block)


def _added(rows, token, count, block: int, n: int, weight=None):
    """-> [n, D] float32: ``rows[i]`` (x ``weight[i]``) added to
    ``token[i]`` for i < count, in the buffer's order. ``rows`` may be
    several buffers, added a block at a time."""
    several = rows if isinstance(rows, tuple) else (rows,)

    def body(at, live, out):
        part = sum(_take(a, at, block).astype(jnp.float32) for a in several)
        part = jnp.where(live, part, 0.0)
        if weight is not None:
            part = part * _take(weight, at, block)
        return out.at[_take(token, at, block)].add(
            part, mode="promise_in_bounds")
    return _walk(body, jnp.zeros((n, several[0].shape[1]), jnp.float32),
                 count, token.shape[0], block)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def rows_in(x, token, count, block: int):
    """x [N, D] -> the buffer [laid, D], twice: row i is ``x[token[i]]``
    for i < count and zero after. Rows past the last group are no
    expert's: ragged_dot leaves them unwritten, forward and transposed (on
    the TPU they hold whatever the memory held), so they are masked where
    they come in, here, and where they go out (``rows_out``): neither a
    value nor a gradient of theirs reaches a token. The one buffer is handed
    out once for each of the two matmuls that read it, so that their two
    cotangents come back apart and are added a block at a time in the walk,
    not in a pass over the buffer."""
    xs = _gathered(x, token, count, block)
    return xs, xs


def _rows_in_fwd(x, token, count, block):
    xs = _gathered(x, token, count, block)
    return (xs, xs), (x, token, count)


def _rows_in_bwd(block, kept, dxs):
    x, token, count = kept
    with jax.named_scope("rt.moe.experts"):
        dx = _added(dxs, token, count, block, x.shape[0]).astype(x.dtype)
    return dx, None, None


rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def rows_out(ys, weight, token, count, block: int, n: int):
    """The buffer ys [laid, D], weight [laid, 1] float32 -> [n, D] float32:
    ``ys[i] x weight[i]`` added to ``token[i]`` for i < count, in float32
    and in the buffer's order."""
    return _added(ys, token, count, block, n, weight)


def _rows_out_fwd(ys, weight, token, count, block, n):
    return _added(ys, token, count, block, n, weight), \
        (ys, weight, token, count)


def _rows_out_bwd(block, n, kept, dy):
    ys, weight, token, count = kept
    laid = token.shape[0]

    def body(at, live, carry):
        dys, dweight = carry
        g = dy.at[_take(token, at, block)].get(mode="promise_in_bounds")
        held = jnp.where(live, _take(ys, at, block).astype(jnp.float32), 0.0)
        back = jnp.where(live, g * _take(weight, at, block), 0.0)
        return (lax.dynamic_update_slice_in_dim(
                    dys, back.astype(ys.dtype), at, 0),
                lax.dynamic_update_slice_in_dim(
                    dweight, (g * held).sum(-1, keepdims=True), at, 0))
    with jax.named_scope("rt.moe.experts"):
        dys, dweight = _walk(
            body, (jnp.zeros_like(ys), jnp.zeros_like(weight)), count, laid,
            block)
    return dys, dweight, None, None


rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


def moe_apply(cfg, moe_params, h):
    """h: [B, S, D] -> (y [B, S, D], stats)."""
    dt = h.dtype
    b, s, d = h.shape
    n, k, held = b * s, cfg.expert_top_k, cfg.held
    x = h.reshape(n, d)
    rows = buffer_rows(cfg, n)              # what the buffer takes
    laid = -(-rows // ROW_MULTIPLE) * ROW_MULTIPLE    # what it is laid out at
    block = walk_block(laid, d)

    with jax.named_scope("rt.moe.route"):
        top_p, top_e = route(cfg, moe_params, x)
        local = top_e - cfg.first_expert
        here = (local >= 0) & (local < held)
        # pairs held elsewhere sort behind every held expert
        key = jnp.where(here, local, held).reshape(n * k)
        order = jnp.argsort(key, stable=True)
        starts = jnp.searchsorted(key[order], jnp.arange(held + 1),
                                  side="left").astype(jnp.int32)
        load = starts[1:] - starts[:-1]                     # [held]
        ends = jnp.minimum(starts, rows)                    # fit the buffer
        sizes = ends[1:] - ends[:-1]
        if laid > n * k:    # rows added to the layout lie past every group
            order = jnp.pad(order, (0, laid - n * k))
        order = order[:laid]
        token = order // k
        weight = top_p.reshape(n * k)[order][:, None]
        stats = {"rows_here": starts[-1], "load": load,
                 "rows_dropped": starts[-1] - ends[-1],
                 "rows_walked": jnp.asarray(
                     blocks_walked(ends[-1], laid, block) * block,
                     jnp.int32)}
        if "router_bias" in moe_params:
            # what moves the bias (train/jax_step.py): the step's (token,
            # expert) pairs of every published expert, held here or not
            stats["counts"] = jnp.bincount(
                top_e.reshape(n * k), length=cfg.num_experts)

    with jax.named_scope("rt.moe.experts"):
        xs, xs_again = rows_in(x, token, ends[-1], block)   # [laid, D]
        w1, w3, w2 = (moe_params[name].astype(dt)
                      for name in ("w1", "w3", "w2"))
        gate = jax.nn.silu(lax.ragged_dot(xs, w1, sizes))
        up = lax.ragged_dot(xs_again, w3, sizes)
        ys = lax.ragged_dot(gate * up, w2, sizes)           # [laid, D]
        y = rows_out(ys, weight, token, ends[-1], block, n).astype(dt)

    if "shared" in moe_params:
        with jax.named_scope("rt.moe.shared"):
            sh = moe_params["shared"]
            mid = jax.nn.silu(x @ sh["w1"].astype(dt)) \
                * (x @ sh["w3"].astype(dt))
            out = mid @ sh["w2"].astype(dt)
            if "gate" in sh:
                open_ = jax.nn.sigmoid(
                    (x @ sh["gate"].astype(dt)).astype(jnp.float32))
                out = out * open_[:, None].astype(dt)
            y = y + out
    return y.reshape(b, s, d), stats


def balance_bias(cfg, counts):
    """counts [..., E]: a step's pairs of each published expert -> what the
    step adds to the router's correction bias, ``rate x sign(mean -
    count)``, float32."""
    counts = counts.astype(jnp.float32)
    return cfg.router_bias_update_rate * jnp.sign(
        counts.mean(-1, keepdims=True) - counts)


def load_balance_loss(probs, top_e):
    """Switch-style auxiliary loss over the whole router (not a share):
    experts x sum_e (fraction of (token, expert) pairs on e) x (mean router
    probability of e). 1 when both are uniform. probs: [N, E] softmax;
    top_e: [N, k] chosen experts. Not part of ``transformer_loss``: the
    published configurations this model runs leave it off."""
    e = probs.shape[-1]
    pairs = jax.nn.one_hot(top_e, e, dtype=jnp.float32).sum(1)    # [N, E]
    frac_pairs = pairs.sum(0) / jnp.maximum(pairs.sum(), 1.0)
    return e * (frac_pairs * probs.mean(0)).sum()

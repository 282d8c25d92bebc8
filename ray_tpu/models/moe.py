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
``load`` (rows of each held expert) and, of a router with a correction
bias, ``counts``: the pairs of each of the ``cfg.num_experts`` published
experts, which is what the balancer moves the bias by (``balance_bias``).

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

import jax
import jax.numpy as jnp
from jax import lax

# Row gathers and scatter-adds cost by the buffer's rows, filled or not, and
# so does the step's memory. Measured on one chip's sixteenth of 512 experts
# at 16,384 tokens (PERF.md, PR 37): 1.5 x the mean dropped rows from the
# third step of a router trained from random weights; 4 x never did in 46
# runs of 45 s (four layers' rows together at most 64,077 of 163,840); the
# full 16 x took 811 ms a step for 633 and 15.2 GB for 14.0, and a step
# stalled for seconds in three of nine runs at that size.
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


def moe_apply(cfg, moe_params, h):
    """h: [B, S, D] -> (y [B, S, D], stats)."""
    dt = h.dtype
    b, s, d = h.shape
    n, k, held = b * s, cfg.expert_top_k, cfg.held
    x = h.reshape(n, d)
    rows = buffer_rows(cfg, n)              # what the buffer takes
    laid = -(-rows // ROW_MULTIPLE) * ROW_MULTIPLE    # what it is laid out at

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
        # Rows past the last group are no expert's: ragged_dot leaves them
        # unwritten, forward and transposed (on the TPU they hold whatever
        # the memory held). Masked where they come in and where they go
        # out, so that neither a value nor a gradient of theirs reaches a
        # token.
        kept = (jnp.arange(laid) < ends[-1])[:, None]
        weight = top_p.reshape(n * k)[order][:, None]
        stats = {"rows_here": starts[-1], "load": load,
                 "rows_dropped": starts[-1] - ends[-1]}
        if "router_bias" in moe_params:
            # what moves the bias (train/jax_step.py): the step's (token,
            # expert) pairs of every published expert, held here or not
            stats["counts"] = jnp.bincount(
                top_e.reshape(n * k), length=cfg.num_experts)

    with jax.named_scope("rt.moe.experts"):
        xs = jnp.where(kept, x[token], 0)                   # [rows, D]
        w1, w3, w2 = (moe_params[name].astype(dt)
                      for name in ("w1", "w3", "w2"))
        gate = jax.nn.silu(lax.ragged_dot(xs, w1, sizes))
        up = lax.ragged_dot(xs, w3, sizes)
        ys = lax.ragged_dot(gate * up, w2, sizes)           # [rows, D]
        ys = jnp.where(kept, ys.astype(jnp.float32), 0.0) * weight
        y = jax.ops.segment_sum(ys, token, num_segments=n).astype(dt)

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

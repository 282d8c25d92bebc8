"""The expert layer's walk over its buffer (models/moe.py ``rows_in``,
``rows_out``): values, gradients and counters against a plain reference that
loops over the (token, expert) pairs in numpy, at every fill of the buffer
the walk tells apart, and the compiled program held to what the walk is for:
no pass over the whole buffer outside a loop's body."""

import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import moe
from ray_tpu.parallel import MeshSpec, build_mesh

D, F = 16, 8
BLOCK = 32                  # rows of a block at ``BLOCK_BYTES`` below
PUSH = 3.0                  # |feature 0| of a token, x the router's 2.0


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(moe, "BLOCK_BYTES", BLOCK * 4 * D)


def layer(tokens, here, *, experts=64, held=4, top_k=2, first=8,
          scoring="softmax", seed=0):
    """A share of ``held`` experts from ``first`` on and ``tokens`` tokens of
    which the first ``here`` route all their ``top_k`` pairs to held experts
    and the others none: feature 0 is +PUSH or -PUSH and the router's row 0
    is large on the held experts' columns alone."""
    cfg = SimpleNamespace(
        expert_top_k=top_k, held=held, num_experts=experts,
        first_expert=first, router_scoring=scoring, norm_topk_prob=True,
        routed_scaling_factor=1.5 if scoring == "sigmoid" else 1.0)
    rng = np.random.default_rng(seed)
    router = 0.1 * rng.standard_normal((D, experts))
    router[0] = 0.0
    router[0, first:first + held] = 2.0
    p = {"router": router,
         "w1": 0.3 * rng.standard_normal((held, D, F)),
         "w3": 0.3 * rng.standard_normal((held, D, F)),
         "w2": 0.3 * rng.standard_normal((held, F, D))}
    if scoring == "sigmoid":
        p["router_bias"] = 0.01 * rng.standard_normal(experts)
    x = rng.standard_normal((tokens, D))
    x[:, 0] = np.where(np.arange(tokens) < here, PUSH, -PUSH)
    x = x[rng.permutation(tokens)]
    dy = rng.standard_normal((tokens, D))
    return cfg, p, x, dy


def silu(a):
    return a / (1.0 + np.exp(-a))


def reference(cfg, p, x, dy, rows):
    """float64, a pair at a time: (y, gradients of sum(y * dy) for x and
    every weight, pairs routed here, pairs dropped). The pairs routed here
    are kept in the order of their experts and, within one, of their tokens,
    as far as the buffer's ``rows`` go."""
    k, first, held = cfg.expert_top_k, cfg.first_expert, cfg.held
    logits = x @ p["router"]
    if cfg.router_scoring == "sigmoid":
        q = 1.0 / (1.0 + np.exp(-logits))
        chosen = np.argsort(-(q + p["router_bias"]), axis=1,
                            kind="stable")[:, :k]
    else:
        e = np.exp(logits - logits.max(1, keepdims=True))
        q = e / e.sum(1, keepdims=True)
        chosen = np.argsort(-q, axis=1, kind="stable")[:, :k]
    raw = np.take_along_axis(q, chosen, 1)
    scale = cfg.routed_scaling_factor
    weight = scale * raw / raw.sum(1, keepdims=True)
    pairs = sorted((chosen[t, j] - first, t, j) for t in range(len(x))
                   for j in range(k) if 0 <= chosen[t, j] - first < held)
    y = np.zeros_like(x)
    g = {name: np.zeros_like(a) for name, a in p.items()}
    dx, dweight = np.zeros_like(x), np.zeros_like(weight)
    for e, t, j in pairs[:rows]:
        a, b = x[t] @ p["w1"][e], x[t] @ p["w3"][e]
        mid = silu(a) * b
        out = mid @ p["w2"][e]
        y[t] += weight[t, j] * out
        dweight[t, j] = dy[t] @ out
        dout = weight[t, j] * dy[t]
        dmid = p["w2"][e] @ dout
        g["w2"][e] += np.outer(mid, dout)
        sig = 1.0 / (1.0 + np.exp(-a))
        da = dmid * b * sig * (1.0 + a * (1.0 - sig))
        db = dmid * silu(a)
        g["w1"][e] += np.outer(x[t], da)
        g["w3"][e] += np.outer(x[t], db)
        dx[t] += p["w1"][e] @ da + p["w3"][e] @ db
    total = raw.sum(1, keepdims=True)
    draw = scale * (dweight / total
                    - (dweight * raw).sum(1, keepdims=True) / total ** 2)
    dq = np.zeros_like(q)
    np.put_along_axis(dq, chosen, draw, 1)
    dlogits = dq * q * (1.0 - q) if cfg.router_scoring == "sigmoid" \
        else q * (dq - (dq * q).sum(1, keepdims=True))
    g["router"] = x.T @ dlogits
    dx += dlogits @ p["router"].T
    return y, dict(g, x=dx), len(pairs), len(pairs) - len(pairs[:rows])


def program(cfg, p, x, dy, *, remat=False, mesh=None):
    """``moe_apply`` in float32 -> (y, gradients, stats)."""
    def loss(p, h):
        apply = lambda p, h: moe.moe_apply(cfg, p, h)
        y, stats = (jax.checkpoint(apply) if remat else apply)(p, h)
        return (y[0] * dy).sum(), (y[0], stats)

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    p, h, dy = jax.tree.map(f32, p), f32(x)[None], f32(dy)
    run = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    if mesh is not None:
        # the experts over ``ep``, the tokens over ``dp``
        at = lambda *spec: NamedSharding(mesh, P(*spec))
        p = {name: jax.device_put(a, at("ep") if a.ndim == 3 else at())
             for name, a in p.items()}
        h = jax.device_put(h, at(None, "dp"))
    with jax.default_matmul_precision("highest"):
        (d_p, d_h), (y, stats) = run(p, h)
    return y, dict(d_p, x=d_h[0]), stats


def close(got, want, what):
    got, want = np.asarray(got, np.float64), np.asarray(want)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-6)
    assert err < 2e-5, (what, err)


# name: (tokens, tokens routed here, the layer's and the program's options)
# -> with 4 of 64 experts and top-2, 256 tokens lay out 4 x 32 = 128 rows:
# four blocks
FILLS = {
    "nothing_routed_here": (256, 0, {}, {}),
    "under_one_block": (256, 5, {}, {}),
    "whole_blocks": (256, 32, {}, {}),
    "blocks_and_a_part": (256, 41, {}, {}),
    "the_whole_buffer": (256, 64, {}, {}),
    "overflow_drops": (256, 100, {}, {}),
    "sigmoid_bias_scaled": (256, 41, {"scoring": "sigmoid"}, {}),
    "few_tokens": (24, 9, {}, {}),              # 48 rows: three blocks of 16
    "few_tokens_one_block": (12, 5, {}, {}),    # 24 rows, walked whole
    # 2 x 127 tokens, top-8, 16 of 256: 4 x 127 = 508 rows, laid out at 512
    "rows_8_does_not_divide": (254, 30, {"experts": 256, "held": 16,
                                         "top_k": 8, "scoring": "sigmoid"},
                               {}),
    "under_checkpoint": (256, 41, {}, {"remat": True}),
    "overflow_under_checkpoint": (256, 100, {}, {"remat": True}),
    "over_an_ep_mesh": (256, 41, {}, {"mesh": MeshSpec(dp=2, ep=4)}),
}


@pytest.mark.parametrize("fill", sorted(FILLS))
def test_walked_layer_is_the_pairwise_reference(fill):
    tokens, here, layer_options, options = FILLS[fill]
    cfg, p, x, dy = layer(tokens, here, **layer_options)
    if "mesh" in options:
        options = {**options, "mesh": build_mesh(options["mesh"])}
    rows = moe.buffer_rows(cfg, tokens)
    laid = -(-rows // moe.ROW_MULTIPLE) * moe.ROW_MULTIPLE
    block = moe.walk_block(laid, D)
    want_y, want_g, routed, dropped = reference(cfg, p, x, dy, rows)
    y, g, stats = program(cfg, p, x, dy, **options)

    assert routed == here * cfg.expert_top_k
    assert int(stats["rows_here"]) == routed
    assert int(stats["rows_dropped"]) == dropped
    assert dropped == max(0, routed - rows)
    assert int(stats["load"].sum()) == routed
    walked = int(stats["rows_walked"])
    assert routed - dropped <= walked <= laid
    assert walked % block == 0
    # whole blocks: the first, and no other than the kept rows reach into
    assert walked == max(block, -(-(routed - dropped) // block) * block)
    close(y, want_y, "y")
    for name, want in want_g.items():
        if name != "router_bias":       # moved by the load, not a gradient
            close(g[name], want, "d " + name)


def test_the_fills_are_the_ones_the_walk_tells_apart():
    cfg, *_ = layer(256, 0)
    assert moe.buffer_rows(cfg, 256) == 4 * BLOCK
    assert moe.walk_block(4 * BLOCK, D) == BLOCK
    assert FILLS["whole_blocks"][1] * cfg.expert_top_k == 2 * BLOCK
    assert FILLS["the_whole_buffer"][1] * cfg.expert_top_k == 4 * BLOCK
    for name, blocks in (("few_tokens", 3), ("few_tokens_one_block", 1)):
        few = FILLS[name][0]
        assert few <= moe.FEW_TOKENS
        assert moe.buffer_rows(cfg, few) == few * 2 \
            == blocks * moe.walk_block(few * 2, D)
    odd, *_ = layer(254, 0, **FILLS["rows_8_does_not_divide"][2])
    assert moe.buffer_rows(odd, 254) == 508


@pytest.mark.parametrize("laid, d, want", [
    (16384, 5120, 256),         # 8 MiB of float32 rows are 409
    (40960, 2048, 1024),
    (32768, 2048, 1024),
    (16, 5120, 16),             # a decode step: the whole buffer
    (1024, 2048, 1024),
    (40, 1 << 20, 8),           # ROW_MULTIPLE divides every layout
    (4000, 2048, 32),           # 2^5 x 125
])
def test_walk_block_divides_the_layout(laid, d, want, monkeypatch):
    monkeypatch.setattr(moe, "BLOCK_BYTES", 8 << 20)
    assert moe.walk_block(laid, d) == want
    assert laid % want == 0


INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?[a-z0-9]+"
                         r"\[([0-9,]*)\]")
COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
# what may give a result the size of the buffer outside a loop: the
# matmuls, the zeros the buffer is made from (a broadcast, or a pad around
# its first block, which is passed over before the loop), the loop itself
# and what only names or hands on a result (a fusion is judged by what it
# fuses)
WHOLE_BUFFER_MAY = (" ragged-dot(", " dot(", " convolution(", " broadcast(",
                    " pad(", " while(", " get-tuple-element(",
                    " parameter(", " tuple(", " bitcast(", " copy(",
                    " constant(", " fusion(", " custom-call(")


def passes_over(text: str, shape):
    """Instructions of a compiled module that pass over a whole [laid, D]
    outside every loop's body and what a body calls: those whose result has
    that shape, but for WHOLE_BUFFER_MAY, and the scatters that take one."""
    shape = tuple(shape)
    lines, calls, dims = {}, {}, {}
    computation = None
    for line in text.splitlines():
        start = COMPUTATION.match(line)
        if start:
            computation = start.group(1)
            lines[computation], calls[computation] = [], set()
            continue
        got = INSTRUCTION.match(line)
        if got and computation is not None:
            # the CPU's compiler gives a gather of rows [laid, 1, D]
            dims[got.group(1)] = tuple(
                int(n) for n in got.group(2).split(",") if n and n != "1")
            lines[computation].append((got.group(1), line))
            calls[computation].update(CALLED.findall(line))
    looped = set(re.findall(r"body=%?([\w.\-]+)", text))
    while True:
        more = set().union(*(calls.get(c, ()) for c in looped)) - looped
        if not more:
            break
        looped |= more
    found = []
    for computation, instructions in lines.items():
        if computation in looped:
            continue
        for name, line in instructions:
            operands = re.findall(r"%([\w.\-]+)", line.split(" = ", 1)[1]
                                  .split(", metadata=")[0])
            if dims[name] == shape and not any(
                    op in line for op in WHOLE_BUFFER_MAY) \
                    or " scatter(" in line and any(
                        dims.get(o) == shape for o in operands):
                found.append(line.strip()[:200])
    return found


@pytest.mark.parametrize("grad", [False, True])
def test_no_pass_over_the_whole_buffer_outside_a_loop(grad):
    """A gather, a select, a multiply, a cast, an add or a scatter-add over
    [laid, D] that stands outside a ``while`` body is the pass the walk
    replaced, put back."""
    tokens = 256
    cfg, p, x, dy = layer(tokens, 41)
    laid = moe.buffer_rows(cfg, tokens)
    assert laid == 4 * BLOCK and laid != tokens

    def apply(p, h):
        return moe.moe_apply(cfg, p, h)

    def loss(p, h):
        y, stats = apply(p, h)
        return (y[0] * dy).sum(), stats
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    args = jax.tree.map(f32, p), f32(x)[None]
    fn = jax.grad(loss, argnums=(0, 1), has_aux=True) if grad else apply
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert " while(" in text
    assert passes_over(text, (laid, D)) == []

    # and the check sees each of them where it is (but the scatter-add,
    # which the CPU's compiler turns into a loop of its own: the TPU's
    # keeps it, tests/test_flash_kernels.py)
    def whole(p, h):
        token = jnp.argsort(h[0, :, 1])[jnp.arange(laid) % tokens]
        xs = jnp.where(h[0][token] > 0, h[0][token], 0.0) * 2.0
        return xs, jax.ops.segment_sum(jnp.tanh(xs), token, tokens)
    seen = " ".join(passes_over(
        jax.jit(whole).lower(*args).compile().as_text(), (laid, D)))
    for op in (" gather(", " select(", " multiply("):
        assert op in seen, (op, seen)

"""The gated delta rule (Gated DeltaNet), chunked, and the short causal
convolution that stands in front of it.

Per head, with ``S`` a ``[dk, dv]`` float32 state that starts at 0, q and k
first taken to unit length over the head (q then times ``dk^-0.5``):

    S_t = exp(g_t) S_{t-1}
    S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t

A loop over positions is latency-bound on a TPU, so the sequence is cut into
chunks of ``chunk`` positions and each chunk is solved at once (the WY / UT
transform of the DeltaNet papers): with ``G`` the running sum of ``g`` inside
a chunk and ``A = strict_lower(diag(beta) K K^T * exp(G_i - G_j))``,

    T = (I + A)^-1        u = T (beta v)        w = T (beta k e^G)

and then, chunk after chunk, ``v' = u - w S``, ``o = (q e^G) S +
lower(Q K^T * decay) v'``, ``S <- e^{G_last} S + (k e^{G_last - G})^T v'``.
Only that last recurrence is sequential (one step a chunk). ``T`` is made by
forward substitution (rows inside a diagonal block, then blocks, doubling
the side), in float32 at the highest precision: it is the one place where
rounding compounds. The large matmuls take their operands in the inputs'
dtype (bfloat16 in the model) and accumulate in float32.

**Two forms, one a backend** (ops/attention.py's rule for flash: no option
chooses). On a TPU the rule is two fused Pallas kernels with a
``jax.custom_vjp`` (ops/gated_delta_pallas: ``rt_gdn_fwd``, ``rt_gdn_bwd``):
a chunk's intermediates stay in VMEM, the state crosses chunks in VMEM
scratch, the backward is a reverse scan of its own. They take head widths
that are multiples of 128 as they come; other widths from 64 up (96 and 192)
go in padded with zeros to the next multiple, which changes neither a head's
length nor a product, and q keeps the scale of its own width. Everywhere
else (the CPU tests, narrow heads) the jnp form below runs, batched matmuls
and one ``lax.scan`` step a chunk with XLA's backward, which keeps one state
a chunk; it is also the kernels' oracle.

**The convolution** in front of the rule (``causal_conv``: K shifted
products summed in float32, a bias where there is one, SiLU) has the same
two forms: on a TPU a Pallas pair where the sequence fills a row block
(``conv_kernels_fit``: a multiple of ``CONV_ROWS`` positions, channels a
multiple of 128; ops/gated_delta_pallas ``rt_gdn_conv_fwd``,
``rt_gdn_conv_bwd``, a ``jax.custom_vjp``), one read of x and one write of
y forward, x and dy read and dx written backward with the taps' and the
bias's gradients summed in the same pass, nothing kept between them;
everywhere else, a served prompt of 128 positions among it, the jnp form,
which is also the pair's oracle. Under a mesh of several devices
``causal_conv_over`` runs the pair per shard of the batch.

**Serving.** ``final_state=True`` also hands back the state after the last
position, float32 ``[B, H, dk, dv]``: what a prefill leaves in the cache.
From there ``gated_delta_step`` takes one position of the recurrence above
on a carried state, elementwise in float32 under the scope ``rt.gdn.step``,
and ``conv_step`` convolves one new column against the last ``K - 1``
inputs (``rt.gdn.conv``); ``gated_delta_step_at`` and ``conv_step_at`` take
them on one slot of the stacks a cache carries, on a TPU as Pallas kernels
that write into the stack they read (``rt_gdn_step``,
``rt_gdn_conv_step``). A state-space mixer (ops/ssd.py) carries the same two
arrays and steps them with the same two kernels: its convolution is this
one with a bias (``bias=``, under the scope ``rt.ssd.conv``), its state
``[slots, B, H, N, P]`` (N the state width where the rule has ``dk``, P a
head's width where it has ``dv``; P = 128 fills the lanes, so nothing is
packed), and one position of it is this step without the delta correction
(``step_kernel(delta=False)``, ``rt_ssd_step``). The carried state is PACKED (``pack_state``):
``state_pack`` heads lie beside each other on the minor dimension, ``[B, H /
r, dk, r dv]``, so that it is a multiple of the TPU's 128 lanes (two heads
of 192 are 384) and the bytes a step moves are the state's own, not a
third more of padding. A head's value columns stay together, so the step
needs no fold: its sums over ``dk`` run down the sublanes.

**What outlives the forward pass.** The forward kernel's four outputs, ``o``
and what ``rt_gdn_bwd`` reads (each chunk's starting state, ``T`` and
``v'``), carry the checkpoint name ``KEPT``, and the model's whole-layer
remat keeps what carries it (models/transformer.py ``_stage_scan``): the
kernel runs once a layer, not again inside the layer's backward. At 2 rows x
8,192, 32 value heads of 128 on chunks of 64, bfloat16, that is 268,435,456
+ 67,108,864 + 134,217,728 + 134,217,728 = 603,979,776 bytes a layer from
its forward to its backward. The operands (q, k, v, g, beta) are recomputed
with the projections and the convolution they come out of. The jnp form
carries no name and keeps nothing: under remat its forward is recomputed
whole, and what XLA's backward reads lives only while that layer's backward
runs. Every operation of both runs under the scope ``rt.gdn.scan``
(``rt.gdn.conv`` for the convolution), which is how the benchmark's reducer
finds their device time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.flash import _on_tpu
from ray_tpu.parallel.sharding import LogicalRules

CHUNK = 64
# the name (jax.ad_checkpoint.checkpoint_name) on what the kernels' forward
# hands to their backward: a remat policy that saves it spares the second run
KEPT = "gdn_kept"
EPS = 1e-6          # under the root of a head's squared length
BASE = 8            # side of the diagonal blocks inverted directly
VPU_SIDE = 32       # blocks up to this side multiply on the VPU


def causal_conv(x, w, bias=None, scope: str = "rt.gdn.conv"):
    """Depthwise causal convolution, with ``bias`` [C] where one is given,
    then SiLU. x: [B, S, C]; w: [C, K] (``w[:, K-1]`` weighs the current
    position). -> [B, S, C] in x's dtype. ``scope``: the mixer's own name
    for it in the device trace (a state-space mixer's is ``rt.ssd.conv``)."""
    with jax.named_scope(scope):
        if conv_kernels_fit(x, w):
            from ray_tpu.ops.gated_delta_pallas import causal_conv_kernels
            return causal_conv_kernels(x, w, bias)
        width = w.shape[1]
        s = x.shape[1]
        padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
        w = w.astype(jnp.float32)
        y = sum(padded[:, j:j + s].astype(jnp.float32) * w[:, j]
                for j in range(width))
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return jax.nn.silu(y).astype(x.dtype)


CONV_ROWS = 512     # positions the sequence's kernels take a multiple of


def conv_kernels_fit(x, w) -> bool:
    """The Pallas pair runs on a TPU where the sequence fills its row
    blocks and the channels the lanes (and the K - 1 positions a tap looks
    back lie in one float32 tile of 8 rows); a shorter sequence (a served
    prompt of 128 positions), and every other shape, runs the jnp form."""
    return (_on_tpu() and x.shape[1] % CONV_ROWS == 0
            and x.shape[2] % 128 == 0 and w.shape[1] <= 8)


def conv_step(tail, x, w, bias=None):
    """``causal_conv`` of one new position against the inputs before it.
    tail: [K-1, B, C], the last K - 1 inputs, oldest first; x: [B, C].
    -> (y [B, C] in x's dtype, the tail with x in and its oldest out)."""
    window = jnp.concatenate([tail.astype(x.dtype), x[None]], axis=0)
    w = w.astype(jnp.float32)
    y = sum(window[j].astype(jnp.float32) * w[:, j]
            for j in range(w.shape[1]))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype), window[1:]


def conv_step_at(tails, slot, x, w, bias=None, scope: str = "rt.gdn.conv"):
    """``conv_step`` on slot ``slot`` of the carried stack ``tails`` [slots,
    K-1, B, C] (positions before rows: a tile of the TPU's then holds rows
    and channels, and the K - 1 = 3 positions pad nothing) -> (y [B, C],
    the stack with the slot's tail moved on). On a TPU a Pallas kernel
    whose output IS the stack (``rt_gdn_conv_step``: it reads and writes
    the slot's block and nothing else); elsewhere the slot is cut out,
    stepped and written back."""
    with jax.named_scope(scope):
        if _on_tpu():
            from ray_tpu.ops.gated_delta_pallas import conv_step_kernel
            return conv_step_kernel(tails, slot, x, w, bias)
        y, tail = conv_step(
            lax.dynamic_index_in_dim(tails, slot, 0, keepdims=False), x, w,
            bias)
        return y, lax.dynamic_update_slice(
            tails, tail[None].astype(tails.dtype), (slot, 0, 0, 0))


def _block_matmul(a, b):
    """a @ b for stacks of small square float32 blocks (the jnp form's; the
    kernels have their own products). Up to VPU_SIDE a side they are
    multiplied and summed elementwise, exactly, which is what a CPU does
    anyway. On a TPU, where this form ran until PR 38 and still runs at
    head widths the kernels do not take: the MXU pads an 8-wide block to its
    128-wide tile, and XLA lowers a batch of such dots as convolutions that
    take ~1 ms for 17 MB of blocks (on a v5e this form's forward and
    backward at [2, 8192, 32, 128] take 39.0 ms this way against 74.1 ms
    through the MXU; PERF.md, PR 37)."""
    if a.shape[-1] <= VPU_SIDE:
        return jnp.sum(a[..., :, :, None] * b[..., None, :, :], axis=-2)
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular ``a`` [..., C, C], float32,
    C a power of two: the diagonal blocks of side BASE by their finite
    Neumann series (terms up to binomial(7, 3), so little cancels), then
    block forward substitution, doubling the side: ``[[Ta, 0], [-Tb A21
    Ta, Tb]]``. The whole series at side 64 has terms of 1e18 that cancel
    to O(1) once keys align, and float32 then returns noise or NaN."""
    c = a.shape[-1]
    side = min(BASE, c)

    def blocks(row0, col0, count, stride):
        return jnp.stack([
            a[..., row0 + i * stride:row0 + i * stride + side,
              col0 + i * stride:col0 + i * stride + side]
            for i in range(count)], axis=-3)

    power = -blocks(0, 0, c // side, side)              # [..., nb, s, s]
    t = jnp.eye(side, dtype=a.dtype) + power
    for _ in range(int(math.log2(side)) - 1):
        power = _block_matmul(power, power)
        t = t + _block_matmul(t, power)
    while side < c:
        below = blocks(side, 0, c // (2 * side), 2 * side)      # the A21s
        ta, tb = t[..., 0::2, :, :], t[..., 1::2, :, :]
        t21 = -_block_matmul(_block_matmul(tb, below), ta)
        t = jnp.concatenate([
            jnp.concatenate([ta, jnp.zeros_like(ta)], -1),
            jnp.concatenate([t21, tb], -1)], -2)
        side *= 2
    return t[..., 0, :, :]


def _mm(spec, a, b, dt):
    return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK,
                     final_state: bool = False):
    """q, k: [B, S, Hk, dk] as the convolution left them: the rule takes
    each head's q and k to unit length in float32 (q then times dk^-0.5),
    as Gated DeltaNet does, and rounds them to v's dtype; v: [B, S, H, dv],
    each of the Hk key heads shared by H / Hk value heads in a row; g (log
    decay, <= 0), beta: [B, S, H]. -> o [B, S, H, dv] in v's dtype, and
    with ``final_state`` (o, the state after position S - 1, float32 [B, H,
    dk, dv]; no gradient flows through it from the kernels). Any S: the
    tail is padded with positions that leave the state alone."""
    with jax.named_scope("rt.gdn.scan"):
        if _kernels_fit(q, v, chunk):
            from ray_tpu.ops.gated_delta_pallas import gated_delta_rule_kernels
            return gated_delta_rule_kernels(q, k, v, g, beta, chunk,
                                            final_state=final_state)
        o, state = _chunked(q, k, v, g, beta, chunk)
        return (o, state) if final_state else o


def _on_one_device(mesh) -> bool:
    """What is traced runs on one device: no mesh, a mesh of one, or the
    inside of a shard_map already."""
    return mesh is None or mesh.size == 1 \
        or bool(jax.sharding.get_abstract_mesh().manual_axes)


def gated_delta_rule_over(mesh, rules: LogicalRules, q, k, v, g, beta):
    """``gated_delta_rule`` of operands laid out by ``rules`` over ``mesh``.
    A Mosaic call cannot be partitioned by GSPMD, so where the kernels run
    under a mesh of several devices they run per shard inside shard_map,
    batch and heads split as ``rules`` say (the rule is independent across
    both), as ops/flash.py does; the jnp form is GSPMD's to partition."""
    rule = gated_delta_rule     # as it is named now: the benchmark's sweep
    #                             plants faults on the name
    if _on_one_device(mesh) or not _kernels_fit(q, v, CHUNK):
        return rule(q, k, v, g, beta)
    head_shards = math.prod(
        mesh.shape[a] for ax in rules.spec(("heads",), mesh)
        for a in ((ax,) if isinstance(ax, str) else ax or ()))
    if q.shape[2] % head_shards:
        # fewer key heads than head shards: one copy a value head
        q, k = (jnp.repeat(x, v.shape[2] // x.shape[2], axis=2)
                for x in (q, k))
    wide = rules.spec(("batch", None, "heads", None), mesh)
    gate = rules.spec(("batch", None, "heads"), mesh)
    return jax.shard_map(rule, mesh=mesh, in_specs=(wide, wide, wide, gate,
                                                    gate),
                         out_specs=wide, check_vma=False)(q, k, v, g, beta)


def causal_conv_over(mesh, rules: LogicalRules, x, w, bias=None,
                     scope: str = "rt.gdn.conv"):
    """``causal_conv`` of an input laid out by ``rules`` over ``mesh``:
    where its kernels run under a mesh of several devices they run per
    shard of the batch inside shard_map, as the rule's do (the channels are
    whole on every device, the packed projection's columns being kept so:
    models/transformer.py ``transformer_logical_axes``); the jnp form is
    GSPMD's to partition."""
    conv = functools.partial(causal_conv, scope=scope)
    if _on_one_device(mesh) or not conv_kernels_fit(x, w):
        return conv(x, w, bias)
    rows = rules.spec(("batch", None, None), mesh)
    whole = jax.sharding.PartitionSpec()
    return jax.shard_map(conv, mesh=mesh, in_specs=(rows, whole, whole),
                         out_specs=rows, check_vma=False)(x, w, bias)


KERNEL_WIDTH_FROM = 64      # narrower heads would be mostly padding


def _kernels_fit(q, v, chunk) -> bool:
    """The Pallas kernels run on a TPU at head widths from KERNEL_WIDTH_FROM
    up (those that are no multiple of 128 padded to the next one);
    everywhere else the jnp form below runs (ops/attention.py's rule for
    flash)."""
    return (_on_tpu() and min(q.shape[-1], v.shape[-1]) >= KERNEL_WIDTH_FROM
            and chunk % 16 == 0 and v.shape[2] % q.shape[2] == 0)


def unit(x):
    """x [..., d] at unit length over d, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + EPS)


def state_pack(hv: int, dv: int) -> int:
    """Heads that lie beside each other on a carried state's minor
    dimension: the fewest that make it a multiple of 128 lanes (1 where the
    heads do not divide into such runs)."""
    r = 128 // math.gcd(dv, 128)
    return r if hv % r == 0 else 1


def pack_state(state):
    """[B, H, dk, dv] -> [B, H / r, dk, r dv], r = ``state_pack``."""
    b, h, dk, dv = state.shape
    r = state_pack(h, dv)
    return jnp.swapaxes(state.reshape(b, h // r, r, dk, dv), 2, 3).reshape(
        b, h // r, dk, r * dv)


def unpack_state(packed, hv: int):
    """``pack_state``'s inverse."""
    b, groups, dk, wide = packed.shape
    r = hv // groups
    return jnp.swapaxes(packed.reshape(b, groups, dk, r, wide // r), 2,
                        3).reshape(b, hv, dk, wide // r)


def _beside(x, r: int, dv: int):
    """x [B, H, ...] -> [B, H / r, ..., r dv]: each of a run's r heads'
    values over its own dv lanes (a concatenation of broadcasts, which
    fuses into what reads it; a reshape of the minor dimension would be a
    copy on a TPU)."""
    x = x.reshape((x.shape[0], x.shape[1] // r, r) + x.shape[2:])
    return jnp.concatenate(
        [jnp.broadcast_to(x[:, :, i, ..., None], x.shape[:2] + x.shape[3:]
                          + (dv,)) for i in range(r)], axis=-1)


def _step_operands(q, k, v):
    """q, k [B, Hk, dk] as the convolution left them -> float32 at unit
    length (q scaled), rounded to v's dtype as the chunked rule rounds
    them, one copy a value head."""
    dk, h, f32 = q.shape[-1], v.shape[1], jnp.float32
    q = (unit(q) * dk ** -0.5).astype(v.dtype).astype(f32)
    k = unit(k).astype(v.dtype).astype(f32)
    if q.shape[1] != h:
        q, k = (jnp.repeat(x, h // x.shape[1], axis=1) for x in (q, k))
    return q, k


def gated_delta_step_at(states, slot, q, k, v, g, beta):
    """``gated_delta_step`` on slot ``slot`` of the carried stack ``states``
    [slots, B, H / r, dk, r dv] -> (o [B, H, dv], the stack with the slot's
    state moved on). On a TPU, where the packed state fills whole tiles, a
    Pallas kernel whose output IS the stack (``rt_gdn_step``): a row's
    state comes into VMEM once, is read for ``S^T k``, updated and read
    for ``S^T q`` there, and goes back once. XLA's form reads the slot
    for each of the two sums, and copied the whole stack around the update
    of a slot it had just read (two copies of 1.48 GB a layer and step at
    48 rows x 15 slots of [15, 96, 384]: the program did not fit). Elsewhere
    the slot is cut out, stepped and written back."""
    with jax.named_scope("rt.gdn.step"):
        _, _, _, dk, wide = states.shape
        if _on_tpu() and dk % 8 == 0 and wide % 128 == 0:
            from ray_tpu.ops.gated_delta_pallas import step_kernel
            return step_kernel(states, slot, *_step_operands(q, k, v), v, g,
                               beta)
        o, state = gated_delta_step(
            lax.dynamic_index_in_dim(states, slot, 0, keepdims=False),
            q, k, v, g, beta)
        return o, lax.dynamic_update_slice(
            states, state[None].astype(states.dtype), (slot, 0, 0, 0, 0))


def gated_delta_step(state, q, k, v, g, beta):
    """One position of the rule on a carried state. state: packed float32
    [B, H / r, dk, r dv] (``pack_state``); q, k: [B, Hk, dk] as the
    convolution left them (unit length taken here, as ``gated_delta_rule``
    does); v: [B, H, dv]; g, beta: [B, H]. -> (o [B, H, dv] in v's dtype,
    the state after the position). Elementwise products and sums over dk in
    float32: an S = 1 step is bound by the state's bytes, and the MXU would
    round the state to its operands' dtype."""
    b, h, dv = v.shape
    dt, f32 = v.dtype, jnp.float32
    r = h // state.shape[1]
    q, k = (_beside(x, r, dv) for x in _step_operands(q, k, v))
    #                                                     [B, H/r, dk, r dv]
    wide = (b, h // r, 1, r * dv)
    decay = _beside(jnp.exp(g.astype(f32)), r, dv).reshape(wide)
    beta = _beside(beta.astype(f32), r, dv).reshape(wide)
    v = v.astype(f32).reshape(wide)
    seen = jnp.sum(state * k, axis=2, keepdims=True)            # S^T k
    fresh = beta * (v - decay * seen)
    state = decay * state + k * fresh           # float32 whatever came in
    o = jnp.sum(state * q, axis=2)
    return o.reshape(b, h, dv).astype(dt), state


def _chunked(q, k, v, g, beta, c):
    """-> (o, the state after the last position)."""
    b, s, h, dv = v.shape
    dk = q.shape[-1]
    dt = v.dtype
    f32 = jnp.float32
    q, k = (unit(q) * dk ** -0.5).astype(dt), unit(k).astype(dt)
    if q.shape[2] != h:
        q, k = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (q, k))
    pad = -s % c
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (s + pad) // c

    def chunks(x):          # [B, S, H, ...] -> [B, H, n, C, ...]
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    gsum = jnp.cumsum(g, axis=-1)                          # [B, H, n, C]
    lower = jnp.tril(jnp.ones((c, c), bool))
    gap = gsum[..., :, None] - gsum[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gap, 0.0)), 0.0)

    k_beta = k.astype(f32) * beta[..., None]
    a = _mm("bhnid,bhnjd->bhnij", k_beta, k, dt) * decay
    t = _unit_lower_inverse(jnp.where(jnp.tril(lower, -1), a, 0.0))
    u = _mm("bhnij,bhnjd->bhnid", t, v.astype(f32) * beta[..., None], dt)
    w = _mm("bhnij,bhnjd->bhnid", t, k_beta * jnp.exp(gsum)[..., None], dt)

    g_last = gsum[..., -1]                                 # [B, H, n]
    k_tail = k.astype(f32) * jnp.exp(g_last[..., None] - gsum)[..., None]

    def step(state, xs):
        w_i, u_i, k_i, decay_i = xs
        fresh = u_i - _mm("bhcd,bhdv->bhcv", w_i, state, dt)
        nxt = state * decay_i[..., None, None] \
            + _mm("bhcd,bhcv->bhdv", k_i, fresh, dt)
        return nxt, (state.astype(dt), fresh.astype(dt))

    def by_chunk(x):        # chunk axis first, for the scan
        return jnp.moveaxis(x, 2, 0)

    last, (states, fresh) = lax.scan(
        step, jnp.zeros((b, h, dk, dv), f32),
        (by_chunk(w.astype(dt)), by_chunk(u), by_chunk(k_tail.astype(dt)),
         by_chunk(jnp.exp(g_last))))
    states, fresh = jnp.moveaxis(states, 0, 2), jnp.moveaxis(fresh, 0, 2)

    within = _mm("bhnid,bhnjd->bhnij", q, k, dt) * decay
    o = _mm("bhnid,bhndv->bhniv",
            q.astype(f32) * jnp.exp(gsum)[..., None], states, dt) \
        + _mm("bhnij,bhnjv->bhniv", within, fresh, dt)
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * c, h, dv)     # [B, S, H, dv]
    return o[:, :s].astype(dt), last

"""The state-space recurrence of Mamba-2 (SSD: a scalar decay a head),
chunked, and one position of it on a carried state.

Per head, with ``S`` an ``[N, P]`` float32 state that starts at 0 (N the
state width, P the head's), ``dt`` the step size and ``g = dt A <= 0`` the
log decay:

    S_t = exp(g_t) S_{t-1} + B_t (dt_t x_t)^T
    y_t = S_t^T C_t + D x_t

``B`` and ``C`` [G, N] are shared by groups of H / G heads in a row. This is
the gated delta rule's dual (ops/gated_delta.py: C as q, B as k, x as v)
without the delta correction, without unit length on q and k, with the step
size on the input and a skip: one sequential step a chunk on the state, and
inside a chunk ``lower(C B^T * exp(G_i - G_j)) (dt x)`` with ``G`` the
running sum of ``g``. The products take their operands in x's dtype and
accumulate in float32; the decays, their sums and the state are float32.

One form, jnp, differentiable by XLA (batched matmuls and one ``lax.scan``
step a chunk); a served prompt of 128 positions is one chunk. Its
operations run under the caller's scope (``rt.ssd.scan``).

**Serving.** ``final_state=True`` also hands back the state after the last
position, float32 ``[B, H, N, P]``: the layout a cache carries it in
(models/generate.py ``cache_shapes``: the minor dimension is a head's P =
128 lanes, nothing to pack). ``ssd_step`` takes one position on a carried
state, elementwise in float32; ``ssd_step_at`` takes it on one slot of the
stack a cache carries, on a TPU as the delta rule's step kernel without its
correction (ops/gated_delta_pallas.py ``step_kernel(delta=False)``,
``rt_ssd_step``), whose output is the stack it reads: a row's state comes
into VMEM once and goes back once. The convolution in front is
ops/gated_delta.py's, with a bias.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.flash import _on_tpu
from ray_tpu.ops.gated_delta import _mm

CHUNK = 128


def ssd_scan(x, b, c, g, dt, skip, *, chunk: int = CHUNK,
             final_state: bool = False):
    """x: [B, S, H, P]; b, c: [B, S, G, N], group j serving heads j H / G ..
    (j + 1) H / G - 1; g (log decay, <= 0), dt (step size): [B, S, H]
    float32; skip: [H] (``D``). -> y [B, S, H, P] in x's dtype, and with
    ``final_state`` (y, the state after position S - 1, float32 [B, H, N,
    P]). Any S: the tail is padded with positions that leave the state
    alone."""
    bsz, s, h, p = x.shape
    grp, n = b.shape[2:]
    r, dtype, f32 = h // grp, x.dtype, jnp.float32
    pad = -s % chunk
    if pad:
        x, b, c, g, dt = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, b, c, g, dt))
    m = (s + pad) // chunk

    def chunks(a, *minor):      # [B, S, ...] -> [B, m, C, ...]
        return a.reshape((bsz, m, chunk) + minor)

    v = chunks(x.astype(f32) * dt.astype(f32)[..., None], grp, r, p)
    b, c = chunks(b, grp, n), chunks(c, grp, n)
    gsum = jnp.cumsum(chunks(g.astype(f32), grp, r), axis=2)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    gap = gsum[:, :, :, None] - gsum[:, :, None, :]     # [B, m, i, j, G, R]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gap, 0.0)), 0.0)
    within = _mm("bmigd,bmjgd->bmijg", c, b, dtype)[..., None] * decay
    y = _mm("bmijgr,bmjgrp->bmigrp", within, v, dtype)
    # what a chunk adds to the state, decayed to the chunk's end
    g_last = gsum[:, :, -1]                                 # [B, m, G, R]
    add = _mm("bmjgd,bmjgrp->bmgrdp", b,
              v * jnp.exp(g_last[:, :, None] - gsum)[..., None], dtype)

    def step(state, xs):
        add_i, decay_i = xs
        return state * decay_i[..., None, None] + add_i, state

    last, before = lax.scan(
        step, jnp.zeros((bsz, grp, r, n, p), f32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(jnp.exp(g_last), 1, 0)))
    y = y + _mm("bmigd,bmgrdp->bmigrp", c, jnp.moveaxis(before, 0, 1),
                dtype) * jnp.exp(gsum)[..., None]
    y = y.reshape(bsz, m * chunk, h, p)[:, :s] \
        + skip.astype(f32)[:, None] * x[:, :s].astype(f32)
    y = y.astype(dtype)
    return (y, last.reshape(bsz, h, n, p)) if final_state else y


def ssd_step(state, x, b, c, g, dt, skip):
    """One position on a carried state. state: float32 [B, H, N, P]; x:
    [B, H, P]; b, c: [B, G, N]; g, dt: [B, H]; skip: [H]. -> (y [B, H, P]
    in x's dtype, the state after the position). Elementwise products and
    sums over N in float32: an S = 1 step is bound by the state's bytes,
    and the MXU would round the state to its operands' dtype."""
    f32 = jnp.float32
    r = x.shape[1] // b.shape[1]
    k, q = (jnp.repeat(a.astype(f32), r, axis=1)[..., None] for a in (b, c))
    xf = x.astype(f32)
    state = jnp.exp(g.astype(f32))[..., None, None] * state \
        + k * (dt.astype(f32)[..., None] * xf)[:, :, None, :]
    y = jnp.sum(state * q, axis=2) + skip.astype(f32)[:, None] * xf
    return y.astype(x.dtype), state


def ssd_step_at(states, slot, x, b, c, g, dt, skip):
    """``ssd_step`` on slot ``slot`` of the carried stack ``states`` [slots,
    B, H, N, P] -> (y [B, H, P], the stack with the slot's state moved on).
    On a TPU, where a head's [N, P] fills whole tiles, the Pallas kernel
    whose output IS the stack (``rt_ssd_step``): one pass over a row's
    state. (XLA's form of cut, step, write copied the whole stack twice a
    layer and step for the delta rule, PERF.md PR 51; for this step, which
    reads its slot once, it compiles without the copy but runs the step in
    4.15 s of a 64-row call where the kernel takes 2.92, a call of 9.34 s
    for 8.07, PERF.md PR 55.) Elsewhere the slot is cut out, stepped and
    written back."""
    _, _, _, n, p = states.shape
    if _on_tpu() and n % 8 == 0 and p % 128 == 0:
        from ray_tpu.ops.gated_delta_pallas import step_kernel
        f32, r = jnp.float32, x.shape[1] // b.shape[1]
        q, k = (jnp.repeat(a.astype(f32), r, axis=1) for a in (c, b))
        y, states = step_kernel(states, slot, q, k, x, g, dt, delta=False)
        y = y.astype(f32) + skip.astype(f32)[:, None] * x.astype(f32)
        return y.astype(x.dtype), states
    y, state = ssd_step(
        lax.dynamic_index_in_dim(states, slot, 0, keepdims=False),
        x, b, c, g, dt, skip)
    return y, lax.dynamic_update_slice(
        states, state[None].astype(states.dtype), (slot, 0, 0, 0, 0))

"""The short causal convolution's Pallas pair (ops/gated_delta_pallas.py
``rt_gdn_conv_fwd``, ``rt_gdn_conv_bwd``) through the interpreter, against
the jnp ``causal_conv`` and ``jax.grad`` of it, and the rule of shapes that
sends an input to one or the other."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta, gated_delta_pallas

ROWS = gated_delta.CONV_ROWS
LANES = gated_delta_pallas.CONV_LANES
K = 4


def operands(dtype, bias, b=2, s=3 * ROWS, c=2 * LANES, seed=0):
    """Three row blocks (of ROWS: no larger block divides the sequence) and
    two channel blocks: both halos are crossed, and the first block reads
    zeros before the sequence."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (b, s, c)).astype(dtype)
    w = 0.5 * jax.random.normal(keys[1], (c, K))
    bs = jax.random.normal(keys[2], (c,)) if bias else None
    dy = jax.random.normal(keys[3], (b, s, c)).astype(dtype)
    return x, w, bs, dy


def jnp_form(x, w, bs):
    assert not gated_delta.conv_kernels_fit(x, w)      # no TPU here
    return gated_delta.causal_conv(x, w, bs)


kernels = partial(gated_delta_pallas.causal_conv_kernels, interpret=True)


def gradients(conv, x, w, bs, dy):
    """(dx, dw, dbias) of sum(conv * dy); dbias None without a bias."""
    def loss(x, w, bs):
        return jnp.sum(conv(x, w, bs).astype(jnp.float32)
                       * dy.astype(jnp.float32))
    by = (0, 1, 2) if bs is not None else (0, 1)
    return (jax.grad(loss, by)(x, w, bs) + (None,))[:3]


def close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    scale = max(1e-6, float(np.abs(want).max()))
    np.testing.assert_allclose(got / scale, want / scale, atol=tol, rtol=0)


@pytest.fixture(scope="module", params=[False, True], ids=["plain", "bias"])
def float32_case(request):
    """In float32 the comparison is of the mathematics: (what the kernels
    give, what the jnp form gives) for y, dx, dw, dbias."""
    x, w, bs, dy = operands(jnp.float32, request.param)
    got = (kernels(x, w, bs),) + gradients(kernels, x, w, bs, dy)
    want = (jnp_form(x, w, bs),) + gradients(jnp_form, x, w, bs, dy)
    return dict(zip(("y", "dx", "dw", "dbias"), zip(got, want)))


@pytest.mark.parametrize("what", ["y", "dx", "dw", "dbias"])
def test_the_kernels_match_the_jnp_form_in_float32(float32_case, what):
    got, want = float32_case[what]
    if want is None:                # no bias: nothing comes back for one
        assert got is None
        return
    assert got.shape == want.shape and got.dtype == want.dtype
    close(got, want, 2e-6)


@pytest.mark.parametrize("bias", [False, True], ids=["plain", "bias"])
def test_the_kernels_take_bfloat16_as_the_jnp_form_does(bias):
    """Dtypes as the jnp form's; y to a rounding of bfloat16 (the float32
    sums differ in their last place at most), dx to two: the jnp form's
    gradient rounds each tap's term to bfloat16 before it adds them, the
    kernel rounds their float32 sum once. dw and dbias are float32 sums
    over every position on both sides."""
    x, w, bs, dy = operands(jnp.bfloat16, bias, seed=1)
    got_y, want_y = kernels(x, w, bs), jnp_form(x, w, bs)
    assert got_y.dtype == want_y.dtype == jnp.bfloat16
    close(got_y, want_y, 2 ** -8)
    assert float(jnp.mean(got_y != want_y)) < 1e-3
    got, want = (gradients(f, x, w, bs, dy) for f in (kernels, jnp_form))
    assert got[0].dtype == want[0].dtype == jnp.bfloat16
    close(got[0], want[0], 2 ** -6)
    close(got[1], want[1], 1e-5)
    if bias:
        close(got[2], want[2], 1e-5)
    # against the float32 truth the kernel's dx is the nearer one
    exact = gradients(jnp_form, x.astype(jnp.float32), w, bs,
                      dy.astype(jnp.float32))[0]

    def off(dx):
        return float(jnp.abs(dx.astype(jnp.float32) - exact).mean())
    assert off(got[0]) < off(want[0])


def test_a_sequences_rows_do_not_reach_the_next_sequences():
    """The halos stop at a sequence's ends: row 1 of the batch reads zeros
    before its first position, not row 0's last, and no gradient comes to
    row 0's last positions from row 1's first."""
    x, w, bs, dy = operands(jnp.float32, True, seed=2)
    y, dx = kernels(x, w, bs), gradients(kernels, x, w, bs, dy)[0]
    for row in range(x.shape[0]):
        x1, dy1 = x[row:row + 1], dy[row:row + 1]
        close(kernels(x1, w, bs)[0], y[row], 0)
        close(gradients(kernels, x1, w, bs, dy1)[0][0], dx[row], 0)


def test_a_long_sequence_goes_in_the_largest_blocks_that_divide_it():
    """Two blocks of CONV_ROWS_MOST rows, 128 channels a step."""
    most = gated_delta_pallas.CONV_ROWS_MOST
    assert gated_delta_pallas._conv_block(2 * most, 384) == (most, 128)
    assert gated_delta_pallas._conv_block(3 * ROWS, LANES) == (ROWS, LANES)
    x, w, bs, dy = operands(jnp.float32, True, b=1, s=2 * most, c=128,
                            seed=6)
    close(kernels(x, w, bs), jnp_form(x, w, bs), 2e-6)
    for got, want in zip(gradients(kernels, x, w, bs, dy),
                         gradients(jnp_form, x, w, bs, dy)):
        close(got, want, 2e-6)


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The shape rule as on a TPU, the pair through the interpreter."""
    monkeypatch.setattr(gated_delta, "_on_tpu", lambda: True)
    monkeypatch.setattr(gated_delta_pallas, "causal_conv_kernels", kernels)


def jnp_off_the_tpu(x, w, bs):
    with pytest.MonkeyPatch.context() as off:
        off.setattr(gated_delta, "_on_tpu", lambda: False)
        return gated_delta.causal_conv(x, w, bs)


@pytest.mark.parametrize("shape,fits", [
    ((2, 128, 2 * LANES), False),           # a served prompt: under a block
    ((2, ROWS + 128, 2 * LANES), False),    # no whole number of blocks
    ((2, 2 * ROWS, 192), False),            # channels that fill no lane tile
    ((2, 3 * ROWS, 2 * LANES), True),
    ((1, ROWS, 384), True),                 # 128 channels a step
])
def test_the_shape_sends_an_input_to_the_kernels_or_the_jnp_form(
        on_a_tpu, shape, fits):
    """One function of ``x.shape`` beside ``_on_tpu()``: no option, no
    model's name. What does not fit runs the jnp form, traced with no
    Mosaic call, to the same numbers as off the TPU."""
    x = jax.random.normal(jax.random.PRNGKey(3), shape)
    w = 0.5 * jax.random.normal(jax.random.PRNGKey(4), (shape[2], K))
    assert gated_delta.conv_kernels_fit(x, w) == fits
    text = str(jax.make_jaxpr(gated_delta.causal_conv)(x, w))
    assert ("rt_gdn_conv_fwd" in text) == fits
    assert ("pallas_call" in text) == fits
    close(gated_delta.causal_conv(x, w), jnp_off_the_tpu(x, w, None),
          2e-6 if fits else 0)


def test_off_the_tpu_the_convolution_is_the_jnp_form():
    x, w, _, _ = operands(jnp.bfloat16, False)
    assert jax.default_backend() != "tpu"
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, w: gated_delta.causal_conv(x, w).astype(
            jnp.float32).sum(), (0, 1)))(x, w))
    assert "pallas_call" not in text and "rt_gdn_conv" not in text


@pytest.mark.parametrize("bias", [False, True], ids=["plain", "bias"])
def test_under_a_mesh_the_kernels_run_per_shard_of_the_batch(on_a_tpu, bias):
    """GSPMD cannot partition a Mosaic call: under a mesh of several
    devices the pair runs inside shard_map, rows of the batch over dp, the
    channels whole; the taps' gradient is summed over the shards."""
    from jax.sharding import Mesh

    from ray_tpu.parallel.sharding import DEFAULT_RULES

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))
    x, w, bs, dy = operands(jnp.float32, bias, s=ROWS, c=LANES, seed=5)
    over = partial(gated_delta.causal_conv_over, mesh, DEFAULT_RULES,
                   scope="rt.ssd.conv")
    traced = str(jax.make_jaxpr(over)(x, w, bs))
    assert "shard_map" in traced and "rt_gdn_conv_fwd" in traced
    got = jax.jit(partial(gradients, over))(x, w, bs, dy)
    want = gradients(jnp_off_the_tpu, x, w, bs, dy)
    for g, wanted in zip(got, want):
        if wanted is not None:
            close(g, wanted, 2e-6)

"""``latent.select``: the indexer's ``topk`` keys a query as a SET, found by
an exact k-th-value threshold and a compaction, against ``lax.top_k`` (whose
ties at the k-th value go to the lowest positions)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import latent


def causal(extents, t):
    """qpos [B, S] whose row sees keys 0 .. extent - 1, kpos [B, T]."""
    qpos = jnp.asarray(extents, jnp.int32) - 1
    return qpos, jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32),
                                  (qpos.shape[0], t))


def normal(seed, shape):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def ties_at_the_kth(t, k):
    """Most of a row is one of five values, so the k-th value is shared by
    hundreds of keys on either side of the cut."""
    x = np.round(normal(1, (2, 3, t)) * 2) / 2
    return x, np.full((2, 3), t)


def negative_and_zeros(t, k):
    """All scores at or under zero (signed head weights), a third of them
    ``+0.0`` and a third ``-0.0``: the total order puts ``-0.0`` under
    ``+0.0``, as ``lax.top_k`` does."""
    x = -np.abs(normal(2, (2, 3, t)))
    x[..., 0::3] = 0.0
    x[..., 1::3] = -0.0
    x[1] = normal(3, (3, t)) - 5.0
    return x, np.full((2, 3), t)


def few_keys(t, k):
    """Rows of 1, k - 1, k, k + 1 and all unmasked keys, and one whose
    unmasked keys all tie."""
    x = normal(4, (2, 3, t))
    x[1, 2] = 1.5
    return x, np.array([[1, k - 1, k], [k + 1, t, k + 3]])


def random_rows(t, k, rows=(2, 3)):
    return normal(5, (*rows, t)), np.random.default_rng(6).integers(
        1, t + 1, rows)


CASES = {
    "ties-40-16": (ties_at_the_kth, 40, 16),
    "ties-300-128": (ties_at_the_kth, 300, 128),
    "ties-32896-2048": (lambda t, k: tuple(
        a[:1, :2] for a in ties_at_the_kth(t, k)), 32896, 2048),
    "negative-zeros-40-16": (negative_and_zeros, 40, 16),
    "negative-zeros-300-128": (negative_and_zeros, 300, 128),
    "few-keys-40-16": (few_keys, 40, 16),
    "few-keys-300-16": (few_keys, 300, 16),
    "few-keys-300-128": (few_keys, 300, 128),
    "few-keys-2100-2048": (few_keys, 2100, 2048),
    "random-40-16": (random_rows, 40, 16),
    "random-300-128": (random_rows, 300, 128),
    "random-32896-2048": (partial(random_rows, rows=(1, 2)), 32896, 2048),
    "decode-300-16": (partial(random_rows, rows=(2, 1)), 300, 16),
    "decode-32896-2048": (partial(random_rows, rows=(2, 1)), 32896, 2048),
}


@pytest.mark.parametrize("case", CASES)
def test_select_is_lax_top_k_as_a_set(case, monkeypatch):
    make, t, k = CASES[case]
    scores, extents = make(t, k)
    qpos, kpos = causal(extents, t)
    monkeypatch.setattr(latent, "index_scores",
                        lambda qi, w, ki: jnp.asarray(scores))
    at, real = jax.jit(partial(latent.select, k))(None, None, None, qpos,
                                                  kpos)
    assert at.shape == real.shape == (*scores.shape[:2], k)
    assert at.dtype == jnp.int32
    masked = np.where(np.arange(t) < np.asarray(extents)[..., None], scores,
                      -np.inf)
    top, want = lax.top_k(jnp.asarray(masked), k)
    at, real, top, want = map(np.asarray, (at, real, top, want))
    assert at.min() >= 0 and at.max() < t      # every slot can be gathered
    for row in np.ndindex(*scores.shape[:2]):
        got = at[row][real[row]]
        assert len(set(got.tolist())) == len(got) \
            == min(int(np.asarray(extents)[row]), k)
        assert set(got.tolist()) == set(want[row][top[row] > -np.inf]
                                        .tolist())


def test_select_over_the_indexers_own_scores():
    """Through ``index_scores`` (signed head weights, so scores of either
    sign), the prefill block's shape at toy widths."""
    b, s, t, j, d, k = 2, 8, 200, 4, 8, 16
    qi, w, ki = (jnp.asarray(normal(7 + i, shape)) for i, shape in enumerate(
        [(b, s, j, d), (b, s, j), (b, t, d)]))
    qpos, kpos = causal(np.arange(t - b * s, t).reshape(b, s) + 1, t)
    at, real = latent.select(k, qi, w, ki, qpos, kpos)
    scores = jnp.where(latent._mask(qpos, kpos, 0),
                       latent.index_scores(qi, w, ki), -jnp.inf)
    want = lax.top_k(scores, k)[1]
    assert bool(real.all())
    np.testing.assert_array_equal(np.sort(np.asarray(at), -1),
                                  np.sort(np.asarray(want), -1))


def test_select_at_the_cells_shape_sorts_nothing():
    """The program of a prefill block, 2 x 128 queries over 32,896 keys for
    the top 2,048, holds no sort: XLA lowers ``top_k`` on the TPU to a full
    sort of every row."""
    shape = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(partial(latent.select, 2048))(
        shape((2, 128, 64, 128), jnp.bfloat16),
        shape((2, 128, 64), jnp.float32),
        shape((2, 32896, 128), jnp.bfloat16),
        shape((2, 128), jnp.int32), shape((2, 32896), jnp.int32))

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    names = list(primitives(jaxpr.jaxpr))
    assert not {n for n in names if "sort" in n or "top_k" in n}, set(names)
    # the threshold's 32 passes are one loop, not 32 copies of its body
    assert len(names) < 250, len(names)

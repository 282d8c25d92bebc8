"""``rt_sparse_attend`` (ops/sparse_attend.py): attention of each query over
the cached rows selected for it, against the ``jnp`` form of
``latent._sparse``; under ``interpret=True`` at a toy size of the dots3
cell's ratios (value part 8 x the rope part, as 512 : 64), and the choice
between the two forms. That Mosaic takes it at the cell's block is held
beside the flash kernels' compile, in tests/test_flash_kernels.py."""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import latent
from ray_tpu.models.transformer import LatentDims
from ray_tpu.ops import sparse_attend as kernel

gen = importlib.import_module("ray_tpu.models.generate")

B, S, H, V, ROPE, T, TOPK = 2, 3, 16, 256, 32, 72, 16
SCALE = 1.0 / math.sqrt(24 + ROPE)


def jnp_form(q, keys, at, real, v, scale):
    """What ``latent._sparse`` does between ``_absorb`` and ``_unabsorb``
    off the kernel's path."""
    rows = jax.vmap(lambda rows, at: rows[at])(keys, at)
    scores = (jnp.einsum("bshr,bskr->bshk", q[..., :v], rows[..., :v])
              + jnp.einsum("bshd,bskd->bshk", q[..., v:], rows[..., v:])) \
        * scale
    p = latent._softmax(scores, real[:, :, None, :])
    return jnp.einsum("bshk,bskr->bshr", p.astype(rows.dtype), rows[..., :v])


def inputs(seed, s=S):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, s, H, V + ROPE)), jnp.bfloat16)
    keys = jnp.asarray(rng.standard_normal((B, T, V + ROPE)), jnp.bfloat16)
    at = np.stack([np.stack([np.sort(rng.choice(T, TOPK, replace=False))
                             for _ in range(s)]) for _ in range(B)])
    return rng, q, keys, at.astype(np.int32), np.ones((B, s, TOPK), bool)


# The last five are what two buffers of rows, filled a query ahead, can get
# wrong: the first query of a batch row has nothing fetched for it, the
# last fetches for nobody, and which buffer a query reads goes by its index.
CASES = ["all_real", "few_keys", "rows_unlike", "ascending", "shuffled",
         "one_query", "odd_queries", "even_queries", "caches_unlike",
         "last_query_one_key"]
QUERIES = {"one_query": 1, "odd_queries": 5, "even_queries": 4}


def selections(case):
    rng, q, keys, at, real = inputs(CASES.index(case), QUERIES.get(case, S))
    if case == "few_keys":      # a query with fewer keys than topk: the
        real[:, 0, 5:] = False  # slots it does not fill point at key 0
        real[1, 2, 1:] = False
        at = np.where(real, at, 0)
    elif case == "rows_unlike":
        at[1] = at[0][:, ::-1] // 2 + 1
        assert not (at[0] == at[1]).all()
    elif case == "shuffled":
        at = rng.permuted(at, axis=-1)
    elif case == "caches_unlike":
        # row 0 selects out of its cache's first half and row 1 out of its
        # second, whose rows are 64 x larger: a row of row 0's cache under
        # row 1's first query, or the other way round, would show
        keys = keys.at[1].multiply(64)
        at = np.stack([[lo + np.sort(rng.choice(T // 2, TOPK, replace=False))
                        for _ in range(S)] for lo in (0, T // 2)])
        at = at.astype(np.int32)
    elif case == "last_query_one_key":  # nothing real but the first slot
        real[0, -1, 1:] = False
        at = np.where(real, at, 0)
    return q, keys, jnp.asarray(at), jnp.asarray(real)


@pytest.mark.parametrize("case", CASES)
def test_kernel_is_the_jnp_form(case):
    q, keys, at, real = selections(case)
    got = kernel.sparse_attend(q, kernel.pack(keys, V), at, real, v=V,
                               scale=SCALE, interpret=True)
    assert got.shape == at.shape[:2] + (H, V) and got.dtype == keys.dtype
    # float32 scores against the jnp form's bfloat16: within its rounding
    # of the float32 answer
    exact = jnp_form(*(a.astype(jnp.float32) for a in (q, keys)), at, real,
                     V, SCALE)
    off = np.abs(np.asarray(got, np.float32) - exact).max()
    ref_off = np.abs(np.asarray(jnp_form(q, keys, at, real, V, SCALE),
                                np.float32) - exact).max()
    assert off <= max(ref_off, 2e-2), (off, ref_off)


def test_a_block_is_its_queries_one_at_a_time():
    """Nothing leaks from a query into its neighbour: a block's output is
    bitwise that of the same kernel called a query at a time, where no row
    is fetched ahead."""
    q, keys, at, real = selections("few_keys")
    packed = kernel.pack(keys, V)
    run = lambda *a: kernel.sparse_attend(            # noqa: E731
        *a, v=V, scale=SCALE, interpret=True)
    block = run(q, packed, at, real)
    alone = jnp.concatenate([run(q[:, j:j + 1], packed, at[:, j:j + 1],
                                 real[:, j:j + 1]) for j in range(S)], 1)
    np.testing.assert_array_equal(np.asarray(block, np.float32),
                                  np.asarray(alone, np.float32))


def test_the_selection_is_a_set():
    """The order of a query's slots moves nothing but the order of a sum."""
    q, keys, at, real = selections("few_keys")
    order = np.random.default_rng(3).permutation(TOPK)
    packed = kernel.pack(keys, V)
    a = kernel.sparse_attend(q, packed, at, real, v=V, scale=SCALE,
                             interpret=True)
    b = kernel.sparse_attend(q, packed, at[..., order], real[..., order],
                             v=V, scale=SCALE, interpret=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=2e-2)


def test_kernel_refuses_sizes_it_does_not_take():
    assert kernel.takes(32896, 576, 512, 2048)
    assert not kernel.takes(32896, 24 + 16, 24, 16)     # the tests' toy
    assert not kernel.takes(1 << 17, 576, 512, 2048)    # no room in VMEM
    q, keys, at, real = selections("all_real")
    with pytest.raises(ValueError, match="does not take"):
        kernel.sparse_attend(q[..., :V + 16], kernel.pack(keys, V), at, real,
                             v=V - 128, scale=SCALE, interpret=True)


def test_sparse_off_a_tpu_takes_the_jnp_path_bitwise(monkeypatch):
    """On the CPU, and at S x topk <= T whatever the platform, ``_sparse``
    returns what it returned: the gather and the three passes."""
    dims = LatentDims(heads=4, q_rank=8, kv_rank=V, nope=24, rope=ROPE, v=24)
    cfg = type("Cfg", (), {"index_topk": TOPK})()
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    s, j, d = 4, 2, 8
    wukv = jax.random.normal(ks[0], (V, 4, 48), jnp.bfloat16)
    q_nope = jax.random.normal(ks[1], (B, s, 4, 24), jnp.bfloat16)
    q_rope = jax.random.normal(ks[2], (B, s, 4, ROPE), jnp.bfloat16)
    keys = jax.random.normal(ks[3], (B, T, V + ROPE), jnp.bfloat16)
    ki = jax.random.normal(ks[4], (B, T, d), jnp.bfloat16)
    qi = jax.random.normal(ks[5], (B, s, j, d), jnp.bfloat16)
    w = jnp.ones((B, s, j), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(T - s, T), (B, s))
    kpos = jnp.arange(T)[None]

    def run():
        return latent._sparse(cfg, dims, wukv, q_nope, q_rope, qpos, keys,
                              kpos, ki, (qi, w))

    assert not latent.sparse_in_kernel(dims, TOPK, s, T)    # the CPU
    o, taps = run()
    at, real = latent.select(TOPK, qi, w, ki, qpos, kpos)
    want = latent._unabsorb(dims, wukv, jnp_form(
        jnp.concatenate([latent._absorb(dims, wukv, q_nope), q_rope], -1),
        keys, at, real, V, 1.0 / math.sqrt(24 + ROPE)))
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(taps["selected"], at[:, -1])

    # a TPU with no more rows to fetch than the cache holds: the same path
    monkeypatch.setattr(latent, "_on_tpu", lambda: True)
    assert not latent.sparse_in_kernel(dims, TOPK, s, T)    # 4 x 16 <= 72
    assert latent.sparse_in_kernel(dims, TOPK, 5, T)        # 5 x 16 > 72
    assert not latent.sparse_in_kernel(dims, TOPK, 256, 4096)   # by block
    np.testing.assert_array_equal(np.asarray(run()[0], np.float32),
                                  np.asarray(o, np.float32))


def test_generate_call_counts_the_kernels_queries(monkeypatch):
    from benchmark.apps import serve_dots3 as app
    from tests.test_dots3 import PUBLISHED

    cfg = app.transformer_config(app.model_kwargs(PUBLISHED, 32896,
                                                  "reference"), remat=False)
    attrs = gen.call_span(cfg, 2, 32768, 128).attrs
    assert attrs["sparse_kernel_queries"] == 0              # the CPU
    monkeypatch.setattr(latent, "_on_tpu", lambda: True)
    attrs = gen.call_span(cfg, 2, 32768, 128).attrs
    # both indexed layers' prompt queries of both rows; the 127 decode
    # steps' 508 queries keep the jnp form
    assert attrs["sparse_kernel_queries"] == 2 * 2 * 32768 == 131072
    assert attrs["keys_attended"] // 2048 > attrs["sparse_kernel_queries"] \
        - 4 * 2048
    # a prompt no longer than the selection selects nothing
    assert gen.call_span(cfg, 2, 1024, 128).attrs[
        "sparse_kernel_queries"] == 0

"""The plain reference (benchmark/reference/llama.py) against the system at
a tiny size on the CPU; at the published widths the same comparison is each
run's ``correct``. Also the seed folding every app uses."""

import numpy as np
import pytest

from bench_paths import config
from benchmark.apps import lm

TINY = {"family": "llama", "hidden_size": 64, "intermediate_size": 160,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "vocab_size": 97, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "param_dtype": "float32"}


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import transformer_init
    cfg = lm.transformer_config(lm.model_kwargs(TINY, 32, "reference"),
                                remat=False)
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)   # judge the maths
    params = transformer_init(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, 97, (4, 24), dtype=np.int32))
    return cfg, params, tokens


def test_forward_agrees_with_the_system(tiny):
    import jax
    from ray_tpu.models import transformer_apply
    cfg, params, tokens = tiny
    ref = lm.reference_module(TINY)
    with jax.default_matmul_precision("highest"):
        system = transformer_apply(params, tokens, cfg)
    plain = ref.forward(lm.reference_weights(params, TINY), tokens, TINY)
    assert plain.shape == (4, 24, 97) and str(plain.dtype) == "float32"
    got = ref.compare_logits(system, plain)
    assert got["rms_over_std"] < 1e-4 and got["max_over_std"] < 1e-3
    assert got["n_logits"] == 4 * 24 * 97


def test_loss_agrees_with_the_system_whatever_the_rows_per_pass(tiny):
    import jax
    from ray_tpu.models.transformer import transformer_loss
    cfg, params, tokens = tiny
    ref = lm.reference_module(TINY)
    with jax.default_matmul_precision("highest"):
        system = float(transformer_loss(params, {"tokens": tokens}, cfg))
    weights = lm.reference_weights(params, TINY)
    for rows in (1, 3, 4):
        assert ref.loss(weights, tokens, TINY, rows_per_pass=rows) == \
            pytest.approx(system, abs=2e-5)


def test_prefill_then_decode_agrees_with_the_full_forward(tiny):
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.generate import decode_step, prefill
    cfg, params, tokens = tiny
    ref = lm.reference_module(TINY)
    p, k = 16, 8
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(params, tokens[:, :p], cfg, max_len=p + k)
        system = [logits]
        for j in range(k - 1):
            logits, cache = decode_step(params, tokens[:, p + j],
                                        jnp.asarray(p + j), cache, cfg)
            system.append(logits)
    plain = ref.forward(lm.reference_weights(params, TINY), tokens, TINY)
    got = ref.compare_logits(jnp.stack(system, 1), plain[:, p - 1:p + k - 1])
    assert got["rms_over_std"] < 1e-4 and got["max_over_std"] < 1e-3


def test_a_wrong_system_is_caught(tiny):
    """What the tolerances are for: one layer left out, or weights rounded
    to 8 bits, moves the comparison far past them."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import transformer_apply
    cfg, params, tokens = tiny
    ref = lm.reference_module(TINY)
    plain = ref.forward(lm.reference_weights(params, TINY), tokens, TINY)
    fewer = dict(params, layers=jax.tree.map(lambda a: a[:2],
                                             params["layers"]))
    got = ref.compare_logits(transformer_apply(fewer, tokens, cfg), plain)
    assert got["rms_over_std"] > 0.04

    def int8(w):
        if w.ndim < 2:
            return w
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        return jnp.round(w / scale) * scale
    rounded = jax.tree.map(int8, params)
    got = ref.compare_logits(transformer_apply(rounded, tokens, cfg), plain)
    assert got["rms_over_std"] > 0.004     # ~40x float32's own 1e-4


def test_reference_takes_bfloat16_weights_as_the_values_they_are(tiny):
    import jax
    import jax.numpy as jnp
    cfg, params, tokens = tiny
    ref = lm.reference_module(TINY)
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    back = jax.tree.map(lambda a: a.astype(jnp.float32), half)
    a = ref.forward(lm.reference_weights(half, TINY), tokens, TINY)
    b = ref.forward(lm.reference_weights(back, TINY), tokens, TINY)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-6


def test_the_reference_keeps_the_published_epsilon_unless_told(tiny):
    """The configuration's ``rms_norm_eps`` is what the reference runs; the
    program's own (fixed at 1e-6 today, its configuration's once it has the
    field) is named by the caller, and the distance between the two is what
    the apps hold as ``program_eps_gap``."""
    import types
    cfg, params, tokens = tiny
    ref = lm.reference_module(TINY)
    weights = lm.reference_weights(params, TINY)
    published = dict(TINY, rms_norm_eps=1e-2)
    a = ref.forward(weights, tokens, published)
    assert float(abs(a - ref.forward(weights, tokens, TINY)).max()) > 1e-3
    b = ref.forward(weights, tokens, published, eps=1e-6)
    assert float(abs(b - ref.forward(weights, tokens, TINY)).max()) == 0.0
    assert ref.loss(weights, tokens, published) != \
        ref.loss(weights, tokens, TINY)
    assert lm.program_rms_norm_eps(cfg) == 1e-6
    assert lm.program_rms_norm_eps(
        types.SimpleNamespace(rms_norm_eps=1e-5)) == 1e-5
    for name in ("mistral-7b-v0.3-l2", "mistral-7b-v0.3-l24",
                 "internlm2-1.8b"):
        data = config(name)
        assert data["rms_norm_eps"] == 1e-5        # as published
        assert "program_rms_norm_eps" not in data


def test_generated_tokens_are_held_to_the_reference(tiny):
    """The compiled ``generate``'s own tokens, teacher-forced through the
    reference: each is its argmax (float32 here); tokens from a loop that
    is one position off lie far under it."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import generate
    cfg, params, tokens = tiny
    ref = lm.reference_module(TINY)
    p, new = 16, 8
    with jax.default_matmul_precision("highest"):
        out = generate(params, tokens[:, :p], cfg=cfg, temperature=0.0,
                       max_new_tokens=new)
    out = jnp.asarray(out)[:, -new:]
    k = new - 1
    forced = jnp.concatenate([tokens[:, :p], out[:, :k]], axis=1)
    plain = ref.forward(lm.reference_weights(params, TINY), forced, TINY)
    got = ref.token_deficit(plain[:, p - 1:p + k], out)
    assert got == {"token_deficit_over_std": 0.0, "token_mismatches": 0,
                   "tokens_checked": 4 * new}
    wrong = ref.token_deficit(plain[:, p - 1:p + k], (out + 1) % 97)
    assert wrong["token_mismatches"] == 4 * new
    assert wrong["token_deficit_over_std"] > 1.0


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 - 1, 2 ** 31, 2 ** 31 + 11,
                                  2 ** 32, 2 ** 32 + 7, 2 ** 63 + 5])
def test_any_seed_folds_to_31_bits(seed):
    import jax
    folded = lm.fold_seed(seed)
    assert 0 <= folded < 2 ** 31
    assert folded == lm.fold_seed(seed)
    jax.random.PRNGKey(folded)
    np.random.default_rng(folded)
    np.random.default_rng([folded, 3])


def test_seeds_a_driver_may_pass_do_not_collide():
    seeds = [0, 7, 31337, 987654321, 2 ** 31, 2 ** 31 + 11, 2 ** 32,
             2 ** 32 + 7, 2000000011, 1234567891]
    assert len({lm.fold_seed(s) for s in seeds}) == len(seeds)


@pytest.mark.parametrize("name,params_b", [
    ("mistral-7b-v0.3-l2", 0.7047), ("mistral-7b-v0.3-l24", 5.5031),
    ("internlm2-1.8b", 1.8891)])
def test_published_sizes_reach_the_program_unchanged(name, params_b):
    from ray_tpu.models.transformer import transformer_num_params
    data = config(name)
    cfg = lm.transformer_config(lm.model_kwargs(data, 2048, "flash"),
                                remat=True)
    assert cfg.head_dim == 128 and cfg.kv_heads == 8
    assert cfg.rope_theta == 1e6 and not cfg.tied_embeddings
    assert transformer_num_params(cfg) / 1e9 == pytest.approx(params_b,
                                                              abs=1e-4)
    assert str(cfg.param_dtype) == data["param_dtype"]
    toy = lm.effective_config(data, rehearse=True)
    assert toy["hidden_size"] == 64 and toy["family"] == data["family"]
    assert lm.effective_config(data, rehearse=False) == data

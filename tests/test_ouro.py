"""A looped stack (the layers run ``loop_steps`` times over one set of
weights, sandwich norms, an exit gate) through ``transformer_apply``,
``prefill``, ``decode_step`` and ``generate``, against the plain reference
``benchmark/reference/ouro.py`` at a toy size on the CPU: seeded random
weights, norm scales drawn away from 1 so that a missing norm shows, the
gate's weight and bias away from 0. float32 compute: the two sides differ
by the order of their sums alone."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.apps import serve_ouro                      # noqa: E402
from benchmark.reference import ouro as reference          # noqa: E402
from benchmark.testdata.sweep_ouro import FAULTS, planted  # noqa: E402
from ray_tpu.models import (TransformerConfig, generate,   # noqa: E402
                            generate_with_stats, init_cache, prefill,
                            transformer_apply, transformer_apply_and_exits,
                            transformer_init, transformer_loss)
from ray_tpu.models.generate import (decode_step_and_exits,  # noqa: E402
                                     prefill_and_exits)
from ray_tpu.models.transformer import transformer_num_params  # noqa: E402

T, L = 3, 2
TOLERANCE = 2e-5        # float32 on both sides; logits are of order 1
CONFIG = {"family": "ouro", "num_attention_heads": 4,
          "num_key_value_heads": 2, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-6, "total_ut_steps": T}


def _cfg(**over):
    return TransformerConfig(**{**dict(
        vocab_size=97, d_model=64, n_layers=L, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=32, loop_steps=T, sandwich_norm=True,
        dtype=jnp.float32, remat=False, attn_impl="reference"), **over})


def _params(cfg, seed=0):
    params = transformer_init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)

    def away_from_one(a, i):
        return 1.0 + 0.5 * jax.random.normal(jax.random.fold_in(key, i),
                                             a.shape)
    layers = dict(params["layers"])
    for i, name in enumerate(("ln1", "ln2", "ln1_post", "ln2_post")):
        if name in layers:
            layers[name] = away_from_one(layers[name], i)
    params = dict(params, layers=layers,
                  final_norm=away_from_one(params["final_norm"], 9))
    if "exit_gate" in params:
        params["exit_gate"] = {"w": 5.0 * params["exit_gate"]["w"],
                               "b": jnp.asarray(0.3, jnp.float32)}
    return params


def _tokens(rows=2, n=12, seed=5):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, n), 0, 97)


def _reference(params, tokens):
    return reference.forward_and_exits(
        serve_ouro.reference_weights(params, CONFIG), tokens, CONFIG)


def _through_the_cache(params, tokens, cfg, s=7, max_len=16):
    """``prefill`` of ``s`` positions, then ``decode_step`` over the rest
    -> (logits, exits) at positions s-1 .. the last."""
    logits, cache, exits = prefill_and_exits(params, tokens[:, :s], cfg,
                                             max_len=max_len)
    got, gates = [logits], [exits]
    for j in range(s, tokens.shape[1]):
        logits, cache, exits = decode_step_and_exits(
            params, tokens[:, j], jnp.asarray(j, jnp.int32), cache, cfg)
        got.append(logits)
        gates.append(exits)
    return jnp.stack(got, 1), \
        None if gates[0] is None else jnp.stack(gates, 1)


def test_full_forward_against_the_reference():
    cfg = _cfg()
    params, tokens = _params(cfg), _tokens()
    logits, exits = transformer_apply_and_exits(params, tokens, cfg)
    want_logits, want_exits = _reference(params, tokens)
    np.testing.assert_allclose(logits, want_logits, atol=TOLERANCE)
    np.testing.assert_allclose(exits, want_exits, atol=TOLERANCE)
    assert exits.shape == (2, 12, T)
    np.testing.assert_allclose(exits.sum(-1), 1.0, atol=1e-6)
    # the gate says something: every step takes a share of the positions
    share = np.asarray(exits).mean(axis=(0, 1))
    assert share.min() > 0.05 and share.max() < 0.9
    np.testing.assert_array_equal(transformer_apply(params, tokens, cfg),
                                  logits)


def test_prefill_then_decode_through_the_cache_against_the_reference():
    cfg = _cfg()
    params, tokens = _params(cfg), _tokens()
    logits, exits = _through_the_cache(params, tokens, cfg)
    want_logits, want_exits = _reference(params, tokens)
    np.testing.assert_allclose(logits, want_logits[:, 6:], atol=TOLERANCE)
    np.testing.assert_allclose(exits, want_exits[:, 6:], atol=TOLERANCE)


@pytest.mark.parametrize("new_tokens", [1, 6])
def test_greedy_tokens_pinned_to_reforward(new_tokens):
    cfg = _cfg()
    params = _params(cfg, seed=7)
    prompt = _tokens(n=6, seed=8)
    seq, steps_sum = prompt, 0.0
    for _ in range(new_tokens):
        logits, exits = transformer_apply_and_exits(params, seq, cfg)
        steps_sum += float((exits[:, -1] * jnp.arange(1, T + 1)).sum())
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    got, stats = generate_with_stats(params, prompt, cfg,
                                     max_new_tokens=new_tokens)
    np.testing.assert_array_equal(got, seq[:, 6:])
    assert float(stats["exit_tokens"]) == 2 * new_tokens
    assert float(stats["exit_steps_sum"]) == pytest.approx(steps_sum,
                                                           rel=1e-5)
    assert 1.0 < steps_sum / (2 * new_tokens) < T
    np.testing.assert_array_equal(
        generate(params, prompt, cfg, max_new_tokens=new_tokens), got)


@pytest.mark.parametrize("prompt_len, new_tokens", [
    pytest.param(8, 64, id="2x32"),
    pytest.param(7, 70, id="prompt-no-multiple-of-8"),
    pytest.param(6, 77, id="steps-no-multiple-of-the-segment"),
])
def test_segmented_generate_is_a_chain_of_whole_cache_steps(prompt_len,
                                                            new_tokens):
    """The token loop in two segments, the first reading a prefix of every
    (loop step, layer)'s slot: token for token, and in the exit counter,
    what ``decode_step_and_exits`` over the whole cache gives."""
    from functools import partial

    from ray_tpu.models.generate import _decode_segments

    assert len(_decode_segments(prompt_len, new_tokens)) == 2
    cfg = _cfg(max_seq=128)
    params = _params(cfg, seed=7)
    prompt = _tokens(n=prompt_len, seed=8)
    logits, cache, exits = prefill_and_exits(
        params, prompt, cfg, max_len=prompt_len + new_tokens)
    step = jax.jit(partial(decode_step_and_exits, cfg=cfg))
    want, steps_sum = [], 0.0
    for i in range(new_tokens):
        want.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        steps_sum += float((exits * jnp.arange(1, T + 1)).sum())
        logits, cache, exits = step(
            params, want[-1], jnp.asarray(prompt_len + i, jnp.int32), cache)
    got, stats = generate_with_stats(params, prompt, cfg,
                                     max_new_tokens=new_tokens)
    np.testing.assert_array_equal(got, jnp.stack(want, axis=1))
    assert float(stats["exit_tokens"]) == 2 * new_tokens
    assert float(stats["exit_steps_sum"]) == pytest.approx(steps_sum,
                                                           rel=1e-5)


def test_an_unlooped_model_has_no_stats():
    cfg = _cfg(loop_steps=1, sandwich_norm=False)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens, stats = generate_with_stats(params, _tokens(n=5), cfg,
                                        max_new_tokens=3)
    assert stats == {} and tokens.shape == (2, 3)
    assert transformer_apply_and_exits(params, _tokens(), cfg)[1] is None


def test_the_cache_has_a_slot_for_every_loop_step_and_layer():
    cfg = _cfg()
    params, tokens = _params(cfg), _tokens()
    assert init_cache(cfg, 2, 16)["k"].shape == (T * L, 2, 16, 2, 16)
    _, cache = prefill(params, tokens[:, :7], cfg, max_len=16)
    assert cache["k"].shape == cache["v"].shape == (T * L, 2, 16, 2, 16)
    for name in ("k", "v"):
        filled = np.asarray(cache[name])
        # every slot holds the prompt's positions, and nothing after them
        assert np.abs(filled[:, :, :7]).max(axis=(1, 2, 3, 4)).min() > 0
        assert not filled[:, :, 7:].any()
    _, after, _ = decode_step_and_exits(params, tokens[:, 7],
                                        jnp.asarray(7, jnp.int32), cache,
                                        cfg)
    for name in ("k", "v"):
        changed = np.asarray(after[name]) != np.asarray(cache[name])
        # exactly position 7, in every one of the T * L slots, every row
        assert not np.delete(changed, 7, axis=2).any()
        assert changed[:, :, 7].any(axis=(2, 3)).all()
    # the slots of one layer differ between loop steps: no step's keys
    # stand in for another's
    k = np.asarray(after["k"])[:, :, :8]
    for t in range(1, T):
        assert np.abs(k[t * L] - k[0]).max() > 1e-3


def _cache_after(params, tokens, cfg, s=7, max_len=16):
    """The cache that ``prefill`` of ``s`` positions and a ``decode_step``
    for each of the rest leave."""
    _, cache, _ = prefill_and_exits(params, tokens[:, :s], cfg,
                                    max_len=max_len)
    for j in range(s, tokens.shape[1]):
        _, cache, _ = decode_step_and_exits(
            params, tokens[:, j], jnp.asarray(j, jnp.int32), cache, cfg)
    return cache


@pytest.mark.parametrize("passes_kept", [1, T])
def test_the_caches_keys_and_values_are_the_references(passes_kept):
    """Slot ``t * L + l`` holds what the reference's layer l made of loop
    step t's state: rotated keys and values, prompt and decoded positions
    alike; ``cache_errors`` reads them a slot at a time."""
    cfg = _cfg()
    params, tokens = _params(cfg), _tokens()
    cache = _cache_after(params, tokens, cfg)
    _, _, want = reference.forward_and_cache(
        serve_ouro.reference_weights(params, CONFIG), tokens, CONFIG,
        passes_kept=passes_kept)
    slots = passes_kept * L
    assert want["k"].shape == want["v"].shape == (slots, 2, 12, 2, 16)
    for name in ("k", "v"):
        np.testing.assert_allclose(cache[name][:slots, :, :12], want[name],
                                   atol=TOLERANCE)
    errors = np.asarray(reference.cache_errors(cache, want, 7))
    assert errors.shape == (slots, 2, 2) and errors.max() < TOLERANCE
    # a key written one position late in one slot, by decode alone, shows
    # in that slot's keys at the decoded positions and nowhere else
    late = dict(cache, k=cache["k"].at[1, :, 8:12].set(cache["k"][1, :, 7:11]))
    errors = np.asarray(reference.cache_errors(late, want, 7))
    assert errors[1, 0, 1] > 1000 * TOLERANCE
    assert np.delete(errors.reshape(-1), 1 * 4 + 1).max() < TOLERANCE


def test_the_first_loop_steps_cache_tells_fewer_bits_from_rounding():
    """What the serving cell's ``cache_over_floor`` rests on, at a toy
    size: int8 weights through the reference move the first loop step's
    keys and values several times as far as bfloat16 activations do, in
    every slot."""
    cfg = _cfg()
    params, tokens = _params(cfg), _tokens()
    weights = serve_ouro.reference_weights(params, CONFIG)
    want, floor, int8 = (
        reference.forward_and_cache(w, tokens, CONFIG, dtype=dtype,
                                    passes_kept=1)[2]
        for w, dtype in ((weights, None), (weights, jnp.bfloat16),
                         (reference.int8_weights(weights), None)))
    over = reference.over_floor(reference.cache_errors(int8, want, 7),
                                reference.cache_errors(floor, want, 7))
    assert over["typical"] > 1.5 and over["worst"] >= over["typical"]
    same = reference.over_floor(reference.cache_errors(floor, want, 7),
                                reference.cache_errors(floor, want, 7))
    assert same == {"typical": pytest.approx(1.0), "worst": 1.0}


def test_loop_steps_1_without_the_new_leaves_is_todays_model():
    """The fields at their defaults: the same tree, bit-for-bit the same
    logits as a configuration that never heard of them, through every
    path."""
    plain = dict(vocab_size=97, d_model=64, n_layers=3, n_heads=4,
                 n_kv_heads=2, d_ff=128, max_seq=32, dtype=jnp.float32,
                 remat=False)
    cfg = TransformerConfig(**plain)
    assert (cfg.loop_steps, cfg.sandwich_norm,
            cfg.early_exit_threshold) == (1, False, 1.0)
    params = transformer_init(jax.random.PRNGKey(2), cfg)
    assert set(params) == {"embed", "layers", "final_norm", "lm_head"}
    assert set(params["layers"]) == {"ln1", "ln2", "attn", "mlp"}
    tokens = _tokens()
    from ray_tpu.models import transformer as tr
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(12), (2, 12))
    x, _ = tr._stage_scan(cfg, None, params["layers"], x, positions)
    todays = (tr._norm(cfg, x, params["final_norm"])
              @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    np.testing.assert_array_equal(transformer_apply(params, tokens, cfg),
                                  todays)
    logits, exits = _through_the_cache(params, tokens, cfg)
    assert exits is None
    np.testing.assert_allclose(logits, todays[:, 6:], atol=TOLERANCE)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_moves_the_logits(fault):
    """Each of the architecture's four faults, planted in the program as
    the chip's sweep plants them, is off the reference by far more than the
    tolerance, on the full forward or through the cache."""
    sound = _cfg()
    params, tokens = _params(sound), _tokens()
    want, _ = _reference(params, tokens)
    with planted(fault, sound, params) as (cfg, faulty):
        full = transformer_apply(faulty, tokens, cfg)
        cached, _ = _through_the_cache(faulty, tokens, cfg)
    off_full = float(jnp.abs(full - want).max())
    off_cached = float(jnp.abs(cached - want[:, 6:]).max())
    if fault == "one_slot_a_layer":     # the cache's fault: decode alone
        assert off_full < TOLERANCE
    else:
        assert off_full > 1000 * TOLERANCE
    assert off_cached > 1000 * TOLERANCE
    # and nothing stays planted
    np.testing.assert_allclose(transformer_apply(params, tokens, sound),
                               want, atol=TOLERANCE)
    np.testing.assert_allclose(_through_the_cache(params, tokens, sound)[0],
                               want[:, 6:], atol=TOLERANCE)


def test_an_early_exit_threshold_under_1_is_refused_in_words():
    with pytest.raises(NotImplementedError) as e:
        _cfg(early_exit_threshold=0.9)
    said = str(e.value)
    assert "leave the loop at different steps" in said
    assert "cache slots of the skipped steps" in said


@pytest.mark.parametrize("make", ["loss", "train_step"])
def test_a_loss_over_a_looped_stack_is_refused_in_words(make):
    cfg = _cfg()
    with pytest.raises(NotImplementedError) as e:
        if make == "loss":
            transformer_loss(_params(cfg), {"tokens": _tokens()}, cfg)
        else:
            from ray_tpu.parallel import MeshSpec, build_mesh
            from ray_tpu.train import make_lm_train_step
            make_lm_train_step(cfg, build_mesh(MeshSpec(dp=1)))
    said = str(e.value)
    assert "exit gate's distribution" in said and "entropy term" in said


@pytest.mark.parametrize("over, says", [
    (dict(pp_stages=2), "pp_stages > 1"),
    (dict(layer_types=("full", "linear"), linear_key_heads=2,
          linear_value_heads=2), "layer pattern"),
    (dict(num_experts=4), "expert layer"),
    (dict(loop_steps=0), "at least 1"),
])
def test_loops_the_program_cannot_run_are_refused_in_words(over, says):
    with pytest.raises(ValueError, match=says):
        _cfg(**over)


def test_the_published_sizes_count_2_667_974_657_parameters():
    cfg = TransformerConfig(vocab_size=49152, d_model=2048, n_layers=48,
                            n_heads=16, n_kv_heads=16, d_ff=5632,
                            loop_steps=4, sandwich_norm=True)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert transformer_num_params(cfg) == 2_667_974_657 == \
        48 * layer + 2 * 100_663_296 + 2_048 + 2_049
    # the loop adds passes, not parameters
    assert transformer_num_params(dataclasses.replace(cfg, loop_steps=2)) \
        == 2_667_974_657

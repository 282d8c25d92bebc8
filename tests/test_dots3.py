"""Latent attention with a learned sparse indexer, window layers, a leading
dense layer and sigmoid bias-corrected routing over a share of the experts
(models/latent.py, models/moe.py, models/generate.py) against the plain
reference of the ``dots3`` family, at a small size on the CPU, in float32 so
that the comparison is of the mathematics. ``index_topk`` 16 and a window of
9 at 64 and more positions, so that both bite."""

import dataclasses
import importlib
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.apps import lm, serve_dots3 as app
from benchmark.reference import dots3 as reference
from ray_tpu.models import (TransformerConfig, generate_with_stats,
                            transformer_apply, transformer_init)
from ray_tpu.models import latent, moe
from ray_tpu.models.transformer import (_output_gate,
                                        transformer_logical_axes,
                                        transformer_num_params)

# the module: ``ray_tpu.models.generate`` by attribute is the function
gen = importlib.import_module("ray_tpu.models.generate")

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(CHECKOUT, "benchmark", "configs",
                       "dots3-note-prev-l5-e32.json")) as f:
    PUBLISHED = json.load(f)
# the rehearsal's toy widths under the published keys, in float32
TOY = dict(lm.effective_config(PUBLISHED, True), param_dtype="float32",
           torch_dtype="float32")
PROMPT, DECODED = 64, 8
SEQ = PROMPT + DECODED
ONE_BLOCK = {"positions": SEQ, "index_queries": SEQ, "queries": SEQ,
             "heads": 64}


def program_config(config=TOY, seq=SEQ, **overrides) -> TransformerConfig:
    cfg = app.transformer_config(app.model_kwargs(config, seq, "reference"),
                                 remat=False)
    return dataclasses.replace(cfg, **overrides)


def seeded(cfg, seed=0):
    """The app's seeded parameters with every leaf moved off its start:
    the norms' scales and the LayerNorm's bias too, so that no term drops
    out of a comparison."""
    params = app.seeded_params(cfg, seed)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def tokens_of(seed=5, rows=2, seq=SEQ):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0,
                              TOY["vocab_size"])


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(1e-6, float(np.abs(want).max()))
    np.testing.assert_allclose(got / scale, want / scale, atol=tol, rtol=0)


def reference_details(params, config, tokens, keep_from=0, sizes=ONE_BLOCK):
    return reference.forward_and_details(
        app.reference_weights(params, config), tokens, config,
        keep_from=keep_from, sizes=sizes)


@pytest.mark.parametrize("layer_types", [
    ["full_attention", "full_attention"],
    ["full_attention", "full_attention", "sliding_attention"],
    ["sliding_attention", "sliding_attention"],
])
def test_each_layer_kind_against_the_reference(layer_types):
    """A dense layer and an expert layer of each kind (the dense one of the
    period's first kind, as the program has it): the selection of 16 of up
    to 72 keys, the window of 9, the head-wise gate, the rescale."""
    config = dict(TOY, num_hidden_layers=len(layer_types),
                  layer_types=layer_types)
    cfg = program_config(config)
    params = seeded(cfg)
    tokens = tokens_of()
    want = reference_details(params, config, tokens)
    close(transformer_apply(params, tokens, cfg), want["logits"])


def test_the_five_layer_stack_forward_against_the_reference():
    cfg = program_config()
    assert cfg.layer_types == ("latent", "window", "window", "window")
    assert (cfg.first_dense_layers, cfg.periods) == (1, 1)
    params = seeded(cfg)
    tokens = tokens_of()
    want = reference_details(params, TOY, tokens)
    close(transformer_apply(params, tokens, cfg), want["logits"])
    # the reference in blocks (as it runs at the published widths) is the
    # reference in one
    blocks = reference_details(params, TOY, tokens, sizes={
        "positions": 32, "index_queries": 8, "queries": 24, "heads": 2})
    close(blocks["logits"], want["logits"], tol=1e-6)


@pytest.mark.parametrize("prompt,chunk", [
    (64, 16), (64, 32), (64, 64),
    # chunks under the ring's 16 rows: of the window's 8 earlier positions,
    # and of fewer, so that a chunk's keys are mostly the ring's
    (64, 8), (64, 4),
    # a prompt shorter than the window of 9, in one chunk and in two: every
    # position before the chunk's window does not exist
    (8, 8), (8, 4),
    (24, 24),       # the ring wraps inside the one chunk
])
def test_chunked_prefill_then_decode_through_the_three_caches(prompt, chunk):
    """Prefill in chunks, then 8 decode steps: logits, the cached latents,
    indexer keys and rings, the selections and the window layers' key
    counts against the reference's full forward. A window layer's chunk
    attends to keys in position order, the 8 before it out of the ring:
    chunks below, at and above the ring's 16 rows, and a first chunk that
    has no earlier keys."""
    seq = prompt + DECODED
    cfg = program_config(seq=seq)
    params = seeded(cfg)
    tokens = tokens_of(seq=seq)
    want = reference_details(params, TOY, tokens, keep_from=prompt - 1,
                             sizes=dict(ONE_BLOCK, positions=seq,
                                        index_queries=seq, queries=seq))
    logits, cache, taps = jax.jit(partial(
        gen.prefill_and_taps, cfg=cfg, max_len=seq, chunk=chunk))(
            params, tokens[:, :prompt])
    assert app.window_keys_off(cfg, taps, prompt - 1) == 0
    step = jax.jit(partial(gen.decode_step_and_taps, cfg=cfg))
    got, picks = [logits], [app._selections(taps)]
    for j in range(DECODED):
        logits, cache, taps = step(params, tokens[:, prompt + j],
                                   jnp.asarray(prompt + j, jnp.int32), cache)
        assert app.window_keys_off(cfg, taps, prompt + j) == 0
        got.append(logits)
        picks.append(app._selections(taps))
    close(jnp.stack(got, 1), want["logits"])
    view = app.cache_view(cfg, cache, seq)
    close(view["latent"], want["latent"])
    close(view["index"], want["index"])
    close(view["window"], want["window"][:, :, max(0, seq - 16):])
    if seq <= cfg.index_topk:       # no more keys than that: none selected
        assert picks == [None] * (1 + DECODED)
        return
    assert reference.selection_overlap(
        jnp.stack([s[0] for s in picks], 2),
        jnp.stack([s[1] for s in picks], 2),
        want["selected"], want["selected_real"]) == 1.0


def test_generate_serves_the_stack_and_counts_its_expert_rows():
    cfg = program_config()
    params = seeded(cfg)
    tokens = tokens_of()
    out, stats = jax.jit(partial(
        generate_with_stats, cfg=cfg, max_new_tokens=DECODED))(
            params, tokens[:, :PROMPT])
    # greedy: each served token is the reference's best, teacher-forced
    fed = jnp.concatenate([tokens[:, :PROMPT], out], axis=1)
    want = reference_details(params, TOY, fed, keep_from=PROMPT - 1)
    assert reference.token_deficit(
        want["logits"][:, :-1], out)["token_deficit_over_std"] < 1e-4
    assert int(stats["moe_rows_dropped"]) == 0
    # the loop's last step runs the last served token through the stack
    # too: every position of prompt + served routed its pairs once
    assert int(stats["moe_rows_here"]) == want["moe_rows_here"]
    # every block walked is a decode step's whole buffer or holds a row
    assert int(stats["moe_rows_here"]) <= int(stats["moe_rows_walked"])


def test_the_window_cache_is_a_ring_of_the_window_rounded_up():
    cfg = program_config()
    assert gen.window_rows(cfg) == 16          # 9 rounded to _WRITE_ROWS
    shapes = gen.cache_shapes(cfg, 2, SEQ)
    assert shapes == {"latent": (2, 2, SEQ, 1, 24),
                      "index": (2, 2, SEQ, 1, 16),
                      "window": (3, 2, 16, 1, 40)}
    np.testing.assert_array_equal(
        latent.ring_positions(jnp.asarray(17), 16),
        [16, 17] + list(range(2, 16)))
    assert int(latent.ring_positions(jnp.asarray(-1), 16).min()) > 1 << 30
    with gen.call_span(cfg, 2, PROMPT, DECODED) as sp:
        pass
    attrs = sp.attrs
    assert attrs["cache_bytes"] == 4 * (2 * 2 * SEQ * 40 + 3 * 2 * 16 * 40)
    assert attrs["cache_bytes_window"] == 4 * 3 * 2 * 16 * 40
    assert attrs["prefill_chunks"] == 1 and attrs["index_topk"] == 16
    assert attrs["window_kernel_queries"] == 0
    total = SEQ - 1
    assert attrs["keys_scored"] == 2 * 2 * total * (total + 1) // 2
    assert attrs["keys_attended"] == 2 * 2 * (16 * 17 // 2
                                              + (total - 16) * 16)


@pytest.mark.parametrize("window,s,t,start", [
    (0, 5, 12, 7), (4, 5, 12, 7),
    # in blocks of 4 queries, each over the 4 + window - 1 keys its band
    # reaches: just the window - 1 keys before the first query (a prefill
    # chunk's), none (the training forward's own keys), more than any
    # query reaches, fewer
    (4, 12, 15, 3), (4, 12, 12, 0), (9, 12, 23, 11), (9, 12, 14, 2),
    (4, 12, 15, 0),         # a prompt's first chunk: 3 keys do not exist
    (1, 12, 12, 0),         # a window of the query's own key
])
def test_absorbed_attention_is_expanded_attention(monkeypatch, window, s, t,
                                                  start):
    """The expanded form, banded by index under a window (keys in position
    order, the last ``s`` the queries' own), against the absorbed form,
    which masks every key by its position as the expanded form did: value
    and, as the training forward takes it, gradient."""
    monkeypatch.setattr(latent, "DENSE_QUERY_BLOCK", 4)
    dims = program_config().latent
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    b = 2
    wukv = jax.random.normal(ks[0], (dims.kv_rank, dims.heads,
                                     dims.nope + dims.v))
    q_nope = jax.random.normal(ks[1], (b, s, dims.heads, dims.nope))
    q_rope = jax.random.normal(ks[2], (b, s, dims.heads, dims.rope))
    keys = jax.random.normal(ks[3], (b, t, dims.cached))
    qpos = jnp.broadcast_to(start + jnp.arange(s), (b, s))
    kpos = start - (t - s) + jnp.arange(t)
    kpos = jnp.where(kpos >= 0, kpos, latent.NEVER)[None]

    def out(form, q_nope, keys, **own):
        return form(dims, wukv, q_nope, q_rope, qpos, keys, kpos, window,
                    **own)

    close(out(latent._expanded, q_nope, keys),
          out(latent._absorbed, q_nope, keys), tol=1e-5)
    grad = lambda form, **own: jax.grad(
        lambda *a: jnp.sum(out(form, *a, **own) ** 2), (0, 1))(q_nope, keys)
    for got, want in zip(grad(latent._expanded, own="reference"),
                         grad(latent._absorbed)):
        close(got, want, tol=1e-5)


def test_where_a_windows_chunk_goes_through_the_flash_kernel(monkeypatch):
    """``window_in_kernel`` reads the code's own view: a TPU, more than one
    query, just the window's keys before them in order, sizes that tiles
    divide; and ``generate.call`` counts the prompt's window-layer queries
    that went that way."""
    cfg = program_config(PUBLISHED, seq=32896)
    dims = cfg.latent_dims("window")
    chunk = (dims, 513, 2048, 2560)
    assert not latent.window_in_kernel(*chunk)                  # the CPU
    assert gen.call_span(cfg, 2, 32768, 128).attrs[
        "window_kernel_queries"] == 0
    monkeypatch.setattr(latent, "_on_tpu", lambda: True)
    assert latent.window_in_kernel(*chunk)
    assert not latent.window_in_kernel(dims, 513, 1, 513)       # a step
    assert not latent.window_in_kernel(dims, 513, 2048, 2048)   # own keys
    assert not latent.window_in_kernel(dims, 513, 2048, 2568)   # unordered
    assert not latent.window_in_kernel(dims, 0, 2048, 2047)     # no window
    assert not latent.window_in_kernel(dims, 500, 2048, 2547)   # off a tile
    assert not latent.window_in_kernel(
        program_config().latent_dims("window"), 513, 2048, 2560)    # widths
    # three window layers' prompt queries of both rows; the decode steps
    # keep the absorbed form over the ring
    assert gen.call_span(cfg, 2, 32768, 128).attrs[
        "window_kernel_queries"] == 2 * 3 * 32768
    assert gen.call_span(cfg, 2, 32767, 128).attrs[     # chunks of 1,057
        "window_kernel_queries"] == 0


def test_with_no_more_keys_than_topk_a_full_layer_is_plain_latent_attention():
    config = dict(TOY, num_hidden_layers=2,
                  layer_types=["full_attention", "full_attention"])
    cfg = program_config(config, seq=16)
    params = seeded(cfg)
    tokens = tokens_of(seq=16)

    def without_index(tree):
        return {k: without_index(v) for k, v in tree.items()
                if k != "index"} if isinstance(tree, dict) else tree

    plain = dataclasses.replace(cfg, index_topk=0, index_heads=0)
    bare = dict(params, dense_layers=without_index(params["dense_layers"]),
                layers=tuple(without_index(s) for s in params["layers"]))
    close(transformer_apply(params, tokens, cfg),
          transformer_apply(bare, tokens, plain), tol=1e-6)


def test_the_published_configuration_is_the_files_arithmetic():
    cfg = program_config(PUBLISHED, seq=32896)
    assert (cfg.d_model, cfg.n_layers, cfg.num_experts, cfg.held,
            cfg.expert_top_k, cfg.index_topk, cfg.window) == \
        (5120, 5, 256, 32, 8, 2048, 513)
    assert cfg.latent == latent.LatentDims(128, 1024, 512, 128, 64, 128, 8e7)
    assert cfg.window_latent == latent.LatentDims(64, 1024, 1024, 192, 64,
                                                  128, 5e4)
    assert cfg.norm_eps == 1e-5 and cfg.dtype == jnp.bfloat16
    n = transformer_num_params(cfg)
    assert n == 4_087_154_176
    assert f"{n:,}" in PUBLISHED["arithmetic"]["parameters"]
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    axes = transformer_logical_axes(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)
                     and all(x is None or isinstance(x, str) for x in a)))
    assert PUBLISHED["reduced"].keys() == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    shapes = gen.cache_shapes(cfg, 2, 32896)
    assert shapes["window"][2] == 520
    assert sum(np.prod(s) for s in shapes.values()) * 2 == \
        2 * 32896 * 2816 + 2 * 3 * 1131520


def test_the_output_gate_takes_both_forms():
    o = jnp.ones((2, 3, 4, 8))
    headwise = jnp.zeros((2, 3, 4)).at[:, :, 1].set(100.0)
    got = _output_gate(o, headwise)
    close(got[:, :, 0], 0.5 * o[:, :, 0])
    close(got[:, :, 1], o[:, :, 1])
    close(_output_gate(o, jnp.zeros_like(o)), 0.5 * o)


def test_a_decode_steps_buffer_is_the_most_any_routing_can_send():
    cfg = program_config()
    assert moe.buffer_rows(cfg, 2) == 2 * 4         # rows x min(top_k, held)
    assert moe.buffer_rows(cfg, 4096) == 4 * 4096   # 4 x the mean of 4096


def test_generate_still_refuses_what_it_cannot_serve():
    cfg = program_config(layer_types=("latent", "full", "window", "window"))
    with pytest.raises(NotImplementedError, match="is not served"):
        gen._refuse_unserved(cfg)
    with pytest.raises(ValueError, match="not a multiple of the chunk"):
        gen.prefill_and_taps(None, jnp.zeros((1, 10), jnp.int32),
                             program_config(), 12, chunk=4)

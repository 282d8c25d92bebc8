"""KV-cache autoregressive generation (models/generate.py).

Gold check: greedy decoding THROUGH THE CACHE must produce exactly the
same tokens as naive re-forwarding of the full sequence each step (the
repo's kernel-verification pattern applied to the decode path)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import (TransformerConfig, generate, prefill,
                            transformer_apply, transformer_init)


def _cfg(**kw):
    base = dict(vocab_size=97, d_model=64, n_layers=3, n_heads=4,
                n_kv_heads=2, max_seq=64, attn_impl="reference",
                dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


def _naive_greedy(params, prompt, cfg, n):
    toks = prompt
    out = []
    for _ in range(n):
        logits = transformer_apply(params, toks, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


def test_cached_greedy_matches_full_reforward():
    cfg = _cfg()
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 7), 0, 97)
    want = _naive_greedy(params, prompt, cfg, 10)
    got = generate(params, prompt, cfg, max_new_tokens=10, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_generate_is_jittable_and_deterministic():
    from functools import partial

    cfg = _cfg()
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (3, 5), 0, 97)
    gen = jax.jit(partial(generate, cfg=cfg, max_new_tokens=8,
                          temperature=0.7, top_k=20, seed=13))
    a = np.asarray(gen(params, prompt))
    b = np.asarray(gen(params, prompt))
    assert a.shape == (3, 8)
    np.testing.assert_array_equal(a, b)   # PRNG is explicit
    assert (a >= 0).all() and (a < 97).all()


def test_prefill_logits_match_forward():
    cfg = _cfg()
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0, 97)
    logits, cache = prefill(params, prompt, cfg, max_len=16)
    full = transformer_apply(params, prompt, cfg)[:, -1]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               rtol=2e-4, atol=2e-4)
    assert cache["k"].shape == (3, 2, 16, 2, 16)


def test_gqa_and_moe_decode():
    cfg = _cfg(n_kv_heads=1, num_experts=4, expert_top_k=2)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 0, 97)
    # The expert layer is dropless in training and serving alike, so the
    # cached and the uncached forward pass route the same rows.
    want = _naive_greedy(params, prompt, cfg, 6)
    got = generate(params, prompt, cfg, max_new_tokens=6, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- the cache is the decode loop's carry, updated in place -----------------

def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in (value if isinstance(value, (tuple, list)) else (value,)):
            v = getattr(v, "jaxpr", v)          # ClosedJaxpr -> Jaxpr
            if hasattr(v, "eqns"):
                yield v


def _scanned_over(jaxpr, shape, inside_scan=False):
    """Every (direction, aval) of that shape which a scan NESTED in another
    scan takes as a scanned input (xs) or gives as a scanned output (ys)."""
    found = []
    for eqn in jaxpr.eqns:
        is_scan = eqn.primitive.name == "scan"
        if is_scan and inside_scan:
            first_x = eqn.params["num_consts"] + eqn.params["num_carry"]
            found += [("xs", v.aval) for v in eqn.invars[first_x:]
                      if v.aval.shape == shape]
            found += [("ys", v.aval)
                      for v in eqn.outvars[eqn.params["num_carry"]:]
                      if v.aval.shape == shape]
        for sub in _sub_jaxprs(eqn):
            found += _scanned_over(sub, shape, inside_scan or is_scan)
    return found


@pytest.mark.parametrize("new_tokens", [5, 70])     # one segment, two
def test_decode_loop_carries_the_cache(new_tokens):
    """Platform-independent statement of "in place": inside the token loop
    no layer scan takes or re-emits the stacked cache (a scanned input and
    a scanned output cannot alias, so XLA would copy the stack a token).
    The prefill's own stacked outputs, at the top level, are allowed."""
    from functools import partial

    cfg = _cfg(max_seq=128)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((2, 7), jnp.int32)
    jaxpr = jax.make_jaxpr(partial(generate, cfg=cfg,
                                   max_new_tokens=new_tokens))(params, prompt)
    cache_shape = (cfg.n_layers, 2, 7 + new_tokens, cfg.kv_heads,
                   cfg.head_dim)
    # the walker sees the cache at all: prefill stacks it at the top level
    top = [v.aval.shape for eqn in jaxpr.jaxpr.eqns
           if eqn.primitive.name == "scan" for v in eqn.outvars]
    assert top.count(cache_shape) >= 2
    assert _scanned_over(jaxpr.jaxpr, cache_shape) == []


# --- the token loop's segments: a step reads the positions written so far ---

def _chain_of_decode_steps(params, prompt, cfg, n):
    """Greedy tokens by ``prefill`` and ``n`` ``decode_step``s, each over
    the whole cache."""
    from functools import partial

    from ray_tpu.models.generate import decode_step

    s = prompt.shape[1]
    logits, cache = prefill(params, prompt, cfg, max_len=s + n)
    step = jax.jit(partial(decode_step, cfg=cfg))
    out = []
    for i in range(n):
        out.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        logits, cache = step(params, out[-1], jnp.asarray(s + i, jnp.int32),
                             cache)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("overrides, prompt_len, new_tokens", [
    pytest.param(dict(n_kv_heads=4), 8, 64, id="dense"),
    pytest.param(dict(n_kv_heads=1), 8, 64, id="gqa"),
    pytest.param(dict(), 7, 70, id="prompt-no-multiple-of-8"),
    # 39 + 38 steps: the second segment starts inside a block of 8
    pytest.param(dict(), 6, 77, id="steps-no-multiple-of-the-segment"),
])
def test_segmented_generate_is_a_chain_of_whole_cache_steps(
        overrides, prompt_len, new_tokens):
    from ray_tpu.models.generate import _decode_segments

    assert len(_decode_segments(prompt_len, new_tokens)) == 2
    cfg = _cfg(max_seq=128, **overrides)
    params = transformer_init(jax.random.PRNGKey(9), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(10), (2, prompt_len), 0,
                                97)
    want = _chain_of_decode_steps(params, prompt, cfg, new_tokens)
    got = generate(params, prompt, cfg, max_new_tokens=new_tokens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(np.unique(np.asarray(got))) > 4      # not one token over


def test_the_segments_cover_what_their_steps_read():
    from ray_tpu.models.generate import (_MAX_SEGMENTS, _MIN_NEW_PART,
                                         _MIN_SEGMENT_STEPS, _WRITE_ROWS,
                                         _decode_segments)

    for prompt in (1, 5, 8, 127, 128, 512):
        for new in (1, 8, 31, 32, 33, 63, 64, 70, 77, 128, 255, 256, 257,
                    1000, 4096):
            t_max = prompt + new
            segments = _decode_segments(prompt, new)
            assert sum(steps for steps, _ in segments) == new
            assert len(segments) <= _MAX_SEGMENTS
            if new < 2 * _MIN_SEGMENT_STEPS or new * _MIN_NEW_PART < t_max:
                assert segments == [(new, t_max)]   # today's one loop
            else:
                lengths = [steps for steps, _ in segments]
                assert min(lengths) >= _MIN_SEGMENT_STEPS
                assert max(lengths) - min(lengths) <= 1
            pos = prompt                # the position the next step writes
            for steps, extent in segments:
                pos += steps
                assert pos <= extent <= t_max       # covers pos + 1 of each
                assert extent % _WRITE_ROWS == 0 or extent == t_max
                assert extent - pos < _WRITE_ROWS   # and no block more
            assert segments[-1][1] == t_max
    # the two serving cells
    assert _decode_segments(128, 256) == [
        (32, extent) for extent in range(160, 385, 32)]
    # a fifth of the cache is new: the tail is a tenth of one loop's reads
    assert _decode_segments(512, 128) == [(128, 640)]
    assert _decode_segments(384, 128) == [
        (32, extent) for extent in (416, 448, 480, 512)]


def _attention_operands(jaxpr):
    """The operand shapes of every dot_general under ``rt.loop.cache``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" \
                and "rt.loop.cache" in str(eqn.source_info.name_stack):
            found += [v.aval.shape for v in eqn.invars]
        for sub in _sub_jaxprs(eqn):
            found += _attention_operands(sub)
    return found


def test_a_segments_attention_reads_its_extent_and_no_more():
    """In the jaxpr of ``generate`` the token loop is one scan a segment,
    and inside segment j both attention einsums take ``extent_j`` positions
    of keys and values: no operand is longer."""
    from functools import partial

    from ray_tpu.models.generate import _decode_segments, call_span

    cfg = _cfg(max_seq=256)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt, new = 7, 130                    # 4 segments: 33, 33, 32, 32
    segments = _decode_segments(prompt, new)
    assert [extent for _, extent in segments] == [40, 80, 112, 137]
    jaxpr = jax.make_jaxpr(partial(generate, cfg=cfg, max_new_tokens=new))(
        params, jnp.zeros((2, prompt), jnp.int32))
    loops = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "scan"
             and "rt.generate.decode" in str(eqn.source_info.name_stack)]
    assert [eqn.params["length"] for eqn in loops] == \
        [steps for steps, _ in segments]
    for eqn, (_, extent) in zip(loops, segments):
        shapes = [s for sub in _sub_jaxprs(eqn)
                  for s in _attention_operands(sub)]
        # the keys; the weights and the values
        assert sorted(s for s in shapes if extent in s) == [
            (2, cfg.kv_heads, 2, extent), (2, extent, cfg.kv_heads, 16),
            (2, extent, cfg.kv_heads, 16)]
        assert max(max(s) for s in shapes) == extent
    # and the call's span says so, from the same helper
    sp = call_span(cfg, 2, prompt, new)
    assert sp.attrs["decode_segments"] == 4
    assert sp.attrs["cache_positions_read"] == \
        33 * 40 + 33 * 80 + 32 * 112 + 32 * 137
    assert sp.attrs["cache_positions_needed"] == \
        sum(prompt + i + 1 for i in range(new))


def test_decode_step_is_functional():
    """decode_step jitted on its own, twice on the SAME un-donated cache:
    equal logits, and the argument's contents are left as they were."""
    from functools import partial

    from ray_tpu.models.generate import decode_step

    cfg = _cfg()
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0, 97)
    _, cache = prefill(params, prompt, cfg, max_len=12)
    before = jax.tree.map(np.array, cache)
    step = jax.jit(partial(decode_step, cfg=cfg))
    token, pos = jnp.asarray([3, 11], jnp.int32), jnp.asarray(6, jnp.int32)
    logits_a, cache_a = step(params, token, pos, cache)
    logits_b, cache_b = step(params, token, pos, cache)
    np.testing.assert_array_equal(np.asarray(logits_a), np.asarray(logits_b))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache[name]), before[name])
        np.testing.assert_array_equal(np.asarray(cache_a[name]),
                                      np.asarray(cache_b[name]))
        # the step wrote position 6 of every layer, and nothing else
        changed = np.asarray(cache_a[name]) != before[name]
        assert changed[:, :, 6].any(axis=(1, 2, 3)).all()
        changed[:, :, 6] = False
        assert not changed.any()


def test_decoded_cache_matches_prefill_of_longer_sequence():
    """prefill + k decode_steps leaves, in every layer, rows <= pos equal to
    the cache of a prefill over the longer sequence, and rows > pos zero."""
    from ray_tpu.models.generate import decode_step

    cfg = _cfg()
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    s, k, max_len = 5, 4, 14
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, s + k), 0, 97)
    _, cache = prefill(params, tokens[:, :s], cfg, max_len=max_len)
    for j in range(k):
        _, cache = decode_step(params, tokens[:, s + j],
                               jnp.asarray(s + j, jnp.int32), cache, cfg)
    _, want = prefill(params, tokens, cfg, max_len=max_len)
    for name in ("k", "v"):
        got = np.asarray(cache[name])
        assert got.shape == (3, 2, max_len, 2, 16)
        np.testing.assert_allclose(got[:, :, :s + k],
                                   np.asarray(want[name])[:, :, :s + k],
                                   rtol=2e-5, atol=2e-5)
        assert np.abs(got[:, :, :s + k]).max() > 0
        assert not got[:, :, s + k:].any()


@pytest.mark.parametrize("overrides, prompt_len, new_tokens", [
    pytest.param(dict(n_kv_heads=4), 6, 7, id="dense"),
    pytest.param(dict(n_kv_heads=1), 6, 7, id="gqa"),
    pytest.param(dict(num_experts=4, expert_top_k=2), 6, 7, id="moe"),
    pytest.param(dict(tied_embeddings=True), 6, 7, id="tied"),
    # a cache shorter than the block of positions a decode step writes
    pytest.param(dict(), 2, 4, id="short-cache"),
])
def test_greedy_tokens_pinned_to_reforward(overrides, prompt_len, new_tokens):
    cfg = _cfg(**overrides)
    params = transformer_init(jax.random.PRNGKey(7), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, prompt_len), 0, 97)
    want = _naive_greedy(params, prompt, cfg, new_tokens)
    got = generate(params, prompt, cfg, max_new_tokens=new_tokens,
                   temperature=0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- one layer and one head, shared by training, prefill and decode ----------

def _last_logits(params, tokens, cfg):
    """The logits after ``tokens``' last position, three ways."""
    from ray_tpu.models import decode_step
    s = tokens.shape[1]
    _, cache = prefill(params, tokens[:, :-1], cfg, max_len=s + 3)
    return {
        "train": transformer_apply(params, tokens, cfg)[:, -1],
        "prefill": prefill(params, tokens, cfg, max_len=s + 3)[0],
        "decode": decode_step(params, tokens[:, -1],
                              jnp.asarray(s - 1, jnp.int32), cache, cfg)[0],
    }


def _scaled(fn, by):
    """``fn`` with its result times ``by`` (of ``_feed_forward``'s pair,
    the result and not the expert layer's counters; of the trunk's, the
    state and not the cache)."""
    def scaled(*a, **kw):
        out = fn(*a, **kw)
        return (by * out[0],) + out[1:] if isinstance(out, tuple) \
            else by * out
    return scaled


def _latent_dims():
    from ray_tpu.models.transformer import LatentDims
    return LatentDims(heads=4, q_rank=16, kv_rank=16, nope=8, rope=8, v=8)


# the four kinds of stack `generate` serves, through its one trunk and its
# one cache: like softmax layers, the same looped, a pattern by kind, and
# a pattern of gated-delta-rule and softmax layers
STACKS = {
    "hybrid": dict(n_layers=4, layer_types=("linear", "full"),
                   linear_key_heads=2, linear_value_heads=2,
                   linear_key_dim=8, linear_value_dim=64,
                   linear_beta_scale=2.0, post_norm_only=True,
                   qk_norm_whole=True, partial_rotary_factor=0.0),
    "llama": dict(),
    "looped": dict(loop_steps=2, sandwich_norm=True),
    "by-kind": dict(n_layers=4, layer_types=("latent", "window"),
                    latent=_latent_dims(), window_latent=_latent_dims(),
                    window=5, index_topk=4, index_heads=2, index_head_dim=8),
}


@pytest.mark.parametrize("path, shared, overrides", [
    pytest.param(path, shared, overrides, id=f"{path}-{shared}-{name}")
    for shared in ("_feed_forward", "_head")
    for path in ("train", "prefill", "decode")
    for name, overrides in (("dense", dict()),
                            ("moe", dict(num_experts=4, expert_top_k=2)))
] + [
    pytest.param(path, "_over_the_layers", overrides,
                 id=f"{path}-the-trunk-{name}")
    for path in ("prefill", "decode") for name, overrides in STACKS.items()
])
def test_every_path_runs_the_one_definition(monkeypatch, path, shared,
                                            overrides):
    """The feed-forward and the head are written once: a marked stand-in
    put in the place of the one function moves the full forward, prefill's
    last-position logits and decode_step's logits alike, and the three go
    on agreeing as they do untouched. The trunk over the cache is written
    once too: a stand-in in its place moves prefill and decode of a llama
    stack, a looped stack, a stack by kind and a stack of linear and
    softmax layers, and leaves training be."""
    import sys

    from ray_tpu.models import transformer
    # ray_tpu.models.generate, the attribute, is the function
    generate_module = sys.modules["ray_tpu.models.generate"]

    cfg = _cfg(**overrides)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0, 97)
    plain = _last_logits(params, tokens, cfg)
    if shared == "_over_the_layers":
        # the state's sign: the final norm would undo a factor
        monkeypatch.setattr(generate_module, shared, _scaled(
            generate_module._over_the_layers, -1.0))
        marked = _last_logits(params, tokens, cfg)
        np.testing.assert_array_equal(np.asarray(marked["train"]),
                                      np.asarray(plain["train"]))
        np.testing.assert_allclose(
            np.asarray(marked[path]), -np.asarray(plain["train"]),
            rtol=2e-4, atol=2e-4)
        assert np.abs(np.asarray(plain[path])).max() > 0.1
        return
    stand_in = _scaled(getattr(transformer, shared), 0.5)
    monkeypatch.setattr(transformer, shared, stand_in)
    if hasattr(generate_module, shared):        # imported by name there
        monkeypatch.setattr(generate_module, shared, stand_in)
    marked = _last_logits(params, tokens, cfg)
    assert not np.allclose(np.asarray(marked[path]), np.asarray(plain[path]),
                           rtol=1e-2, atol=1e-2)
    for other in marked:
        np.testing.assert_allclose(
            np.asarray(marked[path]), np.asarray(marked[other]),
            rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(plain[path]), np.asarray(plain[other]),
            rtol=2e-4, atol=2e-4)
    if shared == "_head":
        np.testing.assert_allclose(np.asarray(marked[path]),
                                   0.5 * np.asarray(plain[path]),
                                   rtol=1e-6, atol=1e-6)


def test_generate_names_no_weight_of_the_layer():
    """What a layer computes from its weights stands in
    transformer._layer_apply alone: no function of generate.py subscripts
    a layer's weight, so a new mechanism of the block is written once."""
    import ast
    import inspect
    import sys

    generate_module = sys.modules["ray_tpu.models.generate"]

    def keys(tree):
        for k, v in tree.items():
            yield k
            if isinstance(v, dict):
                yield from keys(v)

    weights = set()
    for overrides in (dict(), dict(num_experts=2)):
        cfg = _cfg(n_layers=1, **overrides)
        layers = jax.eval_shape(
            lambda: transformer_init(jax.random.PRNGKey(0), cfg))["layers"]
        weights |= set(keys(layers))
    assert {"wq", "wk", "wv", "wo", "w1", "w2", "w3", "moe"} <= weights
    named = [(node.lineno, node.slice.value)
             for node in ast.walk(ast.parse(inspect.getsource(generate_module)))
             if isinstance(node, ast.Subscript)
             and isinstance(node.slice, ast.Constant)
             and node.slice.value in weights]
    assert named == []


# --- one cache by layer kind -------------------------------------------------

@pytest.mark.parametrize("overrides", [
    pytest.param(overrides, id=name) for name, overrides in STACKS.items()])
def test_init_cache_is_zeros_of_cache_shapes(overrides):
    """`cache_shapes` alone knows the cache's arrays: `init_cache` is zeros
    of them, `prefill` fills arrays of those shapes, and `call_span` counts
    their bytes."""
    from ray_tpu.models.generate import (cache_shapes, cache_slots,
                                         call_span, init_cache)

    cfg = _cfg(**overrides)
    shapes = cache_shapes(cfg, 2, 12)
    cache = init_cache(cfg, 2, 12)
    assert {k: v.shape for k, v in cache.items()} == shapes
    assert all(v.dtype == cfg.dtype and not np.asarray(v).any()
               for v in cache.values())
    if "state" in shapes:   # 2 periods of a linear and a softmax layer
        assert sorted(shapes) == ["k", "state", "tail", "v"]
        assert shapes["k"] == (2, 2, 12, cfg.kv_heads, cfg.head_dim)
        assert shapes["state"] == (2, 2, 1, 8, 128)     # two heads a run
        assert shapes["tail"] == (2, 3, 2, 2 * 16 + 128)
    elif "k" in shapes:     # a slot for every (loop step, layer)
        assert shapes["k"] == shapes["v"] == (
            cache_slots(cfg), 2, 12, cfg.kv_heads, cfg.head_dim)
    else:                   # 2 periods of a latent and a window layer
        assert sorted(shapes) == ["index", "latent", "window"]
        assert shapes["latent"] == (2, 2, 12, 1, 24)
        assert shapes["window"] == (2, 2, 8, 1, 24)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    _, filled = prefill(params, jnp.zeros((2, 9), jnp.int32), cfg, max_len=12)
    assert {k: v.shape for k, v in filled.items()} == shapes
    assert call_span(cfg, 2, 9, 3).attrs["cache_bytes"] == 4 * sum(
        int(np.prod(shape)) for shape in shapes.values())


def test_unlooped_prefill_cache_is_the_forwards_keys_and_values(monkeypatch):
    """An unlooped stack's prefill goes through the carried cache and leaves
    in it, bit for bit, the rotated K and V that `transformer_apply`'s own
    layers attend with (and zeros after the prompt); its logits are the
    forward's last."""
    from ray_tpu.models import transformer

    cfg = _cfg(n_kv_heads=1)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0, 97)
    seen = []
    attention = transformer._attention

    def keeping(cfg, q, k, v, *a, **kw):
        # out of the layer scan, a layer at a time and in their order
        jax.debug.callback(lambda k, v: seen.append((k, v)), k, v,
                           ordered=True)
        return attention(cfg, q, k, v, *a, **kw)

    monkeypatch.setattr(transformer, "_attention", keeping)
    want = transformer_apply(params, tokens, cfg)[:, -1]
    jax.effects_barrier()
    monkeypatch.undo()
    assert len(seen) == cfg.n_layers
    logits, cache = prefill(params, tokens, cfg, max_len=14)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    for i, name in enumerate(("k", "v")):
        got = np.asarray(cache[name])
        np.testing.assert_array_equal(
            got[:, :, :9], np.stack([np.asarray(kv[i]) for kv in seen]))
        assert got[:, :, :9].any() and not got[:, :, 9:].any()

"""The parallel block (Falcon-H1: softmax attention and a Mamba-2 state-space
mixer on one normed input, muP multipliers folded) trained and SERVED: the
chunked scan against a loop over positions, the step kernel through the
interpreter in place on a stack, the convolution with a bias, the training
forward and prefill + decode through the cache against the benchmark's plain
reference on the same seeded weights (logits, state, tail, keys, values),
folded multipliers against explicit ones, the vocabulary slice, one training
step, and what ``generate`` still refuses."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.apps import serve_falcon_h1 as app
from benchmark.reference import falcon_h1 as reference
from ray_tpu.models import (TransformerConfig, transformer_apply,
                            transformer_init)
from ray_tpu.models import transformer
from ray_tpu.models.transformer import LatentDims
from ray_tpu.ops import gated_delta, ssd
from ray_tpu.ops import gated_delta_pallas as kernels

gen = importlib.import_module("ray_tpu.models.generate")

# Hugging Face names, as benchmark/configs/falcon-h1-34b-l9.json has them
CONFIG = {
    "family": "falcon_h1", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attn_layer_indices": None, "attention_bias": False, "mlp_bias": False,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_ssm": 64,
    "mamba_n_groups": 2, "mamba_d_state": 8, "mamba_d_conv": 4,
    "mamba_conv_bias": True, "mamba_norm_before_gate": False,
    "mamba_rms_norm": True, "mamba_proj_bias": False,
    "rope_theta": 1e11, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "attention_in_multiplier": 1.0, "attention_out_multiplier": 0.6,
    "embedding_multiplier": 5.656854249492381, "key_multiplier": 0.3,
    "lm_head_multiplier": 0.25, "mlp_multipliers": [0.7, 0.4],
    "ssm_in_multiplier": 0.5, "ssm_multipliers": [0.35, 0.25, 0.7, 0.5, 0.3],
    "ssm_out_multiplier": 0.45, "param_dtype": "float32",
    "torch_dtype": "float32"}
PROMPT, TOTAL = 13, 21


def program_config(remat=False, **over):
    kwargs = dict(app.model_kwargs(CONFIG, 32, "reference"),
                  dtype=jnp.float32)
    kwargs.update(over)
    return app.transformer_config(kwargs, remat=remat)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), \
        np.max(np.abs(a - b))


def scan_operands(b, s, g, h, n, p, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[4], (h,), minval=0.0, maxval=2.0))
    return (jax.random.normal(ks[0], (b, s, h, p), dtype),
            jax.random.normal(ks[1], (b, s, g, n), dtype),
            jax.random.normal(ks[2], (b, s, g, n), dtype),
            dt * a, dt, 1.0 + 0.1 * jax.random.normal(ks[5], (h,)))


def over_positions(x, b, c, g, dt, skip):
    """The recurrence one position at a time, the reference's own loop."""
    r = x.shape[2] // b.shape[2]
    y, state = reference._recurrence(
        x, jnp.repeat(b, r, axis=2), jnp.repeat(c, r, axis=2), dt,
        (g / dt)[0, 0], skip)
    return y, jnp.swapaxes(state, -1, -2)       # [B, H, N, P]


# --- the recurrence: chunked, one position at a time, the kernel --------------

@pytest.mark.parametrize("s", [1, 50, 128, 200, 257])
def test_the_chunked_scan_is_the_loop_over_positions(s):
    """Lengths under a chunk, a chunk, and no multiple of the chunk (the
    tail is padded with positions that leave the state alone); the final
    state is the loop's."""
    operands = scan_operands(2, s, 2, 4, 8, 16)
    want, state = over_positions(*operands)
    y, final = ssd.ssd_scan(*operands, final_state=True)
    close(y, want, tol=1e-4)
    close(final, state, tol=1e-4)
    assert final.shape == (2, 4, 8, 16) and final.dtype == jnp.float32
    close(ssd.ssd_scan(*operands), y, tol=0)
    close(ssd.ssd_scan(*operands, chunk=64), want, tol=1e-4)


def test_the_one_position_step_chained_is_the_scan():
    operands = scan_operands(2, 40, 2, 4, 8, 16)
    want, final = ssd.ssd_scan(*operands, final_state=True)
    state = jnp.zeros_like(final)
    step = jax.jit(ssd.ssd_step)
    outs = []
    for t in range(40):
        y, state = step(state, *(a[:, t] for a in operands[:5]), operands[5])
        outs.append(y)
    close(jnp.stack(outs, 1), want, tol=1e-4)
    close(state, final, tol=1e-4)


def test_the_scan_is_differentiable_by_xla():
    operands = scan_operands(1, 70, 2, 4, 8, 16)

    def loss(fn, *ops):
        return jnp.sum(fn(*ops)[0] ** 2)

    grads = jax.grad(lambda *o: loss(
        lambda *a: ssd.ssd_scan(*a, chunk=32, final_state=True), *o),
        argnums=(0, 1, 2, 4))(*operands)
    wants = jax.grad(lambda *o: loss(over_positions, *o),
                     argnums=(0, 1, 2, 4))(*operands)
    for got, want in zip(grads[:3], wants[:3]):
        assert np.all(np.isfinite(got))
        close(got, want, tol=2e-4)


@pytest.mark.parametrize("h,n,p,dtype", [(4, 16, 128, jnp.float32),
                                         (32, 8, 128, jnp.bfloat16)])
def test_the_step_kernel_writes_its_slot_of_the_stack_and_no_other(h, n, p,
                                                                   dtype):
    """``rt_ssd_step`` (the delta rule's step kernel without its
    correction) through the interpreter against the jnp step, in place on a
    stack of several slots."""
    x, b, c, g, dt, skip = (a[:, 0] if a.ndim > 1 else a for a in
                            scan_operands(3, 1, 2, h, n, p, dtype, seed=3))
    stack = jax.random.normal(jax.random.PRNGKey(7), (4, 3, h, n, p))
    slot = 2
    want_y, want_state = ssd.ssd_step(stack[slot], x, b, c, g, dt, skip)
    f32, r = jnp.float32, h // 2
    q, k = (jnp.repeat(a.astype(f32), r, axis=1) for a in (c, b))
    y, out = kernels.step_kernel(stack, slot, q, k, x, g, dt, delta=False,
                                 interpret=True)
    y = y.astype(f32) + skip[:, None] * x.astype(f32)
    close(y, want_y, tol=1e-5 if dtype == jnp.float32 else 1e-2)
    close(out[slot], want_state, tol=1e-5)
    for other in (0, 1, 3):
        close(out[other], stack[other], tol=0)


def test_the_rules_step_kernel_still_takes_its_correction():
    """``delta=True`` is the default: the gated delta rule's call compiles
    as before this transition came."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, h, dk, dv = 2, 4, 16, 128
    q, k = (gated_delta.unit(jax.random.normal(kk, (b, h, dk)))
            for kk in ks[:2])
    v = jax.random.normal(ks[2], (b, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h)))
    stack = jax.random.normal(ks[0], (2, b, h, dk, dv))
    o, out = kernels.step_kernel(stack, 1, q, k, v, g, beta, interpret=True)
    seen = jnp.einsum("bhkv,bhk->bhv", stack[1], k)
    decay = jnp.exp(g)[..., None]
    fresh = beta[..., None] * (v - decay * seen)
    want = decay[..., None] * stack[1] + k[..., None] * fresh[..., None, :]
    close(out[1], want, tol=1e-5)
    close(o, jnp.einsum("bhkv,bhk->bhv", want, q), tol=1e-5)


@pytest.mark.parametrize("bias", [False, True])
def test_the_convolutions_three_forms_take_an_optional_bias(bias):
    """The whole sequence, a column at a time against a tail, and the
    kernel through the interpreter on a slot of a stack; with no bias the
    call is the one Olmo's and Qwen3-Next's paths make."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (2, 9, 256))
    w = 0.5 * jax.random.normal(ks[1], (256, 4))
    bs = jax.random.normal(ks[2], (256,)) if bias else None
    whole = gated_delta.causal_conv(x, w, bs, scope="rt.ssd.conv")
    if not bias:
        close(whole, gated_delta.causal_conv(x, w), tol=0)
    else:
        assert float(jnp.max(jnp.abs(
            whole - gated_delta.causal_conv(x, w)))) > 0.1
    tail = jnp.zeros((3, 2, 256))
    tails = jnp.zeros((3, 3, 2, 256)).at[0].set(7.0)
    for t in range(9):
        y, tail = gated_delta.conv_step(tail, x[:, t], w, bs)
        close(y, whole[:, t], tol=1e-5)
        yk, tails = kernels.conv_step_kernel(tails, 1, x[:, t], w, bs,
                                             interpret=True)
        close(yk, whole[:, t], tol=1e-5)
    close(tails[1], tail, tol=0)
    close(tails[0], jnp.full((3, 2, 256), 7.0), tol=0)
    close(tail, jnp.swapaxes(x[:, 6:], 0, 1), tol=0)


# --- the block against the reference -----------------------------------------

def seeded(cfg, seed=0):
    """(the tree as a checkpoint would hold it, the tree the program runs)."""
    published = app.published_params(cfg, jax.random.PRNGKey(seed))
    return published, transformer.fold_multipliers(
        published, cfg, **app.multipliers(CONFIG))


def published_weights(params):
    """``published_params``'s tree as the reference's matrices, nothing
    divided out: the multipliers are the reference's to apply."""
    return app.published_weights(params, CONFIG)


def tokens_of(rows=2, length=TOTAL, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, length), 0,
                              CONFIG["vocab_size"])


def test_the_block_is_the_references_with_the_multipliers_folded():
    """The training forward over folded matrices against the reference,
    which applies every multiplier where the published code does, float32:
    the folded multipliers against explicit ones."""
    cfg = program_config()
    published, folded = seeded(cfg)
    tokens = tokens_of()
    want = reference.forward(published_weights(published), tokens, CONFIG)
    close(transformer_apply(folded, tokens, cfg), want)
    # and they matter: the unfolded tree is another model
    off = transformer_apply(published, tokens, cfg)
    assert float(jnp.max(jnp.abs(off - want))) > 100 * 2e-5


def test_the_reference_gets_the_published_tree_and_a_fault_of_the_fold_shows(
        monkeypatch):
    """The app hands the reference the PUBLISHED tree, drawn again from the
    seed a layer at a time and never read back from the program's folded
    one: each layer is the whole draw's, bit for bit, so a sound fold agrees
    with the reference, and a fold that leaves ``key`` out, or scales the B
    segment by the C segment's entry, does not."""
    from benchmark.apps import lm
    cfg = program_config()
    published = jax.jit(lambda key: app.published_params(cfg, key))(
        app.seed_key(5))         # compiled, as ``seeded_params`` draws it
    drawn = app.reference_weights(cfg, CONFIG, 5)
    whole = published_weights(published)
    for i in range(cfg.n_layers):
        for name, w in whole.layer(i).items():
            assert np.array_equal(np.asarray(drawn.layer(i)[name]),
                                  np.asarray(w)), (i, name)
    for name in ("embed", "final_norm", "lm_head"):
        assert np.array_equal(np.asarray(getattr(drawn, name)),
                              np.asarray(published[name])), name
    assert lm.fold_seed(5) != 5     # the benchmark's seeds are folded
    tokens = tokens_of()
    full = app.reference_pass(drawn, CONFIG, tokens, PROMPT, 1e-5)
    program = app.Program(cfg, PROMPT, 32)

    def worst(part):
        errs = app.errors(program.run(app.seeded_params(cfg, CONFIG, 5),
                                      tokens), full, CONFIG, PROMPT)
        return max(errs[part])
    sound = {part: worst(part) for part in ("kv", "tail")}
    whole_fold = transformer.fold_multipliers
    for part, change in (
            ("kv", lambda m: dict(m, key=1.0)),
            ("tail", lambda m: dict(m, ssm=m["ssm"][:2] + (m["ssm"][3],)
                                    + m["ssm"][3:]))):
        monkeypatch.setattr(
            transformer, "fold_multipliers",
            lambda params, cfg, change=change, **m: whole_fold(
                params, cfg, **change(m)))
        assert worst(part) > 1000 * sound[part] > 0, part


@pytest.mark.parametrize("remat", [False, True])
def test_prefill_and_decode_through_the_cache_are_the_references(remat):
    """Logits of the prompt's last position and of every decoded one, and
    the state, the convolution's tail, the rotated keys and the values left
    in the cache after the prompt and after the last position."""
    cfg = program_config(remat=remat)
    published, folded = seeded(cfg, seed=2)
    tokens = tokens_of(seed=3)
    got = app.Program(cfg, PROMPT, 32).run(folded, tokens)
    want = app.reference_pass(published_weights(published), CONFIG, tokens,
                              PROMPT, CONFIG["rms_norm_eps"])
    close(got["logits"], want["logits"])
    for place in ("after_prompt", "after_decode"):
        for name in ("state", "tail", "k", "v"):
            close(got[place][name], want[place][name])
    assert got["cache_dtypes"]["state"] == "float32"
    errs = app.errors(got, want, CONFIG, PROMPT)
    assert len(errs["state"]) == len(errs["tail"]) == 2 * 3
    assert len(errs["kv"]) == 3 * 2 * 2 and max(errs["kv"]) < 1e-6


def test_the_state_stays_float32_under_bfloat16_compute():
    cfg = program_config(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    cache = gen.init_cache(cfg, 2, 32)
    assert cache["state"].dtype == jnp.float32
    assert cache["state"].shape == (3, 2, 4, 8, 16)     # [slots, B, H, N, P]
    assert cache["tail"].shape == (3, 3, 2, 64 + 2 * 2 * 8)
    assert cache["k"].shape == cache["v"].shape == (3, 2, 32, 2, 16)
    assert {cache[n].dtype for n in ("k", "v", "tail")} == {
        jnp.dtype(jnp.bfloat16)}


def test_a_slice_of_the_vocabulary_is_the_whole_models_columns():
    """An eighth of the embedding's rows and of the head's columns: the
    logits over the slice are the whole model's on those columns, for
    token ids drawn from the slice."""
    cfg = program_config()
    _, whole = seeded(cfg, seed=4)
    lo, hi = 32, 48
    part_cfg = dataclasses.replace(cfg, vocab_size=hi - lo)
    part = dict(whole, embed=whole["embed"][lo:hi],
                lm_head=whole["lm_head"][:, lo:hi])
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 12), 0, hi - lo)
    close(transformer_apply(part, ids, part_cfg),
          transformer_apply(whole, ids + lo, cfg)[..., lo:hi])


def test_the_parameter_tree_is_the_issues_arithmetic():
    cfg = program_config()
    n = transformer.transformer_num_params(cfg)
    d, ff, di, conv = 64, 128, 64, 64 + 2 * 2 * 8
    mixer = d * (2 * di + 2 * 16 + 4) + conv * 4 + conv + 3 * 4 + di + di * d
    attn = d * 4 * 16 * 2 + d * 2 * 16 * 2
    assert n == 3 * (mixer + attn + 3 * d * ff + 2 * d) + 2 * 128 * d + d
    axes = transformer.transformer_logical_axes(cfg)
    shapes = jax.eval_shape(lambda: transformer_init(jax.random.PRNGKey(0),
                                                     cfg))
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x))


def test_the_block_trains_through_make_lm_train_step():
    """One step with a finite loss that moved the mixer, and a gradient in
    EVERY leaf of the mixer (seeded as Mamba-2 seeds it, so that heads
    remember and the decay's gradient is no rounding)."""
    import optax
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train.jax_step import make_lm_train_step
    cfg = program_config(remat=True, max_seq=16)
    mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    init_fn, step_fn, place = make_lm_train_step(
        cfg, mesh, optax.sgd(0.1))
    state = init_fn(jax.random.PRNGKey(0))
    before = np.asarray(state.params["layers"][0]["ssm"]["in_proj"])
    state, metrics = step_fn(
        state, place({"tokens": np.asarray(tokens_of(2, 16))}))
    assert np.isfinite(float(metrics["loss"]))
    assert np.any(np.asarray(
        state.params["layers"][0]["ssm"]["in_proj"]) != before)
    _, folded = seeded(cfg, seed=8)
    grads = jax.grad(transformer.transformer_loss)(
        folded, {"tokens": tokens_of(2, 16)}, cfg)
    (stack,) = grads["layers"]
    for mixer in ("ssm", "attn"):
        for name, g in stack[mixer].items():
            g = np.asarray(g)
            assert np.all(np.isfinite(g)), (mixer, name)
            assert np.all(np.any(g.reshape(g.shape[0], -1) != 0, axis=1)), \
                (mixer, name)


# --- generate ----------------------------------------------------------------

PATTERNS = [(("parallel",), "ssd"), (("parallel",), "delta"),
            (("linear", "full"), "ssd"), (("linear", "parallel"), "ssd")]


@pytest.mark.parametrize("kinds,transition", PATTERNS)
def test_generate_is_the_training_forwards_argmax_token_for_token(
        kinds, transition):
    """Every pattern of softmax, linear and parallel layers, either
    transition: the compiled ``generate`` (greedy, its token loop in
    segments) serves what the training forward predicts teacher-forced on
    its own tokens, and each layer has a slot of each of its caches."""
    cfg = program_config(n_layers=4, layer_types=kinds,
                         linear_transition=transition)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = tokens_of(2, 8, seed=6)
    served = jax.jit(lambda p, t: gen.generate(
        p, t, cfg, max_new_tokens=12))(params, prompt)
    fed = jnp.concatenate([prompt, served], axis=1)
    logits = transformer_apply(params, fed, cfg)
    assert np.array_equal(served, jnp.argmax(logits[:, 7:-1], axis=-1))
    slots = gen.kind_slots(cfg)
    layers = 4 // len(kinds)
    assert slots["full"] == layers * sum(
        k in ("full", "parallel") for k in kinds)
    assert slots["linear"] == layers * sum(
        k in ("linear", "parallel") for k in kinds)


def test_a_parallel_layer_takes_a_slot_of_each_of_its_caches():
    cfg = program_config(n_layers=4, layer_types=("linear", "parallel"))
    assert gen._slots(cfg, 1, 0) == {"linear": 2}
    assert gen._slots(cfg, 1, 1) == {"full": 1, "linear": 3}
    assert gen.kind_slots(cfg) == {"full": 2, "linear": 4, "latent": 0,
                                   "window": 0}


def test_the_call_span_says_what_holds_the_caches_bytes():
    cfg = program_config(dtype=jnp.bfloat16)
    with gen.call_span(cfg, 4, 16, 8) as sp:
        pass
    a = sp.attrs
    state, tail = 3 * 4 * 4 * 8 * 16 * 4, 3 * 3 * 4 * 96 * 2
    kv = 2 * 3 * 4 * 24 * 2 * 16 * 2
    assert (a["cache_bytes_state"], a["cache_bytes_tail"],
            a["cache_bytes_kv"]) == (state, tail, kv)
    assert a["cache_bytes"] == state + tail + kv
    assert a["mixers_a_layer"] == 2 and a["cache_slots"] == 6
    assert (a["linear_slots"], a["full_slots"]) == (3, 3)
    olmo = program_config(n_layers=4, layer_types=("linear", "full"),
                          linear_transition="delta")
    with gen.call_span(olmo, 4, 16, 8) as sp:
        pass
    assert sp.attrs["mixers_a_layer"] == 1


def test_generate_refuses_what_it_still_cannot_serve():
    dims = LatentDims(heads=2, q_rank=16, kv_rank=16, nope=8, rope=8, v=8)
    mixed = program_config(n_layers=4, layer_types=("parallel", "latent"),
                           latent=dims)
    with pytest.raises(NotImplementedError) as e:
        gen._refuse_unserved(mixed)
    for words in ("linear and parallel layers beside softmax layers only",
                  "chunked prefill over a carried state", "ROADMAP.md R8"):
        assert words in str(e.value)

    class Mesh:
        size = 4
    with pytest.raises(NotImplementedError) as e:
        gen._refuse_unserved(program_config(), Mesh())
    for words in ("state-space recurrence", "on one device",
                  "layout over a mesh is not built"):
        assert words in str(e.value)
    gen._refuse_unserved(program_config())      # one device: served


def test_the_configuration_refuses_what_it_cannot_name():
    with pytest.raises(ValueError, match="'delta' or 'ssd'"):
        program_config(linear_transition="s4")
    with pytest.raises(ValueError, match="'parallel'"):
        program_config(layer_types=("both",))
    with pytest.raises(ValueError, match="linear_key_heads"):
        TransformerConfig(layer_types=("parallel",))
    with pytest.raises(ValueError, match="untie"):
        cfg = program_config(tied_embeddings=True)
        transformer.fold_multipliers(
            transformer_init(jax.random.PRNGKey(0), cfg), cfg, embedding=2.0)


def test_the_reference_is_plain():
    """No kernel, no cache, nothing of the program's."""
    import inspect
    source = inspect.getsource(reference)
    assert "ray_tpu" not in source.replace("``ray_tpu", "")
    assert "pallas" not in source

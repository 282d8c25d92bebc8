"""The post-normed hybrid block (Olmo-Hybrid: gated-delta-rule layers with
beta up to 2 beside plain softmax layers) trained and SERVED: the
one-position rule against the chunked rule, the kernels through the
interpreter at 96 / 192, prefill and decode through the cache against the
benchmark's plain reference on the same seeded weights (logits, state,
tail), and what ``generate`` still refuses."""

import dataclasses
import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.apps import serve_olmo_hybrid as app
from benchmark.reference import olmo_hybrid as reference
from ray_tpu.models import (TransformerConfig, transformer_apply,
                            transformer_init)
from ray_tpu.models import transformer
from ray_tpu.models.transformer import LatentDims
from ray_tpu.ops import gated_delta
from ray_tpu.ops import gated_delta_pallas as kernels

gen = importlib.import_module("ray_tpu.models.generate")

# Hugging Face names, as benchmark/configs/olmo-hybrid-7b-l20.json has them
CONFIG = {
    "family": "olmo_hybrid", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]
    + ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 24, "linear_value_head_dim": 64,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "param_dtype": "float32",
    "torch_dtype": "float32"}


def program_config(remat=False, **over):
    kwargs = dict(app.model_kwargs(CONFIG, 64, "reference"),
                  dtype=jnp.float32)
    kwargs.update(over)
    return app.transformer_config(kwargs, remat=remat)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), \
        np.max(np.abs(a - b))


def rule_operands(b, s, hk, h, dk, dv, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (b, s, hk, dk), dtype),
            jax.random.normal(ks[1], (b, s, hk, dk), dtype),
            jax.random.normal(ks[2], (b, s, h, dv), dtype),
            -jax.nn.softplus(jax.random.normal(ks[3], (b, s, h))),
            2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))))  # to 2


# --- the rule: one position at a time, chunked, and the kernels --------------

@pytest.mark.parametrize("hk,h,dk,dv", [(3, 6, 96, 192), (2, 2, 128, 128),
                                        (2, 4, 24, 48)])
def test_the_one_position_rule_chained_is_the_chunked_rule(hk, h, dk, dv):
    q, k, v, g, beta = rule_operands(2, 70, hk, h, dk, dv)
    o, final = gated_delta.gated_delta_rule(q, k, v, g, beta,
                                            final_state=True)
    close(gated_delta.gated_delta_rule(q, k, v, g, beta), o, tol=0)
    assert final.shape == (2, h, dk, dv) and final.dtype == jnp.float32
    state = gated_delta.pack_state(jnp.zeros_like(final))
    r = gated_delta.state_pack(h, dv)
    assert state.shape == (2, h // r, dk, r * dv)
    step = jax.jit(gated_delta.gated_delta_step)
    outs = []
    for t in range(70):
        o_t, state = step(state, q[:, t], k[:, t], v[:, t], g[:, t],
                          beta[:, t])
        outs.append(o_t)
    close(jnp.stack(outs, 1), o)
    close(gated_delta.unpack_state(state, h), final)


def test_a_state_of_192_packs_two_heads_to_whole_lane_tiles():
    assert gated_delta.state_pack(30, 192) == 2       # 384 = 3 x 128
    assert gated_delta.state_pack(32, 128) == 1
    assert gated_delta.state_pack(3, 192) == 1        # no pairs of 3 heads
    s = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 8, 192))
    packed = gated_delta.pack_state(s)
    assert packed.shape == (2, 3, 8, 384)
    close(packed[:, 1, :, 192:], s[:, 3], tol=0)      # heads 2, 3 a run
    close(gated_delta.unpack_state(packed, 6), s, tol=0)


@pytest.mark.parametrize("hk,h,dk,dv", [(6, 6, 96, 192), (2, 4, 128, 128)])
def test_the_kernels_through_the_interpreter_match_the_jnp_form(hk, h, dk,
                                                                dv):
    """The forward kernel at 96 / 192 (padded to 128 / 256 inside) and at
    widths it takes as they come: outputs, the final state, and at 96 / 192
    the backward kernel's gradients through the padding."""
    operands = rule_operands(1, 70, hk, h, dk, dv, seed=3)
    want, final = gated_delta._chunked(*operands, 64)
    got, state = kernels.gated_delta_rule_kernels(
        *operands, 64, interpret=True, final_state=True)
    close(got, want)
    close(state, final)
    assert state.shape == (1, h, dk, dv)
    close(kernels.gated_delta_rule_kernels(*operands, 64, interpret=True),
          want)
    if dk == 128:
        return
    probe = jax.random.normal(jax.random.PRNGKey(5), want.shape)
    grads = [jax.grad(lambda *a: (f(*a) * probe).sum(), (0, 1, 2, 3, 4))(
        *operands) for f in (
            lambda *a: kernels.gated_delta_rule_kernels(*a, 64,
                                                        interpret=True),
            lambda *a: gated_delta._chunked(*a, 64)[0])]
    for mine, theirs in zip(*grads):
        close(mine, theirs, tol=2e-4)


@pytest.mark.parametrize("h,dk,dv,dtype", [(6, 96, 192, jnp.float32),
                                           (4, 128, 128, jnp.float32),
                                           (6, 96, 192, jnp.bfloat16)])
def test_the_step_kernel_writes_its_slot_of_the_stack_and_no_other(h, dk, dv,
                                                                   dtype):
    q, k, v, g, beta = (x[:, 5] for x in rule_operands(3, 8, h, h, dk, dv,
                                                       dtype, seed=7))
    stack = jnp.stack([gated_delta.pack_state(
        jax.random.normal(jax.random.PRNGKey(i), (3, h, dk, dv)))
        for i in range(3)])
    o, out = kernels.step_kernel(
        stack, jnp.asarray(1), *gated_delta._step_operands(q, k, v), v, g,
        beta, interpret=True)
    want_o, want = gated_delta.gated_delta_step(stack[1], q, k, v, g, beta)
    assert o.dtype == dtype and out.dtype == jnp.float32
    close(o, want_o, tol=1e-2 if dtype == jnp.bfloat16 else 2e-5)
    close(out[1], want)
    close(out[0], stack[0], tol=0)
    close(out[2], stack[2], tol=0)
    # off the TPU the stack's form cuts the slot out, steps, writes it back
    o2, out2 = jax.jit(gated_delta.gated_delta_step_at)(
        stack, jnp.asarray(1), q, k, v, g, beta)
    close(o2, want_o)
    close(out2, out.at[1].set(want))
    close(out2[0], stack[0], tol=0)


def test_the_convolutions_step_is_the_convolution_a_column_at_a_time():
    u = jax.random.normal(jax.random.PRNGKey(0), (3, 9, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 4))
    whole = gated_delta.causal_conv(u, w)
    tails = jnp.zeros((2, 3, 3, 256))
    for t in range(9):
        y, tails = gated_delta.conv_step_at(tails, jnp.asarray(1), u[:, t], w)
        close(y, whole[:, t])
    close(tails[1], jnp.swapaxes(u[:, -3:], 0, 1), tol=0)   # oldest first
    close(tails[0], 0 * tails[0], tol=0)
    y_k, tails_k = kernels.conv_step_kernel(tails, jnp.asarray(0), u[:, 0],
                                            w, interpret=True)
    y_j, tail_j = gated_delta.conv_step(tails[0], u[:, 0], w)
    close(y_k, y_j)
    close(tails_k[0], tail_j, tol=0)
    close(tails_k[1], tails[1], tol=0)


# --- the block -----------------------------------------------------------------

def test_the_block_is_the_references():
    """Training forward, and prefill + decode steps through the cache,
    against the plain reference's full forward on the same seeded weights:
    logits, and the state and tail of every linear slot after the prompt
    and after the decoded positions."""
    cfg = program_config()
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    assert "ln1" not in params["layers"][0] and \
        "ln1_post" in params["layers"][0]
    assert params["layers"][3]["attn"]["q_norm"].shape == (2, 64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 128)
    weights = app.reference_weights(params, CONFIG)
    p = 16
    full = app.reference_pass(weights, CONFIG, tokens, p, 1e-6)
    close(transformer_apply(params, tokens, cfg)[:, p - 1:], full["logits"],
          tol=2e-4)
    got = app.Program(cfg, p, 32).run(params, tokens)
    close(got["logits"], full["logits"], tol=2e-4)
    for place in ("after_prompt", "after_decode"):
        assert got[place]["state"].shape == (6, 2, 4, 24, 64)
        assert got[place]["tail"].shape == (6, 2, 3, 4 * 24 * 2 + 4 * 64)
        close(got[place]["state"], full[place]["state"], tol=2e-4)
        close(got[place]["tail"], full[place]["tail"], tol=2e-4)
    assert got["cache_dtypes"]["state"] == "float32"


def test_the_state_stays_float32_under_bfloat16_compute():
    cfg = program_config(dtype=jnp.bfloat16)
    cache = gen.init_cache(cfg, 2, 16)
    assert cache["state"].dtype == jnp.float32
    assert cache["tail"].dtype == cache["k"].dtype == jnp.bfloat16
    assert cache["state"].shape == (6, 2, 2, 24, 128)     # two heads a run
    assert cache["tail"].shape == (6, 3, 2, 448)          # positions first
    assert gen.kind_slots(cfg)["linear"] == 6 and gen.kind_slots(cfg)[
        "full"] == 2


def test_beta_reaches_two_only_where_the_configuration_says():
    cfg = program_config()
    p = transformer_init(jax.random.PRNGKey(0), cfg)["layers"][0]["gdn"]
    p = jax.tree.map(lambda a: a[0], p)
    qkv = jnp.zeros((1, 2, 2 * 96 + 256))
    ba = jnp.full((1, 2, 8), 30.0)
    assert float(transformer._rule_operands(cfg, p, qkv, ba)[4].max()) == 2.0
    once = dataclasses.replace(cfg, linear_beta_scale=1.0)
    assert float(transformer._rule_operands(once, p, qkv, ba)[4].max()) == 1.0


def test_no_rotation_at_a_rotary_factor_of_zero():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 16))
    positions = jnp.arange(5)[None]
    assert transformer._rope(x, positions, 1e4, 0) is x
    assert not np.allclose(transformer._rope(x, positions, 1e4, 16), x)


def test_two_norms_of_one_place_are_refused():
    with pytest.raises(ValueError, match="two norms of one place"):
        program_config(qk_norm=True)
    with pytest.raises(ValueError, match="one of the two"):
        program_config(sandwich_norm=True)


def test_the_block_trains_through_make_lm_train_step():
    from jax.sharding import Mesh
    from ray_tpu.train.jax_step import make_lm_train_step
    cfg = program_config(remat=True)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "fsdp", "tp"))
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    batch = place({"tokens": np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 32), 0, 128), np.int32)})
    losses = []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# --- generate ------------------------------------------------------------------

def test_segmented_generate_is_a_chain_of_decode_steps_token_for_token():
    cfg = program_config()
    params = transformer_init(jax.random.PRNGKey(2), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (3, 8), 0, 128)
    new = 72                                # two segments of 36 steps
    assert len(gen._decode_segments(8, new)) == 2
    tokens, _, cache = jax.jit(partial(
        gen.generate_and_cache, cfg=cfg, max_new_tokens=new))(params, prompt)
    logits, chained = jax.jit(partial(gen.prefill, cfg=cfg,
                                      max_len=8 + new))(params, prompt)
    step = jax.jit(partial(gen.decode_step, cfg=cfg))
    for j in range(new):
        token = jnp.argmax(logits, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(token),
                                      np.asarray(tokens[:, j]))
        logits, chained = step(params, token, jnp.asarray(8 + j, jnp.int32),
                               chained)
    for name in ("state", "tail"):
        close(cache[name], chained[name], tol=1e-5)


def test_the_decode_loop_carries_one_state_buffer():
    """Every scan of the token loop has the state stack among its carries
    once, and among its scanned inputs and outputs never: a step updates
    the one buffer (1.6 GB at the cell's size) and keeps no second."""
    cfg = program_config()
    params = jax.eval_shape(partial(transformer_init, cfg=cfg),
                            jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(partial(gen.generate, cfg=cfg,
                                   max_new_tokens=72))(
        params, jax.ShapeDtypeStruct((2, 8), jnp.int32))
    state = gen.cache_shapes(cfg, 2, 80)["state"]

    def is_state(aval):
        return tuple(aval.shape) == state and aval.dtype == jnp.float32

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    token_loops = [e for e in scans(jaxpr.jaxpr)
                   if e.params["length"] == 36]
    assert len(token_loops) == 2            # the two segments
    for eqn in token_loops:
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        carried = [v.aval for v in eqn.invars[n_consts:n_consts + n_carry]]
        rest = [v.aval for v in eqn.invars[:n_consts]
                + eqn.invars[n_consts + n_carry:]] \
            + [v.aval for v in eqn.outvars[n_carry:]]
        assert sum(map(is_state, carried)) == 1
        assert not any(map(is_state, rest))


def test_the_call_span_says_what_holds_the_caches_bytes():
    cfg = program_config(dtype=jnp.bfloat16)
    with gen.call_span(cfg, 4, 16, 48) as sp:
        pass
    a = sp.attrs
    shapes = gen.cache_shapes(cfg, 4, 64)
    assert a["cache_bytes_state"] == 4 * int(np.prod(shapes["state"]))
    assert a["cache_bytes_tail"] == 2 * int(np.prod(shapes["tail"]))
    assert a["cache_bytes_kv"] == 2 * 2 * int(np.prod(shapes["k"]))
    assert a["cache_bytes"] == a["cache_bytes_state"] \
        + a["cache_bytes_tail"] + a["cache_bytes_kv"]
    assert (a["linear_slots"], a["full_slots"], a["cache_slots"]) == (6, 2, 8)
    assert a["attention_path"] == "reference"


def test_generate_refuses_what_it_still_cannot_serve():
    """A linear layer beside latent or window layers, linear layers under a
    mesh of several devices, and (where it is configured) a loop over a
    pattern."""
    dims = LatentDims(heads=2, q_rank=8, kv_rank=8, nope=8, rope=8, v=8)
    cfg = program_config(layer_types=("linear", "latent"), n_layers=4,
                         latent=dims)
    with pytest.raises(NotImplementedError, match="beside latent or window"):
        gen.prefill(None, jnp.zeros((1, 8), jnp.int32), cfg, 16)
    with pytest.raises(NotImplementedError, match="beside latent or window"):
        gen.decode_step(None, jnp.zeros((1,), jnp.int32), 0, {}, cfg)
    with pytest.raises(NotImplementedError, match="is not served"):
        gen._refuse_unserved(program_config(
            layer_types=("full", "window"), n_layers=4, window_latent=dims,
            window=8))
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(NotImplementedError, match="on one device"):
        gen.prefill(None, jnp.zeros((1, 8), jnp.int32), program_config(), 16,
                    mesh=mesh)
    with pytest.raises(ValueError, match="loop_steps > 1 with a layer"):
        program_config(loop_steps=2)
    with pytest.raises(ValueError, match="in one chunk"):
        gen.prefill_and_taps(None, jnp.zeros((1, 8), jnp.int32),
                             program_config(), 16, chunk=4)


def test_the_reference_is_plain():
    """float32, highest precision, a loop over positions: nothing of the
    program's, no kernel, no cache."""
    import inspect
    text = inspect.getsource(reference)
    assert "ray_tpu" not in text.split('"""', 2)[2]
    assert "pallas" not in text and "qwen3_next" not in text.split(
        '"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    q, k, v, g, beta = rule_operands(1, 9, 2, 2, 8, 16)
    qn = gated_delta.unit(q) * 8 ** -0.5
    kn = gated_delta.unit(k)
    o, state = reference._delta_rule(qn, kn, v, g, beta)
    want, final = gated_delta._chunked(q, k, v, g, beta, 16)
    close(o, want)
    close(state, final)

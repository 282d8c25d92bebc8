"""The hybrid block (gated delta rule, gated attention, dropless expert
layer over a share) against the plain reference of its family, at a small
size on the CPU, in float32 so that the comparison is of the mathematics."""

import dataclasses
import re
from collections import Counter
from functools import cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.apps import train_qwen3_next as app
from benchmark.reference import qwen3_next as reference
from ray_tpu.models import (TransformerConfig, generate, transformer_init,
                            transformer_loss)
from ray_tpu.models import moe, transformer

# One period at toy widths, every expert held; Hugging Face key names, as a
# configuration file gives them.
CONFIG = {
    "family": "qwen3_next", "param_dtype": "float32",
    "hidden_size": 64, "intermediate_size": 128, "head_dim": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rms_norm_eps": 1e-6, "full_attention_interval": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": 16,
    "num_experts_published": 16, "first_expert": 0,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_hidden_layers": 4, "vocab_size": 128, "tie_word_embeddings": False,
}
SEQ = 100          # not a multiple of the delta rule's chunk (64)


def program_config(config=CONFIG, **overrides) -> TransformerConfig:
    cfg = app.transformer_config(
        app.model_kwargs(config, SEQ, "reference"), remat=False)
    return dataclasses.replace(cfg, dtype=jnp.float32, **overrides)


def seeded(cfg, seed=0):
    """Parameters with every leaf random: the norms' scales too, so that no
    term drops out of a comparison."""
    params = transformer_init(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def hidden(seed=3, rows=2, seq=SEQ, d=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, seq, d))


def close(got, want, tol=2e-4):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(1e-6, float(np.abs(want).max()))
    np.testing.assert_allclose(got / scale, want / scale, atol=tol, rtol=0)


def layer_of(cfg, params, i):
    """(the program's layer tree, the reference's matrices) of layer i."""
    weights = app.reference_weights(params, CONFIG)
    period = len(cfg.layer_types)
    stack = params["layers"][i % period]
    return jax.tree.map(lambda a: a[i // period], stack), weights.layer(i)


def test_chunked_delta_rule_matches_the_recurrence_values_and_gradients():
    cfg = program_config()
    params = seeded(cfg)
    layer, w = layer_of(cfg, params, 0)
    h = hidden()

    def system(p, x):
        return transformer._gated_delta_mix(
            cfg, p, x, transformer._whole_rule(cfg))[0]

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            return reference._gated_delta_rule(x, p, CONFIG)

    close(system(layer["gdn"], h), plain(w, h))
    probe = jax.random.normal(jax.random.PRNGKey(9), h.shape)
    got = jax.grad(lambda p, x: (system(p, x) * probe).sum(), (0, 1))(
        layer["gdn"], h)
    want = jax.grad(lambda p, x: (plain(p, x) * probe).sum(), (0, 1))(
        {k: w[k] for k in layer["gdn"]}, h)
    close(got[1], want[1])
    for name in layer["gdn"]:
        # the decay's own parameters: where exp(g) is nearly 0 their
        # gradient is a difference of nearly equal float32 terms
        close(got[0][name], want[0][name],
              tol=5e-3 if name in ("A_log", "dt_bias") else 2e-4)


def delta_rule(form):
    """The op by its jnp form, or by the Pallas kernels through the
    interpreter (no platform check ever selects ``interpret``)."""
    from ray_tpu.ops.gated_delta import CHUNK, gated_delta_rule
    from ray_tpu.ops.gated_delta_pallas import gated_delta_rule_kernels
    if form == "jnp":
        return gated_delta_rule
    return lambda *operands: gated_delta_rule_kernels(*operands, CHUNK,
                                                      interpret=True)


def delta_rule_operands(seq, hk, hv, dk=8, dv=8, b=1, seed=None):
    ks = jax.random.split(jax.random.PRNGKey(seq if seed is None else seed),
                          5)
    q = jax.random.normal(ks[0], (b, seq, hk, dk))
    k = 3.0 * jax.random.normal(ks[1], (b, seq, hk, dk))
    v = jax.random.normal(ks[2], (b, seq, hv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, seq, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, seq, hv)))
    return q, k, v, g, beta


def as_the_rule_takes_them(q, k):
    """q, k at unit length over the head, q times dk^-0.5: what the rule
    makes of them before anything else."""
    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    return unit(q) * q.shape[-1] ** -0.5, unit(k)


@pytest.mark.parametrize("form", ["jnp", "kernels"])
@pytest.mark.parametrize("seq", [64, 65, 130])
def test_delta_rule_op_at_chunk_edges(seq, form):
    """The op alone against a loop over positions, at a whole chunk, one
    position past it and two chunks and a bit."""
    b, h, dk, dv = 1, 2, 8, 8
    q, k, v, g, beta = delta_rule_operands(seq, h, h)
    q_t, k_t = (np.asarray(x) for x in as_the_rule_takes_them(q, k))
    state = np.zeros((b, h, dk, dv))
    want = []
    for t in range(seq):
        state = state * np.exp(np.asarray(g[:, t]))[..., None, None]
        seen = np.einsum("bhkv,bhk->bhv", state, k_t[:, t])
        delta = np.asarray(beta[:, t])[..., None] \
            * (np.asarray(v[:, t]) - seen)
        state = state + k_t[:, t][..., :, None] * delta[..., None, :]
        want.append(np.einsum("bhkv,bhk->bhv", state, q_t[:, t]))
    close(delta_rule(form)(q, k, v, g, beta), np.stack(want, 1))


@pytest.mark.parametrize("form", ["jnp", "kernels"])
def test_delta_rule_holds_when_keys_align(form):
    """Keys that point one way, beta near 1, slow decay: (I + A)^-1 has
    entries of O(1) but its Neumann series has terms of 1e18; the blocked
    substitution stays with the recurrence (the series returned NaN)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    b, seq, h, dk, dv = 1, 192, 2, 16, 16
    k = 0.01 * jax.random.normal(ks[0], (b, seq, h, dk)) \
        + jax.random.normal(ks[1], (1, 1, h, dk))
    q = jax.random.normal(ks[2], (b, seq, h, dk))
    v = jax.random.normal(ks[3], (b, seq, h, dv))
    g = jnp.full((b, seq, h), -0.01)
    beta = jnp.full((b, seq, h), 0.95)
    got = delta_rule(form)(q, k, v, g, beta)
    q, k = as_the_rule_takes_them(q, k)

    def position(state, xs):
        q_t, k_t, v_t = xs
        state = state * jnp.exp(-0.01)
        delta = 0.95 * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, want = jax.lax.scan(position, jnp.zeros((b, h, dk, dv)),
                           tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)))
    close(got, jnp.moveaxis(want, 0, 1), tol=1e-4)


@pytest.mark.parametrize("seq,hk,hv", [
    (130, 2, 2),        # a padded tail; the heads fill half of the lanes
    (128, 1, 3),        # three heads share one key head; a lone last head
    (64, 16, 32),       # the model's heads: dq, dk summed over each pair
])
@pytest.mark.parametrize("operand", ["q", "k", "v", "g", "beta"])
def test_delta_rule_kernels_gradient_matches_the_jnp_forms(operand, seq, hk,
                                                           hv):
    """The backward kernel against ``jax.vjp`` of the jnp form. g has its
    own tolerance: its gradient is a sum through the running sum in which
    the terms cancel (``A_log`` / ``dt_bias`` read 5e-3 in the layer's
    test)."""
    at = ["q", "k", "v", "g", "beta"].index(operand)
    got, want = (grads[at] for grads in delta_rule_gradients(seq, hk, hv))
    assert got.shape == want.shape and got.dtype == want.dtype
    close(got, want, tol=5e-5 if operand == "g" else 1e-5)


@cache
def delta_rule_gradients(seq, hk, hv):
    """(the kernels', the jnp form's) gradients of all five operands."""
    operands = delta_rule_operands(seq, hk, hv)
    probe = jax.random.normal(jax.random.PRNGKey(7), operands[2].shape)
    return tuple(
        jax.grad(lambda *a: (delta_rule(form)(*a) * probe).sum(),
                 argnums=(0, 1, 2, 3, 4))(*operands)
        for form in ("kernels", "jnp"))


def test_delta_rule_kernels_take_bfloat16_as_the_jnp_form_does():
    """bfloat16 operands, as the model hands them over: both forms round
    the same products' operands, so they differ by a rounding, not more."""
    q, k, v, g, beta = delta_rule_operands(128, 2, 4, dk=16, dv=16)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = delta_rule("kernels")(q, k, v, g, beta)
    want = delta_rule("jnp")(q, k, v, g, beta)
    assert got.dtype == want.dtype == jnp.bfloat16
    close(got.astype(jnp.float32), want.astype(jnp.float32), tol=1e-2)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """The delta rule as on a TPU, its kernels through the interpreter."""
    from ray_tpu.ops import gated_delta, gated_delta_pallas
    monkeypatch.setattr(gated_delta, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        gated_delta_pallas, "gated_delta_rule_kernels",
        partial(gated_delta_pallas.gated_delta_rule_kernels, interpret=True))


@pytest.mark.parametrize("hk", [4, 2])
def test_delta_rule_kernels_run_per_shard_under_a_mesh(kernels_interpreted,
                                                       hk):
    """Where the kernels run, a mesh of several devices gets them per shard
    (GSPMD cannot partition a Mosaic call): rows over dp, heads over tp, a
    value head staying with its key head; two key heads over four head
    shards are first copied once a value head. The kernels themselves go
    through the interpreter here."""
    from jax.sharding import Mesh

    from ray_tpu.ops import gated_delta
    from ray_tpu.parallel.sharding import DEFAULT_RULES

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))
    operands = delta_rule_operands(64, hk, 4, dk=128, dv=128, b=2)
    got = jax.jit(partial(gated_delta.gated_delta_rule_over, mesh,
                          DEFAULT_RULES))(*operands)
    close(got, gated_delta._chunked(*operands, gated_delta.CHUNK)[0])


def test_off_the_tpu_the_delta_rule_is_the_jnp_form():
    """No platform check selects the kernels here: the op traces no Mosaic
    call on this backend, at the widths that would fit one."""
    from ray_tpu.ops.gated_delta import gated_delta_rule, gated_delta_rule_over
    operands = delta_rule_operands(64, 1, 2, dk=128, dv=128)
    assert jax.default_backend() != "tpu"
    for rule in (gated_delta_rule, partial(gated_delta_rule_over, None,
                                           None)):
        text = jax.jit(jax.grad(
            lambda *a: rule(*a).sum())).lower(*operands).as_text()
        assert "tpu_custom_call" not in text and "rt_gdn" not in text


# The toy period at head widths the kernels take (multiples of 128): three
# delta-rule layers and one attention layer, a key head on two value heads.
KERNEL_WIDTHS = {**CONFIG, "linear_num_key_heads": 1,
                 "linear_num_value_heads": 2, "linear_key_head_dim": 128,
                 "linear_value_head_dim": 128}
GDN_LAYERS = 3


@pytest.fixture
def bare_checkpoint(monkeypatch):
    """``_stage_scan``'s remat as the parent of PR 44 had it: a
    ``jax.checkpoint`` with no policy, which keeps a layer's input alone."""
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)


def equations(jaxpr, into=None) -> Counter:
    """How often a jaxpr, with every jaxpr inside it, holds each primitive
    and, by their own names, each Pallas kernel and each checkpoint name.
    (The printed form will not do: it writes a kernel's body once however
    often it is called.)"""
    into = Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        if eqn.primitive.name in ("pallas_call", "name"):
            into[eqn.params["name"]] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            equations(inner, into)
    return into


def period_loss_and_grads(cfg, mesh=None):
    """value_and_grad of a loss over ``_stage_scan`` of one period, by the
    layers' parameters and the input."""
    def loss(layers, x):
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        y, _ = transformer._stage_scan(cfg, mesh, layers, x, positions)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    return jax.value_and_grad(loss, argnums=(0, 1))


def devices_as(shape):
    from jax.sharding import Mesh
    if shape is None:
        return None
    count = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:count]).reshape(shape), ("dp", "tp"))


@pytest.mark.parametrize("mesh_shape", [None, (2, 1), (2, 2)])
@pytest.mark.parametrize("remat,policy,forwards", [
    (True, "kept", 1), (False, "kept", 1), (True, "bare", 2)])
def test_the_rules_forward_kernel_runs_once_a_layer(
        request, kernels_interpreted, mesh_shape, remat, policy, forwards):
    """What ``rt_gdn_bwd`` and the layer's remat'd tail read of
    ``rt_gdn_fwd`` outlives the forward pass: the gradient of a period
    under whole-layer remat holds one forward and one backward kernel a
    delta-rule layer, as without remat; under a bare ``jax.checkpoint``
    (the parent's) the forward is there twice. Under a mesh the kernels,
    and the names on their outputs, sit inside shard_map, and the policy
    reaches them there (two head shards: the one key head is first copied
    once a value head)."""
    from ray_tpu.ops.gated_delta import KEPT
    if policy == "bare":
        request.getfixturevalue("bare_checkpoint")
    cfg = dataclasses.replace(program_config(KERNEL_WIDTHS), remat=remat)
    layers = seeded(cfg)["layers"]
    mesh = devices_as(mesh_shape)
    found = equations(jax.make_jaxpr(period_loss_and_grads(cfg, mesh))(
        layers, hidden(seq=64)).jaxpr)
    assert found["rt_gdn_fwd"] == forwards * GDN_LAYERS
    assert found["rt_gdn_bwd"] == GDN_LAYERS
    # o, states, t and v' of each forward that is kept
    assert found[KEPT] >= 4 * GDN_LAYERS
    assert (found["shard_map"] > 0) == (mesh is not None)


@pytest.mark.parametrize("mesh_shape", [None, (2, 1)])
@pytest.mark.parametrize("policy,forwards", [("kept", 1), ("bare", 2)])
def test_gated_attentions_flash_forward_runs_once_a_period(
        request, monkeypatch, kernels_interpreted, mesh_shape, policy,
        forwards):
    """The period's one gated-attention layer through the flash kernels at
    the cell's S = 8,192 and head of 256 (traced, not run): what its
    backward kernels read of ``rt_flash_fwd`` carries flash's own name,
    inside shard_map under a mesh, and the same policy keeps it beside the
    delta rule's."""
    from ray_tpu.ops import flash
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    if policy == "bare":
        request.getfixturevalue("bare_checkpoint")
    cfg = dataclasses.replace(
        program_config({**KERNEL_WIDTHS, "head_dim": 256}),
        remat=True, attn_impl="flash", dtype=jnp.bfloat16)
    found = equations(jax.make_jaxpr(period_loss_and_grads(
        cfg, devices_as(mesh_shape)))(
            seeded(cfg)["layers"], hidden(seq=8192)).jaxpr)
    assert found["rt_flash_fwd"] == forwards
    assert found["rt_flash_dkv"] == found["rt_flash_dq"] == 1
    assert found["rt_gdn_fwd"] == forwards * GDN_LAYERS
    assert found[flash.KEPT] >= 2
    # the convolution's pair at this length: nothing of its forward is
    # kept, so the layer's backward runs it again under either policy
    assert found["rt_gdn_conv_fwd"] == 2 * GDN_LAYERS
    assert found["rt_gdn_conv_bwd"] == GDN_LAYERS


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)])
def test_what_is_kept_is_what_the_second_run_would_have_made(
        request, kernels_interpreted, mesh_shape):
    """Loss and every gradient leaf, bit for bit, between the policy and a
    bare ``jax.checkpoint`` of the same body."""
    cfg = dataclasses.replace(program_config(KERNEL_WIDTHS), remat=True)
    layers, x = seeded(cfg)["layers"], hidden(seq=64)
    mesh = devices_as(mesh_shape)
    kept = jax.jit(period_loss_and_grads(cfg, mesh))(layers, x)
    request.getfixturevalue("bare_checkpoint")
    bare = jax.jit(period_loss_and_grads(cfg, mesh))(layers, x)
    got = jax.tree_util.tree_leaves_with_path(kept)
    want = jax.tree.leaves(bare)
    assert len(got) == len(want) == 2 + 3 * 17 + 16
    for (path, leaf), wanted in zip(got, want):
        assert float(jnp.abs(wanted).max()) > 0, path
        np.testing.assert_array_equal(leaf, wanted, err_msg=str(path))


def test_off_the_tpu_a_period_keeps_nothing_and_runs_no_kernel():
    """No patch: the jnp form runs at the widths that would fit the
    kernels, nothing carries the name, and the policy finds nothing."""
    cfg = dataclasses.replace(program_config(KERNEL_WIDTHS), remat=True)
    assert jax.default_backend() != "tpu"
    traced = jax.make_jaxpr(period_loss_and_grads(cfg))(
        seeded(cfg)["layers"], hidden(seq=64))
    found = equations(traced.jaxpr)
    assert found["pallas_call"] == found["name"] == 0
    assert not [k for k in found if k.startswith("rt_gdn")]
    assert "rt_gdn" not in str(traced)


def test_a_dense_models_step_is_the_bare_checkpoints(request):
    """A layer without ``gdn`` holds nothing under the name: the jaxpr of a
    llama configuration's whole train step is the one a bare
    ``jax.checkpoint`` gives, but for the line that prints the policy's
    own address, and holds no ``name`` equation."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_lm_train_step
    dense = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                              n_heads=4, n_kv_heads=2, max_seq=64)
    assert dense.remat
    tokens = {"tokens": jnp.zeros((2, 64), jnp.int32)}

    def step_jaxpr():
        init_fn, step_fn, _ = make_lm_train_step(
            dense, build_mesh(MeshSpec(dp=1)))
        return jax.make_jaxpr(step_fn)(init_fn(jax.random.PRNGKey(0)),
                                       tokens)

    def text(jaxpr):
        return re.sub(r"policy=.*", "policy=", str(jaxpr))

    kept = step_jaxpr()
    found = equations(kept.jaxpr)
    assert found["name"] == 0
    assert "save_only_these_names" in str(kept)      # the remat is there
    request.getfixturevalue("bare_checkpoint")
    bare = step_jaxpr()
    assert "policy=None" in str(bare)
    assert text(kept) == text(bare)


def test_gated_attention_with_partial_rotary_matches_the_reference():
    cfg = program_config()
    params = seeded(cfg)
    layer, w = layer_of(cfg, params, 3)
    h = hidden()
    positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    got, _ = transformer._full_attention_mix(
        cfg, layer["attn"], h, positions,
        lambda q, k, v: (transformer._attention(cfg, q, k, v, None), None))
    with jax.default_matmul_precision("highest"):
        want = reference._gated_attention(h, w, CONFIG)
    close(got, want)
    # the rotation touches the first quarter of a head and nothing else
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 4, 32))
    turned = transformer._rope(x, positions, 1e7, cfg.rotary_dim)
    assert cfg.rotary_dim == 8
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])
    assert not np.allclose(turned[:, 1:, :, :8], x[:, 1:, :, :8])


def test_expert_layer_under_skewed_routing_drops_nothing():
    cfg = program_config()
    params = seeded(cfg)
    layer, w = layer_of(cfg, params, 1)
    h = jnp.abs(hidden()) + 1.0          # every token scores high where the
    m = dict(layer["moe"])               # router's column is large
    m["router"] = m["router"].at[:, 5].set(1.0)
    w = {**w, "router": m["router"]}
    got, stats = moe.moe_apply(cfg, m, h)
    with jax.default_matmul_precision("highest"):
        want = reference._experts(h, w, CONFIG)
    close(got, want)
    tokens = h.shape[0] * h.shape[1]
    assert int(stats["load"][5]) == tokens          # all of them, kept
    assert int(stats["rows_here"]) == 2 * tokens
    assert int(stats["rows_dropped"]) == 0


@pytest.mark.parametrize("skewed", [False, True])
def test_a_share_under_any_routing_value_and_gradients(skewed):
    """Four of sixteen experts held: under a uniform router most of the
    buffer's rows are padding, under one that sends every token here none
    is; either way the value and the gradients for the tokens and the
    router are the reference's, and nothing is dropped."""
    cfg = program_config(experts_held=4, first_expert=4)
    layer, w = layer_of(program_config(), seeded(program_config()), 1)
    h = jnp.abs(hidden()) + 1.0
    tokens = h.shape[0] * h.shape[1]
    router = layer["moe"]["router"]
    if skewed:
        router = router.at[:, 4:6].set(1.0)
    m = {"router": router, "shared": layer["moe"]["shared"],
         **{k: layer["moe"][k][4:8] for k in ("w1", "w3", "w2")}}
    share = {**w, **{k: w[k][4:8] for k in ("w1", "w3", "w2")}}
    (got, stats), vjp = jax.vjp(lambda m, h: moe.moe_apply(cfg, m, h), m, h)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(lambda r, x: reference._experts(
            x, {**share, "router": r},
            {**CONFIG, "num_experts": 4, "first_expert": 4}), router, h)
    close(got, want)
    assert int(stats["rows_here"]) == int(stats["load"].sum())
    assert (int(stats["rows_here"]) == 2 * tokens) == skewed
    assert int(stats["rows_dropped"]) == 0
    dy = hidden(seed=9)
    zeros = jax.tree.map(lambda a: np.zeros(a.shape, jax.dtypes.float0),
                         stats)
    d_m, d_h = vjp((dy, zeros))
    want_router, want_h = want_vjp(dy)
    close(d_h, want_h, tol=2e-3)
    close(d_m["router"], want_router, tol=2e-3)


def test_a_small_share_counts_what_its_buffer_drops():
    """One of sixteen experts held: the buffer is 4 x the mean of 25 pairs.
    A router that sends every token there overruns it, and says by how
    much; holding a quarter or more, the buffer is the most there can be."""
    cfg = program_config(experts_held=1, first_expert=5)
    layer, _ = layer_of(program_config(), seeded(program_config()), 1)
    h = jnp.abs(hidden()) + 1.0
    tokens = h.shape[0] * h.shape[1]
    assert moe.buffer_rows(cfg, tokens) == 4 * 25
    assert moe.buffer_rows(program_config(experts_held=4), tokens) \
        == 2 * tokens
    assert moe.buffer_rows(program_config(), tokens) == 2 * tokens
    m = {"router": layer["moe"]["router"].at[:, 5].set(1.0),
         **{k: layer["moe"][k][5:6] for k in ("w1", "w3", "w2")}}
    _, stats = moe.moe_apply(cfg, m, h)
    assert int(stats["rows_here"]) == tokens
    assert int(stats["rows_dropped"]) == tokens - 100
    assert int(stats["load"][0]) == tokens


def softmax_shares():
    """Four shares of four experts each, of the hybrid block's layer: (the
    uncut configuration, a share's, the layer's ``moe`` tree, the shares'
    first experts, top_k)."""
    whole = program_config()
    layer, _ = layer_of(whole, seeded(whole), 2)
    return (whole, lambda first: program_config(experts_held=4,
                                                first_expert=first),
            layer["moe"], range(0, 16, 4), 2)


def sigmoid_shares():
    """Eight shares of 32 experts each, routed by sigmoid with a correction
    bias that moves the selection, an ungated shared expert, top 8 of 256:
    the routing of the ``dots3`` family at toy widths."""
    def cfg(held, first=0):
        return TransformerConfig(
            d_model=64, num_experts=256, experts_held=held,
            first_expert=first, expert_top_k=8, norm_topk_prob=True,
            expert_ff=16, shared_expert_ff=16, shared_expert_gate=False,
            router_scoring="sigmoid", routed_scaling_factor=1.5,
            dtype=jnp.float32)
    whole = cfg(256)
    m = seeded_tree(transformer._layer_init(jax.random.PRNGKey(4), whole)[
        "moe"], 5)
    return whole, lambda first: cfg(32, first), m, range(0, 256, 32), 8


def seeded_tree(tree, seed):
    leaves, struct = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(struct, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


@pytest.mark.parametrize("shares", [softmax_shares, sigmoid_shares])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The shares' partial results, with the shared expert (which every
    chip computes alike) counted once, are the uncut layer over all the
    experts; under softmax routing each is also the reference's."""
    whole, share_cfg_at, m, firsts, top_k = shares()
    n = len(firsts)
    held = whole.num_experts // n
    h = hidden()
    want, _ = moe.moe_apply(whole, m, h)
    if shares is softmax_shares:
        _, w = layer_of(whole, seeded(whole), 2)
        with jax.default_matmul_precision("highest"):
            close(want, reference._experts(h, w, CONFIG))
    routing = {k: v for k, v in m.items() if k.startswith("router")}
    total, rows = 0.0, 0
    for first in firsts:
        share = {**routing, **{k: m[k][first:first + held]
                               for k in ("w1", "w3", "w2")}}
        routed, stats = moe.moe_apply(share_cfg_at(first), share, h)
        total = total + routed
        rows += int(stats["rows_here"])
        if shares is softmax_shares:
            # the reference, given the same share, gives the same part
            share_config = {**CONFIG, "num_experts": 4,
                            "first_expert": first}
            zero_shared = {**w, **{k: w[k][first:first + 4]
                                   for k in ("w1", "w3", "w2")},
                           "shared_w2": jnp.zeros_like(w["shared_w2"])}
            with jax.default_matmul_precision("highest"):
                close(routed,
                      reference._experts(h, zero_shared, share_config))
    shared_only, _ = moe.moe_apply(
        share_cfg_at(0),
        {**routing, "shared": m["shared"],
         **{k: jnp.zeros_like(m[k][:held]) for k in ("w1", "w3", "w2")}}, h)
    close(total + shared_only, want)
    assert rows == top_k * h.shape[0] * h.shape[1]      # every pair, once


def test_the_correction_bias_moves_the_selection_and_not_the_weights():
    whole, _, m, _, _ = sigmoid_shares()
    x = hidden().reshape(-1, 64)
    w, e = moe.route(whole, m, x)
    scores = jax.nn.sigmoid(x @ m["router"])
    picked = jnp.take_along_axis(scores, e, -1)
    close(w, 1.5 * picked / picked.sum(-1, keepdims=True))
    _, unbiased = moe.route(whole, {**m, "router_bias": jnp.zeros(256)}, x)
    assert not np.array_equal(np.sort(e, -1), np.sort(unbiased, -1))
    want = jax.lax.top_k(scores + m["router_bias"], 8)[1]
    np.testing.assert_array_equal(np.sort(e, -1), np.sort(want, -1))


def reference_arrays(params):
    weights = app.reference_weights(params, CONFIG)
    return [weights.layer(i) for i in range(weights.n_layers)]


def test_whole_model_loss_and_gradients_over_one_period():
    cfg = program_config()
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, SEQ), 0, 128)
    loss, grads = jax.value_and_grad(transformer_loss)(
        params, {"tokens": tokens}, cfg)
    weights = app.reference_weights(params, CONFIG)
    assert abs(float(loss) - reference.loss(weights, tokens, CONFIG)) < 1e-5

    def plain(p):
        return reference.loss_of_arrays(
            reference_arrays(p), p["embed"], p["final_norm"], p["lm_head"],
            tokens, CONFIG)

    want_loss, want = jax.value_and_grad(plain)(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    flat_got = jax.tree_util.tree_leaves_with_path(grads)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, got), wanted in zip(flat_got, flat_want):
        assert float(jnp.abs(wanted).max()) > 0, path
        close(got, wanted, tol=2e-3)


def test_the_reference_takes_its_gradient_a_layer_at_a_time():
    """``loss_and_grads``, the form that fits the cell's size (a row at a
    time, ``jax.vjp`` layer by layer), against ``jax.grad`` of the whole
    loss."""
    params = seeded(program_config())
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, SEQ), 0, 128)

    def plain(p):
        return reference.loss_of_arrays(
            reference_arrays(p), p["embed"], p["final_norm"], p["lm_head"],
            tokens, CONFIG)

    want_loss, want = jax.value_and_grad(plain)(params)
    loss, grads = reference.loss_and_grads(
        app.reference_weights(params, CONFIG), np.asarray(tokens), CONFIG)
    assert abs(loss - float(want_loss)) < 1e-5
    gaps = app.gradient_gaps(
        app.named_leaves(grads),
        app.named_leaves(app.reference_weights(want, CONFIG)))
    assert len(gaps) == 3 + 3 * 17 + 16 + 1      # every leaf, and "all"
    assert max(gaps.values()) < 2e-4, max(gaps, key=gaps.get)


def test_the_steps_gradient_is_read_from_the_state_it_returns():
    """What the benchmark's ``correct`` leans on: after one step from fresh
    moments AdamW's first moment is (1 - b1) x the step's own gradient. A
    state handed back unchanged reads a gap of 1."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_lm_train_step
    cfg = dataclasses.replace(program_config(), remat=True)
    init_fn, step_fn, place = make_lm_train_step(
        cfg, build_mesh(MeshSpec(dp=1)))
    tokens = np.random.default_rng(0).integers(0, 128, (2, SEQ),
                                               dtype=np.int32)
    state = init_fn(jax.random.PRNGKey(0))
    want = jax.grad(transformer_loss)(state.params, {"tokens": tokens}, cfg)
    want = {k: np.asarray(v) for k, v in app.named_leaves(
        app.reference_weights(want, CONFIG)).items()}
    unchanged = app.gradient_gaps(app.first_moment(state, CONFIG), want,
                                  1 / (1 - app.ADAM_B1))
    assert set(unchanged.values()) == {1.0}
    state, _ = step_fn(state, place({"tokens": tokens}))
    gaps = app.gradient_gaps(app.first_moment(state, CONFIG), want,
                             1 / (1 - app.ADAM_B1))
    assert max(gaps.values()) < 1e-4, max(gaps, key=gaps.get)
    checks = app.gradient_checks(gaps)
    assert checks["grad_gap"] == gaps["all"]
    assert checks["grad_gap_worst"] == gaps[checks["grad_gap_worst_leaf"]]


def test_make_lm_train_step_takes_the_hybrid_configuration():
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_lm_train_step
    cfg = dataclasses.replace(program_config(), remat=True,
                              dtype=jnp.bfloat16, attn_impl="auto")
    mesh = build_mesh(MeshSpec(dp=2))
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    batch = place({"tokens": np.random.default_rng(0).integers(
        0, 128, (4, SEQ), dtype=np.int32)})
    losses = []
    for _ in range(4):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert int(metrics["moe_rows_here"]) == 4 * 4 * SEQ * 2   # all held
    assert int(metrics["moe_rows_walked"]) == 4 * 4 * SEQ * 2  # so all walked
    assert int(metrics["moe_rows_dropped"]) == 0
    assert float(metrics["moe_load_max"]) >= float(metrics["moe_load_mean"])
    # the llama configuration's metrics carry no expert counters
    dense = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                              n_heads=4, max_seq=SEQ)
    init_fn, step_fn, place = make_lm_train_step(dense, mesh)
    _, metrics = step_fn(init_fn(jax.random.PRNGKey(0)), batch)
    assert sorted(metrics) == ["grad_norm", "loss", "step"]


def test_generate_serves_the_pattern_and_refuses_it_beside_latent_layers():
    """Since the cache holds a linear layer's state and tail beside keys
    and values, this pattern (gated-delta-rule and gated softmax layers over
    an expert layer) goes through the one trunk: greedy tokens are a chain
    of decode steps'. What is still refused, in words: a linear layer
    beside latent or window layers (tests/test_olmo_hybrid.py has the
    rest)."""
    import dataclasses
    import importlib
    from functools import partial

    from ray_tpu.models.transformer import LatentDims
    gen = importlib.import_module("ray_tpu.models.generate")
    cfg = dataclasses.replace(program_config(), remat=False)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    tokens = jax.jit(partial(generate, cfg=cfg, max_new_tokens=4))(params,
                                                                   prompt)
    logits, cache = gen.prefill(params, prompt, cfg, 12)
    assert sorted(cache) == ["k", "state", "tail", "v"]
    for j in range(4):
        token = jnp.argmax(logits, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(token),
                                      np.asarray(tokens[:, j]))
        logits, cache = gen.decode_step(params, token,
                                        jnp.asarray(8 + j, jnp.int32), cache,
                                        cfg)
    dims = LatentDims(heads=2, q_rank=8, kv_rank=8, nope=8, rope=8, v=8)
    beside = dataclasses.replace(
        cfg, layer_types=("linear", "latent"), latent=dims)
    with pytest.raises(NotImplementedError,
                       match="linear and parallel layers beside softmax"):
        generate(params, prompt, beside, max_new_tokens=2)


def test_a_layer_pattern_is_checked_where_it_is_configured():
    with pytest.raises(ValueError, match="multiple of the period"):
        program_config(n_layers=6)
    with pytest.raises(ValueError,
                       match="'full', 'linear', 'parallel', 'latent' or"):
        program_config(layer_types=("full", "banded"))
    with pytest.raises(ValueError, match="window layers need their widths"):
        program_config(layer_types=("full", "window"))
    with pytest.raises(ValueError, match="linear_key_heads"):
        program_config(linear_key_heads=0)


def test_load_balance_loss_is_one_when_uniform():
    probs = jnp.full((32, 8), 1 / 8)
    top_e = jnp.stack([jnp.arange(32) % 8, (jnp.arange(32) + 1) % 8], 1)
    assert float(moe.load_balance_loss(probs, top_e)) == pytest.approx(1.0)
    onto_one = jnp.zeros((32, 2), jnp.int32)
    peaked = jax.nn.one_hot(jnp.zeros(32, jnp.int32), 8)
    assert float(moe.load_balance_loss(peaked, onto_one)) == \
        pytest.approx(8.0)

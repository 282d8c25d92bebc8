"""JoyAI-LLM-Flash's training step (dense latent attention under a gradient,
sigmoid-routed experts whose correction bias the step's load moves, the
multi-token-prediction module and its second loss: models/transformer.py,
models/latent.py, models/moe.py, train/jax_step.py) against the plain
reference of the ``joyai`` family, at a small size on the CPU, in float32 so
that the comparison is of the mathematics."""

import dataclasses
import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.apps import lm, train_joyai as app
from benchmark.reference import joyai as reference
from ray_tpu.models import (TransformerConfig, generate, transformer_apply,
                            transformer_init)
from ray_tpu.models import latent, moe, transformer
from ray_tpu.models.transformer import (transformer_logical_axes,
                                        transformer_loss_and_stats,
                                        transformer_num_params,
                                        transformer_partition_params)
from ray_tpu.ops import flash
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.train import make_lm_train_step
from test_qwen3_next import bare_checkpoint, equations  # noqa: F401 - a fixture

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(CHECKOUT, "benchmark", "configs",
                       "joyai-llm-flash-l5-e16-mtp1.json")) as f:
    PUBLISHED = json.load(f)
# the rehearsal's toy widths under the published keys, in float32
TOY = dict(lm.effective_config(PUBLISHED, True), param_dtype="float32",
           torch_dtype="float32")
SEQ = 24


def program_config(config=TOY, seq=SEQ, **overrides) -> TransformerConfig:
    cfg = app.transformer_config(app.model_kwargs(config, seq, "reference"),
                                 remat=False)
    return dataclasses.replace(cfg, dtype=jnp.float32, **overrides)


def seeded(cfg, seed=0):
    """Seeded parameters with every leaf moved off its start: the norms'
    scales and the routers' biases too, so that no term drops out of a
    comparison."""
    params = transformer_init(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def tokens_of(seed=5, rows=2, seq=SEQ, vocab=TOY["vocab_size"]):
    return np.random.default_rng(seed).integers(0, vocab, (rows, seq),
                                                dtype=np.int32)


def reference_losses_and_grads(params, tokens, config=TOY):
    """``jax.grad`` of the reference's differentiable form -> ((loss, main,
    module), {name: gradient})."""
    w = app.reference_weights(params, config)
    layers = [w.layer(i) for i in range(w.n_layers)]

    def total(layers, embed, final_norm, lm_head, mtp):
        return reference.losses_of_arrays(layers, embed, final_norm, lm_head,
                                          mtp, jnp.asarray(tokens), config)

    (loss, (main, module)), g = jax.value_and_grad(
        total, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            layers, w.embed, w.final_norm, w.lm_head, w.mtp)
    grads = reference.Weights(embed=g[1], layer=g[0].__getitem__,
                              n_layers=w.n_layers, final_norm=g[2],
                              lm_head=g[3], mtp=g[4])
    return (float(loss), float(main), float(module)), \
        app.named_leaves(grads)


def close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-30)
    assert np.abs(got - want).max() <= tol * scale, \
        (np.abs(got - want).max(), scale)


def test_both_losses_and_every_leafs_gradient_against_the_reference():
    cfg = program_config()
    params, tokens = seeded(cfg), tokens_of()
    (loss, stats), grads = jax.value_and_grad(
        lambda p: transformer_loss_and_stats(p, {"tokens": tokens}, cfg),
        has_aux=True)(params)
    (want, want_main, want_mtp), want_grads = \
        reference_losses_and_grads(params, tokens)
    assert float(stats["loss_main"]) == pytest.approx(want_main, rel=1e-5)
    assert float(stats["loss_mtp"]) == pytest.approx(want_mtp, rel=1e-5)
    assert float(loss) == pytest.approx(want, rel=1e-5)
    assert float(loss) == pytest.approx(
        float(stats["loss_main"]) + 0.3 * float(stats["loss_mtp"]), rel=1e-6)
    got = app.named_leaves(app.reference_weights(grads, TOY))
    assert set(got) == set(want_grads)
    assert sum("mtp." in name for name in got) >= 20
    for name, want_leaf in want_grads.items():
        if name.endswith("router_bias"):        # no gradient reaches it
            assert not np.asarray(got[name]).any()
            assert not np.asarray(want_leaf).any()
        else:
            close(got[name], want_leaf, 5e-4)
    assert float(stats["moe_rows_dropped"]) == 0


def test_the_whole_loop_of_the_reference_is_its_differentiable_form():
    """``loss_and_grads`` (a row at a time, a layer at a time, as the chip
    runs it) gives what ``jax.grad`` of ``losses_of_arrays`` gives."""
    cfg = program_config()
    params, tokens = seeded(cfg), tokens_of()
    losses, grads, counts = reference.loss_and_grads(
        app.reference_weights(params, TOY), tokens, TOY)
    (want, want_main, want_mtp), want_grads = \
        reference_losses_and_grads(params, tokens)
    assert losses["loss"] == pytest.approx(want, rel=1e-5)
    assert losses["loss_main"] == pytest.approx(want_main, rel=1e-5)
    assert losses["loss_mtp"] == pytest.approx(want_mtp, rel=1e-5)
    for name, leaf in app.named_leaves(grads).items():
        close(leaf, want_grads[name], 1e-4)
    assert set(counts) == {1, 2, "mtp"}
    assert int(counts[1].sum()) == 2 * SEQ * TOY["num_experts_per_tok"]
    assert int(counts["mtp"].sum()) == \
        2 * (SEQ - 1) * TOY["num_experts_per_tok"]


def test_the_modules_inputs_and_targets_are_shifted_as_the_equations_say():
    """Six tokens, by hand: position i of the module sees Emb(t_{i+1}) and
    h_i and is scored against t_{i+2}; positions 0 .. 3 count."""
    cfg = program_config(seq=6)
    params = seeded(cfg, 3)
    tokens = np.array([[5, 9, 2, 7, 1, 4]], np.int32)
    _, stats = transformer_loss_and_stats(params, {"tokens": tokens}, cfg)
    w = app.reference_weights(params, TOY)
    h, _ = reference.stack(w, jnp.asarray(tokens), TOY)
    eps = TOY["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        u = jnp.concatenate(
            [reference._rmsnorm(w.embed[tokens[:, [1, 2, 3, 4, 5]]],
                                w.mtp["enorm"], eps),
             reference._rmsnorm(h[:, [0, 1, 2, 3, 4]], w.mtp["hnorm"], eps)],
            -1) @ w.mtp["eh_proj"]
        v, _ = reference._layer(u, w.mtp["block"], TOY)
        logits = reference._rmsnorm(v, w.mtp["final_norm"], eps) @ w.lm_head
    logp = jax.nn.log_softmax(logits[0], -1)
    by_hand = -np.mean([logp[i, tokens[0, i + 2]] for i in range(4)])
    assert float(stats["loss_mtp"]) == pytest.approx(float(by_hand),
                                                     rel=1e-5)
    # the main head: position i against t_{i+1}, positions 0 .. 4
    main = jax.nn.log_softmax(reference.forward(w, jnp.asarray(tokens),
                                                TOY)[0], -1)
    assert float(stats["loss_main"]) == pytest.approx(float(
        -np.mean([main[i, tokens[0, i + 1]] for i in range(5)])), rel=1e-5)


def test_the_shared_embedding_and_head_get_both_losses_gradients():
    base = program_config()
    params, batch = seeded(base), {"tokens": tokens_of()}

    def grads(cfg, pick):
        return jax.grad(lambda p: transformer_loss_and_stats(
            p, batch, cfg)[1][pick] if pick else transformer_loss_and_stats(
                p, batch, cfg)[0])(params)

    whole = grads(base, None)
    main, module = grads(base, "loss_main"), grads(base, "loss_mtp")
    for name in ("embed", "lm_head"):
        assert np.abs(np.asarray(module[name])).max() > 0
        close(whole[name], main[name] + 0.3 * module[name], 1e-5)
    # the module's own weights move by the module's loss alone
    assert not np.asarray(main["mtp"]["eh_proj"]).any()
    # and with weight 0 the module trains nothing
    off = grads(dataclasses.replace(base, mtp_loss_weight=0.0), None)
    assert not np.asarray(off["mtp"]["eh_proj"]).any()
    close(off["embed"], main["embed"], 1e-6)


def one_step(cfg, params_seed=0):
    mesh = build_mesh(MeshSpec(dp=1))
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh)
    state = app.seeded(init_fn, jax.random.PRNGKey(params_seed))
    before = jax.tree.map(np.asarray, state.params)
    state, metrics = step_fn(state, place({"tokens": tokens_of()}))
    return before, state, metrics


def test_the_bias_moves_by_the_load_and_by_nothing_else():
    cfg = program_config()
    before, state, metrics = one_step(cfg)
    tokens = tokens_of()
    _, _, counts = reference.loss_and_grads(
        app.reference_weights(jax.tree.map(jnp.asarray, before), TOY),
        tokens, TOY)
    gamma = cfg.router_bias_update_rate
    was = app.router_biases(before, TOY)
    now = app.router_biases(state.params, TOY)
    assert set(now) == {"layer1", "layer2", "mtp"}
    for name, key in (("layer1", 1), ("layer2", 2), ("mtp", "mtp")):
        c = np.asarray(counts[key], np.float64)
        want = gamma * np.sign(c.mean() - c)
        np.testing.assert_allclose(now[name] - was[name], want, atol=1e-8)
        assert (want != 0).sum() >= 8           # up and down both happen
        assert {-1.0, 1.0} <= set(np.sign(want))
    # AdamW's moments of the bias are untouched, and its decay did not act
    mu = next(s.mu for s in state.opt_state if hasattr(s, "mu"))
    nu = next(s.nu for s in state.opt_state if hasattr(s, "nu"))
    for tree in (mu, nu):
        for bias in app.router_biases(tree, TOY).values():
            assert not bias.any()
    checks = app.bias_checks(
        was, now, {k: np.asarray(reference.bias_delta(c, TOY), np.float64)
                   for k, c in (("layer1", counts[1]), ("layer2", counts[2]),
                                ("mtp", counts["mtp"]))}, gamma)
    assert checks["router_bias_off"] == 0
    assert checks["router_bias_step_off"] < 1e-5
    # a decayed bias is told from a step of exactly gamma
    decayed = {k: v - 3e-4 * 0.01 * was[k] * 20 for k, v in now.items()}
    assert app.bias_checks(was, decayed, {
        k: now[k] - was[k] for k in now}, gamma)["router_bias_step_off"] \
        > 1e-5
    assert float(metrics["router_bias_abs_mean"]) == pytest.approx(
        np.mean([np.abs(b).mean() for b in now.values()]), rel=1e-5)
    assert float(metrics["moe_count_max_over_mean"]) == pytest.approx(max(
        np.asarray(c).max() / np.asarray(c).mean()
        for c in counts.values()), rel=1e-5)
    assert "moe_counts" not in metrics
    assert {"loss_main", "loss_mtp"} <= set(metrics)


def test_a_softmax_routed_step_is_the_step_it_was():
    """No bias, no module: the step is ``value_and_grad`` + AdamW and
    nothing else (no count of the published experts' pairs), and its
    metrics are the ones it had."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        num_experts=8, experts_held=4, expert_top_k=2, norm_topk_prob=True,
        expert_ff=16, shared_expert_ff=16, max_seq=16, attn_impl="reference")
    mesh = build_mesh(MeshSpec(dp=1))
    init_fn, step_fn, place = make_lm_train_step(cfg, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    batch = place({"tokens": tokens_of(seq=16, vocab=64)})
    params = jax.tree.map(jnp.array, state.params)
    tx = optax.adamw(3e-4, weight_decay=0.01)

    @jax.jit
    def by_hand(params, opt_state, batch):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: transformer_loss_and_stats(p, batch, cfg, mesh=mesh),
            has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), loss, stats

    want, want_loss, want_stats = by_hand(params, tx.init(params), batch)
    state, metrics = step_fn(state, batch)
    assert float(metrics["loss"]) == pytest.approx(float(want_loss),
                                                   rel=1e-6)
    for got_leaf, want_leaf in zip(jax.tree.leaves(state.params),
                                   jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(got_leaf),
                                   np.asarray(want_leaf), atol=1e-6)
    assert set(metrics) == {"loss", "grad_norm", "step", "moe_rows_here",
                            "moe_rows_dropped", "moe_rows_walked",
                            "moe_load_max", "moe_load_mean"}
    assert set(want_stats) == set(metrics) - {"loss", "grad_norm", "step"}
    # a sigmoid router of the same sizes counts and reports
    sigmoid = dataclasses.replace(cfg, router_scoring="sigmoid")
    init_fn, step_fn, _ = make_lm_train_step(sigmoid, mesh)
    _, with_bias = step_fn(init_fn(jax.random.PRNGKey(0)), batch)
    assert set(with_bias) - set(metrics) == {"moe_count_max_over_mean",
                                             "router_bias_abs_mean"}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Every share's routed part, and the shared expert once, are the
    reference's layer with every expert held."""
    cfg = program_config()
    shares = cfg.num_experts // cfg.held
    whole = dataclasses.replace(cfg, experts_held=None, first_expert=0)
    p = jax.tree.map(lambda a: a[0], seeded(whole)["layers"][0]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, cfg.d_model))
    routed_only = {k: v for k, v in p.items() if k != "shared"}
    total = 0
    for j in range(shares):
        share = dataclasses.replace(cfg, first_expert=j * cfg.held)
        held = slice(j * cfg.held, (j + 1) * cfg.held)
        y, stats = moe.moe_apply(share, dict(
            routed_only, **{k: p[k][held] for k in ("w1", "w3", "w2")}), h)
        total = total + y
        assert int(stats["counts"].sum()) == 2 * SEQ * cfg.expert_top_k
    with_shared, _ = moe.moe_apply(whole, p, h)
    without, _ = moe.moe_apply(whole, routed_only, h)
    total = total + (with_shared - without)
    uncut = dict(TOY, n_routed_experts=cfg.num_experts, first_expert=0)
    w = {**{k: p[k] for k in ("router", "router_bias", "w1", "w3", "w2")},
         **{"shared_" + k: v for k, v in p["shared"].items()}}
    with jax.default_matmul_precision("highest"):
        want, counts = reference._experts(h, w, uncut)
    close(total, want, 2e-5)
    np.testing.assert_array_equal(np.asarray(stats["counts"]),
                                  np.asarray(counts))


def test_generate_serves_the_main_stack_of_a_tree_that_holds_the_module():
    cfg = program_config(seq=32)
    params = seeded(cfg, 2)
    bare = {k: v for k, v in params.items() if k != "mtp"}
    prompt = jnp.asarray(tokens_of(seq=8))
    got = generate(params, prompt, cfg, max_new_tokens=6)
    want = generate(bare, prompt, dataclasses.replace(cfg, mtp_layers=0),
                    max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(transformer_apply(params, prompt, cfg)),
        np.asarray(transformer_apply(
            bare, prompt, dataclasses.replace(cfg, mtp_layers=0))))


def test_the_trees_that_mirror_the_parameters_know_the_module():
    cfg = program_config()
    shapes = jax.eval_shape(lambda: transformer_init(jax.random.PRNGKey(0),
                                                     cfg))
    axes = transformer_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    named = jax.tree.map(lambda a, ax: len(ax) == a.ndim, shapes, axes,
                         is_leaf=lambda x: is_axes(x))
    assert all(jax.tree.leaves(named))
    assert axes["mtp"]["eh_proj"] == (None, "embed")
    assert axes["mtp"]["block"]["moe"]["w1"] == ("expert", "embed",
                                                 "expert_mlp")
    bare = dataclasses.replace(cfg, mtp_layers=0)
    d = cfg.d_model
    block = transformer_num_params(dataclasses.replace(
        bare, n_layers=cfg.n_layers + 1)) - transformer_num_params(bare)
    assert transformer_num_params(cfg) - transformer_num_params(bare) == \
        block + 2 * d * d + 3 * d
    # the published cut, recounted by the program
    published = app.transformer_config(
        app.model_kwargs(PUBLISHED, 8192, "flash"), remat=True)
    assert transformer_num_params(published) == 680_441_088
    assert (published.held, published.num_experts, published.mtp_layers,
            published.latent.nope + published.latent.rope) == \
        (16, 256, 1, 192)


def test_the_module_is_refused_in_words_where_it_cannot_run():
    with pytest.raises(ValueError, match="pipeline's schedule has no such"):
        TransformerConfig(n_layers=4, pp_stages=2, mtp_layers=1)
    with pytest.raises(ValueError, match="0 or 1"):
        TransformerConfig(mtp_layers=2)
    with pytest.raises(ValueError, match="looped stack has no loss"):
        TransformerConfig(mtp_layers=1, loop_steps=2)
    cfg = TransformerConfig(n_layers=4, pp_stages=2)
    parts = transformer_partition_params(
        transformer_init(jax.random.PRNGKey(0), cfg), cfg, 1)
    assert "mtp" not in parts


def test_a_dense_model_takes_the_module_too():
    """A llama block with a module: the second loss trains it, the step's
    metrics carry both losses and no expert counter."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                            d_ff=64, max_seq=16, attn_impl="reference",
                            mtp_layers=1, dtype=jnp.float32)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    assert "mlp" in params["mtp"]["block"] and "attn" in \
        params["mtp"]["block"]
    (loss, stats), grads = jax.value_and_grad(
        lambda p: transformer_loss_and_stats(
            p, {"tokens": tokens_of(seq=16, vocab=64)}, cfg),
        has_aux=True)(params)
    assert set(stats) == {"loss_main", "loss_mtp"}
    assert np.abs(np.asarray(grads["mtp"]["eh_proj"])).max() > 0


# ---------------------------------------------------------------------------
# dense latent attention that can be differentiated
# ---------------------------------------------------------------------------

def latent_layer(cfg, seed=1):
    return jax.tree.map(lambda a: a[0], seeded(cfg, seed)["layers"][0])


def mix(cfg, layer, h, own: bool):
    positions = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])
    attend = (lambda new: (new, None, None)) if own else \
        (lambda new: (new, positions, None))
    return latent.latent_mix(cfg, layer, h, positions, attend)[0]


def test_over_its_own_keys_a_latent_layer_is_the_same_and_keeps_no_scores(
        monkeypatch):
    """The training forward (key positions None) gives what the cached form
    gives, value and gradient, in query blocks that the backward makes
    again: no [heads, block, keys] array is a residual."""
    monkeypatch.setattr(latent, "DENSE_QUERY_BLOCK", 8)
    cfg = program_config(seq=32)
    layer = latent_layer(cfg)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
    loss = lambda own: lambda layer, h: jnp.sum(
        mix(cfg, layer, h, own) ** 2)
    got, want = (jax.value_and_grad(loss(own), argnums=(0, 1))(layer, h)
                 for own in (True, False))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        close(a, b, 2e-5)
    heads = cfg.latent.heads

    def residual_shapes(own):
        _, vjp = jax.vjp(lambda h: mix(cfg, layer, h, own), h)
        return [x.shape for x in jax.tree.leaves(vjp)
                if hasattr(x, "shape")]

    scores = lambda shapes: [s for s in shapes if len(s) >= 4
                             and s[-1] == 32 and heads in s]
    assert scores(residual_shapes(False))       # the cached form keeps them
    assert not scores(residual_shapes(True))


@pytest.fixture
def flash_interpreted(monkeypatch):
    """flash as on a v5e, its three kernels through the interpreter, a
    sequence over its own keys filled up to 128."""
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    monkeypatch.setattr(latent, "FLASH_MULTIPLE", 128)
    monkeypatch.setattr(flash, "_flash_fwd",
                        partial(flash._flash_fwd, interpret=True))
    monkeypatch.setattr(flash, "_flash_bwd",
                        partial(flash._flash_bwd, interpret=True))


@pytest.mark.parametrize("s", [256, 255])
def test_flash_with_a_value_width_of_its_own_matches_attention(
        s, flash_interpreted):
    """q/k 192 wide, v 128: the kernels through the interpreter against
    plain attention, value and gradient; a length the blocks do not divide
    (a module's S - 1) is filled up and cut."""
    dims = dataclasses.replace(program_config().latent, heads=2, kv_rank=16,
                               nope=128, rope=64, v=128)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    wukv = 0.3 * jax.random.normal(ks[0], (16, 2, 256))
    q_nope = jax.random.normal(ks[1], (1, s, 2, 128))
    q_rope = jax.random.normal(ks[2], (1, s, 2, 64))
    keys = jax.random.normal(ks[3], (1, s, 16 + 64))
    pos = jnp.arange(s)[None]

    def out(own, q_nope, q_rope, keys):
        return jnp.sum(latent._expanded(dims, wukv, q_nope, q_rope, pos,
                                        keys, pos, 0, own=own) ** 2)

    got = jax.value_and_grad(partial(out, "flash"), argnums=(0, 1, 2))(
        q_nope, q_rope, keys)
    want = jax.value_and_grad(partial(out, ""), argnums=(0, 1, 2))(
        q_nope, q_rope, keys)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    for a, b in zip(got[1], want[1]):
        close(a, b, 2e-3)


@pytest.fixture
def one_v5e_chip(monkeypatch):
    """-> the sharding of one chip of a described v5e 2x2, which libtpu
    compiles for without a device; flash takes its TPU branch."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    return SingleDeviceSharding(topo.devices[0])


def mosaic_calls(compiled) -> list:
    return [ln for ln in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def test_the_cells_attention_compiles_for_v5e(one_v5e_chip):
    """Mosaic accepts the three kernels at the cell's shape: 2 rows x 32
    heads, S = 8192, q/k 192 wide, v 128 (and the module's 8191 positions
    filled up to 8192)."""
    dims = app.transformer_config(
        app.model_kwargs(PUBLISHED, 8192, "flash"), remat=True).latent

    def shapes(s):
        like = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                                   sharding=one_v5e_chip)
        return (like(512, 32, 256), like(2, s, 32, 128), like(2, s, 32, 64),
                like(2, s, 576))

    def loss(wukv, q_nope, q_rope, keys):
        pos = jnp.broadcast_to(jnp.arange(keys.shape[1]), keys.shape[:2])
        o = latent._expanded(dims, wukv, q_nope, q_rope, pos, keys, pos, 0,
                             own="flash")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    for s in (8192, 8191):
        calls = mosaic_calls(jax.jit(jax.grad(
            loss, argnums=(0, 1, 2, 3))).lower(*shapes(s)).compile())
        assert len(calls) == 3                  # forward, dkv, dq
        assert all("bf16[64,8192,192]" in ln and "bf16[64,8192,128]" in ln
                   for ln in calls)


# ---------------------------------------------------------------------------
# what flash's forward kernel hands its backward kernels outlives a remat'd
# layer, where a kept byte spares enough of a second run (ops/flash.py)
# ---------------------------------------------------------------------------

# the toy widths with the cell's heads: q/k 128 + 64 wide, v 128
KERNEL_TOY = {**TOY, "num_attention_heads": 2, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128}
# the bodies a step differentiates: the stack of leading dense layers, the
# scanned period and the module's block, one latent layer each
BODIES = 3


def step_gradient(cfg):
    return jax.value_and_grad(lambda params, tokens: (
        transformer_loss_and_stats(params, {"tokens": tokens}, cfg)[0]))


def shapes_of(cfg, rows, seq):
    return (jax.eval_shape(partial(transformer_init, cfg=cfg),
                           jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct((rows, seq), jnp.int32))


@pytest.mark.parametrize("remat,policy,forwards", [
    (True, "kept", 1), (False, "kept", 1), (True, "bare", 2)])
def test_the_flash_forward_kernel_runs_once_a_layer(
        request, flash_interpreted, remat, policy, forwards):
    """At the cell's S = 8,192 and head widths (traced, not run) the whole
    loss's gradient, dense layer, scanned layers and module, holds one
    ``rt_flash_fwd`` a latent layer under whole-layer remat, as without
    remat; under a bare ``jax.checkpoint`` (the parent's) two. The backward
    kernels are there once either way."""
    if policy == "bare":
        request.getfixturevalue("bare_checkpoint")
    cfg = dataclasses.replace(
        program_config(KERNEL_TOY, seq=8192, attn_impl="flash", remat=remat),
        dtype=jnp.bfloat16)
    assert (cfg.first_dense_layers, cfg.mtp_layers) == (1, 1)
    found = equations(jax.make_jaxpr(step_gradient(cfg))(
        *shapes_of(cfg, 2, 8192)).jaxpr)
    assert found["rt_flash_fwd"] == forwards * BODIES
    assert found["rt_flash_dkv"] == found["rt_flash_dq"] == BODIES
    # out and the log-sum-exp of each forward that is traced
    assert found[flash.KEPT] >= 2 * BODIES


def test_what_flash_keeps_is_what_the_second_run_would_have_made(
        request, monkeypatch, flash_interpreted):
    """Loss and every gradient leaf of the whole step's loss, bit for bit,
    between the policy and a bare ``jax.checkpoint`` of the same bodies (at
    a size the interpreter runs, which the rule is told to keep)."""
    monkeypatch.setattr(flash, "KEEP_FROM", 0)
    cfg = program_config(KERNEL_TOY, seq=256, attn_impl="flash", remat=True)
    params, tokens = seeded(cfg), tokens_of(seq=256)
    forwards = lambda: equations(jax.make_jaxpr(step_gradient(cfg))(
        params, tokens).jaxpr)["rt_flash_fwd"]
    assert forwards() == BODIES
    kept = jax.jit(step_gradient(cfg))(params, tokens)
    request.getfixturevalue("bare_checkpoint")
    assert forwards() == 2 * BODIES
    bare = jax.jit(step_gradient(cfg))(params, tokens)
    got = jax.tree_util.tree_leaves_with_path(kept)
    want = jax.tree.leaves(bare)
    assert len(got) == len(want) > 40
    moved = 0
    for (path, leaf), wanted in zip(got, want):
        moved += float(jnp.abs(wanted).max()) > 0
        np.testing.assert_array_equal(leaf, wanted, err_msg=str(path))
    assert moved >= len(want) - 3         # the routers' biases get none


@pytest.mark.parametrize("s,d,d_v,spared,keeps", [
    (8192, 192, 128, 5041, True),         # this cell's latent layers
    (8192, 256, 256, 4064, True),         # Qwen3-Next's gated attention
    (2048, 128, 128, 1008, False)])       # both llama cells
def test_who_keeps_is_read_from_the_kernels_shapes(
        flash_interpreted, s, d, d_v, spared, keeps):
    """The forward rule names ``out`` and the log-sum-exp where the
    multiply-adds a second run would execute for each byte kept reach
    ``KEEP_FROM``, the log-sum-exp as a column, and nowhere else: below it
    the residuals are the kernel's outputs as they are."""
    assert 1100 < flash.KEEP_FROM < 4000
    pairs = s * (s + 1) // 2
    assert pairs * (d + d_v) // (s * (2 * d_v + 4)) == spared
    assert (spared >= flash.KEEP_FROM) == keeps
    like = lambda width: jax.ShapeDtypeStruct((4, s, width), jnp.bfloat16)
    rule = lambda q, k, v: flash._flash_bhsd_fwd(q, k, v, True, 1.0, 512,
                                                 1024)
    found = equations(jax.make_jaxpr(rule)(like(d), like(d), like(d_v)).jaxpr)
    assert found["name"] == found[flash.KEPT] == (2 if keeps else 0)
    assert found["rt_flash_fwd"] == 1
    out, (_, _, _, kept_out, lse, _) = jax.eval_shape(rule, like(d), like(d),
                                                      like(d_v))
    assert out.shape == kept_out.shape == (4, s, d_v)
    assert (lse.shape, lse.dtype) == ((4, s) if keeps else (4, s, 128),
                                      jnp.float32)


def llama_config(**widths):
    return TransformerConfig(vocab_size=128, max_seq=2048, attn_impl="flash",
                             **widths)


def test_under_the_line_a_llama_step_is_the_bare_checkpoints(
        request, flash_interpreted):
    """S = 2,048 at a head of 128 through the flash kernels: nothing
    carries a name, the policy finds nothing, and the step's gradient is
    the jaxpr a bare ``jax.checkpoint`` gives (but for the line that prints
    the policy's own address), the forward kernel twice a layer."""
    cfg = llama_config(d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
                       d_ff=512)
    assert cfg.remat and cfg.head_dim == 128

    def traced():
        return jax.make_jaxpr(step_gradient(cfg))(*shapes_of(cfg, 2, 2048))

    text = lambda jaxpr: re.sub(r"policy=.*", "policy=", str(jaxpr))
    kept = traced()
    found = equations(kept.jaxpr)
    assert found["name"] == 0
    assert (found["rt_flash_fwd"], found["rt_flash_dkv"],
            found["rt_flash_dq"]) == (2, 1, 1)
    assert "save_only_these_names" in str(kept)
    request.getfixturevalue("bare_checkpoint")
    bare = traced()
    assert "policy=None" in str(bare)
    assert text(kept) == text(bare)


@pytest.mark.parametrize("which,calls", [("latent", 3), ("llama", 4)])
def test_a_rematted_layers_gradient_compiled_for_v5e(one_v5e_chip, which,
                                                     calls):
    """One remat'd layer's gradient as the chip's compiler leaves it: the
    cell's dense latent layer (2 rows x 8,192, 32 heads of 192 / 128) holds
    three Mosaic calls, forward, dkv and dq, and no second forward; a llama
    layer at S = 2,048 with heads of 128 holds four, as on the parent."""
    from ray_tpu.parallel.sharding import DEFAULT_RULES
    if which == "latent":
        cfg = app.transformer_config(
            app.model_kwargs(PUBLISHED, 8192, "flash"), remat=True)
        stack, rows, seq = "dense_layers", 2, 8192
    else:
        cfg = llama_config(d_model=1024, n_layers=1, n_heads=8, n_kv_heads=2,
                           d_ff=2048)
        stack, rows, seq = "layers", 2, 2048
    like = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_v5e_chip)
    layer = jax.tree.map(lambda a: like(a.shape[1:], a.dtype),
                         shapes_of(cfg, rows, seq)[0][stack])
    body = transformer._layer_bodies(cfg, None, DEFAULT_RULES)[cfg.kinds[0]]

    def loss(layer, x):
        positions = jnp.broadcast_to(jnp.arange(seq), (rows, seq))
        return jnp.sum(body(layer, x, positions)[0].astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, like((rows, seq, cfg.d_model), jnp.bfloat16)).compile()
    assert len(mosaic_calls(compiled)) == calls

"""The sweep behind ``serve_falcon_h1.LIMITS``: on a TPU v5e, at the
configuration's published widths and the cell's 128 + 383 positions, over
``--seeds`` seeds from ``--first-seed``: the numbers ``correct`` compares,
by the app's own functions, of

- the sound program: ``prefill`` + ``decode_step``s of 2 rows through the
  cache (``Program``, ``reference_pass``, ``errors``, ``over_floors``), and
  the cell's own compiled call of 64 rows x (128 + 384)
  (``generate_and_keep``: its served tokens and what it left in its first
  and last row's cache, ``served_passes``, ``served_numbers``);
- the control: the plain reference over ``int8_weights`` with bfloat16
  activations, in the program's place in both (a second copy of 8.41 GB of
  weights does not fit beside the first, so int8 goes through the
  reference; its "served" tokens are its own argmax after the sound call's
  replies);
- the architecture's own faults, each planted in the PROGRAM:
  ``state_in_bfloat16`` (the cache's state in the compute dtype),
  ``skip_dropped`` (D = 0), ``dt_off_the_input`` (the state takes x, not dt
  x), ``norm_before_gate`` (the grouped norm, then the gate),
  ``groups_swapped`` (heads 0-15 read group 1's B and C),
  ``conv_bias_dropped``; and two in the program's ``fold_multipliers``
  itself, which then makes the tree the program runs while the reference
  keeps the published one: ``key_multiplier_left_out`` (``key`` never
  folded), ``ssm_multiplier_wrong`` (the B segment of the packed
  projection scaled by the C segment's entry). SERVED faults go through
  the 64-row call as well.

Each program is jitted once and run over every seed's weights. One JSON
file: ``{"seeds": [{"seed", "sound", "floor", "control", "faults": {name:
numbers}}]}``. ``--rehearse`` runs the toy sizes on the CPU to debug this
file; its numbers are never a result.

    python benchmark/testdata/sweep_falcon_h1.py --seeds 6 --faults-on 3 \\
        --out chiprun_out/falcon_h1_checks_sweep.json
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark import manifest as manifest_mod      # noqa: E402
from benchmark.apps import lm                       # noqa: E402
from benchmark.apps import serve_falcon_h1 as app   # noqa: E402

CELL = "falconh1-serve-closed64-p128-n384"
FAULTS = ("state_in_bfloat16", "skip_dropped", "dt_off_the_input",
          "norm_before_gate", "key_multiplier_left_out",
          "ssm_multiplier_wrong", "groups_swapped", "conv_bias_dropped")
FOLD_FAULTS = ("key_multiplier_left_out", "ssm_multiplier_wrong")
SERVED = ("state_in_bfloat16",) + FOLD_FAULTS


@contextlib.contextmanager
def planted(fault: str):
    """The program's modules with ``fault`` in them while a program is
    traced (``Program.run``'s first call) or its parameters are made
    (``seeded_params``)."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer
    # ``ray_tpu.models.generate`` the attribute is the function
    generate = importlib.import_module("ray_tpu.models.generate")
    undo = []

    def patch(module, name, new):
        undo.append((module, name, getattr(module, name)))
        setattr(module, name, new)

    def operands(change):
        whole = generate._state_space_operands
        patch(generate, "_state_space_operands",
              lambda *a: change(*whole(*a)))

    def fold(change):
        whole = transformer.fold_multipliers
        patch(transformer, "fold_multipliers",
              lambda params, cfg, **m: whole(params, cfg, **change(m)))

    if fault == "state_in_bfloat16":
        patch(generate, "cache_dtype", lambda cfg, name: cfg.dtype)
    elif fault == "key_multiplier_left_out":
        fold(lambda m: dict(m, key=1.0))
    elif fault == "ssm_multiplier_wrong":
        fold(lambda m: dict(m, ssm=m["ssm"][:2] + (m["ssm"][3],)
                            + m["ssm"][3:]))
    elif fault == "dt_off_the_input":
        # the state takes x: dt x with dt = 1, the decay as it was
        operands(lambda x, b, c, g, dt, d: (x, b, c, g, jnp.ones_like(dt), d))
    elif fault == "groups_swapped":
        operands(lambda x, b, c, g, dt, d: (x, b[..., ::-1, :],
                                            c[..., ::-1, :], g, dt, d))
    elif fault == "norm_before_gate":
        def mix(cfg, p, h, rule):
            dt, f32 = cfg.dtype, jnp.float32
            bsz, s, _ = h.shape
            hv, grp = cfg.linear_value_heads, cfg.linear_key_heads
            vd = hv * cfg.linear_value_dim
            conv = p["conv"].shape[0]
            proj = h @ p["in_proj"].astype(dt)
            y, kept = rule(proj[..., vd:vd + conv],
                           proj[..., vd + conv:].astype(f32), p)
            y = transformer._rmsnorm(
                y.reshape(bsz, s, grp, vd // grp),
                p["norm"].reshape(grp, vd // grp), cfg.norm_eps)
            y = y.reshape(bsz, s, vd) * jax.nn.silu(
                proj[..., :vd].astype(f32)).astype(dt)
            return y @ p["out"].astype(dt), kept
        patch(transformer, "_state_space_mix", mix)
    try:
        yield
    finally:
        for module, name, old in reversed(undo):
            setattr(module, name, old)


def faulty(cfg, params, config: dict, fault: str):
    """The parameter tree ``fault`` runs on: the seed's own arrays but the
    few the fault changes."""
    import jax.numpy as jnp
    (stack,) = params["layers"]
    ssm = stack["ssm"]

    def with_(**parts):
        return dict(params, layers=(dict(stack, **parts),))

    if fault == "skip_dropped":
        return with_(ssm=dict(ssm, D=jnp.zeros_like(ssm["D"])))
    if fault == "conv_bias_dropped":
        return with_(ssm=dict(ssm, conv_bias=jnp.zeros_like(
            ssm["conv_bias"])))
    return params


def numbers(got: dict, full: dict, floor: dict, config: dict,
            prompt: int) -> dict:
    errs = app.errors(got, full, config, prompt)
    return dict(app.over_floors(errs, floor, config),
                cache_dtypes=got.get("cache_dtypes"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=0)
    ap.add_argument("--faults-on", type=int, default=None,
                    help="plant the faults on the first N seeds (all)")
    ap.add_argument("--out", default="chiprun_out/"
                    "falcon_h1_checks_sweep.json")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    cell = manifest_mod.Manifest().cell(CELL)
    config = lm.effective_config(cell["config_data"], args.rehearse)
    traffic = lm.effective_traffic(cell["traffic_data"], args.rehearse)
    p, new = traffic["prompt_tokens"], traffic["new_tokens"]
    k = min(app.CHECK_DECODED, new - 1)
    cfg = app.transformer_config(app.model_kwargs(config, p + new, "auto"),
                                 remat=False)
    facts = lm.device_facts()
    lm.require_chips(facts, 1, args.rehearse)
    reference = lm.reference_module(config)
    eps = app.program_eps(cfg)
    dtype = jnp.dtype(config["torch_dtype"])
    rows = traffic["max_batch_size"]
    programs = {}       # fault or "sound" -> Program, jitted once
    calls = {}          # the same -> the cell's compiled call of ``rows``

    def checked(name: str, params, tokens, full, floor) -> dict:
        if name not in programs:
            programs[name] = app.Program(cfg, p, p + new)
        return numbers(programs[name].run(params, tokens), full, floor,
                       config, p)

    def served(name: str, params, prompts, weights) -> tuple:
        """One call of the cell's compiled program over ``prompts`` -> (its
        numbers, the passes of the reference they were read against)."""
        import numpy as np
        from functools import partial
        if name not in calls:
            calls[name] = jax.jit(partial(app.generate_and_keep, cfg=cfg,
                                          new=new)).lower(
                params, jnp.asarray(prompts)).compile()
        tokens, _, kept = calls[name](params, jnp.asarray(prompts))
        tokens = np.asarray(tokens)
        kept = app.cache_view(kept, p + new)
        at = list(app.kept_rows(rows))
        pairs = [(prompts[r].tolist(), tokens[r].tolist()) for r in at]
        passes = app.served_passes(weights, config, pairs, p, eps)
        return app.served_numbers(passes, [tokens[r].tolist() for r in at],
                                  kept, config, p), passes

    def one_seed(seed: int, with_faults: bool) -> dict:
        """The seed's weights live while this runs and no longer: 8.41 GB,
        of which the chip holds one copy (a fault's tree shares all but the
        arrays it changes; a fault of the fold makes its own once the sound
        tree is gone). The reference's are the published tree, drawn from
        the seed a layer at a time."""
        import numpy as np
        params = app.seeded_params(cfg, config, seed)
        tokens = jnp.asarray(app.check_tokens(seed, cfg.vocab_size, p + k))
        prompts = np.random.default_rng([lm.fold_seed(seed), 0x5E]).integers(
            0, cfg.vocab_size, (rows, p)).astype(np.int32)
        weights = app.reference_weights(cfg, config, seed)
        full = app.reference_pass(weights, config, tokens, p, eps)
        floor = app.errors(app.reference_pass(weights, config, tokens, p,
                                              eps, dtype), full, config, p)
        row = {"seed": seed, "floor": {
            name: [min(v), max(v)] for name, v in floor.items()}}
        row["sound"] = checked("sound", params, tokens, full, floor)
        row["control"] = numbers(app.reference_pass(
            reference.int8_weights(weights), config, tokens, p, eps, dtype),
            full, floor, config, p)
        numbers_served, passes = served("sound", params, prompts, weights)
        row["sound"].update(numbers_served)
        # the control after the sound call's replies: its cache there, and
        # the tokens it would have served, its own argmax a position
        logits, cache = reference.forward_and_cache(
            reference.int8_weights(weights), passes["fed"], config, eps=eps,
            dtype=dtype)
        row["control"].update(app.served_numbers(
            passes, np.asarray(jnp.argmax(logits[:, p - 1:-1], -1)).tolist(),
            {name: np.asarray(a) for name, a in cache.items()}, config, p))
        del logits, cache, passes
        row["faults"] = {}
        for fault in FAULTS if with_faults else ():
            if fault in FOLD_FAULTS:
                continue
            with planted(fault):            # traced on its first run
                tree = faulty(cfg, params, config, fault)
                row["faults"][fault] = checked(fault, tree, tokens, full,
                                               floor)
                if fault in SERVED:
                    row["faults"][fault].update(
                        served(fault, tree, prompts, weights)[0])
            del tree
            gc.collect()
        del params
        for fault in FOLD_FAULTS if with_faults else ():
            with planted(fault):    # the sound programs over a faulty fold
                tree = app.seeded_params(cfg, config, seed)
            row["faults"][fault] = dict(
                checked("sound", tree, tokens, full, floor),
                **served("sound", tree, prompts, weights)[0])
            del tree
            gc.collect()
        return row

    out = {"cell": CELL, "device": facts, "rehearsal": args.rehearse,
           "positions": [p, k], "rows": app.CHECK_ROWS, "served_rows": rows,
           "seeds": []}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.time()
        row = one_seed(seed, args.faults_on is None
                       or seed - args.first_seed < args.faults_on)
        gc.collect()
        row["seconds"] = time.time() - t0
        out["seeds"].append(row)
        print(json.dumps(row), flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:      # after every seed: a cut call
            json.dump(out, f, indent=1)     # keeps what it had
    jax.clear_caches()


if __name__ == "__main__":
    main()

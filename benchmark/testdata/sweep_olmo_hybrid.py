"""The sweep behind ``serve_olmo_hybrid.LIMITS``: on a TPU v5e, at the
configuration's published widths and the cell's 128 + 383 positions, 2 rows,
over ``--seeds`` seeds from ``--first-seed``: the numbers ``correct``
compares (the app's own functions: ``Program``, ``reference_pass``,
``errors``, ``over_floors``) of

- the sound program;
- the control: the plain reference over ``int8_weights`` with bfloat16
  activations, in the program's place (a second copy of 9.87 GB of weights
  does not fit beside the first, so int8 goes through the reference);
- the architecture's own faults, each planted in the PROGRAM (its modules
  patched or its configuration changed, the weights the seed's own):
  ``decay_dropped`` (g = 0), ``beta_not_doubled``, ``tail_not_carried``
  (a decode step convolves against zeros), ``state_in_bfloat16`` (the
  cache's state in the compute dtype), ``unit_length_skipped`` (q and k as
  the convolution left them), ``norms_moved_to_input`` (the two norms of a
  block on its sublayers' inputs).

Each program is jitted once and run over every seed's weights. One JSON
file: ``{"seeds": [{"seed", "sound", "floor", "control", "faults": {name:
numbers}}]}``. ``--rehearse`` runs the toy sizes on the CPU to debug this
file; its numbers are never a result.

    python benchmark/testdata/sweep_olmo_hybrid.py --seeds 12 \\
        --out chiprun_out/olmo_hybrid_checks_sweep.json
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
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
from benchmark.apps import serve_olmo_hybrid as app  # noqa: E402

CELL = "olmohybrid-serve-closed48-p128-n384"
FAULTS = ("decay_dropped", "beta_not_doubled", "tail_not_carried",
          "state_in_bfloat16", "unit_length_skipped",
          "norms_moved_to_input")


@contextlib.contextmanager
def planted(fault: str):
    """The program's modules with ``fault`` in them while a program is
    traced (``Program.run``'s first call)."""
    import importlib

    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops import gated_delta, gated_delta_pallas
    # ``ray_tpu.models.generate`` the attribute is the function
    generate = importlib.import_module("ray_tpu.models.generate")
    undo = []

    def patch(module, name, new):
        undo.append((module, name, getattr(module, name)))
        setattr(module, name, new)

    if fault == "decay_dropped":
        whole = generate._rule_operands

        def no_decay(*a):
            q, k, v, g, beta = whole(*a)
            return q, k, v, jnp.zeros_like(g), beta
        patch(generate, "_rule_operands", no_decay)
    elif fault == "tail_not_carried":
        whole = gated_delta.conv_step_at
        patch(gated_delta, "conv_step_at",
              lambda tails, slot, x, w: whole(lax.dynamic_update_slice(
                  tails, jnp.zeros((1,) + tails.shape[1:], tails.dtype),
                  (slot, 0, 0, 0)), slot, x, w))
    elif fault == "state_in_bfloat16":
        patch(generate, "cache_dtype", lambda cfg, name: cfg.dtype)
    elif fault == "unit_length_skipped":
        patch(gated_delta, "unit", lambda x: x.astype(jnp.float32))
        patch(gated_delta_pallas, "_unit",
              lambda x: (x.astype(jnp.float32),
                         jnp.ones((x.shape[0], 1), jnp.float32)))
    try:
        yield
    finally:
        for module, name, old in reversed(undo):
            setattr(module, name, old)


def faulty(cfg, params, fault: str):
    """(the configuration, the parameter tree) ``fault`` runs on: the
    seed's own arrays, no copy."""
    if fault == "beta_not_doubled":
        return dataclasses.replace(cfg, linear_beta_scale=1.0), params
    if fault == "norms_moved_to_input":
        def moved(stack):
            stack = dict(stack)
            stack["ln1"] = stack.pop("ln1_post")
            stack["ln2"] = stack.pop("ln2_post")
            return stack
        return dataclasses.replace(cfg, post_norm_only=False), dict(
            params, layers=tuple(moved(s) for s in params["layers"]))
    return cfg, params


def numbers(got: dict, full: dict, floor: dict, config: dict) -> dict:
    errs = app.errors(got, full, config)
    return dict(app.over_floors(errs, floor, config),
                cache_dtypes=got.get("cache_dtypes"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=0)
    ap.add_argument("--faults-on", type=int, default=None,
                    help="plant the faults on the first N seeds (all)")
    ap.add_argument("--out", default="chiprun_out/"
                    "olmo_hybrid_checks_sweep.json")
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
    eps = lm.program_rms_norm_eps(cfg)
    dtype = jnp.dtype(config["torch_dtype"])
    programs = {}           # fault or "sound" -> Program, jitted once

    def one_seed(seed: int, with_faults: bool) -> dict:
        """The seed's weights live while this runs and no longer: 9.87 GB,
        of which the chip holds one copy."""
        params = app.seeded_params(cfg, seed)
        tokens = jnp.asarray(app.check_tokens(seed, cfg.vocab_size, p + k))
        weights = app.reference_weights(params, config)
        full = app.reference_pass(weights, config, tokens, p, eps)
        floor = app.errors(app.reference_pass(weights, config, tokens, p,
                                              eps, dtype), full, config)
        row = {"seed": seed, "floor": {
            name: [min(v), max(v)] for name, v in floor.items()}}
        if "sound" not in programs:
            programs["sound"] = app.Program(cfg, p, p + new)
        row["sound"] = numbers(programs["sound"].run(params, tokens), full,
                               floor, config)
        row["control"] = numbers(app.reference_pass(
            reference.int8_weights(weights), config, tokens, p, eps, dtype),
            full, floor, config)
        row["faults"] = {}
        for fault in FAULTS if with_faults else ():
            fault_cfg, fault_params = faulty(cfg, params, fault)
            with planted(fault):            # traced on its first run
                if fault not in programs:
                    programs[fault] = app.Program(fault_cfg, p, p + new)
                row["faults"][fault] = numbers(
                    programs[fault].run(fault_params, tokens), full, floor,
                    config)
        return row

    out = {"cell": CELL, "device": facts, "rehearsal": args.rehearse,
           "positions": [p, k], "rows": app.CHECK_ROWS, "seeds": []}
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

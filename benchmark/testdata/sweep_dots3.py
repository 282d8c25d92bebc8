"""The sweep that the ``dots3`` family's limits are set from
(``benchmark/apps/serve_dots3.py`` LIMITS), on one TPU chip, one process:

    python benchmark/testdata/sweep_dots3.py --seeds 24 --fault-seeds 3 \
        --out chiprun_out/dots3_checks_sweep.json

At the published widths and the cell's sizes, for every seed, as a run's
``selfcheck`` does it, with the app's own functions:

- ``sound``: ``held_to_the_reference``: one call of the compiled
  ``generate`` on the check's prompts, every row's tokens and caches
  against the reference's float32 pass teacher-forced on them, row 0 also
  through the side program (prefill in chunks, CHECK_DECODED decode steps)
  and the router, each error over the floor (the reference with its
  activations rounded to bfloat16);
- ``control_int8``: the precision control, the reference over 8-bit weights
  with bfloat16 activations over row 0, read as if it were the program
  (8-bit weights through the program itself would be a second copy of 8 GB
  of weights);
- ``altered_token``: row 0's served tokens with the last changed to the
  next id, read as served tokens;

and on the first ``--fault-seeds`` seeds each planted fault of FAULTS, a
wrong program that ``correct`` has to refuse: the side program alone, a
compile each, over row 0's prompt and CHECK_DECODED of its tokens, its own
cache read where a run reads the served ``generate``'s (the same layer
code; a ``generate`` a fault would be a compile of minutes each).
A row is ``{"seed", "case", <the numbers judged>}``; the file is rewritten
after every seed, so a run that is cut keeps what it had.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.apps import lm, serve_dots3 as app      # noqa: E402


def strip(tree, name):
    return {k: strip(v, name) for k, v in tree.items() if k != name} \
        if isinstance(tree, dict) else tree


def mapped_layers(params, fn):
    """``fn`` over each stack of layers (the leading dense one too)."""
    out = dict(params, layers=tuple(fn(s) for s in params["layers"]))
    if "dense_layers" in params:
        out["dense_layers"] = fn(params["dense_layers"])
    return out


@contextlib.contextmanager
def patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def faults():
    """{name: (cfg -> cfg, params -> params, a context the program is
    traced in)}: each a wrong program."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from ray_tpu.models import latent, moe
    ref = lm.reference_module({"family": "dots3"})
    same = lambda x: x                                      # noqa: E731
    nothing = contextlib.nullcontext

    def recent(topk, qi, w, ki, qpos, kpos):
        at = qpos[:, :, None] - jnp.arange(topk)[None, None, :]
        return jnp.maximum(at, 0), at >= 0

    def scores_with(activation, weighted):
        def index_scores(qi, w, ki):
            dots = jnp.einsum("bsjd,btd->bsjt", qi, ki,
                              preferred_element_type=jnp.float32)
            return jnp.einsum("bsjt,bsj->bst", activation(dots),
                              w if weighted else jnp.full_like(w, w.mean()))
        return index_scores

    def bias_in_weights(cfg, m, x):
        logits = (x @ m["router"].astype(x.dtype)).astype(jnp.float32)
        biased = jax.nn.sigmoid(logits) + m["router_bias"].astype(jnp.float32)
        top_p, top_e = lax.top_k(biased, cfg.expert_top_k)
        return top_p / top_p.sum(-1, keepdims=True), top_e

    def zero_bias(params):
        return mapped_layers(params, lambda s: dict(s, moe=dict(
            s["moe"], router_bias=jnp.zeros_like(s["moe"]["router_bias"])))
            if "moe" in s else s)

    def gated_shared(params):
        def add(s):
            if "moe" not in s:
                return s
            sh = s["moe"]["shared"]
            gate = 0.02 * jax.random.normal(
                jax.random.PRNGKey(7), sh["w1"].shape[:2], sh["w1"].dtype)
            return dict(s, moe=dict(s["moe"], shared=dict(sh, gate=gate)))
        return mapped_layers(params, add)

    replace = dataclasses.replace
    return {
        "recent_2048_not_top": (
            same, same, lambda: patched(latent, "select", recent)),
        "index_relu_dropped": (
            same, same, lambda: patched(latent, "index_scores",
                                        scores_with(same, True))),
        "index_head_weights_dropped": (
            same, same, lambda: patched(latent, "index_scores",
                                        scores_with(jax.nn.relu, False))),
        "rescale_left_out": (
            lambda c: replace(c, lora_rescale=False), same, nothing),
        "gate_left_out": (
            same, lambda p: mapped_layers(p, lambda s: strip(s, "wg")),
            nothing),
        "window_one_short": (
            lambda c: replace(c, window=c.window - 1), same, nothing),
        "softmax_routing": (
            lambda c: replace(c, router_scoring="softmax"), same, nothing),
        "bias_in_the_weights": (
            same, same, lambda: patched(moe, "route", bias_in_weights)),
        "bias_ignored_in_selection": (same, zero_bias, nothing),
        "shared_expert_gated": (same, gated_shared, nothing),
    }


def numbers(got: dict, full: dict, floor: dict, routing: dict) -> dict:
    """What ``judged`` reads, of ``got`` (a side program's run with its
    cache view laid in, or a pass of the reference) against the float32
    pass cut to what ``got`` holds."""
    import numpy as np
    ref = lm.reference_module({"family": "dots3"})
    errs = app.errors(got, full)
    n = got["logits"].shape[1]
    out = dict(app.over_floors(errs, floor), **routing)
    out["selection_overlap"] = ref.selection_overlap(
        got["selected"][:, :, :n], got["selected_real"][:, :, :n],
        full["selected"][:, :, :n], full["selected_real"][:, :, :n])
    out["moe_rows_dropped"] = int(np.asarray(
        got.get("moe_rows", [0, 0]))[1])
    out["window_keys_off"] = int(got.get("window_keys_off", 0))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=24)
    ap.add_argument("--first-seed", type=int, default=0)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--only", default="", help="comma-separated fault names")
    ap.add_argument("--out", default="chiprun_out/dots3_checks_sweep.json")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, to debug this command on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmark import manifest as manifest_mod

    cell = manifest_mod.Manifest().cell("dots3-serve-closed2-p32k-n128")
    config = lm.effective_config(cell["config_data"], args.rehearse)
    traffic = lm.effective_traffic(cell["traffic_data"], args.rehearse)
    p, new, rows = traffic["prompt_tokens"], traffic["new_tokens"], \
        traffic["max_batch_size"]
    k = min(app.CHECK_DECODED, new - 1)
    cfg = app.transformer_config(app.model_kwargs(config, p + new, "auto"),
                                 remat=False)
    bf16 = jnp.dtype(config["torch_dtype"])
    planted = faults()
    if args.only:
        planted = {n: planted[n] for n in args.only.split(",")}
    out = {"made_by": "benchmark/testdata/sweep_dots3.py",
           "device": jax.devices()[0].device_kind,
           "sizes": {"prompt_tokens": p, "new_tokens": new, "rows": rows,
                     "decoded": k},
           "config": cell["config"], "rows": []}

    def as_got(run: dict, cfg=cfg) -> dict:
        return dict(app.cache_view(cfg, run["cache"], p + k),
                    **{n: run[n] for n in ("logits", "selected",
                                           "selected_real", "moe_rows",
                                           "window_keys_off")})

    def keep():
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=0)

    for n in range(args.seeds):
        seed, t0 = args.first_seed + n, time.time()
        params = app.seeded_params(cfg, seed)
        served = app.served_by(cfg, params, app.check_prompts(
            seed, cfg.vocab_size, p, rows), new)
        print(f"  served {time.time() - t0:.1f} s", flush=True)
        checks, kept = app.held_to_the_reference(
            cfg, params, config, served, app.Program(cfg, p, new), k)
        print(f"  held to the reference {time.time() - t0:.1f} s", flush=True)
        floor, uncut = checks["floor_errors"], kept["reference"]
        sound = dict(app.over_floors(checks["errors"], floor),
                     **{name: checks[name] for name in (
                         "selection_overlap", "routing_weights_off",
                         "routing_missed", "token_deficit_over_floor",
                         "tokens_checked", "window_keys_off",
                         "floor_rms_over_std")},
                     moe_rows_dropped=checks["moe_rows_dropped"]
                     + served["moe_rows_dropped"])
        out["rows"].append(dict(sound, seed=seed, case="sound"))
        fed = jnp.asarray(served["fed"][:1])
        eight_bits = lm.reference_module(config).int8_weights(
            app.reference_weights(params, config))
        control = app.reference_pass(cfg, params, config, fed, p, dtype=bf16,
                                     weights=eight_bits)
        out["rows"].append(dict(
            numbers(control, app.cut_to(cfg, uncut, p + new), floor,
                    app.routing_numbers(cfg, params, config, uncut,
                                        weights=eight_bits)),
            seed=seed, case="control_int8"))
        del control
        tokens = kept["tokens"]
        altered = tokens[:-1] + [(tokens[-1] + 1) % cfg.vocab_size]
        out["rows"].append(dict(
            app.served_deficit(altered, altered, kept["logits"],
                               kept["floor"]),
            seed=seed, case="altered_token"))
        if n < args.fault_seeds:
            short = app.cut_to(cfg, uncut, p + k)
            for name, (on_cfg, on_params, context) in planted.items():
                with context():
                    runner = app.Program(on_cfg(cfg), p, new)
                    wrong = on_params(params)
                    # held to the sound window: the fault's own is what is
                    # being judged
                    run = runner.run(wrong, fed[:, :p + k], k, window_cfg=cfg)
                    runner.unload()
                    routing = app.routing_numbers(runner.cfg, wrong, config,
                                                  uncut)
                out["rows"].append(dict(
                    numbers(as_got(run, runner.cfg), short, floor, routing),
                    seed=seed, case="fault:" + name))
                del wrong
                del run
                keep()
        keep()
        # the next seed's weights do not fit beside this one's
        del params, eight_bits, served, checks, kept, uncut
        print(f"seed {seed}: {time.time() - t0:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

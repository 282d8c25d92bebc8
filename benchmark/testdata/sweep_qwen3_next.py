"""Where the limits of the ``qwen3_next`` training cell come from: what its
``correct`` compares, over many seeds in one process on the cell's chip,
for the sound program and for faults planted in it. Writes
``chiprun_out/sweep/qwen3_next_checks_sweep.json``; the copy kept beside
this file is that file, and ``tests/benchmark/test_bench_qwen3_next.py``
holds the committed limits to it. No run of the benchmark imports this
module.

    chiprun -- python3 benchmark/testdata/sweep_qwen3_next.py --seeds 12 \
        --plant-seeds 4

Per seed, made as ``train_qwen3_next.train_loop`` makes it: parameters from
the seed, the plain reference on the first batch (its loss and its gradient
for every parameter, kept on the host), the state made again, the compiled
step twice on that batch, the first step's gradient read from the state it
returned. Then, on the first ``--plant-seeds`` seeds, the same with each
fault planted in the program (the reference stays whole), and the control:
the reference's own gradient over int8 weights (absmax per output channel)
against the whole reference's, the nearest precision under what the
configuration states. ``bf16_parameters`` runs on ``--bf16-seeds`` seeds,
each with its own reference over what the parameters round to.

Faults, each a patch of the program for the length of its runs:
``topk_not_renormalised`` (``norm_topk_prob`` off), ``decay_left_out`` (the
delta rule with ``g = 0``), ``shared_expert_left_out``,
``output_gate_left_out``, ``half_of_the_batch_left_out`` (the loss and its
gradient over the first half of the rows, the mean taken over those),
``bf16_parameters`` (``param_dtype`` bfloat16: state, update and step in
bfloat16). A state handed back unchanged needs no run: its moments are
still zero and its loss has not fallen, which reads 1 on both gradient gaps
and a fall of 0 (``tests/benchmark/test_bench_qwen3_next.py`` judges that
record).
"""
import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "qwen3next-train-s8192-ep16share"
FAULTS = ("topk_not_renormalised", "decay_left_out",
          "shared_expert_left_out", "output_gate_left_out",
          "half_of_the_batch_left_out", "bf16_parameters")


def load(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


@contextlib.contextmanager
def planted(fault: str):
    """The program with one fault in it."""
    import jax.numpy as jnp

    from ray_tpu.models import moe, transformer
    from ray_tpu.ops import gated_delta
    from ray_tpu.train import jax_step
    patches = []

    def patch(module, name, value):
        patches.append((module, name, getattr(module, name)))
        setattr(module, name, value)

    if fault == "decay_left_out":
        whole = gated_delta.gated_delta_rule
        patch(gated_delta, "gated_delta_rule",
              lambda q, k, v, g, beta: whole(q, k, v, jnp.zeros_like(g),
                                             beta))
    elif fault == "shared_expert_left_out":
        whole_moe = moe.moe_apply
        patch(moe, "moe_apply", lambda cfg, p, h: whole_moe(
            cfg, {k: v for k, v in p.items() if k != "shared"}, h))
    elif fault == "output_gate_left_out":
        patch(transformer, "_output_gate", lambda o, gate: o)
    elif fault == "half_of_the_batch_left_out":
        whole_loss = jax_step.transformer_loss_and_stats
        patch(jax_step, "transformer_loss_and_stats",
              lambda params, batch, cfg, **kw: whole_loss(
                  params, {"tokens": batch["tokens"][
                      :batch["tokens"].shape[0] // 2]}, cfg, **kw))
    try:
        yield
    finally:
        for module, name, value in reversed(patches):
            setattr(module, name, value)


def faulty_config(cfg, fault: str):
    import jax.numpy as jnp
    if fault == "topk_not_renormalised":
        return dataclasses.replace(cfg, norm_topk_prob=False)
    if fault == "bf16_parameters":
        return dataclasses.replace(cfg, param_dtype=jnp.dtype("bfloat16"))
    return cfg


def brief(row: dict) -> dict:
    return {k: v for k, v in row.items()
            if k not in ("grad_gaps", "control_int8")}


def int8_leaf(w):
    """A parameter array of the program rounded to int8 steps, absmax per
    output channel (a stack of vectors, [1, d], comes back as it was)."""
    import jax.numpy as jnp
    if w.ndim < 2:
        return w
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="the rehearsal's toy size on the CPU: to debug "
                         "this script, never a reading")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--plant-seeds", type=int, default=4)
    ap.add_argument("--bf16-seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3700000100)
    ap.add_argument("--out",
                    default="chiprun_out/sweep/qwen3_next_checks_sweep.json")
    args = ap.parse_args()
    sys.path.insert(0, REPO)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.apps import lm
    from benchmark.apps import train_qwen3_next as app
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_lm_train_step
    t0 = time.time()

    def say(*a):
        print(f"[{time.time() - t0:7.1f}s]", *a, flush=True)

    cell = next(w for w in load("BENCHMARK.json")["workloads"]
                if w["name"] == CELL)
    config = lm.effective_config(
        load(f"benchmark/configs/{cell['config']}.json"), args.tiny)
    traffic = lm.effective_traffic(
        load(f"benchmark/traffic/{cell['traffic']}.json"), args.tiny)
    facts = lm.device_facts()
    lm.require_chips(facts, 1, args.tiny)
    sound = app.transformer_config(
        app.model_kwargs(config, traffic["seq"],
                         "auto" if args.tiny else "flash"),
        remat=traffic["remat"])
    mesh = build_mesh(MeshSpec(dp=1))
    rows, seq = traffic["rows_per_chip"], traffic["seq"]
    seeds = [args.first_seed + i for i in range(args.seeds)]
    out = {"doc": __doc__.split("\n\n")[0], "pr": 37, "cell": CELL,
           "commit": os.environ.get("SWEEP_COMMIT", ""), "device": facts,
           "tiny": args.tiny, "seeds": [],
           "faults": {name: [] for name in FAULTS}}
    os.makedirs(os.path.dirname(os.path.join(REPO, args.out)), exist_ok=True)

    def keep():
        with open(os.path.join(REPO, args.out), "w") as f:
            json.dump(out, f, indent=1)

    def program(cfg):
        """-> run(seed, the reference's readings or None) -> (row,
        readings)."""
        init_fn, step_fn, place_batch = make_lm_train_step(cfg, mesh)
        compiled = {}

        def run(s, read=None, control=False):
            t = time.time()
            seed = lm.fold_seed(s)
            key = jax.random.PRNGKey(seed)
            first = {"tokens": np.random.default_rng(seed).integers(
                0, cfg.vocab_size, (rows, seq), dtype=np.int32)}
            row = {"seed": s}
            if read is None:
                params = init_fn(key).params
                read = app.reference_on(params, first["tokens"], config)
                row["reference_seconds"] = time.time() - t
                if control:
                    squeezed = app.reference_on(
                        jax.tree.map(int8_leaf, params), first["tokens"],
                        config)
                    row["control_int8"] = app.gradient_checks(
                        app.gradient_gaps(squeezed["grads"], read["grads"]))
                    row["control_int8"]["loss_gap"] = abs(
                        squeezed["loss"] - read["loss"])
                    del squeezed
                del params
            state = init_fn(key)
            batch = place_batch(first)
            if "step" not in compiled:
                compiled["step"] = step_fn.lower(state, batch).compile()
                say("compiled", lm.compiled_peak(compiled["step"]))
            got = []
            for i in range(2):
                state, metrics = compiled["step"](state, batch)
                got.append({k: float(metrics[k])
                            for k in ("loss",) + app.COUNTERS})
                if i == 0:
                    row.update(app.gradient_checks(app.gradient_gaps(
                        app.first_moment(state, config), read["grads"],
                        1 / (1 - app.ADAM_B1))))
            row.update(
                system_loss=got[0]["loss"], reference_loss=read["loss"],
                loss_gap=abs(got[0]["loss"] - read["loss"]),
                first_update_fall=got[0]["loss"] - got[1]["loss"],
                counters=got[0], seconds=time.time() - t,
                param_dtypes=sorted({str(x.dtype) for x in
                                     jax.tree.leaves(state.params)}))
            del state
            return row, read
        return run

    readings = {}
    run = program(sound)
    for i, s in enumerate(seeds):
        row, read = run(s, control=i < args.plant_seeds)
        if i < args.plant_seeds:
            readings[s] = read          # 2.5 GB of gradients a seed, host
        out["seeds"].append(row)
        keep()
        say(json.dumps(brief(row)))
    del run
    for fault in FAULTS:
        with planted(fault):
            run = program(faulty_config(sound, fault))
            own = fault == "bf16_parameters"
            for s in seeds[:args.bf16_seeds if own else args.plant_seeds]:
                # bfloat16 parameters are other numbers from the same
                # seed: their own reference, over what they round to
                row, _ = run(s, None if own else readings[s])
                out["faults"][fault].append(row)
                keep()
                say(fault, json.dumps(brief(row)))
            del run
    say("done")


if __name__ == "__main__":
    main()

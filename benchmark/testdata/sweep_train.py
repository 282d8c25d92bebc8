"""Where a training cell's limits come from: what its ``correct`` compares,
over many seeds in one process on the cell's chips. Reads
``benchmark/testdata/train_checks_sweep.json``, replaces the part it ran
(``cells.<cell>``, or ``faults.half_of_the_batch_left_out.<cell>`` with
``--half``) and writes the whole to
``chiprun_out/sweep/train_checks_sweep.json``; the copy kept beside this
file is that file, and ``tests/benchmark/test_bench_reference.py`` holds the
committed limits to it. No run of the benchmark imports this module.

    chiprun -- python3 benchmark/testdata/sweep_train.py \
        --cell mistral7b-train-1chip --seeds 64
    chiprun --chips 4 -- python3 benchmark/testdata/sweep_train.py \
        --cell internlm2-train-fsdp4 --seeds 16 [--half] [--add]

Per seed, made as ``train_lm.train_loop`` makes it: state from the seed, the
plain reference's loss on the first batch, the compiled step twice on that
batch; loss_gap = |system - reference| on the first step, first_update_fall
= the first loss less the second. ``--half`` plants "half of the batch left
out, the mean taken over the rest": the step's loss and update are taken
over the first half of the first batch's rows (each twice, the compiled
shape kept), the reference's loss over all of them.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
KEPT = os.path.join(HERE, "train_checks_sweep.json")


def load(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--tiny", action="store_true",
                    help="the rehearsal's toy size on the CPU: to debug "
                         "this script, never a reading")
    ap.add_argument("--seeds", type=int, default=64)
    ap.add_argument("--half", action="store_true",
                    help="the fault: half of the batch left out")
    ap.add_argument("--add", action="store_true",
                    help="keep the part's rows and run only the seeds it "
                         "does not hold yet")
    ap.add_argument("--out",
                    default="chiprun_out/sweep/train_checks_sweep.json")
    args = ap.parse_args()
    sys.path.insert(0, REPO)
    cell = next(w for w in load("BENCHMARK.json")["workloads"]
                if w["name"] == args.cell)
    chips = cell["chips"]
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={chips}"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.apps import lm
    from benchmark.testdata.sweep_serve import sweep_seeds
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_lm_train_step
    t0 = time.time()

    def say(*a):
        print(f"[{time.time() - t0:7.1f}s]", *a, flush=True)

    config = lm.effective_config(
        load(f"benchmark/configs/{cell['config']}.json"), args.tiny)
    traffic = lm.effective_traffic(
        load(f"benchmark/traffic/{cell['traffic']}.json"), args.tiny)
    facts = lm.device_facts()
    lm.require_chips(facts, chips, args.tiny)
    cfg = lm.transformer_config(
        lm.model_kwargs(config, traffic["seq"],
                        "auto" if args.tiny else "flash"),
        remat=traffic["remat"])
    mesh = build_mesh(MeshSpec(**{traffic["mesh_axis"]: chips}))
    init_fn, step_fn, place_batch = make_lm_train_step(cfg, mesh)
    rows, seq = traffic["rows_per_chip"] * chips, traffic["seq"]
    reference = lm.reference_module(config)
    with open(KEPT) as f:
        out = json.load(f)
    part = out["faults"]["half_of_the_batch_left_out"] if args.half \
        else out["cells"]
    mine = {"commit": os.environ.get("SWEEP_COMMIT", ""), "device": facts,
            "seeds": part[args.cell]["seeds"] if args.add else []}
    part[args.cell] = mine
    held = {row["seed"] for row in mine["seeds"]}
    os.makedirs(os.path.dirname(os.path.join(REPO, args.out)), exist_ok=True)
    compiled = None
    for s in (s for s in sweep_seeds(args.seeds) if s not in held):
        t = time.time()
        seed = lm.fold_seed(s)
        state = init_fn(jax.random.PRNGKey(seed))
        first = {"tokens": np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (rows, seq), dtype=np.int32)}
        ref_loss = reference.loss(
            lm.reference_weights(state.params, config),
            jnp.asarray(first["tokens"]), config,
            rows_per_pass=traffic["reference_rows_per_pass"])
        if args.half:
            kept_rows = first["tokens"][:rows // 2]
            first = {"tokens": np.concatenate([kept_rows, kept_rows])}
        batch = place_batch(first)
        if compiled is None:
            compiled = step_fn.lower(state, batch).compile()
            say("compiled", lm.compiled_peak(compiled))
        losses = []
        for _ in range(2):
            state, metrics = compiled(state, batch)
            losses.append(float(metrics["loss"]))
        del state
        row = {"seed": s, "system_loss": losses[0],
               "reference_loss": ref_loss,
               "loss_gap": abs(losses[0] - ref_loss),
               "first_update_fall": losses[0] - losses[1],
               "seconds": time.time() - t}
        mine["seeds"].append(row)
        with open(os.path.join(REPO, args.out), "w") as f:
            json.dump(out, f, indent=1)
        say(json.dumps(row))
    say("done")


if __name__ == "__main__":
    main()

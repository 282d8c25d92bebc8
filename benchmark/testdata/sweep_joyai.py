"""Where the limits of the ``joyai`` training cell come from: what its
``correct`` compares, over many seeds in one process on the cell's chip,
for the sound program and for faults planted in it. Writes
``chiprun_out/sweep/joyai_checks_sweep.json``; the copy kept beside this
file is that file, and ``tests/benchmark/test_bench_joyai.py`` holds the
committed limits to it. No run of the benchmark imports this module.

    chiprun --timeout 3600 -- python3 benchmark/testdata/sweep_joyai.py \
        --seeds 12 --plant-seeds 3

Per seed, made as ``train_joyai.train_loop`` makes it: parameters from the
seed (the routers' biases drawn), the plain reference on the first batch
(both losses, the gradient for every parameter and each router's counts,
kept on the host), the state made again, the compiled step twice on that
batch, the first step's gradient read from the state it returned and each
bias's move from the parameters before and after. Then, on the first
``--plant-seeds`` seeds, the same with each fault planted in the program
(the reference stays whole), and the control: the reference's own readings
over int8 weights (absmax per output channel) against the whole
reference's, the nearest precision under what the configuration states.

Faults, each a patch of the program for the length of its runs:
``module_loss_left_out`` (``mtp_loss_weight`` 0), ``targets_not_shifted``
(the module scored against t_{i+1}), ``embedding_not_shifted`` (Emb(t_i) for
Emb(t_{i+1})), ``hnorm_left_out``, ``h_after_the_final_norm``,
``bias_inside_the_weights`` (the optimizer's own update of the bias, its
weight decay, added to the balancer's), ``bias_update_left_out``,
``scaling_factor_one`` (``routed_scaling_factor`` 1), ``k_rope_not_rotated``.
A state handed back unchanged needs no run: its moments are still zero, its
biases have not moved and its loss has not fallen
(``tests/benchmark/test_bench_joyai.py`` judges that record).
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
CELL = "joyai-train-s8192-ep16share"
FAULTS = ("module_loss_left_out", "targets_not_shifted",
          "embedding_not_shifted", "hnorm_left_out",
          "h_after_the_final_norm", "bias_inside_the_weights",
          "bias_update_left_out", "scaling_factor_one",
          "k_rope_not_rotated")
MODULE_FAULTS = ("targets_not_shifted", "embedding_not_shifted",
                 "hnorm_left_out", "h_after_the_final_norm")


def load(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def faulty_module_loss(fault: str):
    """``transformer._mtp_loss`` with one of the module's faults: the same
    lines, and one of them wrong."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as t

    def mtp_loss(params, h, tokens, cfg, mesh, rules, mask=None):
        m, dt = params["mtp"], cfg.dtype
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s - 1), (b, s - 1))
        if fault == "h_after_the_final_norm":
            h = t._norm(cfg, h, params["final_norm"])
        seen = tokens[:, :-1] if fault == "embedding_not_shifted" \
            else tokens[:, 1:]
        e = t._norm(cfg, params["embed"].astype(dt)[seen], m["enorm"])
        g = h[:, :-1] if fault == "hnorm_left_out" \
            else t._norm(cfg, h[:, :-1], m["hnorm"])
        u = jnp.concatenate([e, g], -1) @ m["eh_proj"].astype(dt)
        body = t._layer_bodies(cfg, mesh, rules)[cfg.kinds[-1]]
        v, _, stats = body(m["block"], u, positions)
        # logits[:, :-1] against targets[:, 1:]: t_{i+2} when sound
        targets = tokens[:, :-1] if fault == "targets_not_shifted" \
            else tokens[:, 1:]

        @jax.checkpoint
        def head_loss(head_params, v):
            return t._next_token_loss(t._head(head_params, v, cfg), targets)

        head_params = {k: params[k] for k in ("embed", "lm_head")
                       if k in params}
        head_params["final_norm"] = m["final_norm"]
        return head_loss(head_params, v), stats

    return mtp_loss


@contextlib.contextmanager
def planted(fault: str):
    """The program with one fault in it."""
    import jax

    from ray_tpu.models import latent, transformer
    from ray_tpu.train import jax_step
    patches = []

    def patch(module, name, value):
        patches.append((module, name, getattr(module, name)))
        setattr(module, name, value)

    if fault in MODULE_FAULTS:
        patch(transformer, "_mtp_loss", faulty_module_loss(fault))
    elif fault == "bias_update_left_out":
        patch(jax_step, "_balance_routers",
              lambda updates, counts, cfg: updates)
    elif fault == "bias_inside_the_weights":
        balance = jax_step._balance_routers
        patch(jax_step, "_balance_routers",
              lambda updates, counts, cfg: jax.tree.map(
                  lambda mine, theirs: theirs if mine is theirs
                  else mine + theirs, balance(updates, counts, cfg),
                  updates))
    elif fault == "k_rope_not_rotated":
        rope = latent._rope
        patch(latent, "_rope", lambda x, positions, theta, rotary_dim=None:
              x if x.shape[2] == 1 else rope(x, positions, theta,
                                             rotary_dim))
    try:
        yield
    finally:
        for module, name, value in reversed(patches):
            setattr(module, name, value)


def faulty_config(cfg, fault: str):
    if fault == "module_loss_left_out":
        return dataclasses.replace(cfg, mtp_loss_weight=0.0)
    if fault == "scaling_factor_one":
        return dataclasses.replace(cfg, routed_scaling_factor=1.0)
    return cfg


def brief(row: dict) -> dict:
    return {k: v for k, v in row.items()
            if k not in ("grad_gaps", "control_int8",
                         "router_bias_off_by_router")}


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
    ap.add_argument("--plant-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4700000100)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out",
                    default="chiprun_out/sweep/joyai_checks_sweep.json")
    args = ap.parse_args()
    sys.path.insert(0, REPO)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))

    import jax
    import numpy as np

    from benchmark.apps import lm
    from benchmark.apps import train_joyai as app
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
    gamma = float(config["router_bias_update_rate"])
    mesh = build_mesh(MeshSpec(dp=1))
    rows, seq = traffic["rows_per_chip"], traffic["seq"]
    seeds = [args.first_seed + i for i in range(args.seeds)]
    faults = [f for f in args.faults.split(",") if f]
    out = {"doc": __doc__.split("\n\n")[0], "pr": 47, "cell": CELL,
           "commit": os.environ.get("SWEEP_COMMIT", ""), "device": facts,
           "tiny": args.tiny, "seeds": [],
           "faults": {name: [] for name in faults}}
    os.makedirs(os.path.dirname(os.path.join(REPO, args.out)), exist_ok=True)

    def keep():
        with open(os.path.join(REPO, args.out), "w") as f:
            json.dump(out, f, indent=1)

    def losses_of(read: dict) -> dict:
        return {k: read[k] for k in ("loss", "loss_main", "loss_mtp")}

    def program(cfg):
        """-> run(seed, the reference's readings or None) -> (row,
        readings)."""
        init_fn, step_fn, place_batch = make_lm_train_step(
            cfg, mesh, learning_rate=traffic["learning_rate"])
        compiled = {}

        def run(s, read=None, control=False):
            t = time.time()
            seed = lm.fold_seed(s)
            key = jax.random.PRNGKey(seed)
            first = {"tokens": np.random.default_rng(seed).integers(
                0, cfg.vocab_size, (rows, seq), dtype=np.int32)}
            row = {"seed": s}
            if read is None:
                params = app.seeded(init_fn, key).params
                read = app.reference_on(params, first["tokens"], config)
                row["reference_seconds"] = time.time() - t
                if control:
                    squeezed = app.reference_on(
                        jax.tree.map(int8_leaf, params), first["tokens"],
                        config)
                    row["control_int8"] = {
                        **app.gradient_checks(app.gradient_gaps(
                            squeezed["grads"], read["grads"])),
                        "loss_gap": abs(squeezed["loss_main"]
                                        - read["loss_main"]),
                        "mtp_loss_gap": abs(squeezed["loss_mtp"]
                                            - read["loss_mtp"]),
                        "router_bias_off": float(np.mean(np.concatenate([
                            np.sign(squeezed["bias_delta"][k])
                            != np.sign(read["bias_delta"][k])
                            for k in read["bias_delta"]])))}
                    del squeezed
                del params
            state = app.seeded(init_fn, key)
            before = app.router_biases(state.params, config)
            batch = place_batch(first)
            if "step" not in compiled:
                tc = time.time()
                compiled["step"] = step_fn.lower(state, batch).compile()
                say("compiled in", round(time.time() - tc, 1),
                    lm.compiled_peak(compiled["step"]))
            got = []
            for i in range(2):
                state, metrics = compiled["step"](state, batch)
                got.append({k: float(metrics[k])
                            for k in ("loss",) + app.COUNTERS})
                if i == 0:
                    row.update(app.gradient_checks(app.gradient_gaps(
                        app.first_moment(state, config), read["grads"],
                        1 / (1 - app.ADAM_B1))))
                    row.update(app.bias_checks(
                        before, app.router_biases(state.params, config),
                        read["bias_delta"], gamma))
            row.update(
                system_loss=got[0]["loss"], reference_loss=read["loss"],
                system_loss_main=got[0]["loss_main"],
                reference_loss_main=read["loss_main"],
                system_loss_mtp=got[0]["loss_mtp"],
                reference_loss_mtp=read["loss_mtp"],
                loss_gap=abs(got[0]["loss_main"] - read["loss_main"]),
                mtp_loss_gap=abs(got[0]["loss_mtp"] - read["loss_mtp"]),
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
            readings[s] = read          # 2.7 GB of gradients a seed, host
        out["seeds"].append(row)
        keep()
        say(json.dumps(brief(row)))
    del run
    for fault in faults:
        with planted(fault):
            run = program(faulty_config(sound, fault))
            for s in seeds[:args.plant_seeds]:
                row, _ = run(s, readings[s])
                out["faults"][fault].append(row)
                keep()
                say(fault, json.dumps(brief(row)))
            del run
    say("done")


if __name__ == "__main__":
    main()

"""Where the serving cell's limits come from: what its ``correct`` compares,
over many seeds in one process on the chip, then the control and the faults.
Writes ``chiprun_out/sweep/serve_checks_sweep.json``; the copy kept beside
this file, ``benchmark/testdata/serve_checks_sweep.json``, is that file, and
``tests/benchmark/test_bench_reference.py`` holds the committed limits to
it. No run of the benchmark imports this module.

    chiprun --timeout 3000 -- python3 benchmark/testdata/sweep_serve.py
    python3 benchmark/testdata/sweep_serve.py --tiny --seeds 4 --faults 2

Per seed, what a run of ``mistral7b-serve-closed32`` does for its
``correct``: weights from the seed as ``make_replica.__init__`` makes them,
``selfcheck``, one call of the compiled ``generate`` on a window's batch
(17 of 32 rows in use), ``aftercheck`` on 2 of its requests drawn from the
seed; then the control on the same seed: the plain reference over
``int8_weights`` in the program's place, its logits against the
reference's, and how far its own first tokens lie under the reference's
best. On ``--faults`` of the seeds (the most and the least sensitive and
3200000101 among them), planted in the program: int8 weights
(``int8_params``), the cache written one position late, the sampler's
second-best token at one step, and a served token altered to another id.
"""
import argparse
import json
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DOC = __doc__.split("\n\n", 2)[2].replace("\n", " ").replace("``", "")


def int8_params(params: dict) -> dict:
    """The control planted on the system's side: the program's own
    parameter tree with every matrix rounded to 8 bits (absmax per output
    channel) and handed back in its dtype, the values
    ``reference.int8_weights`` gives the plain layout."""
    import jax.numpy as jnp

    def q(w, axes):
        scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axes,
                        keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return (jnp.round(w.astype(jnp.float32) / scale)
                * scale).astype(w.dtype)

    layers = params["layers"]
    attn = {k: q(v, (1, 2) if k == "wo" else (1,))
            for k, v in layers["attn"].items()}
    mlp = {k: q(v, (1,)) for k, v in layers["mlp"].items()}
    out = dict(params, embed=q(params["embed"], (1,)),
               layers=dict(layers, attn=attn, mlp=mlp))
    if "lm_head" in params:
        out["lm_head"] = q(params["lm_head"], (0,))
    return out


def sweep_seeds(n: int) -> list:
    """Half small (0 ..), half of the driver's size (31-33 bits), the seed
    of PERF.md's record first among them; the same list for every sweep."""
    half = n // 2
    rnd = random.Random(34)
    big = [3200000101] + [rnd.randrange(2 ** 31, 2 ** 33)
                          for _ in range(half - 1)]
    return list(range(n - half)) + big


def load(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="the rehearsal's toy size on the CPU: to debug "
                         "this script, never a reading")
    ap.add_argument("--seeds", type=int, default=64)
    ap.add_argument("--faults", type=int, default=12)
    ap.add_argument("--out",
                    default="chiprun_out/sweep/serve_checks_sweep.json")
    args = ap.parse_args()
    sys.path.insert(0, REPO)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))

    import importlib
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.apps import lm, serve_lm
    from ray_tpu.models import generate as generate_fn
    from ray_tpu.models import transformer_init
    gen_mod = importlib.import_module("ray_tpu.models.generate")
    t0 = time.time()

    def say(*a):
        print(f"[{time.time() - t0:7.1f}s]", *a, flush=True)

    config = lm.effective_config(
        load("benchmark/configs/mistral-7b-v0.3-l24.json"), args.tiny)
    traffic = lm.effective_traffic(
        load("benchmark/traffic/serve-closed32.json"), args.tiny)
    seeds = sweep_seeds(args.seeds)
    admitted = 3 if args.tiny else traffic["expect_admitted_max"]
    spec = {"seed": seeds[0], "trace": False, "trace_dir": "",
            "rehearse": args.tiny, "config": config,
            "model": lm.model_kwargs(
                config, traffic["prompt_tokens"] + traffic["new_tokens"],
                "auto"),
            "rows": traffic["max_batch_size"],
            "prompt_tokens": traffic["prompt_tokens"],
            "new_tokens": traffic["new_tokens"]}
    rep = serve_lm.make_replica(traffic["max_batch_size"],
                                traffic["batch_wait_timeout_s"])(spec)
    cfg = rep.cfg
    say("replica up", rep.facts, "compiled generate", rep.gen_memory)
    init = jax.jit(partial(transformer_init, cfg=cfg))
    reference = lm.reference_module(config)
    p, new = rep.prompt, spec["new_tokens"]
    k = min(serve_lm.CHECK_DECODED, new - 1)
    vocab = config["vocab_size"]
    eps = lm.program_rms_norm_eps(cfg)

    def set_seed(seed):
        rep.spec["seed"] = seed
        rep.params = None
        rep.params = init(jax.random.PRNGKey(lm.fold_seed(seed)))
        jax.block_until_ready(rep.params)

    def served(gen, params, seed):
        """One batch of the window as ``drive`` sends it, and the requests
        ``drive`` would draw for the after-check."""
        s = lm.fold_seed(seed)
        prompts = np.zeros((rep.rows, p), np.int32)
        for i in range(admitted):
            prompts[i] = np.random.default_rng([s, 32 + i]).integers(
                0, vocab, p)
        picks = sorted(np.random.default_rng([s, 0x5A3D]).choice(
            admitted, size=serve_lm.CHECK_ROWS, replace=False).tolist())
        toks = np.asarray(gen(params, jnp.asarray(prompts)))
        return [(prompts[i].tolist(), toks[i].tolist()) for i in picks]

    def ref_logits_on(pairs, weights=None):
        """reference logits at the positions that predicted each served
        token"""
        tokens = jnp.asarray([list(a) + list(b[:new - 1]) for a, b in pairs],
                             jnp.int32)
        if weights is None:
            return rep._reference(tokens, eps)[:, p - 1:]
        return reference.forward(weights, tokens, config,
                                 eps=eps)[:, p - 1:]

    def deficit(pairs):
        return reference.token_deficit(ref_logits_on(pairs),
                                       [list(b) for _, b in pairs])

    def program_vs(params, mine):
        got = reference.compare_logits(
            rep.program_logits(params, jnp.asarray(mine["tokens"])),
            mine["logits"])
        return {"rms_over_std": got["rms_over_std"],
                "max_over_std": got["max_over_std"]}

    def control(mine):
        """The plain reference over int8 weights, in the program's place."""
        w8 = reference.int8_weights(lm.reference_weights(rep.params, config))
        got = reference.compare_logits(reference.forward(
            w8, jnp.asarray(mine["tokens"]), config,
            eps=eps)[:, p - 1:p + k], mine["logits"])
        first8 = jnp.argmax(ref_logits_on(mine["pairs"], w8), axis=-1)
        return {"rms_over_std": got["rms_over_std"],
                "max_over_std": got["max_over_std"],
                **reference.token_deficit(ref_logits_on(mine["pairs"]),
                                          first8)}

    out = {"doc": "PR 34, one chiprun call on a TPU v5e, the tree that "
                  "stands (commit: the program's; the benchmark's files as "
                  "this PR commits them), by "
                  "benchmark/testdata/sweep_serve.py. " + DOC,
           "pr": 34, "commit": os.environ.get("SWEEP_COMMIT", ""),
           "device": rep.facts, "cell": "mistral7b-serve-closed32",
           "config": "mistral-7b-v0.3-l24",
           "check_rows": serve_lm.CHECK_ROWS, "check_decoded": k,
           "tokens_checked_a_seed": serve_lm.CHECK_ROWS * new,
           "seeds": [], "faults": []}
    os.makedirs(os.path.dirname(os.path.join(REPO, args.out)), exist_ok=True)

    def keep():
        with open(os.path.join(REPO, args.out), "w") as f:
            json.dump(out, f, indent=1)

    kept = {}
    for seed in seeds:
        t = time.time()
        set_seed(seed)
        checks = rep.selfcheck()
        pairs = served(rep.gen, rep.params, seed)
        checks.update(rep.aftercheck(pairs))
        kept[seed] = {"tokens": rep.checked["tokens"],
                      "logits": rep.checked["logits"], "pairs": pairs}
        row = {"seed": seed, "folded": lm.fold_seed(seed),
               **{n: checks[n] for n in (
                   "rms_over_std", "max_over_std", "prefill_max_over_std",
                   "floor_rms_over_std", "program_eps_gap",
                   "token_deficit_over_std", "token_mismatches",
                   "tokens_checked", "reference_std", "compute_dtype")},
               "control_int8_reference": control(kept[seed]),
               "seconds": time.time() - t}
        out["seeds"].append(row)
        keep()
        say(json.dumps(row))

    # the faults, on: the most and the least sensitive seed, 3200000101,
    # and others spread evenly over the order of r
    by_r = sorted(out["seeds"], key=lambda r: r["rms_over_std"])
    want = {by_r[0]["seed"], by_r[-1]["seed"]}
    if 3200000101 in kept:
        want.add(3200000101)
    step = max(1, len(by_r) // max(1, args.faults))
    for r in by_r[step // 2::step]:
        if len(want) >= args.faults:
            break
        want.add(r["seed"])
    fault_seeds = [s for s in seeds if s in want]
    say("fault seeds", fault_seeds)

    quant = jax.jit(int8_params, donate_argnums=0)
    prompts0 = jnp.zeros((rep.rows, p), jnp.int32)

    def compiled_generate():
        g = jax.jit(partial(generate_fn, cfg=cfg, temperature=0.0,
                            max_new_tokens=new))
        return g.lower(rep.params, prompts0).compile()

    # (b) the cache written one position late
    write = gen_mod._write_position

    def write_late(cache, l, pos, row):
        return write(cache, l, jnp.minimum(pos + 1, cache.shape[2] - 1), row)

    gen_mod._write_position = write_late
    gen_late = compiled_generate()
    gen_mod._write_position = write
    # (c) the sampler's second-best token at one step (every row)
    hit = p + new // 3
    decode = gen_mod.decode_step

    def second_best_at_hit(params, token, pos, cache, cfg):
        logits, cache = decode(params, token, pos, cache, cfg)
        top = jnp.argmax(logits, axis=-1)
        masked = logits.at[jnp.arange(logits.shape[0]), top].set(-jnp.inf)
        return jnp.where(pos == hit, masked, logits), cache

    gen_mod.decode_step = second_best_at_hit
    gen_second = compiled_generate()
    gen_mod.decode_step = decode
    say("compiled generate with a late cache write, and with a second-best "
        "token")

    for seed in fault_seeds:
        t = time.time()
        set_seed(seed)
        mine = kept[seed]
        row = {"seed": seed}
        # (c') one served token altered where it is produced (the next id)
        altered = [(a, list(b)) for a, b in mine["pairs"]]
        altered[0][1][new // 3] = (altered[0][1][new // 3] + 1) % vocab
        row["fault_token_altered"] = deficit(altered)
        row["fault_second_best_token"] = deficit(
            served(gen_second, rep.params, seed))
        gen_mod._write_position = write_late
        try:
            late = program_vs(rep.params, mine)
        finally:
            gen_mod._write_position = write
        late.update(deficit(served(gen_late, rep.params, seed)))
        row["fault_cache_one_late"] = late
        # (a) int8 weights on the system's side
        params8 = quant(rep.params)
        rep.params = None
        a = program_vs(params8, mine)
        pairs8 = served(rep.gen, params8, seed)
        del params8
        set_seed(seed)
        a.update(deficit(pairs8))
        row["fault_int8_weights"] = a
        row["seconds"] = time.time() - t
        out["faults"].append(row)
        keep()
        say(json.dumps(row))
    say("done")


if __name__ == "__main__":
    main()

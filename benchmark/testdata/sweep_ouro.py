"""Where the looped cell's limits come from: what its ``correct`` compares,
over several seeds in one process on the chip, then the control and the
faults of this architecture. Writes ``chiprun_out/sweep/
ouro_checks_sweep.json``; the copy kept beside this file,
``benchmark/testdata/ouro_checks_sweep.json``, is that file, and
``tests/benchmark/test_bench_ouro.py`` holds the committed limits to it. No
run of the benchmark imports this module; ``tests/test_ouro.py`` plants the
same faults at a toy size on the CPU (``planted``).

    chiprun --timeout 3000 -- python3 benchmark/testdata/sweep_ouro.py
    python3 benchmark/testdata/sweep_ouro.py --tiny --seeds 2 --faults 1

Per seed, what a run of ``ouro2.6b-serve-closed16`` does for its
``correct``: weights from the seed as the replica's ``__init__`` makes
them, ``selfcheck``, one call of the compiled ``generate`` on a window's
batch, ``aftercheck`` on 2 of its requests drawn from the seed, and what
the served tokens' gaps are made of, a position at a time (the gap, the
floor, and the gap the next token id would read there: the altered
token); then the control on the same seed: the plain reference over
``int8_weights`` in the program's place, its logits, exit distribution
and first loop step's keys and values against the reference's. After the
seeds, ``--calls`` calls of the sound program again, each timed, with how
long this process was kept waiting during it (``calls``, every other call
of a compiled ``generate`` too). On ``--faults`` of the seeds (the most
and the least sensitive among them), planted in the program one at a
time: the loop run one step short, slot ``l`` used for every loop step,
the two output norms of every layer left out, and the final norm applied
once at the end only; each through ``prefill`` + ``decode_step`` against
the sound reference and through its own compiled ``generate``.
"""
import argparse
import contextlib
import dataclasses
import json
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DOC = __doc__.split("\n\n", 2)[2].replace("\n", " ").replace("``", "")
FAULTS = ("one_loop_step_short", "one_slot_a_layer", "no_output_norms",
          "final_norm_at_the_end_only")


@contextlib.contextmanager
def planted(fault: str, cfg, params):
    """-> (cfg, params) as the program with ``fault`` planted runs them;
    what is patched in ``ray_tpu.models`` is put back on the way out. The
    program is reached by its private names here (PERF.md section 7, D9):
    ``generate._slots_of_pass``, ``_over_loop_steps`` (in both modules),
    ``transformer._norm`` and ``exit_distribution``, and the layer's
    ``ln1_post`` / ``ln2_post``."""
    import importlib

    import jax
    import jax.numpy as jnp
    from jax import lax
    gen = importlib.import_module("ray_tpu.models.generate")
    tr = importlib.import_module("ray_tpu.models.transformer")
    patched = []

    def patch(module, name, value):
        patched.append((module, name, getattr(module, name)))
        setattr(module, name, value)

    if fault == "one_loop_step_short":
        cfg = dataclasses.replace(cfg, loop_steps=cfg.loop_steps - 1)
    elif fault == "one_slot_a_layer":
        patch(gen, "_slots_of_pass", lambda cfg, t: jnp.arange(cfg.n_layers))
    elif fault == "no_output_norms":
        params = dict(params, layers={
            k: v for k, v in params["layers"].items()
            if k not in ("ln1_post", "ln2_post")})
    elif fault == "final_norm_at_the_end_only":
        def normed_at_the_end(cfg, params, stack, x, carry=None):
            gate = params["exit_gate"]

            def step(state, t):
                x, carry = stack(*state, t)
                z = tr._norm(cfg, x, params["final_norm"]).astype(
                    jnp.float32) @ gate["w"].astype(jnp.float32)
                return (x, carry), jax.nn.sigmoid(
                    z + gate["b"].astype(jnp.float32))

            (x, carry), gates = lax.scan(step, (x, carry),
                                         jnp.arange(cfg.loop_steps))
            return (tr._norm(cfg, x, params["final_norm"]), carry,
                    tr.exit_distribution(gates))
        patch(tr, "_over_loop_steps", normed_at_the_end)
        patch(gen, "_over_loop_steps", normed_at_the_end)
    else:
        raise ValueError(f"no fault {fault!r} (have: {FAULTS})")
    try:
        yield cfg, params
    finally:
        for module, name, value in reversed(patched):
            setattr(module, name, value)


def sweep_seeds(n: int) -> list:
    """Half small (0 ..), half of the driver's size (31-33 bits)."""
    half = n // 2
    rnd = random.Random(39)
    return list(range(n - half)) + [rnd.randrange(2 ** 31, 2 ** 33)
                                    for _ in range(half)]


def load(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="the rehearsal's toy size on the CPU: to debug "
                         "this script, never a reading")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=4)
    ap.add_argument("--calls", type=int, default=20,
                    help="calls of the sound program made again after the "
                         "seeds, each timed")
    ap.add_argument("--out",
                    default="chiprun_out/sweep/ouro_checks_sweep.json")
    args = ap.parse_args()
    sys.path.insert(0, REPO)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))

    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.apps import lm, serve_ouro
    from ray_tpu.models import generate_with_stats, transformer_init
    t0 = time.time()

    def say(*a):
        print(f"[{time.time() - t0:7.1f}s]", *a, flush=True)

    config = lm.effective_config(
        load("benchmark/configs/ouro-2.6b.json"), args.tiny)
    traffic = lm.effective_traffic(
        load("benchmark/traffic/serve-closed16-p128-n256.json"), args.tiny)
    seeds = sweep_seeds(args.seeds)
    spec = {"seed": seeds[0], "trace": False, "trace_dir": "",
            "rehearse": args.tiny, "config": config,
            "model": serve_ouro.model_kwargs(
                config, traffic["prompt_tokens"] + traffic["new_tokens"],
                "auto"),
            "rows": traffic["max_batch_size"],
            "prompt_tokens": traffic["prompt_tokens"],
            "new_tokens": traffic["new_tokens"]}
    rep = serve_ouro.make_replica(traffic["max_batch_size"],
                                  traffic["batch_wait_timeout_s"])(spec)
    sound_cfg = rep.cfg
    say("replica up", rep.facts, "compiled generate", rep.gen_memory)
    init = jax.jit(partial(transformer_init, cfg=sound_cfg))
    reference = lm.reference_module(config)
    p, new, rows = rep.prompt, spec["new_tokens"], rep.rows
    k = min(serve_ouro.CHECK_DECODED, new - 1)
    vocab = config["vocab_size"]
    eps = lm.program_rms_norm_eps(sound_cfg)
    prompts0 = jnp.zeros((rows, p), jnp.int32)

    def set_seed(seed):
        rep.spec["seed"] = seed
        rep.params, rep.passes, rep.seen = None, {}, {}
        rep.params = init(jax.random.PRNGKey(lm.fold_seed(seed)))
        jax.block_until_ready(rep.params)

    calls = []

    def timed(gen, params, prompts, what):
        """One call of a compiled ``generate`` as the replica makes it,
        with how long it took and how long this process was kept waiting
        during it (``serve_ouro.HostTicker``)."""
        rep.ticker.reset()
        start = time.time()
        called = gen(params, prompts)
        dispatched = time.time()
        tokens, _ = jax.device_get(called)
        calls.append({"what": what, "seconds": time.time() - start,
                      "dispatch_s": dispatched - start,
                      "host_pause_max_s": rep.ticker.longest()})
        return np.asarray(tokens)

    def served(gen, params, seed, what):
        """One batch of the window as ``drive`` sends it, and the requests
        ``drive`` would draw for the after-check."""
        s = lm.fold_seed(seed)
        prompts = np.stack([np.random.default_rng([s, rows + i]).integers(
            0, vocab, p) for i in range(rows)]).astype(np.int32)
        picks = sorted(np.random.default_rng([s, 0x5A3D]).choice(
            rows, size=serve_ouro.CHECK_ROWS, replace=False).tolist())
        toks = timed(gen, params, jnp.asarray(prompts), what)
        return [(prompts[i].tolist(), toks[i].tolist()) for i in picks]

    def fed(pairs):
        return jnp.asarray([list(a) + list(b[:new - 1]) for a, b in pairs],
                           jnp.int32)

    def tokens_against_the_reference(pairs) -> dict:
        """Every served token of ``pairs`` against the sound reference's
        logits at the position that predicted it: what ``correct`` reads
        (the widest gap over its own position's rounding floor), the same
        over the logits' standard deviation, and what the gaps are made
        of, a position at a time: the gap, the floor, and the gap that the
        next token id would read in the served token's place."""
        want = rep.reference_pass(fed(pairs), eps)["logits"][:, p - 1:]
        floor = rep.reference_pass(fed(pairs), eps, torch_dtype)[
            "logits"][:, p - 1:]
        tokens = jnp.asarray([list(b) for _, b in pairs])
        out = reference.token_deficit(want, tokens)
        out["token_deficit_over_floor"] = \
            reference.token_deficit_over_floor(want, floor, tokens)
        out["a_position"] = {
            "deficit": np.asarray(
                reference.token_deficits(want, tokens)).reshape(-1).tolist(),
            "floor": np.asarray(
                reference.errors_a_position(floor, want)).tolist(),
            "deficit_of_the_next_id": np.asarray(reference.token_deficits(
                want, (tokens + 1) % vocab)).reshape(-1).tolist()}
        out["rounded_reference_token_deficit_over_std"] = \
            reference.token_deficit(want, jnp.argmax(floor, axis=-1))[
                "token_deficit_over_std"]
        return out

    def against(got: dict, mine) -> dict:
        """``got``: logits, exits and cache of ``selfcheck``'s positions,
        from whatever stands in the program's place -> every number
        ``correct`` reads of them, and each position's rms error."""
        near = reference.compare_logits(got["logits"], mine["logits"])
        errors = reference.errors_a_position(got["logits"], mine["logits"])
        over = reference.over_floor(errors, mine["floor_errors_a_position"])
        out = {"rms_over_std": near["rms_over_std"],
               "max_over_std": near["max_over_std"],
               "rms_over_floor_a_position": over["typical"],
               "rms_over_floor_worst_position": over["worst"],
               "errors_a_position": np.asarray(errors).tolist(),
               "cache_errors": np.asarray(reference.cache_errors(
                   got["cache"], mine["cache"], p)).tolist()}
        cache = reference.over_floor(out["cache_errors"],
                                     mine["floor_cache_errors"])
        out.update(cache_over_floor=cache["typical"],
                   cache_over_floor_worst=cache["worst"])
        if got["exits"].shape != mine["exits"].shape:   # a loop step short
            return dict(out, exit_rows_off_one=float(jnp.max(jnp.abs(
                got["exits"].sum(-1) - 1.0))), exit_gap=None, exit_rms=None,
                exit_over_floor_a_position=None)
        exit_errors = reference.errors_a_position(got["exits"],
                                                  mine["exits"])
        over = reference.over_floor(exit_errors,
                                    mine["floor_exit_errors_a_position"])
        return dict(
            out, **reference.compare_exits(got["exits"], mine["exits"]),
            exit_over_floor_a_position=over["typical"],
            exit_over_floor_worst_position=over["worst"],
            exit_errors_a_position=np.asarray(exit_errors).tolist())

    def program_vs(cfg, params, mine) -> dict:
        """``prefill`` + ``decode_step`` of (cfg, params) against the sound
        reference's logits, exits and cache on ``selfcheck``'s tokens."""
        rep.cfg = cfg
        try:
            rep.program_logits(params, jnp.asarray(mine["tokens"]))
        finally:
            rep.cfg = sound_cfg
        return against(rep.seen, mine)

    def control(mine) -> dict:
        """The plain reference over int8 weights, in the program's place."""
        w8 = reference.int8_weights(
            serve_ouro.reference_weights(rep.params, config))
        logits, exits, cache = reference.forward_and_cache(
            w8, jnp.asarray(mine["tokens"]), config, eps=eps,
            passes_kept=serve_ouro.CACHE_PASSES)
        return against({"logits": logits[:, p - 1:p + k],
                        "exits": exits[:, p - 1:p + k], "cache": cache},
                       mine)

    out = {"doc": "PR 39, one chiprun call on a TPU v5e, by "
                  "benchmark/testdata/sweep_ouro.py. " + DOC,
           "pr": 39, "tiny": args.tiny, "device": rep.facts,
           "cell": "ouro2.6b-serve-closed16", "config": "ouro-2.6b",
           "compiled_generate": rep.gen_memory,
           "check_rows": serve_ouro.CHECK_ROWS, "check_decoded": k,
           "cache_passes": serve_ouro.CACHE_PASSES,
           "tokens_checked_a_seed": serve_ouro.CHECK_ROWS * new,
           "seeds": [], "faults": [], "calls": calls}
    os.makedirs(os.path.dirname(os.path.join(REPO, args.out)), exist_ok=True)

    def keep():
        with open(os.path.join(REPO, args.out), "w") as f:
            json.dump(out, f, indent=1)

    names = ("rms_over_std", "max_over_std", "prefill_max_over_std",
             "floor_rms_over_std", "token_deficit_over_std",
             "token_mismatches", "tokens_checked", "reference_std",
             "compute_dtype", "exit_rows_off_one", "exit_gap", "exit_rms",
             "floor_exit_rms", "rms_over_floor_a_position",
             "rms_over_floor_worst_position", "exit_over_floor_a_position",
             "exit_over_floor_worst_position", "cache_errors",
             "floor_cache_errors", "token_deficit_over_floor")
    torch_dtype = jnp.dtype(config["torch_dtype"])
    kept = {}
    for seed in seeds:
        t = time.time()
        set_seed(seed)
        checks = rep.selfcheck()
        pairs = served(rep.gen, rep.params, seed, "sound")
        checks.update(rep.aftercheck(pairs))
        rounded = rep.reference_pass(jnp.asarray(rep.checked["tokens"]),
                                     eps, torch_dtype)
        kept[seed] = mine = {
            "tokens": rep.checked["tokens"], "logits": rep.checked["logits"],
            "exits": rep.checked["exits"], "cache": rep.checked["cache"],
            "floor_cache_errors": checks["floor_cache_errors"],
            "floor_errors_a_position": np.asarray(
                reference.errors_a_position(
                    rounded["logits"][:, p - 1:p + k],
                    rep.checked["logits"])).tolist(),
            "floor_exit_errors_a_position": np.asarray(
                reference.errors_a_position(
                    rounded["exits"][:, p - 1:p + k],
                    rep.checked["exits"])).tolist()}
        cache = reference.over_floor(checks["cache_errors"],
                                     checks["floor_cache_errors"])
        row = {"seed": seed, "folded": lm.fold_seed(seed),
               **{n: checks[n] for n in names},
               "cache_over_floor": cache["typical"],
               "cache_over_floor_worst": cache["worst"],
               "errors_a_position": np.asarray(reference.errors_a_position(
                   rep.checked["program"]["logits"],
                   rep.checked["logits"])).tolist(),
               "floor_errors_a_position": mine["floor_errors_a_position"],
               "served": tokens_against_the_reference(pairs),
               "control_int8_reference": control(mine),
               "seconds": time.time() - t}
        out["seeds"].append(row)
        keep()
        say(json.dumps({n: v for n, v in row.items()
                        if not isinstance(v, (list, dict))}),
            "control", json.dumps({
                n: v for n, v in row["control_int8_reference"].items()
                if not isinstance(v, list)}))

    # the sound program's call again and again on the last seed's weights,
    # outside any runtime: how often a call takes longer, and who waited
    prompts = jnp.asarray(np.random.default_rng(39).integers(
        0, vocab, (rows, p)).astype(np.int32))
    rep.passes, rep.seen = {}, {}       # the call needs the chip's memory
    for _ in range(args.calls):
        timed(rep.gen, rep.params, prompts, "again")
    keep()
    say("calls", json.dumps(calls))

    # the faults, on the most and the least sensitive seed and others
    # spread evenly over the order of r
    by_r = sorted(out["seeds"], key=lambda r: r["rms_over_std"])
    want = {by_r[0]["seed"], by_r[-1]["seed"]}
    step = max(1, len(by_r) // max(1, args.faults))
    for r in by_r[step // 2::step]:
        if len(want) >= args.faults:
            break
        want.add(r["seed"])
    fault_seeds = [s for s in seeds if s in want][:args.faults]
    say("fault seeds", fault_seeds)

    set_seed(fault_seeds[0])
    faulty = {}
    for fault in FAULTS:
        with planted(fault, sound_cfg, rep.params) as (cfg, params):
            faulty[fault] = jax.jit(partial(
                generate_with_stats, cfg=cfg, temperature=0.0,
                max_new_tokens=new)).lower(params, prompts0).compile()
        say("compiled generate with", fault)

    for seed in fault_seeds:
        set_seed(seed)
        row = {"seed": seed}
        for fault in FAULTS:
            t = time.time()
            with planted(fault, sound_cfg, rep.params) as (cfg, params):
                got = program_vs(cfg, params, kept[seed])
                pairs = served(faulty[fault], params, seed, fault)
            got.update(tokens_against_the_reference(pairs))
            del got["a_position"]
            rep.passes = {}
            got["seconds"] = time.time() - t
            row[fault] = got
        out["faults"].append(row)
        keep()
        say(json.dumps({f: {n: v for n, v in row[f].items()
                            if not isinstance(v, list)} for f in FAULTS}))
    say("done")


if __name__ == "__main__":
    main()

"""The training application of the ``qwen3_next`` family: the loop a user
writes under ``JaxTrainer``, as ``train_lm`` runs it for the llama family,
for a hybrid model on one chip's share of an expert-parallel group.

``drive`` runs in the benchmark's process and never touches JAX. What runs
in the worker (``train_loop``): weights and optimizer state made on the
device from the seed through ``make_lm_train_step``; the plain reference
(``benchmark/reference/qwen3_next.py``) on the first batch at the timed
size: its loss and that loss's gradient for every parameter, kept on the
host; the step compiled once; two warm-up steps on that batch, which are
what ``correct`` judges: the compiled step's first loss against the
reference's, its gradient (read back from the optimizer state it returns)
against the reference's leaf by leaf, and the second loss, after one update,
fallen by what sound runs read; then the window: every step a fresh seeded batch of token ids drawn from the
vocabulary slice, made on the host and placed while the previous step runs,
the loss and the expert layers' counters fetched in one transfer and
``session.report``ed, all inside a ``train.step`` span of the program's
flight recorder whose ``attrs`` are those counters.

This module owns what is the family's: how the configuration becomes the
program's ``TransformerConfig``, how its parameter tree becomes the
reference's ``Weights``, and the record. Seeds, the compile counter, the
memory report and the judging helpers are ``benchmark/apps/lm.py``'s.
"""

from __future__ import annotations

import time

from benchmark.apps import lm

TRACE_FROM_STEP = 4        # traced run: profile TRACE_STEPS steps from here
TRACE_STEPS = 4            # 4 executions in the trace = 3 whole periods
WARMUP_STEPS = 2
COUNTERS = ("moe_rows_here", "moe_rows_dropped", "moe_load_max",
            "moe_load_mean")
# What ``correct`` holds a run to. Each limit lies between two readings of
# benchmark/testdata/qwen3_next_checks_sweep.json (my chip run, PR 37; the
# table is in PERF.md section 2, and tests/benchmark/test_bench_qwen3_next.py
# holds these numbers to that file).
#
# |system loss - reference loss| on the first batch: 6.8e-6 .. 1.29e-3 over
# 12 seeds, the worst at 0.43 of the limit, which is the llama cells' (the
# system computes in bfloat16, the loss averages 16,382 positions). With
# random weights the loss hardly depends on what the layers compute: the
# planted faults read 9e-5 .. 1.6e-2 and pass it on some seeds. It guards
# the mask, the targets and the head; the next two guard the layers.
LOSS_TOLERANCE = 3e-3
# The compiled step's own gradient on the first batch against the
# reference's (``jax.vjp`` of the plain layers at the timed size), as
# |g - g_ref| / |g_ref|. The step hands out no gradient; after one step from
# fresh moments AdamW's first moment is (1 - b1) x the gradient, so it is
# read from the state the timed program returned. A state handed back
# unchanged reads 1 on both numbers.
# ``grad_gap``, over every parameter together: 0.098 .. 0.110 over 12 seeds
# (0.091 .. 0.110 over 29 sound runs)
# (bfloat16 arithmetic, and the router's near-ties that it decides the
# other way: the routed experts' leaves read 0.14 .. 0.25, the dense ones
# 0.08 .. 0.11, the head 0.06). The control, the reference's own gradient
# over int8 weights, 0.220 .. 0.234 (4 seeds); the output gate left out
# 0.254 .. 0.266, top-k not renormalised 0.56 .. 0.58, half of the batch
# left out 1.00, the shared expert or the decay left out 1.3 .. 1.4 (4 seeds
# each). The limit is the geometric middle of 0.110 and 0.220: 1.41 x the
# worst sound seed, 0.70 x the control's best.
GRAD_GAP_LIMIT = 0.155
# ``grad_gap_worst_leaf``, the parameter array farthest off: 0.246 .. 0.283
# over 12 seeds (the last layer's router; 0.238 .. 0.283 over 29 runs); the control 0.44 .. 0.48; every
# planted fault 0.93 or more. The 32-number ``A_log`` and ``dt_bias`` are
# left to the number above: their gradient is a sum of terms that cancel
# and reads 0.02 .. 0.48 on sound seeds. The limit is 1.59 x the worst
# sound seed and under half of the least fault.
GRAD_GAP_LEAF_LIMIT = 0.45
TINY_LEAVES = ("A_log", "dt_bias")
ADAM_B1 = 0.9              # optax.adamw's, as make_lm_train_step builds it
# The second warm-up step runs on the first batch again: after one AdamW
# update the loss has fallen by 2.728 .. 3.236 (32 sound runs: the sweep's
# 12 seeds and 20 runs of the cell; mean 2.928, sd 0.104, the tail on the
# upper side). The traffic file's ``first_update_fall`` gives ``about`` 2.95
# and how far from it a run may read (``within`` 0.75: 2.6 x the farthest
# of the 32). A state handed back unchanged reads a fall of 0, the decay
# left out 5.8. It guards the optimizer's work, not the arithmetic's
# precision: bfloat16 parameters (state, update and step) fall by 2.49 ..
# 3.17 (12 seeds) and are told apart by ``params_not_as_configured`` alone.


def model_kwargs(config: dict, seq: int, attn_impl: str) -> dict:
    """Hugging Face key names -> ``TransformerConfig`` fields (dtypes as
    strings: this dict crosses a process boundary)."""
    interval = config["full_attention_interval"]
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        layer_types=("linear",) * (interval - 1) + ("full",),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        qk_norm=True, attn_output_gate=True, norm_plus_one=True,
        linear_key_heads=config["linear_num_key_heads"],
        linear_value_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        linear_conv_kernel=config["linear_conv_kernel_dim"],
        num_experts=config.get("num_experts_published",
                               config["num_experts"]),
        experts_held=config["num_experts"],
        first_expert=int(config.get("first_expert", 0)),
        expert_top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        expert_ff=config["moe_intermediate_size"],
        shared_expert_ff=config["shared_expert_intermediate_size"],
        d_ff=config["intermediate_size"], max_seq=seq,
        rope_theta=float(config["rope_theta"]),
        tied_embeddings=bool(config.get("tie_word_embeddings", False)),
        param_dtype=config["param_dtype"], attn_impl=attn_impl)


def transformer_config(kwargs: dict, remat: bool):
    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig
    kwargs = dict(kwargs)
    kwargs["param_dtype"] = jnp.dtype(kwargs["param_dtype"])
    kwargs["layer_types"] = tuple(kwargs["layer_types"])
    cfg = TransformerConfig(**kwargs, remat=remat)
    if lm.program_rms_norm_eps(cfg) != 1e-6:
        raise ValueError("the family's published rms_norm_eps is 1e-6")
    return cfg


def reference_weights(params: dict, config: dict):
    """The program's parameter tree as the reference's plain matrices: the
    same arrays reshaped, one layer at a time."""
    ref = lm.reference_module(config)
    stacks = params["layers"]              # one stack a position of a period
    period = len(stacks)
    d = params["embed"].shape[1]

    def layer(i: int) -> dict:
        stack, j = stacks[i % period], i // period
        m = stack["moe"]
        out = {"ln1": stack["ln1"][j], "ln2": stack["ln2"][j],
               "router": m["router"][j], "w1": m["w1"][j], "w3": m["w3"][j],
               "w2": m["w2"][j], "shared_w1": m["shared"]["w1"][j],
               "shared_w3": m["shared"]["w3"][j],
               "shared_w2": m["shared"]["w2"][j],
               "shared_gate": m["shared"]["gate"][j]}
        if "attn" in stack:
            a = stack["attn"]
            out.update(wq=a["wq"][j].reshape(d, -1),
                       wk=a["wk"][j].reshape(d, -1),
                       wv=a["wv"][j].reshape(d, -1),
                       wo=a["wo"][j].reshape(-1, d),
                       q_norm=a["q_norm"][j], k_norm=a["k_norm"][j])
        else:
            out.update({k: v[j] for k, v in stack["gdn"].items()})
        return out

    n_layers = period * int(stacks[0]["ln1"].shape[0])
    return ref.Weights(embed=params["embed"], layer=layer, n_layers=n_layers,
                       final_norm=params["final_norm"],
                       lm_head=params["lm_head"])


def named_leaves(weights) -> dict:
    """The reference's ``Weights`` as ``{name: array}``."""
    out = {"embed": weights.embed, "final_norm": weights.final_norm,
           "lm_head": weights.lm_head}
    for i in range(weights.n_layers):
        out.update({f"layer{i}.{k}": v
                    for k, v in weights.layer(i).items()})
    return out


def reference_on(params: dict, tokens, config: dict) -> dict:
    """The reference on the first batch at its own size: its loss, and the
    loss's gradient for every parameter, on the host (2.5 GB: the step
    fills the chip)."""
    import numpy as np
    reference = lm.reference_module(config)
    loss, grads = reference.loss_and_grads(
        reference_weights(params, config), tokens, config)
    return {"loss": loss,
            "grads": {k: np.asarray(v)
                      for k, v in named_leaves(grads).items()}}


def first_moment(state, config: dict) -> dict:
    """AdamW's first moment in the reference's layout, ``{name: array}``:
    after one step from fresh moments, (1 - b1) x that step's gradient."""
    mu = next(s.mu for s in state.opt_state if hasattr(s, "mu"))
    return named_leaves(reference_weights(mu, config))


def gradient_gaps(got: dict, want: dict, scale: float = 1.0) -> dict:
    """|scale x got - want| / |want|, a leaf at a time on the device ->
    ``{name: gap}`` and ``"all"``: the same over every leaf together."""
    import math

    import jax
    import jax.numpy as jnp

    @jax.jit
    def squares(a, b):
        a = a.astype(jnp.float32) * scale
        return jnp.sum((a - b) ** 2), jnp.sum(b * b)

    def ratio(off, size):
        return math.sqrt(off / size) if size else (math.inf if off else 0.0)

    gaps, off_all, size_all = {}, 0.0, 0.0
    for name in sorted(want):
        off, size = map(float, squares(got[name], jnp.asarray(want[name])))
        gaps[name] = ratio(off, size)
        off_all, size_all = off_all + off, size_all + size
    gaps["all"] = ratio(off_all, size_all)
    return gaps


def gradient_checks(gaps: dict) -> dict:
    worst = max((k for k in gaps
                 if k != "all" and not k.endswith(TINY_LEAVES)),
                key=gaps.get)
    return {"grad_gap": gaps["all"], "grad_gap_worst": gaps[worst],
            "grad_gap_worst_leaf": worst, "grad_gaps": gaps,
            "grad_gap_limit": GRAD_GAP_LIMIT,
            "grad_gap_leaf_limit": GRAD_GAP_LEAF_LIMIT}


def train_loop(spec: dict) -> None:
    stamps = {"entry": time.time()}
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace as trace_mod
    from benchmark import trace_scopes
    from ray_tpu.air import session
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_lm_train_step, step_span

    compiles = lm.CompileCounter()
    devs = jax.devices()
    stamps["devices"] = time.time()
    facts = lm.device_facts()
    lm.require_chips(facts, spec["chips"], spec["rehearse"])
    chips = spec["chips"]
    cfg = transformer_config(spec["model"], remat=spec["remat"])
    mesh = build_mesh(MeshSpec(**{spec["mesh_axis"]: chips}))
    init_fn, step_fn, place_batch = make_lm_train_step(cfg, mesh)
    seed = lm.fold_seed(spec["seed"])
    key = jax.random.PRNGKey(seed)
    # The parameters alone, the optimizer's moments let go: the reference
    # takes its gradient beside them and needs the room.
    params = init_fn(key).params
    jax.block_until_ready(params)
    stamps["init"] = time.time()

    rows, seq = spec["rows_per_chip"] * chips, spec["seq"]
    rng = np.random.default_rng(seed)

    def make_batch() -> dict:
        # token ids from the chip's slice of the vocabulary
        return {"tokens": rng.integers(0, cfg.vocab_size, (rows, seq),
                                       dtype=np.int32)}

    first = make_batch()
    config = spec["config"]
    read = reference_on(params, first["tokens"], config)
    del params
    state = init_fn(key)                  # the same numbers, with moments
    stamps["reference"] = time.time()

    batch = place_batch(first)
    compiled = step_fn.lower(state, batch).compile()
    step_memory = lm.compiled_peak(compiled)
    # {instruction name: rt.* scope}, what the trace's events are mapped by
    scopes = trace_scopes.scope_map(compiled.as_text()) \
        if spec["trace"] else {}
    stamps["compiled"] = time.time()

    def fetch(metrics) -> dict:
        """The loss and the counters in one transfer (it waits for the
        step)."""
        got = jax.device_get({k: metrics[k] for k in ("loss",) + COUNTERS})
        return {k: float(v) for k, v in got.items()}

    warmup, gaps = [], None
    for _ in range(WARMUP_STEPS):         # the window's own path, report
        state, metrics = compiled(state, batch)       # included; all on
        warmup.append(fetch(metrics))                 # the first batch
        session.report({"warmup": len(warmup), "loss": warmup[-1]["loss"]})
        if gaps is None:                  # the first step's own gradient
            gaps = gradient_gaps(first_moment(state, config),
                                 read.pop("grads"), 1 / (1 - ADAM_B1))
    batch = place_batch(make_batch())
    params = jax.tree.leaves(state.params)
    checks = {
        "system_loss": warmup[0]["loss"], "reference_loss": read["loss"],
        "loss_tolerance": LOSS_TOLERANCE,
        **gradient_checks(gaps),
        "warmup_losses": [w["loss"] for w in warmup],
        "first_update_fall": warmup[0]["loss"] - warmup[1]["loss"],
        "first_update_fall_expected": spec["first_update_fall"],
        "n_params": int(sum(x.size for x in params)),
        "param_dtypes": sorted({str(x.dtype) for x in params}),
        "state_device_sets": sorted({len(x.sharding.device_set)
                                     for x in jax.tree.leaves(state)}),
    }

    trace_dir = spec["trace_dir"] if spec["trace"] else None
    profiler, steps, counters = [], [], []
    compiles_before = compiles.count
    stamps["window_start"] = time.time()
    t0 = time.perf_counter()
    i = 0
    while True:
        if trace_dir and i == TRACE_FROM_STEP:
            a = time.perf_counter()
            trace_mod.start(trace_dir)
            profiler.append([a - t0, time.perf_counter() - t0])
        with jax.profiler.TraceAnnotation("bench.step"), \
                step_span(i) as sp:
            dispatched = time.perf_counter()
            state, metrics = compiled(state, batch)
            with jax.profiler.TraceAnnotation("bench.place"):
                batch = place_batch(make_batch())
            got = fetch(metrics)                   # waits for the step
            ready = time.perf_counter()
            sp.set(**{k: got[k] for k in COUNTERS})
        with jax.profiler.TraceAnnotation("bench.report"):
            session.report({"step": i, **got})
        steps.append([dispatched - t0, ready - t0, got["loss"]])
        counters.append([got[k] for k in COUNTERS])
        i += 1
        if trace_dir and i == TRACE_FROM_STEP + TRACE_STEPS:
            a = time.perf_counter()
            jax.profiler.stop_trace()
            profiler.append([a - t0, time.perf_counter() - t0])
        if ready - t0 >= spec["seconds"]:
            break
    window = {"steps": steps, "profiler": profiler,
              "counters": {"names": list(COUNTERS), "steps": counters},
              # [first, past the last] step whose execution is in the trace
              "traced_steps": [TRACE_FROM_STEP, TRACE_FROM_STEP + TRACE_STEPS]
              if len(profiler) == 2 else None,
              "warmup_counters": [[w[k] for k in COUNTERS] for w in warmup],
              "compiles_in_window": compiles.count - compiles_before,
              "tokens_per_step": rows * seq}
    reduced = {}
    if trace_dir:
        path = trace_mod.find_xplane(trace_dir)
        reduced = trace_mod.reduce_file(path)
        if reduced:
            reduced["scopes"] = trace_scopes.reduce_file(path, scopes)
    session.report({"step": i, "loss": steps[-1][2], "record": {
        "stamps": stamps, "facts": facts, "checks": checks, "window": window,
        "trace": reduced,
        "memory": lm.memory_report(devs, step_memory, "the train step")}})


def judged(record: dict) -> dict:
    """-> every number this cell's ``correct`` compares, as
    ``{name: [value, limit]}``: correct while each value is at or under its
    limit. A limit of 0 is an exact comparison."""
    import math
    checks, window = record["checks"], record["window"]
    expected = checks["first_update_fall_expected"]
    losses = checks["warmup_losses"] + [s[2] for s in window["steps"]]
    dropped = window["counters"]["names"].index("moe_rows_dropped")
    return {
        "loss_gap": [abs(checks["system_loss"] - checks["reference_loss"]),
                     checks["loss_tolerance"]],
        "grad_gap": [checks["grad_gap"], checks["grad_gap_limit"]],
        "grad_gap_worst_leaf": [checks["grad_gap_worst"],
                                checks["grad_gap_leaf_limit"]],
        "first_update_fall_off": [
            abs(checks["first_update_fall"] - expected["about"]),
            expected["within"]],
        "losses_not_finite": [
            sum(1 for x in losses if not math.isfinite(x)), 0],
        "params_not_as_configured": [
            int(checks["param_dtypes"] != [record["param_dtype"]]), 0],
        "state_not_on_every_chip": [
            int(checks["state_device_sets"] != [record["facts"]["count"]]),
            0],
        "moe_rows_dropped": [
            sum(row[dropped] for row in window["counters"]["steps"]
                + window["warmup_counters"]), 0],
    }


WHAT_EACH_CHECK_SAYS = {
    "loss_gap": "system loss against the plain reference's on the first "
                "batch",
    "grad_gap": "the compiled step's gradient on the first batch (from "
                "the first moment of the state it returned) against the "
                "plain reference's, |g - g_ref| / |g_ref| over every "
                "parameter",
    "grad_gap_worst_leaf": "the same for the one parameter array that is "
                           "farthest off (named in the record's checks; "
                           "the 32-number A_log and dt_bias left out)",
    "first_update_fall_off": "how far the loss's fall on the same batch "
                             "after one update lies from what this cell's "
                             "sound runs read: the backward pass or the "
                             "optimizer is not doing its work, or a part "
                             "of the model is not the configuration's",
    "losses_not_finite": "losses in the run that are not finite",
    "params_not_as_configured": "the parameters' dtype is not the "
                                "configuration's param_dtype",
    "state_not_on_every_chip": "parameters or optimizer state are not "
                               "spread over every chip",
    "moe_rows_dropped": "(token, expert) rows routed to a held expert that "
                        "the expert layer's buffer did not take, over the "
                        "warm-up and the window",
}


def judge(record: dict) -> list:
    """-> reasons this run is not correct (empty: correct), each naming
    the check, its number and its limit. Leaves ``record["judged"]``."""
    record["judged"] = judged(record)
    return lm.over_their_limits(record["judged"], WHAT_EACH_CHECK_SAYS)


def drive(run) -> dict:
    """``run`` is ``benchmark.run.RunContext``. -> the run's record."""
    import ray_tpu as rt
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    run.phase("configure")
    cell = run.cell
    config = lm.effective_config(cell["config_data"], run.rehearse)
    traffic = lm.effective_traffic(cell["traffic_data"], run.rehearse)
    chips = cell["chips"]
    spec = {
        "seed": run.seed, "seconds": run.seconds, "trace": run.trace,
        "trace_dir": run.path("trace"), "rehearse": run.rehearse,
        "chips": chips, "config": config,
        "model": model_kwargs(config, traffic["seq"],
                              "auto" if run.rehearse else "flash"),
        "remat": traffic["remat"], "mesh_axis": traffic["mesh_axis"],
        "seq": traffic["seq"], "rows_per_chip": traffic["rows_per_chip"],
        "first_update_fall": traffic["first_update_fall"],
    }
    # Here, before anything starts: a program without this family's
    # mechanisms (no layer pattern, no head width, no share of the experts)
    # refuses the configuration at once.
    transformer_config(spec["model"], remat=spec["remat"])
    run.phase("rt.init")
    run.init_runtime(rt, chips)
    scaling = ScalingConfig(num_workers=1) if run.rehearse else \
        ScalingConfig(num_workers=1, use_tpu=True, tpus_per_worker=chips)
    run.phase("lease+train")
    called = time.time()
    result = JaxTrainer(
        train_loop, train_loop_config=spec, scaling_config=scaling,
        run_config=RunConfig(name="bench", storage_path=run.path("trial"))
    ).fit()
    history = result.metrics_history or []
    record = next((m["record"] for m in reversed(history)
                   if "record" in m), None)
    if result.error is not None or record is None:
        # No step report yet: the lease, the worker's start or the set-up
        # failed, and the window was never entered. One more try is allowed.
        before_window = not any("step" in m for m in history)
        raise run.failure(f"JaxTrainer failed: {result.error}",
                          before_window=before_window)
    record["stamps"]["called"] = called
    record["window_start"] = record["stamps"].pop("window_start")
    record["compiles_in_window"] = record["window"]["compiles_in_window"]
    record["param_dtype"] = config["param_dtype"]
    record["attempted"] = len(record["window"]["steps"])
    record["failed"] = 0
    record["why_not_correct"] = judge(record)
    run.phase("shutdown")
    return record

"""The serving application of the ``ouro`` family: ``serve_lm``'s replica
behind ``serve.run`` and the proxy, for a looped stack.

What is the family's own is here: how the configuration becomes the
program's ``TransformerConfig`` (``loop_steps``, the sandwich norm, the exit
gate's threshold) and its parameter tree the reference's ``Weights``; a
replica that compiles ``generate_with_stats`` and opens the program's
``generate.call`` span around each call, with the exit gate's counter on it;
the exit distribution and the first loop step's cached keys and values
through ``prefill`` and ``decode_step`` against the reference's, and the
served tokens' gaps over their own positions' floor; how long each call
took and whether the host stood still during it (``HostTicker``); device
time by the program's scopes (``rt.loop.cache``) in a traced run; and the
limits, read from this family's own sweep. Everything else (the handler
under ``@serve.batch``, the exact checks, the trace window, the dump) is
``serve_lm``'s replica, subclassed.
``serve_lm.drive`` builds its spec and its replica from the llama tree
(``lm.model_kwargs``, ``lm.reference_weights``), so ``drive`` is written out
here again.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

from benchmark.apps import lm, serve_lm
from benchmark.hermetic import log

CHECK_ROWS, CHECK_DECODED = serve_lm.CHECK_ROWS, serve_lm.CHECK_DECODED
# A call here takes 11.8 s and is 1.76 M device events, the window holds
# four calls, and the profiler needs 25 us and 3 KB of host memory an event
# to write a trace out. So the traced run profiles one whole period and no
# more: from the start of the window's 2nd call until TRACE_INTO_NEXT_S into
# its 3rd (the trace then holds two starts of the program, which is what
# ``trace.reduce_device`` counts a period by; ``serve_lm`` profiles its 3rd
# to 5th call whole).
TRACE_FROM_BATCH = 1
TRACE_INTO_NEXT_S = 1.0
# What ``correct`` holds a run to. Every reading is of
# benchmark/testdata/ouro_checks_sweep.json (my chip run, PR 39, made on a
# TPU v5e by sweep_ouro.py beside it: 12 seeds, on each the control, int8
# weights through the reference, and an altered token; on 4 of them the
# architecture's four faults planted in the program; the table is in
# PERF.md section 2, and tests/benchmark/test_bench_ouro.py holds these
# numbers to that file). Each number is the program's error over what the
# reference's own bfloat16-rounded activations do to the same seed's model
# at the same place, read one place at a time (``reference.over_floor``):
# the typical place (the geometric mean) and the worst.
#
# A looped stack of random weights (192 layer passes, every sublayer's
# output normed) amplifies a rounding by a factor that differs 27-fold from
# one position to the next and from seed to seed, and by the logits the
# residual stream's own rounding (it grows to 10 x a sublayer's output
# within a pass) has caught up with what 8-bit weights do: at the logits
# the int8 control reads 1.66 .. 4.73 x the floor against 0.94 .. 1.94 for
# sound runs. What tells fewer bits from rounding on every seed is read
# where nothing has been amplified yet: THE FIRST LOOP STEP'S KEYS AND
# VALUES, as ``prefill`` and 15 ``decode_step``s left them in the cache
# (slot l of the first CACHE_PASSES x 48; layer 0's are a norm and a matmul
# away from the embedding), against the reference's own keys and values,
# 48 slots x (keys, values) x (the prompt's positions, the decoded ones).
# Typical: sound 1.185 .. 1.197 over the 12 seeds, the control 2.999 ..
# 3.268; worst slot: sound 1.258 .. 1.280, the control 3.924 .. 3.972 (its
# LEAST slot reads 2.66). Each limit is 1.5 x and more over the sound
# seeds' worst and 0.63 x / 0.56 x the control's least. Slot ``l`` for every
# loop step reads 112.7 and more here, the output norms left out 63.1 and
# more; a loop step short and the final norm at the end only leave the
# first loop step as it is (1.19 .. 1.20).
CACHE_PASSES = 1
CACHE_OVER_FLOOR = 1.9
CACHE_OVER_FLOOR_WORST = 2.2
# The logits of ``prefill`` + 15 ``decode_step``s of 2 rows, a position at
# a time over the 32 (``serve_lm``'s r / f, both over all positions
# together, is the ratio at the few worst positions: 0.76 .. 2.65 sound
# here, planted faults from 1.5). Typical position: sound 0.944 .. 1.943
# (ten of the twelve 1.11 .. 1.44); a loop step short 5.59 .. 17.25, the
# output norms left out 4.55 .. 13.64, the final norm at the end only 9.88
# .. 98.2 (4 seeds each). Worst position: sound 1.249 .. 2.730; the same
# three 18.9 .. 25.3, 9.28 .. 19.7, 45.1 .. 156: one position off a
# thousandfold moves the typical one 1.24 x and this one a thousandfold.
# Each limit is 1.5 x the worst sound seed, 0.64 x / 0.44 x the least of
# those. NOT for these two to tell apart: slot ``l`` for every loop step
# (2.37 .. 4.76 and 3.16 .. 11.7: prefill's logits are sound under it; the
# cache above and the exit distribution below read it) and the int8
# control (1.66 .. 4.73 and 1.76 .. 10.3; the cache above reads it).
RMS_OVER_FLOOR = 2.9
RMS_OVER_FLOOR_WORST = 4.1
# The exit distribution of the same positions, the same way: its rows sum
# to 1 within EXIT_ROWS_SUM_WITHIN (1.2e-7 at most), and its typical
# position's distance from the reference's over what rounding alone does
# to it reads 0.908 .. 2.299 sound; the final norm at the end only 8.7 ..
# 30.3, one slot a layer 26.7 .. 55.5, the output norms left out 27.7 ..
# 68.0; a loop step short has a distribution of another length. The limit
# is 1.5 x the worst sound seed, 0.4 x the least fault. One exact check
# (``exits_off``); the gate reads every loop step's state, so it is the
# number that tells all four faults apart on every seed. (Its worst
# position, four numbers against four, reads 3.5 .. 14.3 sound: reported.)
EXIT_OVER_FLOOR = 3.45
EXIT_ROWS_SUM_WITHIN = 1e-5
# The served tokens of 2 of the window's requests, 2 x 256: the widest gap
# of a token under the reference's best, over what rounding alone does to
# the logits AT THAT POSITION (the reference once more over the served
# sequences with its activations rounded: ``token_deficit_over_floor``).
# Over the logits' standard deviation, as ``serve_lm`` reads it, sound
# runs reach 0.661 (the rounded reference itself 0.794) and an altered
# token reads from 0.75: greedy sequences of this random model repeat one
# or two tokens, a near-tie recurs for a hundred steps, and where the
# logits are nearly flat another token is not far under. Over its own
# floor a near-tie reads 0 .. 9.50 (12 seeds; seven of them 0 .. 2.3), and
# the next token id in a served token's place 32.4 .. 742. The limit is
# 2.5 x the worst sound reading and 0.74 x the least altered one. The
# planted faults read 0 .. 475: 0 where the model serves one token
# whatever the loop does (4 of 16), so this guards the timed path's gross
# faults and the three numbers above guard the architecture.
# ``token_deficit_over_std`` stays in the record's checks, reported.
TOKEN_OVER_FLOOR = 24.0
NEEDS_OF_THE_PROGRAM = ("loop_steps", "early_exit_threshold",
                        "sandwich_norm")


def model_kwargs(config: dict, seq: int, attn_impl: str) -> dict:
    """Hugging Face key names -> ``TransformerConfig`` fields (dtypes as
    strings: this dict crosses a process boundary)."""
    return dict(
        lm.model_kwargs(config, seq, attn_impl),
        loop_steps=config["total_ut_steps"],
        early_exit_threshold=float(config["early_exit_threshold"]),
        sandwich_norm=True)


def transformer_config(kwargs: dict, remat: bool):
    """Raises in words where the program lacks what the family needs."""
    from ray_tpu.models import TransformerConfig
    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [k for k in NEEDS_OF_THE_PROGRAM if k not in have]
    if missing:
        raise ValueError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            "run a looped stack (the layers run several times over one set "
            "of weights, a norm on each sublayer's output, an exit gate)")
    return lm.transformer_config(kwargs, remat)


def reference_weights(params: dict, config: dict):
    """The program's parameter tree as the reference's plain matrices: the
    llama tree's, the two output norms of each layer and the gate."""
    ref = lm.reference_module(config)
    llama = lm.reference_weights(params, {"family": "llama"})
    layers = params["layers"]

    def layer(i: int) -> dict:
        return dict(llama.layer(i), ln1_post=layers["ln1_post"][i],
                    ln2_post=layers["ln2_post"][i])

    return ref.Weights(embed=llama.embed, layer=layer,
                       n_layers=llama.n_layers, final_norm=llama.final_norm,
                       lm_head=llama.lm_head,
                       gate_w=params["exit_gate"]["w"],
                       gate_b=params["exit_gate"]["b"])


class HostTicker:
    """A thread that asks to be woken every TICK_S and keeps the longest it
    was kept waiting beyond that since ``reset()``: what this process (the
    interpreter lock, the scheduler, the whole host) was stopped for while
    a call ran. One call in twenty of this cell takes 0.4-1.1 s longer than
    the others, which agree to a millisecond; with this beside the call's
    own time a record says whether the host stood still or the device
    did."""
    TICK_S = 0.005

    def __init__(self):
        import threading
        self.pause = 0.0
        threading.Thread(target=self._run, daemon=True,
                         name="bench-host-ticker").start()

    def _run(self):
        last = time.monotonic()
        while True:
            time.sleep(self.TICK_S)
            now = time.monotonic()
            self.pause = max(self.pause, now - last - self.TICK_S)
            last = now

    def reset(self) -> None:
        self.pause = 0.0

    def longest(self) -> float:
        return self.pause


def reduce_trace(path: str, scopes: dict) -> dict:
    """``trace.reduce_file`` and, under ``scopes``, the first chip's
    (the cell's only one) ``trace_scopes.reduce_device``, from one reading
    of the ``.xplane.pb``; ``{}`` without a whole period."""
    from benchmark import trace as trace_mod
    from benchmark import trace_scopes
    devices, host = trace_mod.read_xplane(path)
    reduced = trace_mod.combine(
        [trace_mod.reduce_device(ops, async_ops, modules, host)
         for ops, async_ops, modules in devices.values()])
    if reduced:
        ops, _, modules = devices[min(devices)]
        reduced["scopes"] = trace_scopes.reduce_device(ops, modules, scopes)
    return reduced


def reduce_apart(path: str, scopes: dict) -> dict:
    """``reduce_trace`` in a child process that opens no accelerator (this
    module run as a program)."""
    import subprocess
    import sys
    import tempfile
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with tempfile.TemporaryDirectory() as tmp:
        asked, told = os.path.join(tmp, "in.json"), \
            os.path.join(tmp, "out.json")
        with open(asked, "w") as f:
            json.dump({"path": path, "scopes": scopes}, f)
        subprocess.run(
            [sys.executable, "-m", "benchmark.apps.serve_ouro", asked, told],
            check=True, cwd=checkout, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(told) as f:
            return json.load(f)


def make_replica(max_batch_size: int, batch_wait_timeout_s: float):
    """``serve_lm``'s replica class with what a looped stack changes."""
    from ray_tpu import serve
    base = serve_lm.make_replica(max_batch_size, batch_wait_timeout_s)

    class OuroReplica(base):
        def __init__(self, spec: dict):
            self.stamps = {"entry": time.time()}
            from functools import partial
            import threading

            import jax
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models import generate_with_stats, transformer_init

            self.jax, self.jnp, self.np = jax, jnp, np
            self.spec = spec
            self.compiles = lm.CompileCounter()
            self.devs = jax.devices()
            self.stamps["devices"] = time.time()
            self.facts = lm.device_facts()
            lm.require_chips(self.facts, 1, spec["rehearse"])
            self.cfg = cfg = transformer_config(spec["model"], remat=False)
            self.params = jax.jit(partial(transformer_init, cfg=cfg))(
                jax.random.PRNGKey(lm.fold_seed(spec["seed"])))
            jax.block_until_ready(self.params)
            self.stamps["init"] = time.time()
            self.rows, self.prompt = spec["rows"], spec["prompt_tokens"]
            gen = jax.jit(partial(generate_with_stats, cfg=cfg,
                                  temperature=0.0,
                                  max_new_tokens=spec["new_tokens"]))
            prompts = jnp.zeros((self.rows, self.prompt), jnp.int32)
            self.gen = gen.lower(self.params, prompts).compile()
            self.gen_memory = lm.compiled_peak(self.gen)
            # {instruction name: rt.* scope}, what the trace's events are
            # mapped by
            from benchmark import trace_scopes
            self.scopes = trace_scopes.scope_map(self.gen.as_text()) \
                if spec["trace"] else {}
            self.stamps["ready"] = time.time()
            self.lock = threading.Lock()    # one generate call at a time
            self.requests, self.batches, self.profiler = {}, [], []
            self.inside, self.inside_max = 0, 0     # requests in __call__
            self.count_lock = threading.Lock()
            self.reduced, self.marks, self.stopper = {}, None, None
            self.seen = {}      # what the last pass of the program saw
            self.passes = {}    # the reference's passes, kept until read
            self.ticker = HostTicker()

        def program_logits(self, params, tokens):
            """As ``serve_lm``'s, through ``prefill_and_exits`` and
            ``decode_step_and_exits``; the logits, the exit distributions
            of the same positions [rows, decoded + 1, T] and the cache the
            steps left, its first CACHE_PASSES loop steps' slots up to the
            last position written, are kept as ``self.seen``."""
            from functools import partial

            from ray_tpu.models.generate import (decode_step_and_exits,
                                                 prefill_and_exits)
            jax, jnp = self.jax, self.jnp
            p, new = self.prompt, self.spec["new_tokens"]
            logits, cache, exits = jax.jit(partial(
                prefill_and_exits, cfg=self.cfg, max_len=p + new))(
                    params, tokens[:, :p])
            system, gates = [logits], [exits]
            step = jax.jit(partial(decode_step_and_exits, cfg=self.cfg))
            for j in range(tokens.shape[1] - p):
                logits, cache, exits = step(
                    params, tokens[:, p + j], jnp.asarray(p + j, jnp.int32),
                    cache)
                system.append(logits)
                gates.append(exits)
            logits = jnp.stack(system, axis=1)
            slots = CACHE_PASSES * self.cfg.n_layers
            self.seen = {"logits": logits, "exits": jnp.stack(gates, axis=1),
                         "cache": {name: stack[:slots, :, :tokens.shape[1]]
                                   for name, stack in cache.items()}}
            return logits

        def reference_pass(self, tokens, eps: float, dtype=None) -> dict:
            """One pass of the plain reference -> its logits, exit
            distribution [rows, S, T] and the first CACHE_PASSES loop
            steps' keys and values; kept until ``self.passes`` is cleared,
            so that ``serve_lm``'s checks and this family's read one pass."""
            key = (str(dtype or "float32"), float(eps),
                   self.np.asarray(tokens).tobytes())
            if key not in self.passes:
                config = self.spec["config"]
                logits, exits, cache = lm.reference_module(
                    config).forward_and_cache(
                        reference_weights(self.params, config), tokens,
                        config, eps=eps, dtype=dtype,
                        passes_kept=CACHE_PASSES)
                self.passes[key] = {"logits": logits, "exits": exits,
                                    "cache": cache}
            return self.passes[key]

        def _reference(self, tokens, eps: float, dtype=None):
            return self.reference_pass(tokens, eps, dtype)["logits"]

        def selfcheck(self) -> dict:
            """``serve_lm``'s, and through the same cache against the same
            pass of the reference: the exit distribution of the same
            positions, and the keys and values that ``prefill`` and the
            ``decode_step``s wrote for the first loop step."""
            jnp, np = self.jnp, self.np
            out = super().selfcheck()
            reference = lm.reference_module(self.spec["config"])
            p = self.prompt
            k = min(CHECK_DECODED, self.spec["new_tokens"] - 1)
            full = self.reference_pass(
                jnp.asarray(self.checked["tokens"]),
                lm.program_rms_norm_eps(self.cfg))
            program = self.seen
            # on the host for aftercheck(): nothing of the check stays on
            # the device while the window runs
            self.checked.update(
                exits=np.asarray(full["exits"][:, p - 1:p + k]),
                cache={n: np.asarray(v) for n, v in full["cache"].items()},
                program={n: np.asarray(program[n])
                         for n in ("logits", "exits")})
            out.update(reference.compare_exits(
                self.checked["program"]["exits"], self.checked["exits"]))
            out["cache_errors"] = np.asarray(reference.cache_errors(
                program["cache"], full["cache"], p)).tolist()
            self.seen, self.passes = {}, {}
            return out

        def aftercheck(self, pairs: list) -> dict:
            """``serve_lm``'s, and from its pass of the reference with the
            activations rounded to the file's ``torch_dtype``, over
            ``selfcheck``'s positions: what rounding alone does to the
            logits, the exit distribution and the first loop step's keys
            and values, and the program's error over each, one position
            (or slot) at a time. Then one more rounded pass, over the served
            sequences: each served token's gap over its own position's
            floor."""
            np = self.np
            out = super().aftercheck(pairs)
            config, p = self.spec["config"], self.prompt
            k = min(CHECK_DECODED, self.spec["new_tokens"] - 1)
            reference = lm.reference_module(config)
            eps = lm.program_rms_norm_eps(self.cfg)
            dtype = self.jnp.dtype(config["torch_dtype"])
            rounded = self.reference_pass(
                self.jnp.asarray(self.checked["tokens"]), eps, dtype)
            near = {n: rounded[n][:, p - 1:p + k]
                    for n in ("logits", "exits")}
            program = self.checked["program"]
            out["floor_exit_rms"] = reference.compare_exits(
                near["exits"], self.checked["exits"])["exit_rms"]
            for name, mine in (("rms", "logits"), ("exit", "exits")):
                over = reference.over_floor(
                    reference.errors_a_position(program[mine],
                                                self.checked[mine]),
                    reference.errors_a_position(near[mine],
                                                self.checked[mine]))
                out[name + "_over_floor_a_position"] = over["typical"]
                out[name + "_over_floor_worst_position"] = over["worst"]
            out["floor_cache_errors"] = np.asarray(reference.cache_errors(
                rounded["cache"], self.checked["cache"], p)).tolist()
            new = self.spec["new_tokens"]
            fed = self.jnp.asarray(
                [list(prompt) + list(served[:new - 1])
                 for prompt, served in pairs], self.jnp.int32)
            out["token_deficit_over_floor"] = \
                reference.token_deficit_over_floor(
                    self.reference_pass(fed, eps)["logits"][:, p - 1:],
                    self.reference_pass(fed, eps, dtype)["logits"][:, p - 1:],
                    [list(served) for _, served in pairs])
            return out

        @serve.batch(max_batch_size=max_batch_size,
                     batch_wait_timeout_s=batch_wait_timeout_s)
        def generate_batch(self, items: list) -> list:
            """``serve_lm``'s, inside the program's ``generate.call`` span
            with the exit gate's counter, fetched with the tokens."""
            from benchmark import trace as trace_mod
            from ray_tpu.models.generate import call_span
            jax, np = self.jax, self.np
            prompts = np.zeros((self.rows, self.prompt), np.int32)
            for i, (prompt, _) in enumerate(items):
                prompts[i, :len(prompt)] = prompt
            with self.lock:
                tracing = self.spec["trace"] and self.marks is not None
                index = len(self.batches) - self.marks["batches"] \
                    if tracing else -1
                if tracing and index == TRACE_FROM_BATCH:
                    a = time.time()
                    trace_mod.start(self.spec["trace_dir"])
                    self.profiler.append([a, time.time()])
                if tracing and index == TRACE_FROM_BATCH + 1:
                    self._stop_trace(TRACE_INTO_NEXT_S)
                self.ticker.reset()
                start = time.time()
                with jax.profiler.TraceAnnotation("bench.generate"), \
                        call_span(self.cfg, self.rows, self.prompt,
                                  self.spec["new_tokens"]) as sp:
                    called = self.gen(self.params, self.jnp.asarray(prompts))
                    dispatched = time.time()
                    tokens, stats = jax.device_get(called)
                    exit_steps_mean = float(stats["exit_steps_sum"]
                                            / stats["exit_tokens"])
                    sp.set(exit_steps_mean=exit_steps_mean)
                end = time.time()
                self.batches.append({"start": start, "end": end,
                                     "rows": len(items),
                                     "padded_rows": self.rows,
                                     "exit_steps_mean": exit_steps_mean,
                                     "dispatch_s": dispatched - start,
                                     "host_pause_max_s":
                                         self.ticker.longest(),
                                     "rids": [rid for _, rid in items]})
            return [tokens[i].tolist() for i in range(len(items))]

        def _stop_trace(self, after_s: float = 0.0) -> None:
            """``serve_lm``'s, ``after_s`` from now: on a thread of its
            own, so that no batch waits for the trace to be written out."""
            import threading

            def stop():
                time.sleep(after_s)
                a = time.time()
                self.jax.profiler.stop_trace()
                self.profiler.append([a, time.time()])
            self.stopper = threading.Thread(target=stop, daemon=True,
                                            name="bench-stop-trace")
            self.stopper.start()

        def dump(self) -> dict:
            """``serve_lm``'s, with the trace read once for both
            reductions (by whole periods, and by the program's scopes), in
            a process of its own: a call's 1.76 M device events take the
            interpreter lock for longer than the controller waits for a
            replica's health ping (10 s), and a replica that misses one is
            killed and made anew."""
            from benchmark import trace as trace_mod
            if self.profiler and self.stopper is None:
                self._stop_trace()          # the window was too short
            if self.stopper is not None:
                self.stopper.join()
            profiler = self.profiler
            if profiler:
                self.reduced = reduce_apart(
                    trace_mod.find_xplane(self.spec["trace_dir"]),
                    self.scopes)
            self.profiler = []      # ``serve_lm`` would read it all again
            try:
                return dict(super().dump(), profiler=profiler)
            finally:
                self.profiler = profiler

    return OuroReplica


def judged(record: dict, config: dict, traffic: dict) -> dict:
    """``serve_lm``'s exact checks, and this family's numbers under its own
    limits, each the program's error over what rounding alone does, one
    position, slot or token at a time (``reference.over_floor``): the first
    loop step's keys and values in the cache, the logits, the exit
    distribution, the served tokens: ``{name: [value, limit]}``.
    ``serve_lm``'s two ratios over all positions together are in the
    record's ``checks`` and not judged here."""
    out = serve_lm.judged(record, config, traffic)
    checks = record["checks"]
    del out["token_deficit_over_std"]
    cache = lm.reference_module(config).over_floor(
        checks["cache_errors"], checks["floor_cache_errors"])
    out["cache_over_floor"] = [cache["typical"], CACHE_OVER_FLOOR]
    out["cache_over_floor_worst"] = [cache["worst"], CACHE_OVER_FLOOR_WORST]
    out["rms_over_floor"] = [checks["rms_over_floor_a_position"],
                             RMS_OVER_FLOOR]
    out["rms_over_floor_worst"] = [checks["rms_over_floor_worst_position"],
                                   RMS_OVER_FLOOR_WORST]
    out["token_deficit_over_floor"] = [checks["token_deficit_over_floor"],
                                       TOKEN_OVER_FLOOR]
    out["exits_off"] = [
        int(not (checks["exit_rows_off_one"] <= EXIT_ROWS_SUM_WITHIN
                 and checks["exit_over_floor_a_position"]
                 <= EXIT_OVER_FLOOR)), 0]
    return out


WHAT_EACH_CHECK_SAYS = dict(
    {name: says for name, says in serve_lm.WHAT_EACH_CHECK_SAYS.items()
     if name != "token_deficit_over_std"},
    cache_over_floor="the keys and values that prefill and the decode steps "
                     "left in the first loop step's cache slots are off the "
                     "reference's (rms), at the typical slot (the geometric "
                     "mean over slots, keys and values, prompt and decoded "
                     "positions), by this many times what bfloat16 rounding "
                     "alone does to them",
    cache_over_floor_worst="the same at the worst slot",
    rms_over_floor="at the typical position (the geometric mean over the "
                   "compared positions) prefill+decode logits are off the "
                   "reference (rms) by this many times what bfloat16 "
                   "rounding alone does to this seed's model there",
    rms_over_floor_worst="the same at the worst position",
    token_deficit_over_floor="a token the compiled generate served lies "
                             "under the reference's best by this many times "
                             "what bfloat16 rounding alone does to the "
                             "logits at its position",
    exits_off="the exit distribution through prefill+decode does not sum "
              "to 1 over the loop steps, or at the typical position is off "
              "the reference's by more than EXIT_OVER_FLOOR times what "
              "bfloat16 rounding alone does to it (exit_rows_off_one, "
              "exit_over_floor_a_position in the record's checks)")


def judge(record: dict, config: dict, traffic: dict) -> list:
    """-> reasons this run is not correct (empty: correct), each naming
    the check, its number and its limit. Leaves ``record["judged"]``."""
    record["judged"] = judged(record, config, traffic)
    return lm.over_their_limits(record["judged"], WHAT_EACH_CHECK_SAYS)


def drive(run) -> dict:
    """``run`` is ``benchmark.run.RunContext``. -> the run's record."""
    import numpy as np

    run.phase("configure")
    cell = run.cell
    config = lm.effective_config(cell["config_data"], run.rehearse)
    traffic = lm.effective_traffic(cell["traffic_data"], run.rehearse)
    spec = {
        "seed": run.seed, "trace": run.trace,
        "trace_dir": run.path("trace"), "rehearse": run.rehearse,
        "config": config,
        "model": model_kwargs(
            config, traffic["prompt_tokens"] + traffic["new_tokens"],
            "auto"),
        "rows": traffic["max_batch_size"],
        "prompt_tokens": traffic["prompt_tokens"],
        "new_tokens": traffic["new_tokens"],
    }
    # Here, in the benchmark's own process and before anything starts: a
    # program without the family's mechanisms refuses the configuration at
    # once (importing the models touches no backend), and no replica dies
    # in a worker while this process waits out its deadline.
    transformer_config(spec["model"], remat=False)
    # Every process of the run allocates from one arena, traced or not, so
    # that both kinds of run time one program: in a traced run the profiler
    # writes the trace out on a thread of the replica's, 12 GB of small
    # allocations, and glibc grows a thread's own arena by system calls
    # that this sandbox makes slow (a period's trace in 241 s, in 46 s
    # from one arena). The runtime's daemon, and so every worker, inherits
    # this process's environment.
    os.environ["MALLOC_ARENA_MAX"] = "1"

    import ray_tpu as rt
    from benchmark.loadgen import Loadgen
    from ray_tpu import serve

    run.phase("rt.init")
    run.init_runtime(rt, cell["chips"])
    replica_cls = make_replica(traffic["max_batch_size"],
                               traffic["batch_wait_timeout_s"])
    deployment = serve.deployment(
        replica_cls, name="lm", route_prefix="/lm", init_grace_s=900.0,
        max_ongoing_requests=traffic["max_ongoing_requests"],
        ray_actor_options={"num_tpus": 0 if run.rehearse else 1})
    run.phase("lease+replica")
    called = time.time()
    try:
        handle = serve.run(deployment.bind(spec), http_host="127.0.0.1",
                           http_port=0)          # port 0: the OS picks one
        run.serve = serve
        run.phase("selfcheck")
        checks = rt.get(handle.options(method_name="selfcheck").remote(),
                        timeout=900)
    except Exception as e:
        raise run.failure(f"replica did not come up: {e!r}",
                          before_window=True) from e

    seed = lm.fold_seed(run.seed)
    vocab, plen = config["vocab_size"], traffic["prompt_tokens"]

    def body(rid: int) -> bytes:
        # requests 0 and 1 (both in the warm-up round) carry one prompt
        prompt = np.random.default_rng([seed, max(rid, 1)]).integers(
            0, vocab, plen)
        return json.dumps({"prompt": prompt.tolist(), "rid": rid}).encode()

    def parse(data: bytes) -> tuple:
        tokens = json.loads(data)["tokens"]
        return True, len(tokens), {"tokens": tokens}

    gen = Loadgen("127.0.0.1", handle.http_port, "/lm", traffic, body, parse)
    run.phase("warmup")
    warmup = gen.warmup()
    bad = [r for r in warmup if not r["ok"]]
    if bad:
        raise run.failure(f"{len(bad)} of {len(warmup)} warm-up requests "
                          f"failed, e.g. {bad[0]}", before_window=True)
    rt.get(handle.options(method_name="mark").remote(), timeout=60)
    run.phase("window")
    window = gen.window(run.seconds)
    run.phase("dump")
    record = serve_lm.patiently(rt, handle, "dump")
    run.phase("aftercheck")
    # CHECK_ROWS requests the window finished, drawn from the seed; a
    # window too short to finish that many falls back on the warm-up's
    done = sorted((r for r in window["rows"] if r["ok"]
                   and len(r["extra"]["tokens"]) == traffic["new_tokens"]),
                  key=lambda r: r["rid"])
    if len(done) < CHECK_ROWS:
        done = [r for r in warmup if r["rid"] > 0]
    picks = np.random.default_rng([seed, 0x5A3D]).choice(
        len(done), size=min(CHECK_ROWS, len(done)), replace=False)
    sample = [done[int(i)] for i in sorted(picks)]
    pairs = [(json.loads(body(r["rid"]))["prompt"], r["extra"]["tokens"])
             for r in sample]
    checks["tokens_checked_of"] = [r["rid"] for r in sample]
    checks.update(serve_lm.patiently(rt, handle, "aftercheck", pairs))
    record["stamps"]["called"] = called
    record["window_start"] = window["start"]
    record["request_timeout_s"] = gen.timeout
    record["host_cpus"] = os.cpu_count()
    log(f"regime: host has {record['host_cpus']} cpus; at most "
        f"{record['admitted_max']} of {traffic['clients']} callers' requests "
        "were inside the replica at once")
    calls = sorted(b["end"] - b["start"] for b in record["batches"])
    for b in record["batches"]:
        took = b["end"] - b["start"]
        if took > 1.02 * calls[len(calls) // 2] + 0.1:
            log(f"slow call: {took:.3f} s against a median of "
                f"{calls[len(calls) // 2]:.3f}; dispatch took "
                f"{b['dispatch_s']:.3f} s, and the longest this process "
                f"was kept waiting during it was "
                f"{b['host_pause_max_s']:.3f} s")
    record["checks"] = checks
    record["warmup"] = warmup
    record["window"] = window
    rows = window["rows"]
    record["attempted"] = len(rows)
    record["failed"] = sum(1 for r in rows if not r["ok"])
    record["why_not_correct"] = judge(record, config, traffic)
    run.phase("shutdown")
    return record


if __name__ == "__main__":              # ``reduce_apart``'s child
    import sys
    with open(sys.argv[1]) as f:
        asked = json.load(f)
    with open(sys.argv[2], "w") as f:
        json.dump(reduce_trace(asked["path"], asked["scopes"]), f)

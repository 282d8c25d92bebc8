"""The serving application: one replica behind ``serve.run`` and the proxy.

``drive`` runs in the benchmark's process and never touches JAX: it deploys
the replica with ``num_tpus=1``, waits for it, has it check itself against
the plain reference, sends a warm-up round and then the window's traffic
through the HTTP proxy with ``benchmark.loadgen``, asks the replica for its
rows, and last has it hold the tokens its compiled ``generate`` returned in
the warm-up round against the reference. The replica is what a user would
write: weights made on the device from the seed in the served dtype, a
greedy ``generate`` of one shape compiled once, and a handler under
``@serve.batch`` that pads a short batch to the full rows.
"""

from __future__ import annotations

import json
import os
import time

from benchmark.apps import lm
from benchmark.hermetic import log

TRACE_FROM_BATCH = 2       # traced run: profile from the start of the
TRACE_BATCHES = 3          # window's 3rd batch to the end of its 5th:
#                            3 executions in the trace = 2 whole periods
DUMP_PATIENCE_S = 120      # a request the proxy gave up on may still be in
#                            the replica and hold the dump at its cap
CHECK_ROWS = 2             # self-check: rows, and decoded positions after
CHECK_DECODED = 15         # the prefill: 2 * 16 * vocab ~ 1e6 logits
# Error of prefill-then-decode logits against the reference's full forward,
# as shares of the reference logits' own standard deviation (~1.28 with
# these random weights). The weights are the same bfloat16 values on both
# sides, so what is judged is the system's bfloat16 arithmetic through 24
# layers and its cache.
#
# rms: 0.0154 .. 0.0218 over the 9 seeds tried on the chip (PR 24). Judged
# at 0.04: 1.8x the worst seen. bfloat16 rounds each activation to 2**-9
# relative (0.11% rms); int8 weights (absmax per channel, normal weights:
# step 4 sigma / 127, rms error 0.9% of sigma) perturb every product ~8x more
# and fp8-e4m3 (3 mantissa bits, 3.6%) ~30x more, which adds in quadrature
# to ~0.12 and ~0.5 of std: both fail, with room.
RMS_TOLERANCE = 0.04
# max over ~1e6 logits: wanders from seed to seed (0.090 .. 0.139 of std
# over the same seeds; the tail of a million near-normal errors sits ~5 sigma
# out and one outlier moves it). Kept as a loose guard against a single
# wrong position or row, which moves one logit by O(1) std: 0.3 is 2.2x the
# worst seen.
MAX_TOLERANCE = 0.3
# The logits above come from ``prefill`` and ``decode_step`` jitted on their
# own; what the callers get is the compiled ``generate``'s tokens. Two
# warm-up replies' first CHECK_DECODED + 1 tokens are held against the
# reference's logits at the positions that predicted them (teacher-forced
# on the system's own tokens): each must be the reference's argmax or lie
# within this share of std under it. The system's choice departs from the
# argmax only where the reference's top two lie closer than the difference
# of the system's two errors, at most 2 x 0.139 of std by the maxima above
# and ~0.05 typically; a token from a wrong loop, cache update or sampler
# lies ~4 std under.
TOKEN_TOLERANCE = 0.3
# rms distance between the reference at the published epsilon (1e-5) and at
# the one the program runs (fixed at 1e-6), as a share of std: the known
# difference, held apart from the arithmetic above so that it cannot hide in
# it. Read 0.0368 .. 0.0475 on the chip (PR 24, 4 seeds) and 0.0434, 0.0564
# on the CPU (2 seeds, other tokens): 0.1 is 1.8x the worst. A program that
# takes the configuration's epsilon reads exactly 0, and the next benchmark
# PR sets this to 0.
EPS_GAP_TOLERANCE = 0.1


def make_replica(max_batch_size: int, batch_wait_timeout_s: float):
    """The replica's class, with ``@serve.batch`` set from the traffic file
    (a decorator's arguments are fixed when the class is made)."""
    from ray_tpu import serve

    class LMReplica:
        def __init__(self, spec: dict):
            self.stamps = {"entry": time.time()}
            from functools import partial
            import threading

            import jax
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models import generate, transformer_init

            self.jax, self.jnp, self.np = jax, jnp, np
            self.spec = spec
            self.compiles = lm.CompileCounter()
            self.devs = jax.devices()
            self.stamps["devices"] = time.time()
            self.facts = lm.device_facts()
            lm.require_chips(self.facts, 1, spec["rehearse"])
            self.cfg = cfg = lm.transformer_config(spec["model"],
                                                   remat=False)
            self.params = jax.jit(partial(transformer_init, cfg=cfg))(
                jax.random.PRNGKey(lm.fold_seed(spec["seed"])))
            jax.block_until_ready(self.params)
            self.stamps["init"] = time.time()
            self.rows, self.prompt = spec["rows"], spec["prompt_tokens"]
            gen = jax.jit(partial(generate, cfg=cfg, temperature=0.0,
                                  max_new_tokens=spec["new_tokens"]))
            prompts = jnp.zeros((self.rows, self.prompt), jnp.int32)
            self.gen = gen.lower(self.params, prompts).compile()
            self.gen_memory = lm.compiled_peak(self.gen)
            self.stamps["ready"] = time.time()
            self.lock = threading.Lock()    # one generate call at a time
            self.requests, self.batches, self.profiler = {}, [], []
            self.inside, self.inside_max = 0, 0     # requests in __call__
            self.count_lock = threading.Lock()
            self.reduced, self.marks, self.stopper = {}, None, None

        def selfcheck(self) -> dict:
            """Prefill through the cache, then further decoded positions,
            against the plain reference's full forward, on logits."""
            from functools import partial

            from ray_tpu.models.generate import decode_step, prefill
            jax, jnp, np = self.jax, self.jnp, self.np
            spec, cfg = self.spec, self.cfg
            config = spec["config"]
            p, k = self.prompt, min(CHECK_DECODED, spec["new_tokens"] - 1)
            tokens = jnp.asarray(np.random.default_rng(
                [lm.fold_seed(spec["seed"]), 0xC4EC]).integers(
                    0, cfg.vocab_size, (CHECK_ROWS, p + k), dtype=np.int32))
            logits, cache = jax.jit(partial(
                prefill, cfg=cfg, max_len=p + spec["new_tokens"]))(
                    self.params, tokens[:, :p])
            system = [logits]
            step = jax.jit(partial(decode_step, cfg=cfg))
            for j in range(k):
                logits, cache = step(self.params, tokens[:, p + j],
                                     jnp.asarray(p + j, jnp.int32), cache)
                system.append(logits)
            system = jnp.stack(system, axis=1)       # [rows, k + 1, vocab]
            del cache
            reference = lm.reference_module(config)
            full = self._reference(tokens, lm.program_rms_norm_eps(cfg))
            out = reference.compare_logits(system, full[:, p - 1:p + k])
            # for aftercheck(), on the host: nothing of the check stays on
            # the device while the window runs
            self.checked = {"tokens": np.asarray(tokens),
                            "logits": np.asarray(full[:, p - 1:p + k])}
            out["prefill_max_over_std"] = float(jnp.max(jnp.abs(
                system[:, 0] - full[:, p - 1]))) / out["reference_std"]
            leaves = jax.tree.leaves(self.params)
            out.update(
                rms_tolerance=RMS_TOLERANCE, max_tolerance=MAX_TOLERANCE,
                n_params=int(sum(x.size for x in leaves)),
                param_dtypes=sorted({str(x.dtype) for x in leaves}))
            self.stamps["checked"] = time.time()
            return out

        def _reference(self, tokens, eps: float):
            config = self.spec["config"]
            return lm.reference_module(config).forward(
                lm.reference_weights(self.params, config), tokens, config,
                eps=eps)

        def aftercheck(self, pairs: list) -> dict:
            """After the window, so that nothing of it sits between the
            warm-up and the measured calls (``generate`` leaves 0.7 GB of
            the chip free). ``pairs``: CHECK_ROWS x (prompt, the tokens
            ``generate`` returned for it through the proxy and the batcher
            in the warm-up round): their first decoded positions against
            the reference, teacher-forced. And the reference at the
            published epsilon on ``selfcheck``'s tokens against the one at
            the program's that judged the arithmetic. Both have the shape
            of ``selfcheck``'s forward."""
            spec, p = self.spec, self.prompt
            config = spec["config"]
            reference = lm.reference_module(config)
            k = min(CHECK_DECODED, spec["new_tokens"] - 1)
            published_eps = float(config["rms_norm_eps"])
            program_eps = lm.program_rms_norm_eps(self.cfg)
            tokens = self.jnp.asarray(
                [list(prompt) + list(new[:k]) for prompt, new in pairs],
                self.jnp.int32)
            out = reference.token_deficit(
                self._reference(tokens, program_eps)[:, p - 1:p + k],
                [list(new[:k + 1]) for _, new in pairs])
            out["token_tolerance"] = TOKEN_TOLERANCE
            out["rms_norm_eps"] = {"published": published_eps,
                                   "program": program_eps}
            out["program_eps_gap"] = 0.0 if program_eps == published_eps \
                else reference.compare_logits(
                    self.checked["logits"], self._reference(
                        self.jnp.asarray(self.checked["tokens"]),
                        published_eps)[:, p - 1:p + k])["rms_over_std"]
            out["eps_gap_tolerance"] = EPS_GAP_TOLERANCE
            return out

        @serve.batch(max_batch_size=max_batch_size,
                     batch_wait_timeout_s=batch_wait_timeout_s)
        def generate_batch(self, items: list) -> list:
            from benchmark import trace as trace_mod
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
                start = time.time()
                with jax.profiler.TraceAnnotation("bench.generate"):
                    tokens = np.asarray(self.gen(self.params,
                                                 self.jnp.asarray(prompts)))
                end = time.time()
                self.batches.append({"start": start, "end": end,
                                     "rows": len(items),
                                     "padded_rows": self.rows,
                                     "rids": [rid for _, rid in items]})
                if tracing and \
                        index == TRACE_FROM_BATCH + TRACE_BATCHES - 1:
                    self._stop_trace()
            return [tokens[i].tolist() for i in range(len(items))]

        def _stop_trace(self) -> None:
            """Writing the trace out takes seconds: on a thread of its
            own, so that neither this batch's replies nor the next batch
            wait for it (the proxy gives a request up after 30 s)."""
            import threading

            def stop():
                a = time.time()
                self.jax.profiler.stop_trace()
                self.profiler.append([a, time.time()])
            self.stopper = threading.Thread(target=stop, daemon=True,
                                            name="bench-stop-trace")
            self.stopper.start()

        def __call__(self, prompt: list, rid: int) -> dict:
            enter = time.time()
            if len(prompt) > self.prompt:
                raise ValueError(f"prompt of {len(prompt)} tokens; this "
                                 f"replica serves up to {self.prompt}")
            with self.count_lock:
                self.inside += 1
                self.inside_max = max(self.inside_max, self.inside)
            try:
                with self.jax.profiler.TraceAnnotation("bench.request"):
                    tokens = self.generate_batch((prompt, rid))
            finally:
                with self.count_lock:
                    self.inside -= 1
            self.requests[rid] = [enter, time.time()]
            return {"rid": rid, "tokens": tokens}

        def mark(self) -> None:
            """The window starts: what came before was warm-up."""
            self.marks = {"batches": len(self.batches),
                          "compiles": self.compiles.count}
            with self.count_lock:
                self.inside_max = self.inside

        def dump(self) -> dict:
            from benchmark import trace as trace_mod
            if self.profiler and self.stopper is None:
                self._stop_trace()          # the window was too short
            if self.stopper is not None:
                self.stopper.join()
            if self.profiler:
                self.reduced = trace_mod.reduce_file(
                    trace_mod.find_xplane(self.spec["trace_dir"]))
            return {
                "stamps": self.stamps, "facts": self.facts,
                "requests": {str(k): v for k, v in self.requests.items()},
                "batches": self.batches[self.marks["batches"]:],
                "profiler": self.profiler, "trace": self.reduced,
                "admitted_max": self.inside_max,
                "compiles_in_window":
                    self.compiles.count - self.marks["compiles"],
                "memory": lm.memory_report(self.devs, self.gen_memory,
                                           "generate")}

    return LMReplica


def judge(record: dict, config: dict, traffic: dict) -> list:
    """-> reasons this run is not correct (empty: correct)."""
    checks, why = record["checks"], []
    if not checks["rms_over_std"] <= checks["rms_tolerance"]:
        why.append(f"prefill+decode logits are off the reference by "
                   f"{checks['rms_over_std']:.4f} of its std (rms), over "
                   f"{checks['rms_tolerance']}")
    if not checks["max_over_std"] <= checks["max_tolerance"]:
        why.append(f"a logit is off the reference by "
                   f"{checks['max_over_std']:.3f} of its std, over "
                   f"{checks['max_tolerance']}")
    if not checks["program_eps_gap"] <= checks["eps_gap_tolerance"]:
        why.append(f"the reference at the published epsilon "
                   f"{checks['rms_norm_eps']['published']} and at the "
                   f"program's {checks['rms_norm_eps']['program']} differ by "
                   f"{checks['program_eps_gap']:.4f} of std (rms), over "
                   f"{checks['eps_gap_tolerance']}")
    if not checks["token_deficit_over_std"] <= checks["token_tolerance"]:
        why.append(f"a token the compiled generate returned lies "
                   f"{checks['token_deficit_over_std']:.3f} of std under "
                   f"the reference's best, over {checks['token_tolerance']}")
    if checks["param_dtypes"] != [config["param_dtype"]]:
        why.append(f"weights are {checks['param_dtypes']}, the "
                   f"configuration says {config['param_dtype']}")
    good = [r for r in record["warmup"] + record["window"]["rows"]
            if r["ok"]]
    vocab = config["vocab_size"]
    for r in good:
        toks = r["extra"]["tokens"]
        if len(toks) != traffic["new_tokens"] or \
                not all(0 <= t < vocab for t in toks):
            why.append(f"request {r['rid']}: {len(toks)} tokens, or one "
                       "outside the vocabulary")
            break
    twins = [r["extra"]["tokens"] for r in record["warmup"]
             if r["ok"] and r["rid"] in (0, 1)]
    if len(twins) != 2 or twins[0] != twins[1]:
        why.append("one prompt sent twice returned different tokens")
    return why


def patiently(rt, handle, method: str, *args):
    """A direct call to the replica after the window: a request the proxy
    gave up on may still be inside and hold the call at the replica's cap."""
    deadline = time.monotonic() + DUMP_PATIENCE_S
    while True:
        try:
            return rt.get(handle.options(method_name=method).remote(*args),
                          timeout=600)
        except Exception as e:              # noqa: BLE001 - re-raised
            if "ReplicaBusyError" not in repr(e) or \
                    time.monotonic() > deadline:
                raise
            time.sleep(1.0)


def drive(run) -> dict:
    """``run`` is ``benchmark.run.RunContext``. -> the run's record."""
    import numpy as np

    import ray_tpu as rt
    from benchmark.loadgen import Loadgen
    from ray_tpu import serve

    run.phase("configure")
    cell = run.cell
    config = lm.effective_config(cell["config_data"], run.rehearse)
    traffic = lm.effective_traffic(cell["traffic_data"], run.rehearse)
    spec = {
        "seed": run.seed, "trace": run.trace,
        "trace_dir": run.path("trace"), "rehearse": run.rehearse,
        "config": config,
        "model": lm.model_kwargs(
            config, traffic["prompt_tokens"] + traffic["new_tokens"],
            "auto"),
        "rows": traffic["max_batch_size"],
        "prompt_tokens": traffic["prompt_tokens"],
        "new_tokens": traffic["new_tokens"],
    }
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
        # The model is built and compiled in the replica's __init__; a
        # direct call waits for it without the proxy's request deadline.
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
        reply = json.loads(data)
        tokens = reply["tokens"]
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
    record = patiently(rt, handle, "dump")
    run.phase("aftercheck")
    # requests 1 and 2 carry two different prompts (with two callers only,
    # request 1 stands twice)
    replies = {r["rid"]: r["extra"]["tokens"] for r in warmup}
    pairs = [(json.loads(body(rid))["prompt"], replies[rid])
             for rid in (1, min(2, len(warmup) - 1))]
    checks.update(patiently(rt, handle, "aftercheck", pairs))
    record["stamps"]["called"] = called
    record["window_start"] = window["start"]
    record["request_timeout_s"] = gen.timeout
    record["host_cpus"] = os.cpu_count()
    expected = traffic.get("expect_admitted_max")
    log(f"regime: host has {record['host_cpus']} cpus; at most "
        f"{record['admitted_max']} of {traffic['clients']} callers' requests "
        f"were inside the replica at once (the cell states {expected})")
    if expected is not None and record["admitted_max"] != expected:
        log(f"ANOTHER REGIME than the cell states: admitted "
            f"{record['admitted_max']}, not {expected}; tokens/s, p95, "
            "batch.fill and ingress.proxy_ms do not compare with the "
            "ledger's")
    record["checks"] = checks
    record["warmup"] = warmup
    record["window"] = window
    rows = window["rows"]
    record["attempted"] = len(rows)
    record["failed"] = sum(1 for r in rows if not r["ok"])
    record["why_not_correct"] = judge(record, config, traffic)
    run.phase("shutdown")
    return record

"""The serving application of the ``olmo_hybrid`` family: ``serve_lm``'s
replica behind ``serve.run`` and the proxy, for a stack of gated-delta-rule
and softmax layers.

What is the family's own is here: how the configuration becomes the
program's ``TransformerConfig`` (the layer pattern, the linear layers'
widths, beta's factor 2, output-only norms, the q/k norm over the whole
projection, no rotation) and its parameter tree the reference's
``Weights``; a replica that compiles ``generate_with_stats`` and opens the
program's ``generate.call`` span around each call; ``prefill`` and
``decode_step`` through the cache against the reference's full forward, on
logits, and the STATE and the convolution's TAIL that they left in the
cache, after the prompt and after the decoded positions, against the
reference's ``S`` and last inputs; the served tokens' gaps over their own
positions' floor; device time by the program's scopes, by the call's two
phases, and by scope within the token loop; and the limits, read from this
family's own sweep. Everything else (the handler under ``@serve.batch``,
the exact checks, the host's ticker, the trace window and its reduction in
a child) is ``serve_lm``'s and ``serve_ouro``'s replica, subclassed.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

from benchmark.apps import lm, serve_lm, serve_ouro
from benchmark.hermetic import log

CHECK_ROWS = serve_lm.CHECK_ROWS
# Decode steps the check takes after the prompt. A recurrent layer's fault may
# sit in what a step CARRIES (a state rounded on its way through the cache
# adds a rounding a step, and only a head that remembers keeps them), so the
# check decodes as far as the cell does: every ``decode_step`` that a served
# row takes, 383 after the 128 of the prompt.
CHECK_DECODED = 383
# The step size's bias as the rule's authors draw it (Gated DeltaNet in the
# Flash Linear Attention library, after Mamba 2; as recalled, not fetched):
# softplus(dt_bias) log-uniform in [DT_MIN, DT_MAX]. ``transformer_init``'s
# ``dt_bias`` = 1 (Qwen3-Next's) makes softplus 1.31, and with ``A`` uniform
# up to 16 a head then forgets within a position or two: a stack whose carried
# state holds no history, which is the one thing this cell is there to carry.
DT_MIN, DT_MAX = 1e-3, 1e-1
TRACE_FROM_BATCH = 1       # as serve_ouro: one whole period, the window's
TRACE_INTO_NEXT_S = 1.0    # 2nd call and the start of its 3rd
NEEDS_OF_THE_PROGRAM = ("post_norm_only", "qk_norm_whole",
                        "linear_beta_scale")
DECODE = "rt.generate.decode"
# What ``correct`` holds a run to: LIMITS, each set from
# benchmark/testdata/olmo_hybrid_checks_sweep.json (my chip runs, PR 51, on
# a TPU v5e at the published widths and the cell's 128 + 383 positions, 2
# rows: sweep_olmo_hybrid.py beside it over 13 seeds, with the control, the
# reference over int8 weights with bfloat16 activations, and the
# architecture's six faults planted in the program on every one; the runs of
# the cell this PR made are in the same file; the table with every reading
# is in PERF.md section 2, and tests/benchmark/test_bench_olmo_hybrid.py
# holds these numbers to that file). Each ``*_over_floor`` is an rms error
# against the float32 reference over what the reference's own
# bfloat16-rounded activations do to the same seed's model at the same
# place, read one place at a time (``reference.over_floor``): the typical
# place (the geometric mean) and the worst. A post-normed stack's residual
# stream is not renormed on the way, so a rounding is amplified alike from
# seed to seed, and over 384 positions the typical place reads within 5 % from
# seed to seed. Sound (the 13 seeds and the review round's 10 runs of the
# cell) .. the control .. the least that any of the five gross faults read ..
# a state CARRIED IN BFLOAT16, in the comments; every limit has 1.3 x of room
# or more over the worst sound reading and lies at 0.67 x the control's least
# or under.
LIMITS = {
    # the logits of ``prefill`` + 383 ``decode_step``s, a position at a time:
    # typical 1.303-1.344 .. 2.951-3.022 .. 5.99 .. 1.63-1.86; worst
    # 1.588-1.765 .. 3.581-3.915 .. 7.33 .. 1.99-2.52
    "rms_over_floor": 1.8,
    "rms_over_floor_worst": 2.4,
    # the 15 linear slots' states [30, 96, 192] float32 as ``prefill`` left
    # them after position 127 and as 383 ``decode_step``s left them after
    # position 510, against the reference's S, a (slot, place) at a time:
    # typical 1.391-1.457 .. 3.604-3.833 .. 3.81 .. 1.66-1.88; worst
    # 1.946-1.991 .. 6.016-6.067 .. 193 .. 3.29-4.53. The worst place is
    # the first slot after the decoded positions: its input is the
    # embedding, nothing has amplified a rounding yet, and its heads
    # remember (DT_MIN / DT_MAX), so a rounding of the state a step adds up
    # there: this is the limit that a state carried in bfloat16 fails on
    # every seed, at 1.27 x or more (``state_not_float32`` holds the cache's
    # dtype exactly besides)
    "state_over_floor": 1.9,
    "state_over_floor_worst": 2.6,
    # the convolution's last 3 inputs in the same slots at the same two
    # places: typical 1.331-1.412 .. 4.075-4.171 .. 5.51 .. 1.59-1.79; worst
    # 1.501-1.632 .. 5.666-5.775 .. 52.5 .. 1.96-2.59
    "tail_over_floor": 1.9,
    "tail_over_floor_worst": 2.2,
    # the widest gap of a served token (2 x 384 of the window's) under the
    # reference's best, over what rounding alone does to the logits at its
    # position: 4.84-6.70 over the review round's 10 runs of the cell; the
    # next id in the place of one served token read 17.6-34.7 on the same
    # runs (``altered_token_over_floor``, reported by every run). 1.79 x
    # the worst sound run, 0.68 x the least altered one: a guard of the
    # served path's tokens against gross faults
    "token_deficit_over_floor": 12.0,
}


def layer_pattern(config: dict) -> tuple:
    """One period of the published ``layer_types`` in the program's names;
    refuses a pattern that does not repeat."""
    names = {"linear_attention": "linear", "full_attention": "full"}
    kinds = [names[t] for t in config["layer_types"]]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{config['num_hidden_layers']} layers")
    period = kinds.index("full") + 1 if "full" in kinds else len(kinds)
    if kinds != kinds[:period] * (len(kinds) // period):
        raise ValueError(f"layer_types {kinds} is no repeated period of "
                         f"{period}")
    return tuple(kinds[:period])


def model_kwargs(config: dict, seq: int, attn_impl: str) -> dict:
    """Hugging Face key names -> ``TransformerConfig`` fields (dtypes as
    strings: this dict crosses a process boundary)."""
    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("this family's app runs no rotation: "
                         "rope_parameters.rope_theta is not null")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq=seq,
        tied_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype=config["param_dtype"], attn_impl=attn_impl,
        norm_eps=float(config["rms_norm_eps"]),
        layer_types=list(layer_pattern(config)),
        linear_key_heads=config["linear_num_key_heads"],
        linear_value_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        linear_conv_kernel=config["linear_conv_kernel_dim"],
        linear_beta_scale=2.0 if config["linear_allow_neg_eigval"] else 1.0,
        post_norm_only=True, qk_norm_whole=True, partial_rotary_factor=0.0)


def transformer_config(kwargs: dict, remat: bool):
    """Raises in words where the program lacks what the family needs."""
    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig
    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [k for k in NEEDS_OF_THE_PROGRAM if k not in have]
    if missing:
        raise ValueError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            "run a post-normed stack of gated-delta-rule layers with beta up "
            "to 2 beside softmax layers normed over the whole projection")
    kwargs = dict(kwargs, layer_types=tuple(kwargs["layer_types"]),
                  param_dtype=jnp.dtype(kwargs["param_dtype"]))
    return TransformerConfig(**kwargs, remat=remat)


def seeded_params(cfg, seed: int):
    """``transformer_init``'s tree from the seed, with every linear layer's
    ``dt_bias`` drawn as DT_MIN / DT_MAX say (from the same seed)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import transformer_init

    def init(key):
        params = transformer_init(key, cfg=cfg)
        layers = []
        for at, stack in enumerate(params["layers"]):
            if "gdn" in stack:
                old = stack["gdn"]["dt_bias"]
                u = jax.random.uniform(
                    jax.random.fold_in(jax.random.fold_in(key, 0xD7), at),
                    old.shape, jnp.float32)
                dt = jnp.exp(u * (jnp.log(DT_MAX) - jnp.log(DT_MIN))
                             + jnp.log(DT_MIN))
                # the inverse of softplus
                bias = (dt + jnp.log(-jnp.expm1(-dt))).astype(old.dtype)
                stack = dict(stack, gdn=dict(stack["gdn"], dt_bias=bias))
            layers.append(stack)
        return dict(params, layers=type(params["layers"])(layers))

    params = jax.jit(init)(jax.random.PRNGKey(lm.fold_seed(seed)))
    jax.block_until_ready(params)
    return params


def reference_weights(params: dict, config: dict):
    """The program's parameter tree as the reference's plain matrices: the
    same arrays, the packed projections cut into the reference's own ([all
    q | all k | all v | all z], [all b | all a]), heads folded into columns;
    a layer at a time. A tree with input norms (a planted fault's) is not
    this family's: the reference has nowhere to put them."""
    ref = lm.reference_module(config)
    kinds = ref.layer_kinds(config)
    period = len(layer_pattern(config))
    stacks = params["layers"]
    d = params["embed"].shape[1]
    h, dk = config["linear_num_key_heads"], config["linear_key_head_dim"]
    kd = h * dk
    vd = config["linear_num_value_heads"] * config["linear_value_head_dim"]

    def layer(i: int) -> dict:
        stack, at = stacks[i % period], i // period
        m = stack["mlp"]
        out = {"w1": m["w1"][at], "w3": m["w3"][at], "w2": m["w2"][at],
               "ln1_post": stack["ln1_post"][at],
               "ln2_post": stack["ln2_post"][at]}
        if kinds[i] == "full":
            a = stack["attn"]
            out.update(wq=a["wq"][at].reshape(d, -1),
                       wk=a["wk"][at].reshape(d, -1),
                       wv=a["wv"][at].reshape(d, -1),
                       wo=a["wo"][at].reshape(-1, d),
                       q_norm=a["q_norm"][at], k_norm=a["k_norm"][at])
            return out
        g = stack["gdn"]
        qkvz, ba = g["in_qkvz"][at], g["in_ba"][at]
        heads = ba.shape[1] // 2
        out.update(wq=qkvz[:, :kd], wk=qkvz[:, kd:2 * kd],
                   wv=qkvz[:, 2 * kd:2 * kd + vd], wg=qkvz[:, 2 * kd + vd:],
                   wb=ba[:, :heads], wa=ba[:, heads:], conv=g["conv"][at],
                   A_log=g["A_log"][at], dt_bias=g["dt_bias"][at],
                   norm=g["norm"][at], wo=g["out"][at])
        return out

    head = params["embed"].T if "lm_head" not in params else params["lm_head"]
    return ref.Weights(embed=params["embed"], layer=layer, kinds=kinds,
                       final_norm=params["final_norm"], lm_head=head)


# ---------------------------------------------------------------------------
# the check: prefill and decode_step through the cache against the reference
# ---------------------------------------------------------------------------

def check_tokens(seed: int, vocab: int, length: int):
    import numpy as np
    return np.random.default_rng([lm.fold_seed(seed), 0xC4EC]).integers(
        0, vocab, (CHECK_ROWS, length), dtype=np.int32)


def cache_view(cfg, cache: dict) -> dict:
    """The linear slots of the program's cache as the reference lays them
    out: ``state`` unpacked to [slots, B, H, dk, dv], ``tail`` as [slots, B,
    K-1, C], both float32 on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.gated_delta import unpack_state
    state = jax.vmap(lambda s: unpack_state(s, cfg.linear_value_heads))(
        cache["state"].astype(jnp.float32))
    return {"state": np.asarray(state),
            "tail": np.asarray(jnp.swapaxes(cache["tail"], 1, 2).astype(
                jnp.float32))}


class Program:
    """``prefill`` and ``decode_step`` of one configuration, jitted once and
    run over any seed's parameters."""

    def __init__(self, cfg, prompt: int, max_len: int):
        from functools import partial

        import jax
        from ray_tpu.models.generate import decode_step, prefill
        self.cfg, self.prompt = cfg, prompt
        self.prefill = jax.jit(partial(prefill, cfg=cfg, max_len=max_len))
        self.step = jax.jit(partial(decode_step, cfg=cfg))

    def run(self, params, tokens) -> dict:
        """tokens [rows, prompt + k] -> the logits of the prompt's last
        position and the k after it [rows, k + 1, vocab], the linear
        slots' cache after the prompt and after the last position, and the
        cache's dtypes."""
        import jax.numpy as jnp
        p = self.prompt
        logits, cache = self.prefill(params, tokens[:, :p])
        out = {"after_prompt": cache_view(self.cfg, cache),
               "cache_dtypes": {n: str(a.dtype) for n, a in cache.items()}}
        system = [logits]
        for j in range(tokens.shape[1] - p):
            logits, cache = self.step(params, tokens[:, p + j],
                                      jnp.asarray(p + j, jnp.int32), cache)
            system.append(logits)
        out.update(logits=jnp.stack(system, axis=1),
                   after_decode=cache_view(self.cfg, cache))
        return out


def reference_pass(weights, config: dict, tokens, prompt: int, eps: float,
                   dtype=None) -> dict:
    """The plain reference over ``tokens`` and over their prompt alone ->
    on the host: the logits from the prompt's last position on, and what a
    cache would hold of the linear layers after the prompt and after the
    last position."""
    import numpy as np
    reference = lm.reference_module(config)
    logits, after_decode = reference.forward_and_cache(
        weights, tokens, config, eps=eps, dtype=dtype)
    _, after_prompt = reference.forward_and_cache(
        weights, tokens[:, :prompt], config, eps=eps, dtype=dtype)
    host = lambda tree: {k: np.asarray(v) for k, v in tree.items()}
    return {"logits": np.asarray(logits[:, prompt - 1:]),
            "after_prompt": host(after_prompt),
            "after_decode": host(after_decode)}


def errors(got: dict, reference: dict, config: dict) -> dict:
    """``got`` (a program's ``run`` or a reference pass) against the float32
    reference pass: the logits' rms error a position, and the linear slots'
    state and tail errors [2 places x slots] (after the prompt, after the
    decoded positions)."""
    import numpy as np
    ref = lm.reference_module(config)
    cache = np.stack([np.asarray(ref.cache_errors(got[place],
                                                  reference[place]))
                      for place in ("after_prompt", "after_decode")])
    return {"logits": np.asarray(ref.errors_a_position(
                got["logits"], reference["logits"])).tolist(),
            "state": cache[..., 0].reshape(-1).tolist(),
            "tail": cache[..., 1].reshape(-1).tolist()}


def over_floors(errs: dict, floor: dict, config: dict) -> dict:
    """``errors`` of the program over ``errors`` of the rounded reference,
    one place at a time -> the judged numbers."""
    ref = lm.reference_module(config)
    out = {}
    for name, mine in (("rms", "logits"), ("state", "state"),
                       ("tail", "tail")):
        over = ref.over_floor(errs[mine], floor[mine])
        out[name + "_over_floor"] = over["typical"]
        out[name + "_over_floor_worst"] = over["worst"]
    return out


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

def reduce_trace(path: str, scopes: dict, phases: dict) -> dict:
    """``serve_dots3.reduce_trace`` and, as ``decode_scopes``, the same
    reduction by the program's scopes over the instructions of the token
    loop alone (``rt.generate.decode``), from one reading of the file."""
    from benchmark import trace as trace_mod
    from benchmark import trace_scopes
    devices, host = trace_mod.read_xplane(path)
    reduced = trace_mod.combine(
        [trace_mod.reduce_device(ops, async_ops, modules, host)
         for ops, async_ops, modules in devices.values()])
    if reduced:
        ops, _, modules = devices[min(devices)]
        reduced["scopes"] = trace_scopes.reduce_device(ops, modules, scopes)
        reduced["phases"] = trace_scopes.reduce_device(ops, modules, phases)
        reduced["decode_scopes"] = trace_scopes.reduce_device(
            ops, modules, {name: scope for name, scope in scopes.items()
                           if phases.get(name) == DECODE})
    return reduced


def reduce_apart(path: str, scopes: dict, phases: dict) -> dict:
    """``reduce_trace`` in a child process that opens no accelerator."""
    import subprocess
    import sys
    import tempfile
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with tempfile.TemporaryDirectory() as tmp:
        asked, told = os.path.join(tmp, "in.json"), \
            os.path.join(tmp, "out.json")
        with open(asked, "w") as f:
            json.dump({"path": path, "scopes": scopes, "phases": phases}, f)
        subprocess.run(
            [sys.executable, "-m", "benchmark.apps.serve_olmo_hybrid", asked,
             told], check=True, cwd=checkout, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(told) as f:
            return json.load(f)


# ---------------------------------------------------------------------------
# the replica
# ---------------------------------------------------------------------------

def make_replica(max_batch_size: int, batch_wait_timeout_s: float):
    """``serve_ouro``'s replica class with what this family changes."""
    from ray_tpu import serve
    base = serve_ouro.make_replica(max_batch_size, batch_wait_timeout_s)

    class OlmoHybridReplica(base):
        def __init__(self, spec: dict):
            self.stamps = {"entry": time.time()}
            from functools import partial
            import threading

            import jax
            import jax.numpy as jnp
            import numpy as np

            from benchmark import trace_scopes
            from benchmark.apps.serve_dots3 import phase_map
            from ray_tpu.models import generate_with_stats

            self.jax, self.jnp, self.np = jax, jnp, np
            self.spec = spec
            self.compiles = lm.CompileCounter()
            self.devs = jax.devices()
            self.stamps["devices"] = time.time()
            self.facts = lm.device_facts()
            lm.require_chips(self.facts, 1, spec["rehearse"])
            self.cfg = cfg = transformer_config(spec["model"], remat=False)
            self.params = seeded_params(cfg, spec["seed"])
            self.stamps["init"] = time.time()
            self.rows, self.prompt = spec["rows"], spec["prompt_tokens"]
            gen = jax.jit(partial(generate_with_stats, cfg=cfg,
                                  temperature=0.0,
                                  max_new_tokens=spec["new_tokens"]))
            prompts = jnp.zeros((self.rows, self.prompt), jnp.int32)
            self.gen = gen.lower(self.params, prompts).compile()
            self.gen_memory = lm.compiled_peak(self.gen)
            self.scopes, self.phases = {}, {}
            if spec["trace"]:
                text = self.gen.as_text()
                self.scopes = trace_scopes.scope_map(text)
                self.phases = phase_map(text)
            self.stamps["ready"] = time.time()
            self.lock = threading.Lock()    # one generate call at a time
            self.requests, self.batches, self.profiler = {}, [], []
            self.inside, self.inside_max = 0, 0     # requests in __call__
            self.count_lock = threading.Lock()
            self.reduced, self.marks, self.stopper = {}, None, None
            self.ticker = serve_ouro.HostTicker()

        def _weights(self):
            return reference_weights(self.params, self.spec["config"])

        def selfcheck(self) -> dict:
            """``prefill`` and CHECK_DECODED ``decode_step``s of CHECK_ROWS
            seeded rows through the cache, against the plain reference's
            float32 pass over the same weights: the logits, and the state
            and tail of every linear slot after the prompt and after the
            decoded positions. What ``aftercheck`` compares again is kept
            on the host."""
            import gc
            jax, jnp = self.jax, self.jnp
            spec, cfg = self.spec, self.cfg
            config, p = spec["config"], self.prompt
            k = min(CHECK_DECODED, spec["new_tokens"] - 1)
            tokens = jnp.asarray(check_tokens(spec["seed"], cfg.vocab_size,
                                              p + k))
            program = Program(cfg, p, p + spec["new_tokens"]).run(
                self.params, tokens)
            program["logits"] = self.np.asarray(program["logits"])
            reference = lm.reference_module(config)
            full = reference_pass(self._weights(), config, tokens, p,
                                  lm.program_rms_norm_eps(cfg))
            out = reference.compare_logits(program["logits"], full["logits"])
            self.checked = {"tokens": self.np.asarray(tokens), "full": full,
                            "errors": errors(program, full, config)}
            leaves = jax.tree.leaves(self.params)
            out.update(
                n_params=int(sum(x.size for x in leaves)),
                param_dtypes=sorted({str(x.dtype) for x in leaves}),
                compute_dtype=str(jnp.dtype(cfg.dtype)),
                cache_dtypes=program["cache_dtypes"])
            del program
            jax.clear_caches()      # the check's programs: not the window's
            gc.collect()
            self.stamps["checked"] = time.time()
            return out

        def aftercheck(self, pairs: list) -> dict:
            """After the window: the reference once more over
            ``selfcheck``'s tokens with its activations rounded to the
            type the configuration's file states (the floor), the
            program's errors over it one place at a time; then the served
            tokens of ``pairs`` (CHECK_ROWS requests the window finished)
            against the reference teacher-forced on them, each gap over
            its own position's floor."""
            jnp = self.jnp
            spec, p = self.spec, self.prompt
            config = spec["config"]
            reference = lm.reference_module(config)
            eps = lm.program_rms_norm_eps(self.cfg)
            dtype = jnp.dtype(config["torch_dtype"])
            weights = self._weights()
            checked = self.checked
            rounded = reference_pass(
                weights, config, jnp.asarray(checked["tokens"]), p, eps,
                dtype)
            floor = errors(rounded, checked["full"], config)
            out = over_floors(checked["errors"], floor, config)
            out.update(errors=checked["errors"], floor_errors=floor,
                       floor_rms_over_std=reference.compare_logits(
                           rounded["logits"],
                           checked["full"]["logits"])["rms_over_std"])
            del rounded
            new = spec["new_tokens"]
            fed = jnp.asarray([list(prompt) + list(served[:new - 1])
                               for prompt, served in pairs], jnp.int32)
            served = [list(served) for _, served in pairs]
            exact = reference.forward(weights, fed, config, eps=eps)[:, p - 1:]
            rounded = reference.forward(weights, fed, config, eps=eps,
                                        dtype=dtype)[:, p - 1:]
            out.update(reference.token_deficit(exact, served))
            out["token_deficit_over_floor"] = \
                reference.token_deficit_over_floor(exact, rounded, served)
            # reported, not judged: what one altered token would have read
            # on this seed (the next id in the place of the first checked
            # reply's token a third of the way in)
            at = new // 3
            altered = [list(row) for row in served]
            altered[0][at] = (altered[0][at] + 1) % self.cfg.vocab_size
            out["altered_token_over_floor"] = \
                reference.token_deficit_over_floor(exact, rounded, altered)
            published = float(config["rms_norm_eps"])
            out["rms_norm_eps"] = {"published": published, "program": eps}
            return out

        @serve.batch(max_batch_size=max_batch_size,
                     batch_wait_timeout_s=batch_wait_timeout_s)
        def generate_batch(self, items: list) -> list:
            """``serve_ouro``'s, inside the program's ``generate.call``
            span (a stack without a loop or experts has no counters)."""
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
                                  self.spec["new_tokens"]):
                    called = self.gen(self.params, self.jnp.asarray(prompts))
                    dispatched = time.time()
                    tokens, _ = jax.device_get(called)
                end = time.time()
                self.batches.append({"start": start, "end": end,
                                     "rows": len(items),
                                     "padded_rows": self.rows,
                                     "dispatch_s": dispatched - start,
                                     "host_pause_max_s":
                                         self.ticker.longest(),
                                     "rids": [rid for _, rid in items]})
            return [tokens[i].tolist() for i in range(len(items))]

        def dump(self) -> dict:
            """``serve_ouro``'s, with the trace reduced by the program's
            scopes, by the call's two phases and by scope within the token
            loop."""
            from benchmark import trace as trace_mod
            if self.profiler and self.stopper is None:
                self._stop_trace()          # the window was too short
            if self.stopper is not None:
                self.stopper.join()
            if self.profiler:
                self.reduced = reduce_apart(
                    trace_mod.find_xplane(self.spec["trace_dir"]),
                    self.scopes, self.phases)
            return {
                "stamps": self.stamps, "facts": self.facts,
                "profiler": self.profiler,
                "requests": {str(k): v for k, v in self.requests.items()},
                "batches": self.batches[self.marks["batches"]:],
                "trace": self.reduced, "admitted_max": self.inside_max,
                "compiles_in_window":
                    self.compiles.count - self.marks["compiles"],
                "memory": lm.memory_report(self.devs, self.gen_memory,
                                           "generate")}

    return OlmoHybridReplica


def judged(record: dict, config: dict, traffic: dict) -> dict:
    """``serve_lm``'s exact checks and this family's numbers under LIMITS:
    ``{name: [value, limit]}``. ``serve_lm``'s ratio over all positions
    together and its gap over the logits' spread are in the record's
    ``checks`` and not judged here; the state's dtype is held exactly."""
    checks = record["checks"]
    out = serve_lm.judged(record, config, traffic)
    del out["token_deficit_over_std"], out["rms_over_floor"]
    out.update({name: [checks[name], limit]
                for name, limit in LIMITS.items()})
    out["state_not_float32"] = [
        int(checks["cache_dtypes"].get("state") != "float32"), 0]
    return out


WHAT_EACH_CHECK_SAYS = dict(
    {name: says for name, says in serve_lm.WHAT_EACH_CHECK_SAYS.items()
     if name not in ("token_deficit_over_std", "rms_over_floor")},
    rms_over_floor="at the typical position (the geometric mean over the "
                   "compared positions) prefill+decode logits are off the "
                   "reference (rms) by this many times what bfloat16 "
                   "rounding alone does to this seed's model there",
    rms_over_floor_worst="the same at the worst position",
    state_over_floor="the recurrent states that prefill and the decode steps "
                     "left in the cache's linear slots are off the "
                     "reference's S (rms), at the typical slot and place "
                     "(after the prompt, after the decoded positions), by "
                     "this many times what bfloat16 rounding of the "
                     "activations alone does to them",
    state_over_floor_worst="the same at the worst slot and place",
    tail_over_floor="the convolution's last inputs in the same slots at the "
                    "same places, the same way",
    tail_over_floor_worst="the same at the worst slot and place",
    token_deficit_over_floor="a token the compiled generate served lies "
                             "under the reference's best by this many times "
                             "what bfloat16 rounding alone does to the "
                             "logits at its position",
    state_not_float32="the cache's recurrent state is not float32")


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
    os.environ["MALLOC_ARENA_MAX"] = "1"    # as serve_ouro: one arena

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
    for name in ("scopes", "phases", "decode_scopes"):
        reduced = (record.get("trace") or {}).get(name) or {}
        if reduced:     # a traced run: where the period's device time went
            log(f"trace {name} over {reduced['periods']} period(s): "
                + json.dumps({scope or "(no scope)": round(seconds, 4)
                              for scope, seconds in sorted(
                                  reduced["seconds"].items(),
                                  key=lambda kv: -kv[1])}))
    log("rows a call of the window: "
        f"{[b['rows'] for b in record['batches']]}")
    lost = [r for r in window["rows"] if not r["ok"]]
    if lost:
        kinds = {}
        for r in lost:
            key = (r.get("status"), str(r.get("error"))[:160])
            kinds[key] = kinds.get(key, 0) + 1
        log(f"{len(lost)} requests of the window failed: " + "; ".join(
            f"{n} x status {status}: {error}"
            for (status, error), n in sorted(kinds.items(),
                                             key=lambda kv: -kv[1])[:4])
            + f"; the first took {lost[0]['last'] - lost[0]['send']:.2f} s")
    calls = sorted(b["end"] - b["start"] for b in record["batches"])
    for b in record["batches"]:
        took = b["end"] - b["start"]
        if took > 1.02 * calls[len(calls) // 2] + 0.1:
            log(f"slow call: {took:.3f} s against a median of "
                f"{calls[len(calls) // 2]:.3f}; dispatch took "
                f"{b['dispatch_s']:.3f} s, and the longest this process "
                f"was kept waiting during it was "
                f"{b['host_pause_max_s']:.3f} s")
    log("checks: " + json.dumps({k: checks[k] for k in (
        "rms_over_std", "floor_rms_over_std", "token_deficit_over_std",
        "altered_token_over_floor", *LIMITS)}))
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
        json.dump(reduce_trace(asked["path"], asked["scopes"],
                               asked["phases"]), f)

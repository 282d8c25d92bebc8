"""The serving application of the ``falcon_h1`` family: ``serve_lm``'s
replica behind ``serve.run`` and the proxy, for a stack of parallel blocks
(softmax attention and a Mamba-2 state-space mixer on one normed input).

What is the family's own is here: how the configuration becomes the
program's ``TransformerConfig`` (every layer ``"parallel"``, the mixer's
widths under the ``linear_*`` fields with the transition ``"ssd"``) and its
muP multipliers the arguments of ``fold_multipliers``; the published tree
drawn from the seed a layer at a time, as Mamba-2 draws its own; the
program's tree, that draw folded by the program's ``fold_multipliers`` in one
compiled program; the REFERENCE's weights, the same draw made again and
never read back from the folded tree, so that the fold itself is held to the
reference's use of the multipliers where published; ``prefill`` and
``decode_step`` through the cache against the reference's full forward, on
logits, and on ALL FOUR arrays a layer leaves in the cache (state,
convolution tail, rotated keys, values), after the prompt and after the
decoded positions; the TIMED program's own served call (``generate_and_keep``:
the compiled call the window drives hands back the first and last row of its
cache) against the reference teacher-forced on the tokens it served, on the
same four arrays; the limits, read from this family's own sweep. Everything
else (the replica's ``generate_batch`` under ``@serve.batch`` inside the
program's ``generate.call`` span, the trace reduced by scope and phase in a
child, the host's ticker) is ``serve_olmo_hybrid``'s replica, subclassed.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time

from benchmark.apps import lm, serve_lm, serve_olmo_hybrid, serve_ouro
from benchmark.hermetic import log

CHECK_ROWS = serve_lm.CHECK_ROWS
CHECK_DECODED = serve_olmo_hybrid.CHECK_DECODED   # every step a row takes
# Mamba-2's own initialiser (as recalled, not fetched): the step size
# log-uniform in [DT_MIN, DT_MAX] through the inverse softplus in dt_bias, A
# uniform in [1, 16], D = 1; the depthwise convolution's taps and bias as
# PyTorch draws a Conv1d's, uniform in +-1 / sqrt(K).
DT_MIN, DT_MAX = 1e-3, 1e-1
NEEDS_OF_THE_PROGRAM = ("linear_transition",)
CACHE_PARTS = ("state", "tail", "kv")
# What ``correct`` holds a run to: LIMITS, each set from
# benchmark/testdata/falcon_h1_checks_sweep.json (my chip runs, PR 55, on a
# TPU v5e at the published widths: sweep_falcon_h1.py beside it over 6
# seeds, with the control, the reference over int8 weights with bfloat16
# activations, on every one, and the architecture's eight faults planted on
# the first three, two of them in ``fold_multipliers`` itself; the runs of
# the cell this PR made on its final tree, 19, are in the same file;
# tests/benchmark/test_bench_falcon_h1.py holds these numbers to that file,
# and PERF.md section 2 has the table). Each ``*_over_floor`` is an rms error
# against the float32 reference ON THE PUBLISHED TREE over what the
# reference's own bfloat16-rounded activations do to the same seed's model
# at the same place, one place at a time (``reference.over_floor``): the
# typical place (the geometric mean) and the worst. The floor is rounded by
# ``lax.reduce_precision`` (the reference's ``_rounder``). A sound program
# reads 1.1-1.3, not 1: it rounds where the reference rounds AND runs
# matrices that ``fold_multipliers`` rounded to bfloat16 a second time (with
# the reference on the folded tree divided back, before the review, the same
# program read 0.90-1.05). Sound (the 6 seeds and the cell's runs) .. the
# control's least .. the least that any of the eight faults reads where it
# is that number's to catch, in the comments. The control has to fail one
# limit, not each: the logits', the tails', and the keys' and values' limits
# lie between the sound readings and the control's, 1.25 x the worst sound
# reading or more and 0.85 x the control's least or under, and the control
# fails ten of them on every seed; the STATE's limits do not try (the
# control reads 1.48-1.51 where sound seeds read 1.18-1.31, and at its worst
# place 1.57-1.71 INSIDE the sound seeds' 1.34-1.78): they lie 1.22 x and
# 1.41 x over the worst sound reading and a quarter or less of what the
# state's own faults read.
LIMITS = {
    # the logits of ``prefill`` + 383 ``decode_step``s of 2 rows, a position
    # at a time: typical 1.077-1.104 .. 1.835 .. 7.27 (the groups swapped; a
    # bfloat16 state and a wrong multiplier of the B segment read 1.13 and
    # 1.20: the state's and the tail's to catch); worst 1.195-1.320 .. 1.949
    "rms_over_floor": 1.42,
    "rms_over_floor_worst": 1.65,
    # the 9 layers' float32 states [2, 32, 256, 128] as ``prefill`` left them
    # after position 127 and as 383 ``decode_step``s left them after
    # position 510, against the reference's S, a (slot, place) at a time:
    # typical 1.228-1.310 .. (1.479) .. 8.81 (``key_multiplier`` left out of
    # the fold; a state CARRIED IN BFLOAT16 15.1, the wrong multiplier
    # 89.5); worst 1.383-1.740 .. (1.603) .. 16.1 (a bfloat16 state 190)
    "state_over_floor": 1.6,
    "state_over_floor_worst": 2.5,
    # the convolution's last 3 inputs in the same slots at the same places:
    # typical 1.070-1.110 .. 2.142 .. 5.17; worst 1.149-1.254 .. 3.340 .. 7.39
    "tail_over_floor": 1.5,
    "tail_over_floor_worst": 2.0,
    # the rotated keys and the values of the same slots, over the prompt's
    # positions (``prefill``'s) and over the decoded ones (``decode_step``'s):
    # typical 1.123-1.134 .. 2.132 .. 5.35 (``key_multiplier`` left out
    # 447); worst 1.428-1.451 .. 3.325 .. 7.76
    "kv_over_floor": 1.5,
    "kv_over_floor_worst": 2.2,
    # the widest gap of a served token (2 x 384 of the window's) under the
    # reference's best, over what rounding alone does to the logits at its
    # position: a guard of the served path's tokens against gross faults
    # (``key_multiplier`` left out of the fold 69.7; the next id in one
    # served token's place, ``altered_token_over_floor``, 242 or more). The
    # control's own argmax tokens read 3.3-7.9 through it: a lower precision
    # is not this number's to see, and the served cache's numbers below are
    # there for it. Sound 1.62-4.66 over the sweep and the cell's runs
    "token_deficit_over_floor": 10.0,
    # what the TIMED program (the compiled call of 64 rows the window
    # drives, ``generate_and_keep``) left in the first and the last row of
    # its cache after its 512 positions, against the reference
    # teacher-forced on the tokens the call served there, a slot at a time,
    # the limits of the same arrays above. State: typical 1.182-1.264 ..
    # (1.475) .. 7.26 (``key_multiplier``; a bfloat16 state 84.8), worst
    # 1.337-1.778 .. (1.565) .. 10.9 (a bfloat16 state 186); tail: 1.070-1.116
    # .. 2.122 .. 10.8, worst 1.122-1.258 .. 3.276; keys and values:
    # 1.113-1.123 .. 2.131 .. 447, worst 1.428-1.453 .. 3.328
    "served_state_over_floor": 1.6,
    "served_state_over_floor_worst": 2.5,
    "served_tail_over_floor": 1.5,
    "served_tail_over_floor_worst": 2.0,
    "served_kv_over_floor": 1.5,
    "served_kv_over_floor_worst": 2.2,
}


def model_kwargs(config: dict, seq: int, attn_impl: str) -> dict:
    """Hugging Face key names -> ``TransformerConfig`` fields (dtypes as
    strings: this dict crosses a process boundary)."""
    if config["attn_layer_indices"] is not None:
        raise ValueError("this family's app puts attention in every layer: "
                         "attn_layer_indices is not null")
    if config["mamba_n_heads"] * config["mamba_d_head"] \
            != config["mamba_d_ssm"]:
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    if not config["mamba_conv_bias"] or config["mamba_norm_before_gate"] \
            or not config["mamba_rms_norm"] or config["mamba_proj_bias"] \
            or config["attention_bias"] or config["mlp_bias"]:
        raise ValueError("the program's state-space mixer has a bias on its "
                         "convolution and nowhere else, and gates before "
                         "its grouped norm")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"],
        d_ff=config["intermediate_size"], max_seq=seq,
        rope_theta=float(config["rope_theta"]),
        tied_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype=config["param_dtype"], attn_impl=attn_impl,
        norm_eps=float(config["rms_norm_eps"]),
        layer_types=["parallel"], linear_transition="ssd",
        linear_key_heads=config["mamba_n_groups"],
        linear_value_heads=config["mamba_n_heads"],
        linear_key_dim=config["mamba_d_state"],
        linear_value_dim=config["mamba_d_head"],
        linear_conv_kernel=config["mamba_d_conv"])


def transformer_config(kwargs: dict, remat: bool):
    """Raises in words where the program lacks what the family needs."""
    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig
    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = [k for k in NEEDS_OF_THE_PROGRAM if k not in have]
    if missing:
        raise ValueError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            "run a block that holds softmax attention and a state-space "
            "(Mamba-2 SSD) mixer side by side")
    kwargs = dict(kwargs, layer_types=tuple(kwargs["layer_types"]),
                  param_dtype=jnp.dtype(kwargs["param_dtype"]))
    return TransformerConfig(**kwargs, remat=remat)


def program_eps(cfg) -> float:
    """The epsilon the program's RMSNorm runs: its configuration's
    (``lm.program_rms_norm_eps`` looks for a field of another name and
    falls back on the block's default)."""
    return float(cfg.norm_eps)


def multipliers(config: dict) -> dict:
    """The published muP constants as ``fold_multipliers`` names them."""
    return dict(
        embedding=float(config["embedding_multiplier"]),
        lm_head=float(config["lm_head_multiplier"]),
        attention_in=float(config["attention_in_multiplier"]),
        key=float(config["key_multiplier"]),
        attention_out=float(config["attention_out_multiplier"]),
        ssm_in=float(config["ssm_in_multiplier"]),
        ssm=tuple(float(m) for m in config["ssm_multipliers"]),
        ssm_out=float(config["ssm_out_multiplier"]),
        mlp=tuple(float(m) for m in config["mlp_multipliers"]))


def published_layer(cfg, key, i):
    """Layer ``i`` of the tree as a checkpoint would hold it, no multiplier
    in it, drawn from (``key``, ``i``) alone: ``transformer_init``'s one
    layer, with the state-space mixer's own arrays drawn as DT_MIN / DT_MAX
    and the module's comment say. ``i`` may be traced."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import transformer_init
    key = jax.random.fold_in(key, i)
    (stack,) = transformer_init(
        key, cfg=dataclasses.replace(cfg, n_layers=1))["layers"]
    layer = jax.tree.map(lambda a: a[0], stack)
    old = layer["ssm"]
    ks = jax.random.split(jax.random.fold_in(key, 0xD7), 3)
    dt = jnp.exp(jax.random.uniform(ks[0], old["dt_bias"].shape, jnp.float32)
                 * (jnp.log(DT_MAX) - jnp.log(DT_MIN)) + jnp.log(DT_MIN))
    bound = cfg.linear_conv_kernel ** -0.5

    def uniform(k, like):
        return jax.random.uniform(k, like.shape, jnp.float32, -bound,
                                  bound).astype(like.dtype)
    return dict(layer, ssm=dict(
        old, conv=uniform(ks[1], old["conv"]),
        conv_bias=uniform(ks[2], old["conv_bias"]),
        # the inverse of softplus
        dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(old["dt_bias"].dtype)))


def published_ends(cfg, key) -> dict:
    """The published tree without its layers: the embedding, the final
    norm's scale and the untied head."""
    from ray_tpu.models import transformer_init
    ends = transformer_init(key, cfg=dataclasses.replace(cfg, n_layers=1))
    return {name: ends[name] for name in ("embed", "final_norm", "lm_head")}


def published_params(cfg, key):
    """The whole published tree as ``transformer_init`` lays one out:
    ``published_ends`` and the stack of ``published_layer``s."""
    import jax
    import jax.numpy as jnp
    layers = jax.vmap(lambda i: published_layer(cfg, key, i))(
        jnp.arange(cfg.n_layers))
    return dict(published_ends(cfg, key), layers=(layers,))


def seed_key(seed: int):
    import jax
    return jax.random.PRNGKey(lm.fold_seed(seed))


def seeded_params(cfg, config: dict, seed: int):
    """The tree the program runs: ``published_params`` from the seed with
    the configuration's multipliers folded in by the program's
    ``fold_multipliers``, one compiled program (the unfolded tree never
    stands beside the folded one)."""
    import jax
    from ray_tpu.models import transformer
    params = jax.jit(lambda key: transformer.fold_multipliers(
        published_params(cfg, key), cfg, **multipliers(config)))(
            seed_key(seed))
    jax.block_until_ready(params)
    return params


def _as_published(layer: dict) -> dict:
    """A layer of the program's tree under the reference's names, float32,
    heads folded into columns: nothing is scaled."""
    import jax.numpy as jnp
    a, s, f = layer["attn"], layer["ssm"], layer["mlp"]
    d = layer["ln1"].shape[0]
    out = {"wq": a["wq"].reshape(d, -1), "wk": a["wk"].reshape(d, -1),
           "wv": a["wv"].reshape(d, -1), "wo": a["wo"].reshape(-1, d),
           "w_in": s["in_proj"], "conv": s["conv"],
           "conv_bias": s["conv_bias"], "dt_bias": s["dt_bias"],
           "A_log": s["A_log"], "D": s["D"], "norm": s["norm"],
           "w_out": s["out"], "w1": f["w1"], "w3": f["w3"], "w2": f["w2"],
           "ln1": layer["ln1"], "ln2": layer["ln2"]}
    del layer, a, s, f
    # a matrix at a time: its own dtype's copy goes as its float32 one comes
    return {name: out.pop(name).astype(jnp.float32) for name in list(out)}


def published_weights(params: dict, config: dict):
    """A whole published tree (``published_params``'s, or a checkpoint's
    laid out so) as the reference's ``Weights``: the multipliers are the
    reference's to apply."""
    import jax
    ref = lm.reference_module(config)
    (stack,) = params["layers"]
    return ref.Weights(
        embed=params["embed"],
        layer=lambda i: _as_published(jax.tree.map(lambda a: a[i], stack)),
        n_layers=int(stack["ln1"].shape[0]),
        final_norm=params["final_norm"], lm_head=params["lm_head"])


def reference_weights(cfg, config: dict, seed: int):
    """What the reference runs on: the PUBLISHED tree of the seed, drawn
    again from the seed's key and never read from the program's folded tree,
    so that what ``fold_multipliers`` did to the program's matrices is held
    to the reference's own use of the multipliers where the published code
    has them. A layer is drawn when the reference asks for it (a second tree
    of 8.41 GB does not fit beside the first): ``published_layer`` is a
    function of (key, i) alone, so the draw is the one the program's tree
    was folded from, bit for bit. The embedding and the head's slice whole,
    in float32 (0.67 GB each)."""
    import jax.numpy as jnp
    ref = lm.reference_module(config)
    key = seed_key(seed)
    ends, draw = _published_draws(cfg)
    ends = {name: a.astype(jnp.float32) for name, a in ends(key).items()}
    return ref.Weights(
        embed=ends["embed"], n_layers=cfg.n_layers,
        layer=lambda i: _as_published(draw(key, jnp.asarray(i, jnp.int32))),
        final_norm=ends["final_norm"], lm_head=ends["lm_head"])


@functools.lru_cache(maxsize=None)
def _published_draws(cfg):
    """(key -> ``published_ends``, (key, i) -> ``published_layer``), each
    jitted once a configuration and handing back the parameters' own dtype:
    an upcast inside the same program would let the compiler drop the
    rounding to that dtype (the reference's ``_rounder`` says where that
    was met), so the reference's float32 copy is made outside it."""
    import jax
    return (jax.jit(lambda key: published_ends(cfg, key)),
            jax.jit(lambda key, i: published_layer(cfg, key, i)))


# ---------------------------------------------------------------------------
# the check: prefill and decode_step through the cache against the reference
# ---------------------------------------------------------------------------

check_tokens = serve_olmo_hybrid.check_tokens


def cache_view(cache: dict, upto: int) -> dict:
    """The program's cache as the reference lays a layer's arrays out, on
    the host in float32: ``state`` [L, B, H, P, N] (the program carries the
    transpose), ``tail`` [L, B, K-1, C], ``k``, ``v`` the first ``upto``
    positions [L, B, upto, KVH, hd]."""
    import jax.numpy as jnp
    import numpy as np
    f32 = jnp.float32
    return {"state": np.asarray(jnp.swapaxes(cache["state"].astype(f32),
                                             -1, -2)),
            "tail": np.asarray(jnp.swapaxes(cache["tail"], 1, 2).astype(f32)),
            "k": np.asarray(cache["k"][:, :, :upto].astype(f32)),
            "v": np.asarray(cache["v"][:, :, :upto].astype(f32))}


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
        position and the k after it [rows, k + 1, vocab], the cache after
        the prompt and after the last position, and the cache's dtypes."""
        import jax.numpy as jnp
        p, total = self.prompt, tokens.shape[1]
        logits, cache = self.prefill(params, tokens[:, :p])
        out = {"after_prompt": cache_view(cache, p),
               "cache_dtypes": {n: str(a.dtype) for n, a in cache.items()}}
        system = [logits]
        for j in range(total - p):
            logits, cache = self.step(params, tokens[:, p + j],
                                      jnp.asarray(p + j, jnp.int32), cache)
            system.append(logits)
        out.update(logits=jnp.stack(system, axis=1),
                   after_decode=cache_view(cache, total))
        return out


def reference_pass(weights, config: dict, tokens, prompt: int, eps: float,
                   dtype=None) -> dict:
    """The plain reference over ``tokens`` and over their prompt alone ->
    on the host: the logits from the prompt's last position on, and what a
    cache would hold after the prompt and after the last position."""
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


def errors(got: dict, reference: dict, config: dict, prompt: int) -> dict:
    """``got`` (a program's ``run`` or a reference pass) against the float32
    reference pass: the logits' rms error a position; the slots' state and
    tail errors [2 places x L] (after the prompt, after the decoded
    positions); the keys' and values' [L x 2 x 2] over the prompt's and over
    the decoded positions as the last place has them."""
    import numpy as np
    ref = lm.reference_module(config)
    places = [ref.cache_errors(got[place], reference[place], prompt)
              for place in ("after_prompt", "after_decode")]
    return {"logits": np.asarray(ref.errors_a_position(
                got["logits"], reference["logits"])).tolist(),
            "state": np.stack([np.asarray(p["state"]) for p in places]
                              ).reshape(-1).tolist(),
            "tail": np.stack([np.asarray(p["tail"]) for p in places]
                             ).reshape(-1).tolist(),
            "kv": np.asarray(places[1]["kv"]).reshape(-1).tolist()}


def over_floors(errs: dict, floor: dict, config: dict,
                parts=("logits",) + CACHE_PARTS, prefix: str = "") -> dict:
    """``errors`` of the program over ``errors`` of the rounded reference,
    one place at a time -> the judged numbers."""
    ref = lm.reference_module(config)
    out = {}
    for part in parts:
        name = prefix + ("rms" if part == "logits" else part)
        over = ref.over_floor(errs[part], floor[part])
        out[name + "_over_floor"] = over["typical"]
        out[name + "_over_floor_worst"] = over["worst"]
    return out


# ---------------------------------------------------------------------------
# the served call: what the TIMED program left in its cache
# ---------------------------------------------------------------------------

def kept_rows(rows: int) -> tuple:
    """The rows of a served call whose cache the call hands back: the first
    and the last (a fault that depends on the row, in a kernel's grid over
    the batch or in the padding, reads differently at the two ends)."""
    return tuple(sorted({0, rows - 1}))


def generate_and_keep(params, prompts, cfg, new: int):
    """The program the window times: ``generate_and_cache`` (what
    ``generate_with_stats`` is the first two results of), greedy, with
    ``kept_rows``' rows of the cache as the call's last step left it: tens
    of MB cut from the token loop's carry at its end."""
    from ray_tpu.models import generate_and_cache
    tokens, stats, cache = generate_and_cache(
        params, prompts, cfg, temperature=0.0, max_new_tokens=new)
    return tokens, stats, rows_of(cache, kept_rows(prompts.shape[0]))


def rows_of(cache: dict, rows) -> dict:
    """``rows`` of the batch of each of the cache's arrays (the second
    dimension of all but ``tail``, whose third it is), each cut where it
    lies: a gather by an array of indices made the compiler keep a second
    state stack for the call (2.25 GiB more as compiled for a v5e here)."""
    import jax
    import jax.numpy as jnp

    def cut(name, a):
        axis = 2 if name == "tail" else 1
        return jnp.concatenate([jax.lax.slice_in_dim(a, r, r + 1, axis=axis)
                                for r in rows], axis=axis)
    return {name: cut(name, a) for name, a in cache.items()}


def served_passes(weights, config: dict, pairs: list, prompt: int,
                  eps: float) -> dict:
    """The reference teacher-forced on served replies, ``pairs`` [(prompt
    ids, served ids)]: exact and with its activations rounded to the
    configuration's type -> each ``(logits from the prompt's last position
    to the one before the last served token, the cache after the last
    served token)``, on the host."""
    import jax.numpy as jnp
    import numpy as np
    reference = lm.reference_module(config)
    fed = jnp.asarray([list(p) + list(served) for p, served in pairs],
                      jnp.int32)

    def one(dtype):
        logits, cache = reference.forward_and_cache(weights, fed, config,
                                                    eps=eps, dtype=dtype)
        return (np.asarray(logits[:, prompt - 1:-1]),
                {k: np.asarray(v) for k, v in cache.items()})
    return {"fed": fed, "exact": one(None),
            "rounded": one(jnp.dtype(config["torch_dtype"]))}


def served_numbers(passes: dict, served: list, cache: dict, config: dict,
                   prompt: int) -> dict:
    """``served`` tokens [rows][new] and the ``cache`` their call left (laid
    out as ``cache_view``'s, the same rows) against ``served_passes``: the
    widest gap of a served token under the reference's best over its
    position's floor, and the state, tail, keys and values over their
    floors, a slot at a time."""
    import numpy as np
    reference = lm.reference_module(config)
    (exact, exact_cache), (rounded, rounded_cache) = \
        passes["exact"], passes["rounded"]
    out = dict(reference.token_deficit(exact, served))
    out["token_deficit_over_floor"] = reference.token_deficit_over_floor(
        exact, rounded, served)

    def flat(got):
        errs = reference.cache_errors(got, exact_cache, prompt)
        return {part: np.asarray(errs[part]).reshape(-1).tolist()
                for part in CACHE_PARTS}
    out.update(over_floors(flat(cache), flat(rounded_cache), config,
                           CACHE_PARTS, "served_"))
    return out


# ---------------------------------------------------------------------------
# the replica
# ---------------------------------------------------------------------------

def make_replica(max_batch_size: int, batch_wait_timeout_s: float):
    """``serve_olmo_hybrid``'s replica class with what this family
    changes: how its parameters are made and what its check compares."""
    base = serve_olmo_hybrid.make_replica(max_batch_size,
                                          batch_wait_timeout_s)

    class FalconH1Replica(base):
        def __init__(self, spec: dict):
            self.stamps = {"entry": time.time()}
            from functools import partial
            import threading

            import jax
            import jax.numpy as jnp
            import numpy as np

            from benchmark import trace_scopes
            from benchmark.apps.serve_dots3 import phase_map

            self.jax, self.jnp, self.np = jax, jnp, np
            self.spec = spec
            self.compiles = lm.CompileCounter()
            self.devs = jax.devices()
            self.stamps["devices"] = time.time()
            self.facts = lm.device_facts()
            lm.require_chips(self.facts, 1, spec["rehearse"])
            self.cfg = cfg = transformer_config(spec["model"], remat=False)
            self.params = seeded_params(cfg, spec["config"], spec["seed"])
            self.stamps["init"] = time.time()
            self.rows, self.prompt = spec["rows"], spec["prompt_tokens"]
            prompts = jnp.zeros((self.rows, self.prompt), jnp.int32)
            compiled = jax.jit(partial(
                generate_and_keep, cfg=cfg, new=spec["new_tokens"])).lower(
                    self.params, prompts).compile()
            self.kept = []      # a call of ``batches``: its ``kept_rows``

            def gen(params, prompts):
                tokens, stats, kept = compiled(params, prompts)
                self.kept.append(kept)
                return tokens, stats
            self.gen = gen
            self.gen_memory = lm.compiled_peak(compiled)
            self.scopes, self.phases = {}, {}
            if spec["trace"]:
                text = compiled.as_text()
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
            return reference_weights(self.cfg, self.spec["config"],
                                     self.spec["seed"])

        def selfcheck(self) -> dict:
            """``prefill`` and CHECK_DECODED ``decode_step``s of CHECK_ROWS
            seeded rows through the cache, against the plain reference's
            float32 pass over the same weights: the logits, and the state,
            tail, keys and values of every layer after the prompt and
            after the decoded positions. What ``aftercheck`` compares again
            is kept on the host."""
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
                                  program_eps(cfg))
            out = reference.compare_logits(program["logits"], full["logits"])
            self.checked = {"tokens": self.np.asarray(tokens), "full": full,
                            "errors": errors(program, full, config, p)}
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

        def aftercheck(self, pairs: list, first_rid: int,
                       slots: list) -> dict:
            """After the window: the reference once more over
            ``selfcheck``'s tokens with its activations rounded to the type
            the configuration's file states (the floor), the program's
            errors over it one place at a time; then what the TIMED program
            served: the tokens of ``pairs`` (the requests in ``kept_rows``'
            rows ``slots`` of the call whose first row served ``first_rid``)
            and what that call left in those rows of its cache, against the
            reference teacher-forced on the same tokens, each over its own
            floor (``served_numbers``)."""
            jnp = self.jnp
            spec, p = self.spec, self.prompt
            config = spec["config"]
            reference = lm.reference_module(config)
            eps = program_eps(self.cfg)
            weights = self._weights()
            checked = self.checked
            rounded = reference_pass(
                weights, config, jnp.asarray(checked["tokens"]), p, eps,
                jnp.dtype(config["torch_dtype"]))
            floor = errors(rounded, checked["full"], config, p)
            out = over_floors(checked["errors"], floor, config)
            out.update(errors=checked["errors"], floor_errors=floor,
                       floor_rms_over_std=reference.compare_logits(
                           rounded["logits"],
                           checked["full"]["logits"])["rms_over_std"])
            del rounded
            call = [b["rids"][0] for b in self.batches].index(first_rid)
            kept = cache_view(rows_of(self.kept[call], slots),
                              p + spec["new_tokens"])
            self.kept.clear()
            passes = served_passes(weights, config, pairs, p, eps)
            served = [list(served) for _, served in pairs]
            out.update(served_numbers(passes, served, kept, config, p))
            # reported, not judged: what one altered token would have read
            # on this seed (the next id in the place of the first checked
            # reply's token a third of the way in)
            at = spec["new_tokens"] // 3
            altered = [list(row) for row in served]
            altered[0][at] = (altered[0][at] + 1) % self.cfg.vocab_size
            out["altered_token_over_floor"] = \
                reference.token_deficit_over_floor(
                    passes["exact"][0], passes["rounded"][0], altered)
            published = float(config["rms_norm_eps"])
            out["rms_norm_eps"] = {"published": published, "program": eps}
            return out

        def dump(self) -> dict:
            """``serve_olmo_hybrid``'s, and the calls before the window."""
            return dict(super().dump(), warmup_batches=self.batches[
                :self.marks["batches"]])

    return FalconH1Replica


def served_call(phases: list, whole: set, seed: int) -> tuple:
    """The call whose tokens and cache the aftercheck compares -> (the rid
    its first row served, which of ``kept_rows`` to compare, their rids).
    ``phases``: lists of a replica's ``batches``, the preferred first;
    ``whole``: the rids whose replies came back whole. A full call (every
    row a request: both kept rows) of the first phase that has one, else
    the first row of any call; among them one drawn from the seed."""
    import numpy as np
    for full in (True, False):
        for batches in phases:
            found = []
            for b in batches:
                rows = kept_rows(b["padded_rows"]) if full else (0,)
                if full and b["rows"] < b["padded_rows"]:
                    continue
                rids = [b["rids"][row] for row in rows]
                if all(rid in whole for rid in rids):
                    found.append((rids[0], list(range(len(rows))), rids))
            if found:
                return found[int(np.random.default_rng(
                    [seed, 0x5A3D]).integers(len(found)))]
    raise ValueError("no call of the warm-up or the window came back whole")


def judged(record: dict, config: dict, traffic: dict) -> dict:
    """``serve_lm``'s exact checks and this family's numbers under LIMITS:
    ``{name: [value, limit]}``; the state's dtype is held exactly."""
    checks = record["checks"]
    out = serve_lm.judged(record, config, traffic)
    del out["token_deficit_over_std"], out["rms_over_floor"]
    out.update({name: [checks[name], limit]
                for name, limit in LIMITS.items()})
    out["state_not_float32"] = [
        int(checks["cache_dtypes"].get("state") != "float32"), 0]
    return out


WHAT_EACH_CHECK_SAYS = dict(
    serve_olmo_hybrid.WHAT_EACH_CHECK_SAYS,
    kv_over_floor="the rotated keys and the values that prefill and the "
                  "decode steps left in the cache are off the reference's "
                  "(rms), at the typical slot and place (the prompt's "
                  "positions, the decoded ones), by this many times what "
                  "bfloat16 rounding of the activations alone does to them",
    kv_over_floor_worst="the same at the worst slot and place",
    **{"served_" + part + "_over_floor" + worst:
       f"{what} that a compiled generate call of the window left in the "
       "first and last row of its cache after its last token are off the "
       "reference's, teacher-forced on the tokens the call served (rms), "
       f"at the {'worst' if worst else 'typical'} slot, by this many times "
       "what bfloat16 rounding of the activations alone does to them"
       for part, what in (("state", "the recurrent states"),
                          ("tail", "the convolution's last inputs"),
                          ("kv", "the rotated keys and the values"))
       for worst in ("", "_worst")})


def judge(record: dict, config: dict, traffic: dict) -> list:
    """-> reasons this run is not correct (empty: correct), each naming
    the check, its number and its limit. Leaves ``record["judged"]``."""
    record["judged"] = judged(record, config, traffic)
    return lm.over_their_limits(record["judged"], WHAT_EACH_CHECK_SAYS)


def drive(run) -> dict:
    """``run`` is ``benchmark.run.RunContext``. -> the run's record.
    ``serve_olmo_hybrid.drive`` with this family's configuration, replica
    and judgement."""
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
    # the served call the aftercheck holds to the reference: a full call of
    # the window, drawn from the seed (a window too short to finish one
    # falls back on the warm-up's)
    whole = {r["rid"]: r["extra"]["tokens"] for r in warmup + window["rows"]
             if r["ok"] and len(r["extra"]["tokens"]) == traffic["new_tokens"]}
    before = record.pop("warmup_batches")
    first_rid, slots, rids = served_call([record["batches"], before],
                                         set(whole), seed)
    pairs = [(json.loads(body(rid))["prompt"], whole[rid]) for rid in rids]
    checks["tokens_checked_of"] = rids
    checks.update(serve_lm.patiently(rt, handle, "aftercheck", pairs,
                                     first_rid, slots))
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
    # a round waits for its last caller (flush on full): where a p95 that
    # is one call plus the fan-out read 0.1 s more, this says which it was
    log("calls of the window, seconds: "
        f"{[round(b['end'] - b['start'], 3) for b in record['batches']]}"
        ", of it dispatch: "
        f"{[round(b['dispatch_s'], 3) for b in record['batches']]}"
        ", gaps before them: "
        + str([round(b["start"] - a["end"], 3) for a, b in zip(
            before[-1:] + record["batches"], record["batches"])]))
    lost = [r for r in window["rows"] if not r["ok"]]
    if lost:
        log(f"{len(lost)} requests of the window failed, the first: "
            f"status {lost[0].get('status')}: "
            f"{str(lost[0].get('error'))[:160]} after "
            f"{lost[0]['last'] - lost[0]['send']:.2f} s")
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

"""The serving application of the ``dots3`` family: ``serve_lm``'s replica
behind ``serve.run`` and the proxy, for a stack of latent-attention layers
(full layers with a learned sparse indexer, window layers) over expert
layers with sigmoid bias-corrected routing, one member's share of an
expert group.

What is the family's own is here: how the published keys become the
program's ``TransformerConfig`` and its parameter tree the reference's
``Weights``; the router's correction bias drawn from the seed; a replica
that compiles ``generate_and_cache`` and opens the program's
``generate.call`` span around each call with the expert layers' counters on
it; and the comparison that decides ``correct``, of the served program
itself at the timed sizes: the compiled ``generate`` is called once on the
check's prompts, no two rows alike, and every token it emitted and the
cache it left (the latents and indexer keys of every position, the window
rings) are held, a row at a time, to a float32 pass of the plain reference
teacher-forced on that row's own tokens. Beside it, over row 0's prompt and
the first CHECK_DECODED of its tokens, ``prefill_and_taps`` in the timed
path's chunks and ``decode_step_and_taps`` (the functions ``generate``
runs, jitted apart: what a call of ``generate`` does not hand out) give
logits, the indexer's selections, the window layers' key counts and the
expert layers' dropped rows, and the router is run on the reference's
inputs. A second pass of the reference over row 0 with its activations
rounded to bfloat16 is the floor each error is read over. The reference's
passes need the room a loaded ``generate`` holds for its temporaries, so the
served program is given up after its call and compiled again after them;
the tokens it then serves a warm-up twin (row 0's prompt) are held to the
same reference logits as far as they are the tokens of the check's call.
Everything else (the handler under ``@serve.batch``, the trace window, the
dump) is ``serve_lm``'s and ``serve_ouro``'s.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import time

from benchmark.apps import lm, serve_lm, serve_ouro
from benchmark.hermetic import log

CHECK_DECODED = 8          # greedy decode steps after the prefill
TRACE_FROM_BATCH = 1       # as serve_ouro: one whole period, the window's
TRACE_INTO_NEXT_S = 1.0    # 2nd call and the start of its 3rd
NEEDS_OF_THE_PROGRAM = ("latent", "window_latent", "index_topk",
                        "first_dense_layers", "router_scoring",
                        "norm_eps", "attn_gate", "lora_rescale")
SWEEP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "dots3_checks_sweep.json")
# What ``correct`` holds a run to: LIMITS, each set from
# benchmark/testdata/dots3_checks_sweep.json (my chip runs, PR 45, on a TPU
# v5e at the published widths and the timed sizes: sweep_dots3.py beside it
# over seeds 0-5, with the control on each and every planted fault on two or
# three of them, and the runs of the cell this PR made, whose result lines
# carry the same functions' numbers; the table with every reading is in
# PERF.md section 2, and tests/benchmark/test_bench_zdots3.py holds these
# numbers to that file). Each ``*_over_floor`` is an rms error against the
# float32 reference over what the reference's own bfloat16-rounded
# activations do to the same seed's model at the same place. Sound .. the
# control (the reference over int8 weights with bfloat16 activations) in the
# comments.
LIMITS = {
    # the side program's logits of the prompt's last position and
    # CHECK_DECODED decoded ones, a position at a time: the typical one
    # (geometric mean), 0.916-1.147 .. 1.711-2.158; the worst, 1.07-2.62 ..
    # 2.56-4.31, tells the two apart on no seed and is held against gross
    # faults only (5-18)
    "rms_over_floor": 1.5,
    "rms_over_floor_worst": 4.0,
    # the latents and indexer keys of every position and the window rings
    # that the served generate's own call left, both rows, a (row, kind,
    # slot) at a time: typical 1.121-1.165 .. 2.424-2.527, worst 2.037-2.044
    # (layer 0's latent: _rope's bfloat16 cos and sin) .. 5.846-5.858
    "cache_over_floor": 1.6,
    "cache_over_floor_worst": 3.0,
    # 1 - the share of the reference's selected positions the side program
    # selected too, over both full layers, the prefill's last query and the
    # decoded ones: 0.067-0.088 .. 0.151-0.174
    "selection_missed": 0.12,
    # the widest gap of the 2 x 128 tokens the served generate emitted under
    # the reference's best at their positions, over the typical position's
    # floor (PERF.md section 2 has the sound runs' readings and, from the
    # same runs, a row that served the other row's tokens and one token
    # altered): a guard of the served path's tokens against gross faults, a
    # flipped selection moves one position's logits severalfold
    "token_deficit_over_floor": 20.0,
    # the program's route() over the expert layers' inputs as the
    # reference had them (the last 256 positions of the prompt and the
    # served ones, four layers): the typical position's weights against the
    # reference's, 9.7e-5-1.04e-4 (the bias used in the weights 0.0088-
    # 0.0091), and the share of its chosen experts not chosen, 0.0014-0.0031
    # (the bias ignored in the selection, or softmax scores, 0.111-0.121)
    "routing_weights_off": 0.004,
    "routing_missed": 0.015,
    # the keys a window layer's last query attended to (the side program's
    # prefill's and each decode step's), against min(position + 1,
    # sliding_window_size)
    "window_keys_off": 0,
    # the side program's, the served call's of the check and every call's
    # of the window
    "moe_rows_dropped": 0,
}


def latent_dims(config: dict, pre: str) -> dict:
    return dict(heads=config[pre + "num_attention_heads"],
                q_rank=config[pre + "q_lora_rank"],
                kv_rank=config[pre + "kv_lora_rank"],
                nope=config[pre + "qk_nope_head_dim"],
                rope=config[pre + "qk_rope_head_dim"],
                v=config[pre + "v_head_dim"],
                rope_theta=float(config[pre + "rope_theta"]))


def layer_pattern(config: dict) -> tuple:
    """(leading dense layers, one period of the program's kinds): the
    published ``layer_types`` after the ``first_k_dense_replace`` leading
    layers have to repeat one period, and the leading layers to be of the
    period's first kind, as the program has them."""
    names = {"full_attention": "latent", "sliding_attention": "window"}
    kinds = [names[t] for t in config["layer_types"]]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    lead = config["first_k_dense_replace"]
    rest = kinds[lead:]
    period = next(n for n in range(1, len(rest) + 1)
                  if len(rest) % n == 0
                  and rest == rest[:n] * (len(rest) // n))
    if set(kinds[:lead]) - {rest[0]}:
        raise ValueError(
            f"the leading dense layers are {kinds[:lead]}: the program has "
            f"them of the period's first kind, {rest[0]}")
    return lead, tuple(rest[:period])


def model_kwargs(config: dict, seq: int, attn_impl: str) -> dict:
    """Hugging Face key names -> ``TransformerConfig`` fields (plain values:
    this dict crosses a process boundary)."""
    lead, period = layer_pattern(config)
    if config["scoring_func"] != "sigmoid" or \
            config["topk_method"] != "noaux_tc" or \
            config["moe_layer_freq"] != 1 or config["n_shared_experts"] != 1:
        raise ValueError("the family routes by sigmoid with a correction "
                         "bias, every layer after the dense ones, one "
                         "shared expert")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], max_seq=seq,
        rope_theta=float(config["rope_theta"]),
        tied_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype=config["param_dtype"], dtype=config["torch_dtype"],
        attn_impl=attn_impl, norm_eps=float(config["rms_norm_eps"]),
        num_experts=config.get("n_routed_experts_published",
                               config["n_routed_experts"]),
        experts_held=config["n_routed_experts"],
        first_expert=config.get("first_expert", 0),
        expert_top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        expert_ff=config["moe_intermediate_size"],
        shared_expert_ff=config["moe_intermediate_size"]
        * config["n_shared_experts"],
        shared_expert_gate=False, router_scoring="sigmoid",
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        layer_types=period, first_dense_layers=lead,
        latent=latent_dims(config, ""),
        window_latent=latent_dims(config, "swa_"),
        window=config["sliding_window_size"],
        index_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        attn_gate=config["attention_gate_type"],
        lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]))


def transformer_config(kwargs: dict, remat: bool):
    """Raises in words where the program lacks what the family needs."""
    import jax.numpy as jnp
    from ray_tpu import models
    have = {f.name for f in dataclasses.fields(models.TransformerConfig)}
    missing = [k for k in NEEDS_OF_THE_PROGRAM if k not in have]
    if missing:
        raise ValueError(
            f"this program's TransformerConfig has no {missing}: it cannot "
            "run latent attention with an indexer, window layers, leading "
            "dense layers and sigmoid bias-corrected routing")
    from ray_tpu.models.transformer import LatentDims
    kwargs = dict(kwargs)
    for name in ("param_dtype", "dtype"):
        kwargs[name] = jnp.dtype(kwargs[name])
    for name in ("latent", "window_latent"):
        kwargs[name] = LatentDims(**kwargs[name])
    kwargs["layer_types"] = tuple(kwargs["layer_types"])
    return models.TransformerConfig(**kwargs, remat=remat)


def seeded_params(cfg, seed: int):
    """``transformer_init`` from the seed and, in place of the zeros a
    correction bias starts training from, a bias normal(0, 0.01) from the
    seed: zero would leave selection by ``score + bias`` untested."""
    from functools import partial

    import jax
    from ray_tpu.models import transformer_init

    def make(key):
        params = transformer_init(key, cfg)
        stacks = []
        for i, stack in enumerate(params["layers"]):
            bias = stack["moe"]["router_bias"]
            stacks.append(dict(stack, moe=dict(
                stack["moe"], router_bias=(0.01 * jax.random.normal(
                    jax.random.fold_in(key, 0xB1A5 + i), bias.shape)
                ).astype(bias.dtype))))
        return dict(params, layers=tuple(stacks))

    params = jax.jit(make)(jax.random.PRNGKey(lm.fold_seed(seed)))
    jax.block_until_ready(params)
    return params


def reference_weights(params: dict, config: dict):
    """The program's parameter tree as the reference's ``Weights``: the
    same arrays, a layer at a time."""
    ref = lm.reference_module(config)
    lead = config["first_k_dense_replace"]
    period = len(params["layers"])

    def layer(i: int) -> dict:
        if i < lead:
            stack, at = params["dense_layers"], i
        else:
            stack = params["layers"][(i - lead) % period]
            at = (i - lead) // period
        one = {k: v[at] for k, v in stack.items() if k in ("ln1", "ln2")}
        attn = stack.get("mla", stack.get("swa"))
        one["attn"] = {k: ({n: w[at] for n, w in v.items()}
                           if isinstance(v, dict) else v[at])
                       for k, v in attn.items()}
        if "mlp" in stack:
            one["mlp"] = {k: v[at] for k, v in stack["mlp"].items()}
        else:
            moe = stack["moe"]
            one["moe"] = {k: moe[k][at] for k in ("router", "router_bias")}
            one["moe"]["shared"] = {k: v[at]
                                    for k, v in moe["shared"].items()}
            one["moe"]["expert"] = lambda e: tuple(
                moe[k][at, e] for k in ("w1", "w3", "w2"))
        return one

    return ref.Weights(embed=params["embed"], layer=layer,
                       n_layers=config["num_hidden_layers"],
                       final_norm=params["final_norm"],
                       lm_head=params["lm_head"])


def prompt_of(seed: int, rid: int, vocab: int, length: int):
    """Request ``rid``'s prompt; requests 0 and 1 (both in the warm-up
    round) carry one prompt, which is also the check's."""
    import numpy as np
    return np.random.default_rng([lm.fold_seed(seed), max(rid, 1)]).integers(
        0, vocab, length)


# ---------------------------------------------------------------------------
# the comparison that decides ``correct`` (the sweep runs the same functions)
# ---------------------------------------------------------------------------

class Program:
    """``prefill_and_taps`` over one row in the timed path's chunks and
    ``decode_step_and_taps``, compiled once for a configuration."""

    def __init__(self, cfg, prompt: int, new: int):
        import importlib
        from functools import partial

        import jax
        gen = importlib.import_module("ray_tpu.models.generate")
        self.cfg, self.prompt, self.new = cfg, prompt, new
        self.prefill = jax.jit(partial(
            gen.prefill_and_taps, cfg=cfg, max_len=prompt + new,
            chunk=gen.prefill_chunk(prompt)))
        self.step = jax.jit(partial(gen.decode_step_and_taps, cfg=cfg))

    def unload(self) -> None:
        """Give the two compiled programs up: a loaded program holds its
        temporaries' room on the chip (1-3 GB here), which the reference
        needs."""
        import jax
        self.prefill = self.step = None
        jax.clear_caches()

    def run(self, params, tokens, decoded: int, window_cfg=None) -> dict:
        """``tokens`` [1, P + decoded]: the prompt through the prefill, then
        ``decoded`` steps, step j fed ``tokens[:, P + j]`` (what the served
        ``generate`` emitted: its own greedy tokens would be another
        sequence as soon as one differs) -> ``logits`` [1, decoded + 1,
        vocab], the cache the steps left, ``selected`` / ``selected_real``
        [full layers, 1, decoded + 1, topk], ``moe_rows`` [here, dropped],
        ``window_keys_off`` (held to ``window_cfg``'s window where given:
        a planted fault's own is what is being judged)."""
        import jax.numpy as jnp
        p, cfg = self.prompt, window_cfg or self.cfg
        tokens = jnp.asarray(tokens, jnp.int32)
        logits, cache, taps = self.prefill(params, tokens[:, :p])
        system, picks, rows = [logits], [_selections(taps)], taps["moe_rows"]
        off = window_keys_off(cfg, taps, p - 1)
        for j in range(decoded):
            logits, cache, taps = self.step(
                params, tokens[:, p + j], jnp.asarray(p + j, jnp.int32),
                cache)
            system.append(logits)
            picks.append(_selections(taps))
            rows = rows + taps["moe_rows"]
            off = max(off, window_keys_off(cfg, taps, p + j))
        out = {"logits": jnp.stack(system, axis=1), "cache": cache,
               "moe_rows": rows, "window_keys_off": off}
        if picks[0] is not None:
            out["selected"] = jnp.stack([s[0] for s in picks], axis=2)
            out["selected_real"] = jnp.stack([s[1] for s in picks], axis=2)
        return out


def _tapped(taps: dict, *names):
    """The taps of ``_over_the_kinds`` -> each of ``names`` [layers that
    have it, B, ...] in layer order, None where no layer has them."""
    import jax.numpy as jnp
    found = ([taps["lead"]] if taps["lead"] else []) \
        + [t for t in taps["periods"] if t]
    found = [t for t in found if names[0] in t]
    if not found:
        return None
    return tuple(jnp.concatenate([t[name] for t in found])
                 for name in names)


def _selections(taps: dict):
    """(selected, selected_real) [full layers, B, topk], or None."""
    return _tapped(taps, "selected", "selected_real")


def window_keys_off(cfg, taps: dict, position: int) -> int:
    """How far the keys a window layer's query at ``position`` attended to
    are from the window's ``min(position + 1, window)``: the largest
    difference over the window layers and rows; 0 without such layers."""
    import numpy as np
    found = _tapped(taps, "window_keys")
    if found is None:
        return 0
    return int(np.abs(np.asarray(found[0])
                      - min(position + 1, cfg.window)).max())


def ring_held(cfg, positions: int):
    """The positions a window layer's ring holds, in slot order, once
    ``positions`` positions are written (numpy; -1: a slot never
    written)."""
    import numpy as np
    from ray_tpu.models.generate import window_rows
    rows, last = window_rows(cfg), positions - 1
    held = last - (last - np.arange(rows)) % rows
    return np.where(held >= 0, held, -1)


def ring_order(cfg, positions: int):
    """The ring's written slots, in the order of the positions they
    hold."""
    import numpy as np
    held = ring_held(cfg, positions)
    return np.argsort(held)[(held < 0).sum():]


def cut_to(cfg, details: dict, positions: int) -> dict:
    """A pass's details as a cache holds them once ``positions`` positions
    are written: the latents and indexer keys of those positions, and of
    the sliding layers' entries those a ring then holds."""
    import numpy as np
    out = dict(details)
    for kind in ("latent", "index"):
        if out.get(kind) is not None:
            out[kind] = out[kind][:, :, :positions]
    if out.get("window") is not None:
        held = ring_held(cfg, positions)
        out["window"] = out["window"][:, :, np.sort(held[held >= 0])]
    return out


def reference_pass(cfg, params, config: dict, tokens, prompt: int,
                   dtype=None, weights=None, cut: bool = True) -> dict:
    """The reference over ``tokens`` [B, S], the logits from the prompt's
    last position on; ``cut_to`` S positions unless ``cut`` is False."""
    ref = lm.reference_module(config)
    out = ref.forward_and_details(
        weights or reference_weights(params, config), tokens, config,
        dtype=dtype, keep_from=prompt - 1)
    return cut_to(cfg, out, tokens.shape[1]) if cut else out


def cache_view(cfg, cache: dict, positions: int) -> dict:
    """The program's cache as the reference's details are laid out: [slots,
    B, positions, width] of the latents and indexer keys, [slots, B, the
    rings' positions in order, width] of the rings."""
    out = {kind: cache[kind][:, :, :positions, 0]
           for kind in ("latent", "index") if kind in cache}
    if "window" in cache:
        out["window"] = cache["window"][:, :, ring_order(cfg, positions), 0]
    return out


def errors(got: dict, reference: dict) -> dict:
    """``got`` (a cache as ``cache_view`` lays it out, with a program's
    ``logits`` where it has them, or another pass of the reference) against
    the float32 reference: the logits' rms error a position, over the
    positions both have from the prompt's last on, and the caches' a (kind,
    slot)."""
    import numpy as np
    ref = lm.reference_module({"family": "dots3"})

    def last_common(a, b):      # a ring of another length holds fewer
        n = min(a.shape[1], b.shape[1])
        return a[:, a.shape[1] - n:], b[:, b.shape[1] - n:]

    out = {"cache": {f"{kind}.{slot}": ref.rms(*last_common(
        got[kind][slot], reference[kind][slot]))
        for kind in ("latent", "index", "window")
        if reference.get(kind) is not None and kind in got
        for slot in range(reference[kind].shape[0])}}
    if "logits" in got:
        n = min(got["logits"].shape[1], reference["logits"].shape[1])
        out["logits"] = np.asarray(ref.errors_a_position(
            got["logits"][:, :n], reference["logits"][:, :n])).tolist()
    return out


def compare(program: dict, served_cache: dict, reference: dict) -> dict:
    """The check's numbers that need no floor: the caches are the served
    ``generate``'s own (``served_cache``: row 0's ``cache_view``), the
    logits and the taps the side program's over the same tokens."""
    import numpy as np
    ref = lm.reference_module({"family": "dots3"})
    n = program["logits"].shape[1]
    out = dict(ref.compare_logits(program["logits"],
                                  reference["logits"][:, :n]))
    out["errors"] = errors(dict(served_cache, logits=program["logits"]),
                           reference)
    rows = np.asarray(program["moe_rows"]).tolist()
    out["moe_rows_here"], out["moe_rows_dropped"] = int(rows[0]), int(rows[1])
    out["window_keys_off"] = int(program.get("window_keys_off", 0))
    if reference.get("selected") is not None and "selected" in program:
        out["selection_overlap"] = ref.selection_overlap(
            program["selected"], program["selected_real"],
            reference["selected"][:, :, :n],
            reference["selected_real"][:, :, :n])
    return out


def routing_numbers(cfg, params, config: dict, reference: dict,
                    weights=None) -> dict:
    """The program's ``moe.route`` over the expert layers' inputs as the
    reference had them, against the reference's weights for every
    published expert: ``routing_weights_off``, the median over (layer,
    position) of the weights' distance over their length (a flipped
    near-tie moves a position, not the median: it reads what the weights
    are made of), and ``routing_missed``, the share of the reference's
    (position, expert) pairs the program did not choose (it reads what the
    selection is made by)."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import moe
    if reference.get("routed_inputs") is None:
        return {}
    weights = weights or reference_weights(params, config)
    layers = [i for i in range(config["num_hidden_layers"])
              if i >= config["first_k_dense_replace"]]
    off, missed, pairs = [], 0, 0
    for i, h, want in zip(layers, reference["routed_inputs"],
                          reference["routed_weights"]):
        m = weights.layer(i)["moe"]
        x = jnp.asarray(h).reshape(-1, h.shape[-1]).astype(cfg.dtype)
        w, e = jax.jit(partial(moe.route, cfg))(
            {"router": m["router"], "router_bias": m["router_bias"]}, x)
        want = np.asarray(want, np.float32).reshape(-1, want.shape[-1])
        got = np.zeros_like(want)
        np.put_along_axis(got, np.asarray(e), np.asarray(w, np.float32), -1)
        off += (np.linalg.norm(got - want, axis=-1)
                / np.linalg.norm(want, axis=-1)).tolist()
        missed += int(((want > 0) & (got == 0)).sum())
        pairs += int((want > 0).sum())
    return {"routing_weights_off": float(np.median(off)),
            "routing_missed": missed / max(pairs, 1)}


def over_floors(errs: dict, floor: dict) -> dict:
    """``errs["cache"]`` names ``kind.slot`` (row 0) or ``kind.slot@row``;
    a row is read over row 0's floor of its (kind, slot)."""
    ref = lm.reference_module({"family": "dots3"})
    n = len(errs["logits"])
    logits = ref.over_floor(errs["logits"], floor["logits"][:n])
    names = sorted(errs["cache"])
    cache = ref.over_floor([errs["cache"][n] for n in names],
                           [floor["cache"][n.split("@")[0]] for n in names])
    return {"rms_over_floor": logits["typical"],
            "rms_over_floor_worst": logits["worst"],
            "cache_over_floor": cache["typical"],
            "cache_over_floor_worst": cache["worst"]}


def token_gaps(served: list, reference_logits, floor: float) -> list:
    """Each served token's gap under the reference's best at its position,
    over ``floor``."""
    import numpy as np
    logits = np.asarray(reference_logits, np.float32)
    return [float(logits[j].max() - logits[j][token]) / floor
            for j, token in enumerate(served[:logits.shape[0]])]


def served_deficit(served: list, checked: list, reference_logits,
                   floor: float) -> dict:
    """Tokens a compiled ``generate`` served a prompt, against the
    reference's logits teacher-forced on ``checked`` (the tokens the check's
    own call of that ``generate`` served it): as far as the two agree, and
    the first served token that differs, the widest ``token_gaps``."""
    same = 0
    while same < min(len(served), len(checked)) \
            and served[same] == checked[same]:
        same += 1
    gaps = token_gaps(served[:same + 1], reference_logits, floor)
    return {"token_deficit_over_floor": max(gaps),
            "tokens_checked": len(gaps), "tokens_as_the_check": same}


def check_prompts(seed: int, vocab: int, length: int, rows: int):
    """The prompts of the check's own call of the served ``generate``, a row
    each and no two alike: row 0's is the warm-up twins'."""
    import numpy as np
    return np.stack([prompt_of(seed, 1 + row, vocab, length)
                     for row in range(rows)])


def jitted_generate(cfg, new: int):
    """The served program: ``generate_and_cache``, whose cache the check
    reads and a request's call leaves on the chip."""
    from functools import partial

    import jax
    from ray_tpu.models.generate import generate_and_cache
    return jax.jit(partial(generate_and_cache, cfg=cfg, temperature=0.0,
                           max_new_tokens=new))


def chip_bytes(what: str = "bytes_in_use") -> int:
    import jax
    return int((jax.devices()[0].memory_stats() or {}).get(what, 0))


def said(t0: float, what: str) -> None:
    log(f"check: {what} at {time.time() - t0:.1f} s, "
        f"{chip_bytes() / 1e9:.2f} GB on the chip, "
        f"{chip_bytes('peak_bytes_in_use') / 1e9:.2f} GB at the most so far")


def served_by(cfg, params, prompts, new: int) -> dict:
    """One call of the served ``generate`` on ``prompts`` [rows, P] -> on
    the host: ``fed`` [rows, P + new] (the prompts and every token it
    emitted), ``views`` (a row's ``cache_view`` of the cache it left) and
    its dropped rows. The program is then given up, as ``Program.unload``
    gives its own up: loaded, it holds its 3 GB of temporaries' room on the
    chip, which the reference's passes need; whoever serves it compiles it
    again after them, from the compile cache where there is one."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np
    t0 = time.time()
    gen = jitted_generate(cfg, new)
    tokens, stats, cache = gen(params, jnp.asarray(prompts, jnp.int32))
    tokens = np.asarray(tokens)
    said(t0, "the served generate compiled and called once")
    view = jax.device_get(cache_view(
        cfg, cache, prompts.shape[1] + tokens.shape[1]))
    dropped = int(stats["moe_rows_dropped"])
    del gen, cache, stats
    jax.clear_caches()
    gc.collect()
    said(t0, "its program given up")
    held = sum(x.nbytes for x in jax.tree.leaves(params))
    if chip_bytes() > held + 2 ** 30:
        raise RuntimeError(
            f"{chip_bytes()} bytes are in use on the chip beside {held} of "
            "weights after the served program was given up: the reference's "
            "passes would not fit")
    return {"fed": np.concatenate([prompts, tokens], axis=1),
            "views": [{kind: a[:, row:row + 1] for kind, a in view.items()}
                      for row in range(prompts.shape[0])],
            "moe_rows_dropped": dropped}


def held_to_the_reference(cfg, params, config: dict, served: dict,
                          runner, decoded: int) -> dict:
    """What ``correct`` reads of the served ``generate``'s own call
    (``served``: ``fed`` [rows, P + new], the prompts and the tokens it
    emitted, ``views`` [a row's ``cache_view`` of the cache it left]):
    every row's tokens and caches against the reference's float32 pass
    teacher-forced on that row; row 0 also through ``runner`` (the side
    program: its logits, selections, window counts and dropped rows over
    the prompt and ``decoded`` of those tokens) and through the router; and
    the reference once more over row 0 with its activations rounded to the
    file's ``torch_dtype``: the floor each error is read over. -> (the
    checks, what ``aftercheck`` needs: row 0's ``tokens``, the reference's
    ``logits`` for them and the logits' ``floor``; and for the sweep the
    whole float32 pass over row 0, ``reference``, on the host)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = lm.reference_module(config)
    p = runner.prompt
    fed = served["fed"]
    new = fed.shape[1] - p
    t0 = time.time()
    program = runner.run(params, fed[:1, :p + decoded], decoded)
    del program["cache"]            # the caches read are the served call's
    runner.unload()
    said(t0, "the side program run and given up")
    uncut = reference_pass(cfg, params, config, jnp.asarray(fed[:1]), p,
                           cut=False)
    out = compare(program, served["views"][0],
                  cut_to(cfg, uncut, fed.shape[1]))
    del program
    out.update(routing_numbers(cfg, params, config, uncut))
    uncut = jax.device_get(uncut)
    said(t0, "row 0's float32 pass and its comparisons")
    full = cut_to(cfg, uncut, fed.shape[1])
    rounded = reference_pass(cfg, params, config, jnp.asarray(fed[:1]), p,
                             dtype=jnp.dtype(config["torch_dtype"]))
    floor = out["floor_errors"] = errors(rounded, full)
    out["floor_rms_over_std"] = ref.compare_logits(
        rounded["logits"], full["logits"])["rms_over_std"]
    del rounded
    said(t0, "row 0's rounded pass (the floor)")
    # one floor for every token, the typical position's: a position's own
    # swings fivefold with the rounded pass's own flipped selections
    typical = float(np.exp(np.mean(np.log(floor["logits"]))))
    keep = {"tokens": fed[0, p:].tolist(),
            "logits": np.asarray(full["logits"])[0],
            "floor": typical, "reference": uncut}
    del uncut
    gaps = token_gaps(keep["tokens"], keep["logits"], typical)
    for row in range(1, fed.shape[0]):
        del full
        full = jax.device_get(reference_pass(
            cfg, params, config, jnp.asarray(fed[row:row + 1]), p))
        for name, err in errors(served["views"][row], full)["cache"].items():
            out["errors"]["cache"][f"{name}@{row}"] = err
        gaps += token_gaps(fed[row, p:].tolist(),
                           np.asarray(full["logits"])[0], typical)
        said(t0, f"row {row}'s float32 pass")
    out["token_deficit_over_floor"] = max(gaps)
    out["tokens_checked"] = len(gaps)
    # reported beside it, not judged: how the gaps lie, and what two planted
    # faults would have read on this seed (a row served the tokens of the
    # one after it; row 0's last token the next id)
    out["token_gaps"] = {
        "mean": float(np.mean(gaps)), "p90": float(np.quantile(gaps, 0.9)),
        "p99": float(np.quantile(gaps, 0.99)),
        "zero": int(np.sum(np.asarray(gaps) == 0))}
    out["token_deficit_rows_swapped"] = max(token_gaps(
        fed[-1, p:].tolist(), keep["logits"], typical))
    out["token_deficit_one_altered"] = token_gaps(
        [0] * (new - 1) + [(keep["tokens"][-1] + 1) % cfg.vocab_size],
        keep["logits"], typical)[-1]
    return out, keep


# ---------------------------------------------------------------------------
# the replica
# ---------------------------------------------------------------------------

PHASE = re.compile(r"rt\.(?!generate\.)[a-z_]+(?:\.[a-z_]+)+")


def phase_map(hlo_text: str) -> dict:
    """{instruction: rt.generate.prefill | rt.generate.decode}: the scope
    map of the module's text with every other scope's name taken out."""
    from benchmark import trace_scopes
    return trace_scopes.scope_map(PHASE.sub("x", hlo_text))


def reduce_trace(path: str, scopes: dict, phases: dict) -> dict:
    """``serve_ouro.reduce_trace`` and, under ``phases``, the same
    reduction by the call's two phases, from one reading of the file."""
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
            [sys.executable, "-m", "benchmark.apps.serve_dots3", asked, told],
            check=True, cwd=checkout, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        with open(told) as f:
            return json.load(f)


def served_memory(report: dict) -> dict:
    """``lm.memory_report`` with ``peak_bytes`` the served program's: the
    compiler's peak of the compiled ``generate``, its weights, outputs and
    temporaries. The runtime's high-water mark, which cannot be set back, is
    the check's (the float32 reference beside the weights, before the
    served program is loaded for good) and is kept as
    ``check_peak_bytes``."""
    served = report["compiled"].get("peak_memory_in_bytes", 0)
    if not served:
        return report
    return dict(report, peak_bytes=served,
                check_peak_bytes=report["runtime_peak_bytes"],
                peak_is="the compiler's peak_memory_in_bytes of the served "
                        "generate; the runtime's peak_bytes_in_use is the "
                        "check's (check_peak_bytes)")


def make_replica(max_batch_size: int, batch_wait_timeout_s: float):
    """``serve_ouro``'s replica class with what this family changes."""
    from ray_tpu import serve
    base = serve_ouro.make_replica(max_batch_size, batch_wait_timeout_s)

    class Dots3Replica(base):
        def __init__(self, spec: dict):
            self.stamps = {"entry": time.time()}
            from functools import partial
            import threading

            import jax
            import jax.numpy as jnp
            import numpy as np

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
            # the served program is compiled in ``selfcheck``
            self.gen, self.gen_memory = None, {}
            self.scopes, self.phases = {}, {}
            self.lock = threading.Lock()    # one generate call at a time
            self.requests, self.batches, self.profiler = {}, [], []
            self.inside, self.inside_max = 0, 0     # requests in __call__
            self.count_lock = threading.Lock()
            self.reduced, self.marks, self.stopper = {}, None, None
            self.ticker = serve_ouro.HostTicker()

        def compile_generate(self) -> None:
            spec = self.spec
            prompts = self.jnp.zeros((self.rows, self.prompt), self.jnp.int32)
            self.gen = jitted_generate(self.cfg, spec["new_tokens"]).lower(
                self.params, prompts).compile()
            self.gen_memory = lm.compiled_peak(self.gen)
            if spec["trace"]:
                from benchmark import trace_scopes
                text = self.gen.as_text()
                self.scopes = trace_scopes.scope_map(text)
                self.phases = phase_map(text)

        def selfcheck(self) -> dict:
            """The served ``generate`` itself, compiled as it is timed, is
            what is compared: one call of it on the check's prompts
            (``served_by``), then ``held_to_the_reference``; every program
            of the check is given up, and the served one is compiled for
            good."""
            jax, jnp = self.jax, self.jnp
            spec, cfg, config = self.spec, self.cfg, self.spec["config"]
            served = served_by(cfg, self.params, check_prompts(
                spec["seed"], cfg.vocab_size, self.prompt, self.rows),
                spec["new_tokens"])
            self.stamps["served_the_check"] = time.time()
            out, kept = held_to_the_reference(
                cfg, self.params, config, served,
                Program(cfg, self.prompt, spec["new_tokens"]),
                min(CHECK_DECODED, spec["new_tokens"] - 1))
            del kept["reference"]
            self.checked = kept
            out["moe_rows_dropped"] += served["moe_rows_dropped"]
            leaves = jax.tree.leaves(self.params)
            out.update(
                n_params=int(sum(x.size for x in leaves)),
                param_dtypes=sorted({str(x.dtype) for x in leaves}),
                compute_dtype=str(jnp.dtype(cfg.dtype)),
                rms_norm_eps={"published": float(config["rms_norm_eps"]),
                              "program": float(cfg.norm_eps)})
            jax.clear_caches()          # the reference's programs, too
            self.stamps["checked"] = time.time()
            self.compile_generate()
            self.stamps["ready"] = time.time()
            return out

        def aftercheck(self, pairs: list) -> dict:
            """After the window: the tokens the served ``generate`` gave a
            warm-up twin (``pairs``: [(prompt, served tokens)]; the prompt is
            the check's row 0) as far as they are the tokens the check's
            call gave that prompt, against the check's reference logits.
            Host arithmetic only."""
            (_, served), = pairs[:1]
            kept = self.checked
            return {"twin_" + k: v for k, v in served_deficit(
                list(served), kept["tokens"], kept["logits"],
                kept["floor"]).items()}

        @serve.batch(max_batch_size=max_batch_size,
                     batch_wait_timeout_s=batch_wait_timeout_s)
        def generate_batch(self, items: list) -> list:
            """``serve_ouro``'s, inside the program's ``generate.call``
            span with the expert layers' counters, fetched with the
            tokens."""
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
                    tokens, stats = jax.device_get(called[:2])
                    del called          # the cache the call left
                    counters = {k: int(v) for k, v in stats.items()}
                    sp.set(**counters)
                end = time.time()
                self.batches.append(dict(
                    counters, start=start, end=end, rows=len(items),
                    padded_rows=self.rows, dispatch_s=dispatched - start,
                    host_pause_max_s=self.ticker.longest(),
                    rids=[rid for _, rid in items]))
            return [tokens[i].tolist() for i in range(len(items))]

        def dump(self) -> dict:
            """``serve_ouro``'s, with the trace reduced by the program's
            scopes and by the call's two phases."""
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
                "memory": served_memory(lm.memory_report(
                    self.devs, self.gen_memory, "generate"))}

    return Dots3Replica


def judged(record: dict, config: dict, traffic: dict) -> dict:
    """``serve_lm``'s exact checks and this family's numbers under LIMITS:
    ``{name: [value, limit]}``."""
    checks = record["checks"]
    checks.setdefault("token_deficit_over_std", 0.0)   # not judged here
    out = serve_lm.judged(record, config, traffic)
    del out["token_deficit_over_std"], out["rms_over_floor"]
    over = over_floors(checks["errors"], checks["floor_errors"])
    checks["over_floors"] = over
    for name, value in over.items():
        out[name] = [value, LIMITS[name]]
    out["selection_missed"] = [1.0 - checks.get("selection_overlap", 1.0),
                               LIMITS["selection_missed"]]
    for name in ("routing_weights_off", "routing_missed", "window_keys_off"):
        out[name] = [checks[name], LIMITS[name]]
    out["token_deficit_over_floor"] = [
        max(checks["token_deficit_over_floor"],
            checks["twin_token_deficit_over_floor"]),
        LIMITS["token_deficit_over_floor"]]
    out["moe_rows_dropped"] = [
        checks["moe_rows_dropped"]
        + sum(b.get("moe_rows_dropped", 0) for b in record["batches"]),
        LIMITS["moe_rows_dropped"]]
    return out


WHAT_EACH_CHECK_SAYS = dict(
    {name: says for name, says in serve_lm.WHAT_EACH_CHECK_SAYS.items()
     if name not in ("token_deficit_over_std", "rms_over_floor")},
    rms_over_floor="at the typical position (the geometric mean over the "
                   "prompt's last and the decoded positions) the logits of "
                   "chunked prefill + decode through the caches are off the "
                   "reference (rms) by this many times what bfloat16 "
                   "rounding alone does to this seed's model there",
    rms_over_floor_worst="the same at the worst position",
    cache_over_floor="the latents and indexer keys of every position and "
                     "the window rings that the compiled generate's own "
                     "call left in its cache, both rows, are off the "
                     "reference's (rms), at the typical (row, kind, slot), "
                     "by this many times what bfloat16 rounding alone does "
                     "to them",
    cache_over_floor_worst="the same at the worst (row, kind, slot)",
    selection_missed="this share of the positions the reference's indexer "
                     "selected (both full layers, the prompt's last query "
                     "and the decoded ones) the program did not select",
    token_deficit_over_floor="of the 2 x 128 tokens the compiled generate "
                             "served the check's prompts (and of what it "
                             "then served a warm-up twin), one lies under "
                             "the reference's best at its position by this "
                             "many times what bfloat16 rounding alone does "
                             "to the logits at the typical position",
    routing_weights_off="at the typical (expert layer, position) the "
                        "weights the program's router gives the published "
                        "experts are this far from the reference's, over "
                        "their length",
    routing_missed="this share of the (position, expert) pairs the "
                   "reference's router chose the program's did not",
    window_keys_off="a window layer's query attended to this many keys "
                    "more or fewer than the window holds at its position "
                    "(one key of 513 moves the logits by less than their "
                    "floor: counted, not inferred)",
    moe_rows_dropped="rows routed to held experts that the expert layers' "
                     "buffer dropped, in the check (the side program and "
                     "the served call) and in the window's calls: in "
                     "serving a dropped row is a wrong answer")


def judge(record: dict, config: dict, traffic: dict) -> list:
    """-> reasons this run is not correct (empty: correct), each naming
    the check, its number and its limit. Leaves ``record["judged"]``."""
    record["judged"] = judged(record, config, traffic)
    return lm.over_their_limits(record["judged"], WHAT_EACH_CHECK_SAYS)


def drive(run) -> dict:
    """``run`` is ``benchmark.run.RunContext``. -> the run's record."""
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
    # In the benchmark's own process, before anything starts: a program
    # without the family's mechanisms refuses the configuration at once.
    transformer_config(spec["model"], remat=False)
    os.environ["MALLOC_ARENA_MAX"] = "1"       # as serve_ouro

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
        # With no compiled code to start from (the driver's first run of a
        # cell) the check is about 12 minutes, 8 of them the chip's host
        # compiling: as long a wait as the harness's own deadline leaves
        # beside the warm-up and the window.
        checks = rt.get(handle.options(method_name="selfcheck").remote(),
                        timeout=1050)
    except Exception as e:
        raise run.failure(f"replica did not come up: {e!r}",
                          before_window=True) from e

    vocab, plen = config["vocab_size"], traffic["prompt_tokens"]

    def body(rid: int) -> bytes:
        prompt = prompt_of(run.seed, rid, vocab, plen)
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
    # the warm-up's twins carry the check's row 0: what the served generate
    # gave one of them is held to the check's reference logits
    twin = next(r for r in warmup if r["rid"] == 1)
    pairs = [(json.loads(body(1))["prompt"], twin["extra"]["tokens"])]
    checks.update(serve_lm.patiently(rt, handle, "aftercheck", pairs))
    record["stamps"]["called"] = called
    record["window_start"] = window["start"]
    record["request_timeout_s"] = gen.timeout
    record["host_cpus"] = os.cpu_count()
    log(f"regime: host has {record['host_cpus']} cpus; at most "
        f"{record['admitted_max']} of {traffic['clients']} callers' requests "
        "were inside the replica at once")
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

"""Operations, bytes and parameters of the ``dots3`` family: the arithmetic
side of the yardstick for its cells, OF THE PUBLISHED MATHEMATICS at the
cell's sizes, from a configuration file and a traffic file, never from what
the program happens to do. ``benchmark/ops.py`` keeps the peaks and the
conventions (one multiply-add is 2 operations; causal work counted as
causal; a lookup is no matmul). Found by the configuration's ``family``
(``benchmark.ops_<family>``).

What is counted, a token and layer:

- the projections of a latent layer: ``W_dq``, ``W_uq``, ``W_dkv``,
  ``W_ukv`` (a position's keys and values are made once, by its own token),
  ``W_o``, the gate, and in a full layer the indexer's three;
- the indexer over EVERY causal key: ``2 x index_n_heads x index_head_dim``
  a (query, key) pair, ``t + 1`` keys for the query at position ``t``;
- sparse attention over ``min(t + 1, index_topk)`` selected keys in the
  absorbed form, ``2 x heads x (2 kv_lora_rank + qk_rope_head_dim)`` a pair:
  each query has keys of its own, so no expanded key is shared;
- window attention over ``min(t + 1, sliding_window_size)`` keys, expanded,
  ``2 x heads x (nope + rope + v)`` a pair;
- the dense SwiGLU of the leading layers; in every other layer the router,
  the shared expert, and this member's part of the routed experts:
  ``num_experts_per_tok x held / published`` experts a token (one at 8 of
  256 with 32 held).

A decode step is bound by bytes: every weight but the routed experts once,
the routed experts the step's rows touch here (a row's expected number,
never more than are held), the indexer's keys of every position so far, the
``index_topk`` gathered cache rows, the rings.
"""

from __future__ import annotations

from benchmark import ops

KINDS = {"full_attention": "", "sliding_attention": "swa_"}


def dims(config: dict, kind: str) -> dict:
    pre = KINDS[kind]
    return {"heads": config[pre + "num_attention_heads"],
            "q_rank": config[pre + "q_lora_rank"],
            "kv_rank": config[pre + "kv_lora_rank"],
            "nope": config[pre + "qk_nope_head_dim"],
            "rope": config[pre + "qk_rope_head_dim"],
            "v": config[pre + "v_head_dim"]}


def sizes(config: dict) -> dict:
    kinds = config["layer_types"]
    return {"d": config["hidden_size"], "ff": config["intermediate_size"],
            "expert_ff": config["moe_intermediate_size"],
            "layers": config["num_hidden_layers"],
            "dense": config["first_k_dense_replace"],
            "full": sum(k == "full_attention" for k in kinds),
            "sliding": sum(k == "sliding_attention" for k in kinds),
            "vocab": config["vocab_size"],
            "held": config["n_routed_experts"],
            "experts": config.get("n_routed_experts_published",
                                  config["n_routed_experts"]),
            "top_k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "ih": config["index_n_heads"], "id": config["index_head_dim"],
            "topk": config["index_topk"],
            "window": config["sliding_window_size"]}


def mixer_params(config: dict, kind: str) -> dict:
    """Matrix parameters of one layer's mixer, by part."""
    z, m = sizes(config), dims(config, kind)
    d = z["d"]
    out = {"wdq": d * m["q_rank"],
           "wuq": m["q_rank"] * m["heads"] * (m["nope"] + m["rope"]),
           "wdkv": d * (m["kv_rank"] + m["rope"]),
           "wukv": m["kv_rank"] * m["heads"] * (m["nope"] + m["v"]),
           "wo": m["heads"] * m["v"] * d, "gate": d * m["heads"],
           "norms": m["q_rank"] + m["kv_rank"]}
    if kind == "full_attention":
        out["index"] = m["q_rank"] * z["ih"] * z["id"] + d * z["id"] \
            + d * z["ih"]
        out["norms"] += 2 * z["id"]
    out["matmul"] = sum(v for k, v in out.items() if k != "norms")
    return out


def param_counts(config: dict) -> dict:
    """Parameters by part; ``total`` is what this member of the expert
    group holds (``transformer_num_params`` of the program's cut)."""
    z = sizes(config)
    d = z["d"]
    expert = 3 * d * z["expert_ff"]
    mixers = {k: mixer_params(config, k) for k in KINDS}
    out = {"mixer": {k: v["matmul"] for k, v in mixers.items()},
           "expert": expert, "routed_held": z["held"] * expert,
           "shared": z["shared"] * expert,
           "router": d * z["experts"] + z["experts"],
           "dense_ffn": 3 * d * z["ff"], "embed": z["vocab"] * d,
           "head": z["vocab"] * d}
    total = out["embed"] + out["head"] + d
    for i, kind in enumerate(config["layer_types"]):
        total += mixers[kind]["matmul"] + mixers[kind]["norms"] + 2 * d
        total += out["dense_ffn"] if i < z["dense"] else \
            out["routed_held"] + out["shared"] + out["router"]
    out["total"] = total
    return out


def experts_a_token(config: dict) -> float:
    """Routed experts of this member a token takes, in expectation."""
    z = sizes(config)
    return z["top_k"] * z["held"] / z["experts"]


def matmul_ops_a_token(config: dict, head: bool = True) -> float:
    """Forward operations of one token's projections and feed-forwards,
    and of its logits where ``head``: every matrix it passes, twice."""
    z, p = sizes(config), param_counts(config)
    d = z["d"]
    touched = p["head"] if head else 0
    for i, kind in enumerate(config["layer_types"]):
        touched += p["mixer"][kind]
        touched += p["dense_ffn"] if i < z["dense"] else \
            d * z["experts"] + p["shared"] \
            + experts_a_token(config) * p["expert"]
    return 2 * touched


def causal_keys(first: int, count: int, most: int = 0) -> int:
    """``sum of min(t + 1, most)`` over the ``count`` queries at positions
    ``first ..``; every key where ``most`` is 0."""
    total = 0
    for t in range(first, first + count):
        total += min(t + 1, most) if most else t + 1
    return total


def pair_ops(config: dict) -> dict:
    """Operations a (query, key) pair: the indexer's, sparse attention's
    (absorbed), window attention's (expanded)."""
    z = sizes(config)
    full, win = dims(config, "full_attention"), \
        dims(config, "sliding_attention")
    return {"index": 2 * z["ih"] * z["id"],
            "sparse": 2 * full["heads"] * (2 * full["kv_rank"]
                                           + full["rope"]),
            "window": 2 * win["heads"] * (win["nope"] + win["rope"]
                                          + win["v"])}


def phase_ops(config: dict, rows: int, first: int, count: int,
              logits: int = None) -> dict:
    """Operations of ``count`` positions from ``first`` on, ``rows`` rows,
    of which ``logits`` go through the head (all where None; a prompt's
    last alone): by part, over all layers."""
    z, pair = sizes(config), pair_ops(config)
    logits = count if logits is None else logits
    return {
        "matmul": rows * (count * matmul_ops_a_token(config, head=False)
                          + logits * 2 * param_counts(config)["head"]),
        "index": rows * z["full"] * pair["index"]
        * causal_keys(first, count),
        "sparse": rows * z["full"] * pair["sparse"]
        * causal_keys(first, count, z["topk"]),
        "window": rows * z["sliding"] * pair["window"]
        * causal_keys(first, count, z["window"])}


def cached_bytes_a_position(config: dict) -> int:
    """bfloat16 bytes of one position of one row in the full layers: the
    latent, the shared key and the indexer's key."""
    z, full = sizes(config), dims(config, "full_attention")
    return z["full"] * (full["kv_rank"] + full["rope"] + z["id"]) * 2


def ring_bytes(config: dict) -> int:
    """bfloat16 bytes of one row's rings: the window rounded up to 8
    positions, in every sliding layer."""
    z, win = sizes(config), dims(config, "sliding_attention")
    return z["sliding"] * -(-z["window"] // 8) * 8 \
        * (win["kv_rank"] + win["rope"]) * 2


def decode_step_bytes(config: dict, rows: int, pos: int,
                      weight_bytes: int) -> dict:
    """Bytes a decode step at position ``pos`` has to read, by part."""
    z, p = sizes(config), param_counts(config)
    full = dims(config, "full_attention")
    experts = z["layers"] - z["dense"]
    weights = p["head"] + sum(
        p["mixer"][k] for k in config["layer_types"]) \
        + z["dense"] * p["dense_ffn"] \
        + experts * (p["shared"] + z["d"] * z["experts"])
    routed = experts * min(rows * experts_a_token(config), z["held"]) \
        * p["expert"]
    return {"weights": (weights + routed) * weight_bytes,
            "index": rows * z["full"] * (pos + 1) * z["id"] * 2,
            "sparse": rows * z["full"] * min(pos + 1, z["topk"])
            * (full["kv_rank"] + full["rope"]) * 2,
            "window": rows * ring_bytes(config)}


def generate_least_seconds(config: dict, rows: int, prompt: int, new: int,
                           weight_dtype: str, device_kind: str) -> dict:
    """Least time for one ``generate`` call as it is issued: the prompt's
    operations over the matrix peak, then each decode step's bytes over
    bandwidth or its operations over the peak, whichever is larger. By
    part too: ``index_seconds`` and ``sparse_seconds`` are the least of the
    indexer and of the sparse attention over the whole call (prefill at
    the matrix peak, each decode step by its bytes or its operations),
    ``window_seconds`` the window layers' attention."""
    pk = ops.peaks(device_kind)
    flops, bw = pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"]
    wbytes = ops._DTYPE_BYTES[weight_dtype]
    pre = phase_ops(config, rows, 0, prompt, logits=1)
    part = {name: pre[name] / flops for name in ("index", "sparse",
                                                  "window")}
    t_prefill = sum(pre.values()) / flops
    t_decode = 0.0
    decode_ops = {name: 0.0 for name in pre}
    for step in range(new):
        pos = prompt + step
        step_ops = phase_ops(config, rows, pos, 1)
        step_bytes = decode_step_bytes(config, rows, pos, wbytes)
        for name in decode_ops:
            decode_ops[name] += step_ops[name]
        for name in part:
            part[name] += max(step_ops[name] / flops, step_bytes[name] / bw)
        t_decode += max(sum(step_ops.values()) / flops,
                        sum(step_bytes.values()) / bw)
    return {"seconds": t_prefill + t_decode, "prefill_seconds": t_prefill,
            "decode_seconds": t_decode, "prefill_ops": pre,
            "decode_ops": decode_ops,
            "index_seconds": part["index"], "sparse_seconds": part["sparse"],
            "window_seconds": part["window"],
            "cache_bytes": rows * ((prompt + new)
                                   * cached_bytes_a_position(config)
                                   + ring_bytes(config)),
            "bound": "prefill compute, decode memory"}

"""Operations, bytes and parameters of the ``joyai`` family (JoyAI-LLM-Flash:
latent attention in every layer, a leading dense layer, sigmoid-routed
experts with a shared one, a multi-token-prediction module): the arithmetic
side of the yardstick for its cells, computed from a configuration file's
sizes and a traffic file's shapes, never from the program.
``benchmark/ops.py`` keeps the peaks and the conventions (one multiply-add
is 2 operations; causal attention counted as causal; training is 3x the
forward matmul work, recomputation not counted; a lookup is no matmul).
Found by the configuration's ``family`` (``benchmark.ops_<family>``).

A configuration here is the chip's share (``benchmark/configs``): it holds
``n_routed_experts`` of the ``n_routed_experts_published`` experts and
``vocab_size`` rows of the vocabulary. A token is routed to
``num_experts_per_tok`` of the published experts, so on average ``k x held /
published`` of its routed rows fall here; that expectation is what the
per-token numbers use, and the roofline of the expert matmuls uses the rows
the run really routed. The module is one more expert layer, over S - 1
positions; the per-token numbers count it at S.
"""

from __future__ import annotations

from benchmark import ops


def sizes(c: dict) -> dict:
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "rq": c["q_lora_rank"], "rkv": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "v": c["v_head_dim"], "f": c["intermediate_size"],
            "fe": c["moe_intermediate_size"],
            "held": c["n_routed_experts"],
            "published": c.get("n_routed_experts_published",
                               c["n_routed_experts"]),
            "k": c["num_experts_per_tok"],
            "shared": c["n_shared_experts"],
            "layers": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"],
            "modules": c["num_nextn_predict_layers"],
            "vocab": c["vocab_size"]}


def mixer_matmul_params(c: dict) -> int:
    """The mixer's five projections (its two latent norms left out)."""
    z = sizes(c)
    qk = z["nope"] + z["rope"]
    return (z["d"] * z["rq"] + z["rq"] * z["h"] * qk
            + z["d"] * (z["rkv"] + z["rope"])
            + z["rkv"] * z["h"] * (z["nope"] + z["v"])
            + z["h"] * z["v"] * z["d"])


def param_counts(c: dict) -> dict:
    """Parameters by part. ``total`` is what a program holding this
    configuration holds; ``whole_model`` the published model (all experts,
    the whole vocabulary, ``num_hidden_layers_published`` layers, the
    module)."""
    z = sizes(c)
    d = z["d"]
    mixer = mixer_matmul_params(c) + z["rq"] + z["rkv"]
    expert = 3 * d * z["fe"]
    router = d * z["published"] + z["published"]

    def expert_layer(held):
        return (mixer + 2 * d + router + held * expert
                + z["shared"] * expert)

    dense_layer = mixer + 2 * d + 3 * d * z["f"]

    def model(layers, held, vocab):
        module = expert_layer(held) + 2 * d * d + 3 * d
        return (z["dense"] * dense_layer
                + (layers - z["dense"]) * expert_layer(held)
                + z["modules"] * module + 2 * vocab * d + d)

    return {"mixer": mixer, "expert": expert, "router": router,
            "expert_layer": expert_layer(z["held"]),
            "dense_layer": dense_layer,
            "module": expert_layer(z["held"]) + 2 * d * d + 3 * d,
            "embed_and_head": 2 * z["vocab"] * d,
            "total": model(z["layers"], z["held"], z["vocab"]),
            "whole_model": model(
                c.get("num_hidden_layers_published", z["layers"]),
                z["published"], c.get("vocab_size_published", z["vocab"]))}


def forward_ops_per_token(c: dict, seq: int) -> dict:
    z = sizes(c)
    d = z["d"]
    mixers = z["layers"] + z["modules"]
    expert_layers = z["layers"] - z["dense"] + z["modules"]
    routed_here = z["k"] * z["held"] / z["published"]
    expert = 2 * 3 * d * z["fe"]
    parts = {
        "projections": mixers * 2 * mixer_matmul_params(c),
        # causal: S/2 keys a query on average, (qk + v) a head and pair
        "scores": mixers * seq * z["h"] * (z["nope"] + z["rope"] + z["v"]),
        "heads": (1 + z["modules"]) * 2 * d * z["vocab"],
        "dense_ffn": z["dense"] * 2 * 3 * d * z["f"],
        "experts": expert_layers * (2 * d * z["published"]
                                    + z["shared"] * expert
                                    + routed_here * expert)
        + z["modules"] * 2 * 2 * d * d}
    parts["total"] = sum(parts.values())
    return parts


def train_ops_per_token(c: dict, seq: int) -> float:
    """Forward + backward (2x forward) operations a training step needs per
    token; what ``train.mfu.joyai`` divides by the peak."""
    return 3.0 * forward_ops_per_token(c, seq)["total"]


def _least(ops_n: float, bytes_n: float, device_kind: str) -> dict:
    pk = ops.peaks(device_kind)
    t_ops = ops_n / pk["bf16_flops_per_s"]
    t_bytes = bytes_n / pk["hbm_bytes_per_s"]
    return {"ops": ops_n, "bytes": bytes_n, "seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory"}


def mla_attend_step_least_seconds(c: dict, seq: int, rows: int,
                                  device_kind: str) -> dict:
    """Least time one chip could spend in one training step's attention
    over the expanded keys (the scope ``rt.mla.dense``), every latent layer
    and the module's together: the IDEAL work, ``heads x (qk + v)``
    multiply-adds a causal (query, key) pair, forward and backward (2x);
    what the kernels make again (the remat'd forward, the scores in both
    backward kernels), a padded width or an upper triangle not skipped is
    not counted, and reads as a lower share. Bytes: q, k, v read and o
    written a forward pass; those, o and its gradient read and three
    gradients written in the backward (bfloat16)."""
    z = sizes(c)
    qk, v = z["nope"] + z["rope"], z["v"]
    pairs = z["layers"] * rows * seq * (seq + 1) / 2 \
        + z["modules"] * rows * (seq - 1) * seq / 2
    tokens = rows * (z["layers"] * seq + z["modules"] * (seq - 1))
    forward_io = (2 * qk + 2 * v) * z["h"] * 2
    backward_io = (2 * qk + 3 * v) * z["h"] * 2 + (2 * qk + v) * z["h"] * 2
    least = _least(3 * 2 * pairs * z["h"] * (qk + v),
                   tokens * (forward_io + backward_io), device_kind)
    least["layers"] = z["layers"] + z["modules"]
    return least


def moe_experts_step_least_seconds(c: dict, routed_rows: float, remat: bool,
                                   device_kind: str) -> dict:
    """Least time for the grouped matmuls over the held experts in one
    training step, at ``routed_rows`` (token, expert) rows routed here in
    the step, all expert layers and the module's together (the counter
    ``moe_rows_here``): three matmuls of d x fe a row, forward (twice under
    remat) and backward (2x). Bytes: every held expert's three matrices
    once a pass (bf16 in the forward passes, and their float32 gradients
    written once), and each row's input and output (bf16)."""
    z = sizes(c)
    d, f = z["d"], z["fe"]
    layers = z["layers"] - z["dense"] + z["modules"]
    passes = (2 if remat else 1) + 2
    ops_n = routed_rows * 2 * 3 * d * f * passes
    weights = layers * z["held"] * 3 * d * f
    bytes_n = weights * 2 * passes + weights * 4 \
        + routed_rows * 2 * d * 2 * passes
    return _least(ops_n, bytes_n, device_kind)

"""Operations, bytes and parameters of the ``qwen3_next`` family: the
arithmetic side of the yardstick for its cells, computed from a
configuration file's sizes and a traffic file's shapes, never from the
program. ``benchmark/ops.py`` keeps the peaks and the conventions (one
multiply-add is 2 operations; causal attention counted as causal; training
is 3x the forward matmul work, recomputation not counted; a lookup is no
matmul). Found by the configuration's ``family``
(``benchmark.ops_<family>``).

A configuration here is the chip's share (``benchmark/configs``): it holds
``num_experts`` of the ``num_experts_published`` experts and ``vocab_size``
rows of the vocabulary. A token is routed to ``num_experts_per_tok`` of the
published experts, so on average ``k x held / published`` of its routed
rows fall here; that expectation is what the per-token numbers use, and the
roofline of the expert matmuls uses the rows the run really routed.
"""

from __future__ import annotations

from benchmark import ops

CHUNK = 64          # the chunked delta rule's chunk, ray_tpu/ops/gated_delta


def sizes(c: dict) -> dict:
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "kvh": c["num_key_value_heads"], "hd": c["head_dim"],
            "hk": hk, "hv": hv, "dk": dk, "dv": dv,
            "key_dim": hk * dk, "value_dim": hv * dv,
            "conv": c["linear_conv_kernel_dim"],
            "held": c["num_experts"],
            "published": c.get("num_experts_published", c["num_experts"]),
            "k": c["num_experts_per_tok"], "f": c["moe_intermediate_size"],
            "fs": c["shared_expert_intermediate_size"],
            "layers": c["num_hidden_layers"],
            "interval": c["full_attention_interval"],
            "vocab": c["vocab_size"]}


def layer_kinds(c: dict) -> list:
    """"full" | "linear" for each layer, as published: full where
    ``(i + 1) % full_attention_interval == 0``."""
    return ["full" if (i + 1) % c["full_attention_interval"] == 0
            else "linear" for i in range(c["num_hidden_layers"])]


def param_counts(c: dict) -> dict:
    """Parameters by part. ``total`` is what a program holding this
    configuration holds; ``whole_model`` the published model (all experts,
    the whole vocabulary, ``num_hidden_layers_published`` layers)."""
    z = sizes(c)
    d = z["d"]
    linear_mixer = (d * (2 * z["key_dim"] + 2 * z["value_dim"])    # in_qkvz
                    + d * 2 * z["hv"]                              # in_ba
                    + (2 * z["key_dim"] + z["value_dim"]) * z["conv"]
                    + 2 * z["hv"] + z["dv"]          # dt_bias, A_log, norm
                    + z["value_dim"] * d)                          # out
    full_mixer = (d * z["h"] * 2 * z["hd"]           # query and gate
                  + 2 * d * z["kvh"] * z["hd"] + z["h"] * z["hd"] * d
                  + 2 * z["hd"])                     # q and k norms
    layer_rest = 2 * d + d * z["published"] + 3 * d * z["fs"] + d
    expert = 3 * d * z["f"]
    kinds = layer_kinds(c)

    def model(layers, held, vocab):
        n_full = sum(1 for i in range(layers)
                     if (i + 1) % z["interval"] == 0)
        return ((layers - n_full) * linear_mixer + n_full * full_mixer
                + layers * (layer_rest + held * expert)
                + 2 * vocab * d + d)

    return {"linear_mixer": linear_mixer, "full_mixer": full_mixer,
            "layer_rest": layer_rest, "expert": expert,
            "linear_layer": linear_mixer + layer_rest + z["held"] * expert,
            "full_layer": full_mixer + layer_rest + z["held"] * expert,
            "embed": z["vocab"] * d, "head_matmul": d * z["vocab"],
            "total": model(len(kinds), z["held"], z["vocab"]),
            "whole_model": model(
                c.get("num_hidden_layers_published", len(kinds)),
                z["published"], c.get("vocab_size_published", z["vocab"]))}


def gdn_scan_ops_per_token(c: dict) -> float:
    """Forward operations of the chunked delta rule for one token, all
    value heads, counting only what the algorithm needs: triangular
    products as triangles, and ``(I + A)^-1`` applied to [u | w] by
    substitution (the program forms the inverse with matmuls instead, which
    costs more and is not credited). Per head and token, chunk C:
    ``K K^T`` and ``Q K^T`` lower C*dk each; the solve C*(dk + dv);
    ``w S``, ``q S`` and the state update 2*dk*dv each; ``attn v'`` lower
    C*dv."""
    z = sizes(c)
    dk, dv = z["dk"], z["dv"]
    per_head = (2 * CHUNK * dk + CHUNK * (dk + dv) + 6 * dk * dv
                + CHUNK * dv)
    return float(per_head * z["hv"])


def forward_ops_per_token(c: dict, seq: int) -> dict:
    z = sizes(c)
    d = z["d"]
    kinds = layer_kinds(c)
    n_full = kinds.count("full")
    n_linear = len(kinds) - n_full
    linear_proj = 2 * (d * (2 * z["key_dim"] + 2 * z["value_dim"])
                       + d * 2 * z["hv"] + z["value_dim"] * d)
    conv = 2 * z["conv"] * (2 * z["key_dim"] + z["value_dim"])
    full_proj = 2 * (d * z["h"] * 2 * z["hd"] + 2 * d * z["kvh"] * z["hd"]
                     + z["h"] * z["hd"] * d)
    scores = 2 * seq * z["h"] * z["hd"]              # causal: S/2 keys
    routed_here = z["k"] * z["held"] / z["published"]
    experts = (2 * d * z["published"] + 2 * 3 * d * z["fs"] + 2 * d
               + routed_here * 2 * 3 * d * z["f"])
    parts = {"linear_projections": n_linear * (linear_proj + conv),
             "linear_scan": n_linear * gdn_scan_ops_per_token(c),
             "full_projections": n_full * full_proj,
             "full_scores": n_full * scores,
             "experts": len(kinds) * experts,
             "head": 2 * d * z["vocab"]}
    parts["total"] = sum(parts.values())
    return parts


def train_ops_per_token(c: dict, seq: int) -> float:
    """Forward + backward (2x forward) operations a training step needs per
    token; what ``train.mfu.family`` divides by the peak."""
    return 3.0 * forward_ops_per_token(c, seq)["total"]


def _least(ops_n: float, bytes_n: float, device_kind: str) -> dict:
    pk = ops.peaks(device_kind)
    t_ops = ops_n / pk["bf16_flops_per_s"]
    t_bytes = bytes_n / pk["hbm_bytes_per_s"]
    return {"ops": ops_n, "bytes": bytes_n, "seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory"}


def gdn_scan_step_least_seconds(c: dict, seq: int, rows: int, remat: bool,
                                device_kind: str) -> dict:
    """Least time one chip could spend in the chunked delta rule of one
    training step: forward (twice under whole-layer remat) and backward (2x
    forward), over every linear layer. Bytes a token and layer: a forward
    pass reads q and k (key heads, before they are repeated to the value
    heads), v (bf16) and g, beta (f32) and writes o (bf16); the backward
    reads those with o's gradient in o's place and writes the five
    gradients; the states between chunks stay on the chip in the best case
    and are not counted."""
    z = sizes(c)
    n_linear = layer_kinds(c).count("linear")
    tokens = seq * rows
    forwards = 2 if remat else 1
    qk = 2 * z["hk"] * z["dk"] * 2
    v_or_o = z["hv"] * z["dv"] * 2
    gates = 2 * z["hv"] * 4
    forward_io = qk + 2 * v_or_o + gates
    backward_io = (qk + 2 * v_or_o + gates) + (qk + v_or_o + gates)
    least = _least(
        gdn_scan_ops_per_token(c) * tokens * (forwards + 2) * n_linear,
        (forward_io * forwards + backward_io) * tokens * n_linear,
        device_kind)
    least["layers"] = n_linear
    return least


def moe_experts_step_least_seconds(c: dict, routed_rows: float, remat: bool,
                                   device_kind: str) -> dict:
    """Least time for the grouped matmuls over the held experts in one
    training step, at ``routed_rows`` (token, expert) rows routed here in
    the step, all layers together (the counter ``moe_rows_here``): three
    matmuls of d x f a row, forward (twice under remat) and backward (2x).
    Bytes: every held expert's three matrices once a pass (bf16 in the
    forward passes, and their float32 gradients written once), and each
    row's input and output (bf16)."""
    z = sizes(c)
    d, f = z["d"], z["f"]
    layers = z["layers"]
    passes = (2 if remat else 1) + 2
    ops_n = routed_rows * 2 * 3 * d * f * passes
    weights = layers * z["held"] * 3 * d * f
    bytes_n = weights * 2 * passes + weights * 4 \
        + routed_rows * 2 * d * 2 * passes
    return _least(ops_n, bytes_n, device_kind)

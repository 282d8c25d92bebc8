"""Operations, bytes and parameters of the ``olmo_hybrid`` family (a period
of three gated-delta-rule layers and one softmax layer, dense SwiGLU in
every layer): the arithmetic side of the yardstick for its cells, OF THE
PUBLISHED MATHEMATICS at the cell's sizes, from a configuration file and a
traffic file, never from what the program happens to do.
``benchmark/ops.py`` keeps the peaks and the conventions (one multiply-add
is 2 operations; causal attention counted as causal; a lookup is no
matmul). Found by the configuration's ``family``
(``benchmark.ops_<family>``).

What a linear layer adds to a plain stack's arithmetic:

- its projections (q, k of ``H x dk``, v and the output gate of ``H x dv``,
  b and a of ``H``, the output projection) and a depthwise convolution of
  ``K`` taps over the ``2 H dk + H dv`` channels of [q; k; v];
- the rule, counted as the RECURRENCE, the least any form computes: a
  position and head decays the state (``dk dv``), reads it against k (``2
  dk dv``), adds an outer product (``2 dk dv``) and reads it against q (``2
  dk dv``): ``7 dk dv`` operations. (The chunked form that a prefill runs
  does more, in matrix products; its own roofline is
  ``benchmark/ops_qwen3_next.py``'s.)
- in a decode step, bytes that do not grow with the position: a row's
  float32 state ``H x dk x dv x 4`` is read once and written once a step
  and layer, and the convolution's last ``K - 1`` inputs likewise. The
  softmax layers' keys and values grow with the position as in a plain
  stack, in a quarter of the layers.
"""

from __future__ import annotations

from benchmark import ops

STATE_BYTES = 4         # the recurrent state is float32 whatever is served


def sizes(config: dict) -> dict:
    kinds = config["layer_types"]
    h, dk, dv = (config["linear_num_value_heads"],
                 config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    return dict(
        ops.sizes(config),
        linear=sum(k == "linear_attention" for k in kinds),
        full=sum(k == "full_attention" for k in kinds),
        lh=h, lhk=config["linear_num_key_heads"], dk=dk, dv=dv,
        conv=config["linear_conv_kernel_dim"],
        channels=2 * config["linear_num_key_heads"] * dk + h * dv)


def param_counts(config: dict) -> dict:
    """Parameters by part; ``total`` is what the configuration's file
    holds (the published model's where ``num_hidden_layers`` is)."""
    z = sizes(config)
    d = z["d"]
    mlp = 3 * d * z["ff"]
    linear_matmul = d * (z["channels"] + z["lh"] * z["dv"]) \
        + d * 2 * z["lh"] + z["lh"] * z["dv"] * d
    linear_mixer = linear_matmul + z["channels"] * z["conv"] \
        + 2 * z["lh"] + z["dv"]
    full_matmul = d * z["h"] * z["hd"] * 2 + d * z["kvh"] * z["hd"] * 2
    full_mixer = full_matmul + z["h"] * z["hd"] + z["kvh"] * z["hd"]
    linear_layer = linear_mixer + mlp + 2 * d
    full_layer = full_mixer + mlp + 2 * d
    embed = z["vocab"] * d
    head = 0 if z["tied"] else d * z["vocab"]
    return {"mlp": mlp, "linear_matmul": linear_matmul,
            "linear_mixer": linear_mixer, "full_matmul": full_matmul,
            "full_mixer": full_mixer, "linear_layer": linear_layer,
            "full_layer": full_layer, "embed": embed, "head": head,
            "head_matmul": d * z["vocab"],
            "total": z["linear"] * linear_layer + z["full"] * full_layer
            + embed + head + d}


def rule_ops_a_position(config: dict) -> int:
    """The recurrence's operations for one position of one linear layer."""
    z = sizes(config)
    return 7 * z["lh"] * z["dk"] * z["dv"]


def forward_ops_per_token(config: dict, seq: int) -> dict:
    """Forward operations for one token of a sequence of ``seq`` tokens,
    by part; the softmax layers' attention counted as causal (``seq / 2``
    keys a query)."""
    z, p = sizes(config), param_counts(config)
    matmuls = 2 * (z["linear"] * (p["linear_matmul"] + p["mlp"])
                   + z["full"] * (p["full_matmul"] + p["mlp"]))
    conv = 2 * z["conv"] * z["channels"] * z["linear"]
    rule = rule_ops_a_position(config) * z["linear"]
    attention = 2 * seq * z["h"] * z["hd"] * z["full"]
    head = 2 * p["head_matmul"]
    return {"layers": matmuls, "conv": conv, "rule": rule,
            "attention": attention, "head": head,
            "total": matmuls + conv + rule + attention + head}


def state_bytes_a_row(config: dict) -> int:
    """One row's recurrent state in one linear layer."""
    z = sizes(config)
    return z["lh"] * z["dk"] * z["dv"] * STATE_BYTES


def tail_bytes_a_row(config: dict) -> int:
    """One row's convolution tail in one linear layer, bfloat16."""
    z = sizes(config)
    return (z["conv"] - 1) * z["channels"] * 2


def kv_bytes_a_position(config: dict) -> int:
    """Keys and values of one cached position of one row over the softmax
    layers, bfloat16."""
    z = sizes(config)
    return 2 * z["full"] * z["kvh"] * z["hd"] * 2


def cache_bytes(config: dict, rows: int, positions: int) -> dict:
    z = sizes(config)
    state = rows * z["linear"] * state_bytes_a_row(config)
    tail = rows * z["linear"] * tail_bytes_a_row(config)
    kv = rows * positions * kv_bytes_a_position(config)
    return {"state": state, "tail": tail, "kv": kv,
            "total": state + tail + kv}


def rule_step_bytes(config: dict, rows: int) -> int:
    """The least bytes one decode step's convolution and rule move in ONE
    linear layer: each row's state in and out once, its tail in and out
    once, the new column of [q; k; v] in, g and beta, the head outputs out;
    the convolution's taps once a layer. What lies between the convolution
    and the rule (q, k, v) need not touch memory."""
    z = sizes(config)
    a_row = 2 * state_bytes_a_row(config) + 2 * tail_bytes_a_row(config) \
        + z["channels"] * 2 + 2 * z["lh"] * 4 + z["lh"] * z["dv"] * 2
    return rows * a_row + z["channels"] * z["conv"] * 2


def generate_least_seconds(config: dict, rows: int, prompt: int, new: int,
                           weight_dtype: str, device_kind: str) -> dict:
    """Least time for one ``generate`` call as it is issued, as
    ``ops.generate_least_seconds`` counts it for a plain stack. Prefill:
    operations over peak, or the bytes (the weights once, the cache
    written) over bandwidth, whichever is larger. Each decode step: every
    weight once, each row's state and tail read and written once a linear
    layer, the keys and values of the positions so far in the softmax
    layers, against the step's operations. ``rule_seconds``: of the decode
    steps, the convolution's and the rule's part (``rule_step_bytes`` over
    bandwidth, or their operations over peak), what ``gdn.step_roofline``
    reads."""
    z, p, pk = sizes(config), param_counts(config), ops.peaks(device_kind)
    wbytes = ops._DTYPE_BYTES[weight_dtype]
    weights = (z["linear"] * (p["linear_matmul"] + p["mlp"])
               + z["full"] * (p["full_matmul"] + p["mlp"])
               + z["linear"] * z["channels"] * z["conv"]
               + p["head_matmul"]) * wbytes
    fwd = forward_ops_per_token(config, prompt)
    prefill_ops = rows * prompt * (fwd["total"] - fwd["head"]) \
        + rows * fwd["head"]                     # head on the last position
    kv = kv_bytes_a_position(config)
    fixed = cache_bytes(config, rows, 0)["total"]       # states and tails
    prefill_bytes = weights + rows * prompt * kv + fixed
    t_prefill = max(prefill_ops / pk["bf16_flops_per_s"],
                    prefill_bytes / pk["hbm_bytes_per_s"])
    rule_ops = rows * z["linear"] * (rule_ops_a_position(config)
                                     + 2 * z["conv"] * z["channels"])
    rule_bytes = z["linear"] * rule_step_bytes(config, rows)
    t_rule = new * max(rule_ops / pk["bf16_flops_per_s"],
                       rule_bytes / pk["hbm_bytes_per_s"])
    t_decode = decode_ops = decode_bytes = kv_read = 0.0
    for step in range(new):
        pos = prompt + step                       # attends to pos + 1 keys
        step_ops = rows * (fwd["layers"] + fwd["conv"] + fwd["rule"]
                           + fwd["head"]
                           + 4 * (pos + 1) * z["h"] * z["hd"] * z["full"])
        step_bytes = weights + 2 * fixed + rows * (pos + 1) * kv
        decode_ops += step_ops
        decode_bytes += step_bytes
        kv_read += rows * (pos + 1) * kv
        t_decode += max(step_ops / pk["bf16_flops_per_s"],
                        step_bytes / pk["hbm_bytes_per_s"])
    return {"seconds": t_prefill + t_decode, "prefill_seconds": t_prefill,
            "decode_seconds": t_decode, "rule_seconds": t_rule,
            "prefill_ops": prefill_ops, "decode_ops": decode_ops,
            "decode_bytes": decode_bytes, "weight_bytes_a_step": weights,
            "state_bytes_a_step": 2 * fixed, "kv_bytes_read": kv_read,
            "rule_bytes_a_step": rule_bytes,
            "cache_bytes": cache_bytes(config, rows, prompt + new)["total"],
            "bound": "prefill compute, decode memory"
            if prefill_ops / pk["bf16_flops_per_s"]
            >= prefill_bytes / pk["hbm_bytes_per_s"] else "memory"}

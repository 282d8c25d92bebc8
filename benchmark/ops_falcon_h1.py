"""Operations, bytes and parameters of the ``falcon_h1`` family (every block:
softmax attention AND a Mamba-2 state-space mixer on one normed input, then a
dense SwiGLU): the arithmetic side of the yardstick for its cells, OF THE
PUBLISHED MATHEMATICS at the cell's sizes, from a configuration file and a
traffic file, never from what the program happens to do.
``benchmark/ops.py`` keeps the peaks and the conventions (one multiply-add is
2 operations; causal attention counted as causal; a lookup is no matmul).
Found by the configuration's ``family`` (``benchmark.ops_<family>``).

What a state-space mixer adds to a plain block's arithmetic (H heads of P,
G groups of state width N, K taps):

- its packed projection (``d x (2 H P + 2 G N + H)``: z, x, B, C, dt), the
  output projection (``H P x d``) and a depthwise convolution of ``K`` taps
  and a bias over the ``H P + 2 G N`` channels of [x | B | C];
- the recurrence, counted as the RECURRENCE, the least any form computes: a
  position and head decays the state (``N P``), adds an outer product (``2 N
  P``) and reads it against C (``2 N P``): ``5 N P`` operations (the gated
  delta rule's 7 less its read against k). The chunked form that a prefill
  runs does more, in matrix products.
- in a decode step, bytes that do not grow with the position: a row's
  float32 state ``H x N x P x 4`` is read once and written once a step and
  layer, and the convolution's last ``K - 1`` inputs likewise. The keys and
  values of the block's attention grow with the position as in a plain
  stack, in every layer, over 4 KV heads.
"""

from __future__ import annotations

from benchmark import ops

STATE_BYTES = 4         # the recurrent state is float32 whatever is served


def sizes(config: dict) -> dict:
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    g, n = config["mamba_n_groups"], config["mamba_d_state"]
    return dict(ops.sizes(config), sh=h, p=p, g=g, n=n, inner=h * p,
                conv=config["mamba_d_conv"], channels=h * p + 2 * g * n,
                in_proj=2 * h * p + 2 * g * n + h)


def param_counts(config: dict) -> dict:
    """Parameters by part; ``total`` is what the configuration's file
    holds (the published model's where ``num_hidden_layers`` and
    ``vocab_size`` are)."""
    z = sizes(config)
    d = z["d"]
    mlp = 3 * d * z["ff"]
    ssm_matmul = d * z["in_proj"] + z["inner"] * d
    ssm_mixer = ssm_matmul + z["channels"] * (z["conv"] + 1) + 3 * z["sh"] \
        + z["inner"]
    attn = d * z["h"] * z["hd"] * 2 + d * z["kvh"] * z["hd"] * 2
    layer = ssm_mixer + attn + mlp + 2 * d
    embed = z["vocab"] * d
    head = 0 if z["tied"] else d * z["vocab"]
    return {"mlp": mlp, "ssm_matmul": ssm_matmul, "ssm_mixer": ssm_mixer,
            "attention": attn, "layer": layer, "embed": embed, "head": head,
            "head_matmul": d * z["vocab"],
            "total": z["layers"] * layer + embed + head + d}


def scan_ops_a_position(config: dict) -> int:
    """The recurrence's operations for one position of one layer."""
    z = sizes(config)
    return 5 * z["sh"] * z["n"] * z["p"]


def forward_ops_per_token(config: dict, seq: int) -> dict:
    """Forward operations for one token of a sequence of ``seq`` tokens, by
    part; attention counted as causal (``seq / 2`` keys a query)."""
    z, p = sizes(config), param_counts(config)
    matmuls = 2 * z["layers"] * (p["ssm_matmul"] + p["attention"] + p["mlp"])
    conv = 2 * z["conv"] * z["channels"] * z["layers"]
    scan = scan_ops_a_position(config) * z["layers"]
    attention = 2 * seq * z["h"] * z["hd"] * z["layers"]
    head = 2 * p["head_matmul"]
    return {"layers": matmuls, "conv": conv, "scan": scan,
            "attention": attention, "head": head,
            "total": matmuls + conv + scan + attention + head}


def state_bytes_a_row(config: dict) -> int:
    """One row's recurrent state in one layer."""
    z = sizes(config)
    return z["sh"] * z["n"] * z["p"] * STATE_BYTES


def tail_bytes_a_row(config: dict) -> int:
    """One row's convolution tail in one layer, bfloat16."""
    z = sizes(config)
    return (z["conv"] - 1) * z["channels"] * 2


def kv_bytes_a_position(config: dict) -> int:
    """Keys and values of one cached position of one row over the layers,
    bfloat16."""
    z = sizes(config)
    return 2 * z["layers"] * z["kvh"] * z["hd"] * 2


def cache_bytes(config: dict, rows: int, positions: int) -> dict:
    z = sizes(config)
    state = rows * z["layers"] * state_bytes_a_row(config)
    tail = rows * z["layers"] * tail_bytes_a_row(config)
    kv = rows * positions * kv_bytes_a_position(config)
    return {"state": state, "tail": tail, "kv": kv,
            "total": state + tail + kv}


def ssd_step_bytes(config: dict, rows: int) -> int:
    """The least bytes one decode step's convolution and recurrence move in
    ONE layer: each row's state in and out once, its tail in and out once,
    the new column of [x | B | C] in, dt, the heads' outputs out; the
    convolution's taps and bias once a layer. What lies between the
    convolution and the recurrence (x, B, C) need not touch memory."""
    z = sizes(config)
    a_row = 2 * state_bytes_a_row(config) + 2 * tail_bytes_a_row(config) \
        + z["channels"] * 2 + z["sh"] * 4 + z["inner"] * 2
    return rows * a_row + z["channels"] * (z["conv"] + 1) * 2


def generate_least_seconds(config: dict, rows: int, prompt: int, new: int,
                           weight_dtype: str, device_kind: str) -> dict:
    """Least time for one ``generate`` call as it is issued, as
    ``ops.generate_least_seconds`` counts it for a plain stack. Prefill:
    operations over peak, or the bytes (the weights once, the cache
    written) over bandwidth, whichever is larger. Each decode step: every
    weight once, each row's state and tail read and written once a layer,
    the keys and values of the positions so far, against the step's
    operations. ``ssd_seconds``: of the decode steps, the convolution's and
    the recurrence's part (``ssd_step_bytes`` over bandwidth, or their
    operations over peak), what ``ssd.step_roofline`` reads."""
    z, p, pk = sizes(config), param_counts(config), ops.peaks(device_kind)
    wbytes = ops._DTYPE_BYTES[weight_dtype]
    weights = (z["layers"] * (p["ssm_matmul"] + p["attention"] + p["mlp"]
                              + z["channels"] * (z["conv"] + 1))
               + p["head_matmul"]) * wbytes
    fwd = forward_ops_per_token(config, prompt)
    prefill_ops = rows * prompt * (fwd["total"] - fwd["head"]) \
        + rows * fwd["head"]                     # head on the last position
    kv = kv_bytes_a_position(config)
    fixed = cache_bytes(config, rows, 0)["total"]       # states and tails
    prefill_bytes = weights + rows * prompt * kv + fixed
    t_prefill = max(prefill_ops / pk["bf16_flops_per_s"],
                    prefill_bytes / pk["hbm_bytes_per_s"])
    ssd_ops = rows * z["layers"] * (scan_ops_a_position(config)
                                    + 2 * z["conv"] * z["channels"])
    ssd_bytes = z["layers"] * ssd_step_bytes(config, rows)
    t_ssd = new * max(ssd_ops / pk["bf16_flops_per_s"],
                      ssd_bytes / pk["hbm_bytes_per_s"])
    t_decode = decode_ops = decode_bytes = kv_read = 0.0
    for step in range(new):
        pos = prompt + step                       # attends to pos + 1 keys
        step_ops = rows * (fwd["layers"] + fwd["conv"] + fwd["scan"]
                           + fwd["head"]
                           + 4 * (pos + 1) * z["h"] * z["hd"] * z["layers"])
        step_bytes = weights + 2 * fixed + rows * (pos + 1) * kv
        decode_ops += step_ops
        decode_bytes += step_bytes
        kv_read += rows * (pos + 1) * kv
        t_decode += max(step_ops / pk["bf16_flops_per_s"],
                        step_bytes / pk["hbm_bytes_per_s"])
    return {"seconds": t_prefill + t_decode, "prefill_seconds": t_prefill,
            "decode_seconds": t_decode, "ssd_seconds": t_ssd,
            "prefill_ops": prefill_ops, "decode_ops": decode_ops,
            "decode_bytes": decode_bytes, "weight_bytes_a_step": weights,
            "state_bytes_a_step": 2 * fixed, "kv_bytes_read": kv_read,
            "ssd_bytes_a_step": ssd_bytes,
            "cache_bytes": cache_bytes(config, rows, prompt + new)["total"],
            "bound": "prefill compute, decode memory"
            if prefill_ops / pk["bf16_flops_per_s"]
            >= prefill_bytes / pk["hbm_bytes_per_s"] else "memory"}

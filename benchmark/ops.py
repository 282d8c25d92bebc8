"""Operations, bytes and peaks: the arithmetic side of the yardstick.

Everything here is computed from a configuration file's published sizes and
a traffic file's shapes, never from the program. A configuration is the
dict of ``benchmark/configs/<name>.json`` (Hugging Face key names).

Conventions, fixed here so that every PR counts the same way:

- one multiply-add is 2 operations;
- causal attention is counted as causal: a query attends to half of the
  keys on average, so ``QK^T`` and ``PV`` together cost ``2 * S * H * hd``
  operations per token and layer in the forward pass (``bench.py`` counted
  the full square, ``12 * L * d * S`` for training, and so credited a causal
  kernel with work it does not do);
- training is forward plus backward, 3x the forward matmul work;
  recomputation (remat) is not counted;
- the embedding lookup is a gather, not a matmul: it adds no operations and
  only the looked-up rows' bytes.
"""

from __future__ import annotations

# Peaks of one chip, keyed by ``device_kind`` as jax reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip). Copied from
# ``bench.py``'s ``PEAK_BF16`` (keyed there by generation), which stays in
# the program for a later PR to delete. A kind that is not here is an
# error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16 * 2 ** 30},
}


class UnknownDevice(Exception):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark/ops.py (known: {sorted(PEAKS)})") from None


_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def sizes(config: dict) -> dict:
    """The sizes the arithmetic needs, from Hugging Face key names."""
    h = config["num_attention_heads"]
    hd = config.get("head_dim") or config["hidden_size"] // h
    return {"d": config["hidden_size"], "ff": config["intermediate_size"],
            "h": h, "kvh": config.get("num_key_value_heads") or h, "hd": hd,
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"],
            "tied": bool(config.get("tie_word_embeddings", False))}


def param_counts(config: dict) -> dict:
    """Parameters: one layer's matrices, its norms, embedding, head."""
    z = sizes(config)
    attn = z["d"] * z["h"] * z["hd"] * 2 + z["d"] * z["kvh"] * z["hd"] * 2
    mlp = 3 * z["d"] * z["ff"]
    layer_matmul = attn + mlp
    embed = z["vocab"] * z["d"]
    head = 0 if z["tied"] else z["d"] * z["vocab"]
    total = embed + head + z["layers"] * (layer_matmul + 2 * z["d"]) + z["d"]
    return {"layer_matmul": layer_matmul, "attn": attn, "mlp": mlp,
            "embed": embed, "head": head, "head_matmul": z["d"] * z["vocab"],
            "total": total}


def forward_ops_per_token(config: dict, seq: int) -> dict:
    """Forward operations for one token of a sequence of ``seq`` tokens,
    split by part so that a reader can see what the head weighs."""
    z, p = sizes(config), param_counts(config)
    layers = 2 * p["layer_matmul"] * z["layers"]
    attention = 2 * seq * z["h"] * z["hd"] * z["layers"]   # causal: S/2 keys
    head = 2 * p["head_matmul"]
    return {"layers": layers, "attention": attention, "head": head,
            "total": layers + attention + head}


def train_ops_per_token(config: dict, seq: int) -> float:
    """Forward + backward (2x forward) operations a training step needs per
    token; what ``train.mfu`` divides by the peak."""
    return 3.0 * forward_ops_per_token(config, seq)["total"]


# ---------------------------------------------------------------------------
# Flash attention (ops/flash.py): three Mosaic kernels. A "unit" is one
# [S, hd] x [hd, S] (or transposed) matmul of one head under the causal
# mask: 2 * S * S * hd / 2 operations.
#   forward:  S = QK^T, O = PV                          -> 2 units
#   dkv:      S again (P is not stored), dP = dO V^T,
#             dV = P^T dO, dK = dS^T Q                  -> 4 units
#   dq:       S again, dP = dO V^T, dQ = dS K           -> 3 units
# Under whole-layer remat the forward kernel runs twice per layer and step.
# The roofline counts every call that ran, with what that call must compute.
FLASH_UNITS = {"forward": 2, "dkv": 4, "dq": 3}


def flash_calls_per_layer_step(remat: bool) -> dict:
    return {"forward": 2 if remat else 1, "dkv": 1, "dq": 1}


def flash_step_least_seconds(config: dict, seq: int, rows_per_chip: int,
                             remat: bool, device_kind: str) -> dict:
    """Least time one chip could spend in the flash kernels of one training
    step: the larger of operations over peak and bytes over bandwidth,
    summed over the calls the step makes."""
    z, pk = sizes(config), peaks(device_kind)
    unit = 2.0 * seq * seq * z["hd"] / 2.0 * z["h"] * rows_per_chip
    # operands as the kernel sees them: k and v repeated to the q heads
    # (transformer._attention hands mha [B, S, H, hd] after the GQA repeat)
    tensor = rows_per_chip * z["h"] * seq * z["hd"] * 2    # bf16 bytes
    lse = rows_per_chip * z["h"] * seq * 4
    bytes_by = {"forward": 4 * tensor + lse,               # q k v -> o, lse
                "dkv": 6 * tensor + 2 * lse,    # q k v do, lse delta -> dk dv
                "dq": 5 * tensor + 2 * lse}     # q k v do, lse delta -> dq
    calls = flash_calls_per_layer_step(remat)
    ops = sum(FLASH_UNITS[k] * unit * n for k, n in calls.items())
    byts = sum(bytes_by[k] * n for k, n in calls.items())
    ops, byts = ops * z["layers"], byts * z["layers"]
    t_ops = ops / pk["bf16_flops_per_s"]
    t_bytes = byts / pk["hbm_bytes_per_s"]
    return {"ops": ops, "bytes": byts, "seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory",
            "calls": sum(calls.values()) * z["layers"]}


# ---------------------------------------------------------------------------
# One ``generate`` call (models/generate.py): prefill of ``prompt`` tokens
# for ``rows`` rows, then ``new`` decode steps through the KV cache.

def generate_least_seconds(config: dict, rows: int, prompt: int, new: int,
                           weight_dtype: str, device_kind: str) -> dict:
    """Least time for one call as it is issued (``rows`` includes padding;
    ``batch.fill`` says how much of it was useful). Prefill: operations over
    peak or weight bytes over bandwidth, whichever is larger. Each decode
    step: every layer's and the head's weights once, plus the keys and
    values of the positions so far (the algorithm needs only those, whatever
    length the cache was allocated at), against the step's operations."""
    z, p, pk = sizes(config), param_counts(config), peaks(device_kind)
    wbytes = _DTYPE_BYTES[weight_dtype]
    weights = (p["layer_matmul"] * z["layers"] + p["head_matmul"]) * wbytes
    fwd = forward_ops_per_token(config, prompt)
    prefill_ops = rows * prompt * (fwd["layers"] + fwd["attention"]) \
        + rows * fwd["head"]                     # head on the last position
    kv_row_pos = 2 * z["layers"] * z["kvh"] * z["hd"] * 2   # k+v, bf16
    prefill_bytes = weights + rows * prompt * kv_row_pos
    t_prefill = max(prefill_ops / pk["bf16_flops_per_s"],
                    prefill_bytes / pk["hbm_bytes_per_s"])
    t_decode = 0.0
    decode_ops = decode_bytes = 0.0
    for step in range(new):
        pos = prompt + step                       # attends to pos + 1 keys
        ops = rows * (2 * p["layer_matmul"] * z["layers"] + fwd["head"]
                      + 4 * (pos + 1) * z["h"] * z["hd"] * z["layers"])
        byts = weights + rows * (pos + 1) * kv_row_pos
        decode_ops += ops
        decode_bytes += byts
        t_decode += max(ops / pk["bf16_flops_per_s"],
                        byts / pk["hbm_bytes_per_s"])
    return {"seconds": t_prefill + t_decode, "prefill_seconds": t_prefill,
            "decode_seconds": t_decode, "prefill_ops": prefill_ops,
            "decode_ops": decode_ops, "decode_bytes": decode_bytes,
            "weight_bytes": weights,
            "bound": "prefill compute, decode memory"
            if prefill_ops / pk["bf16_flops_per_s"]
            >= prefill_bytes / pk["hbm_bytes_per_s"] else "memory"}

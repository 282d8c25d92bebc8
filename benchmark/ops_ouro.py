"""Operations, bytes and parameters of the ``ouro`` family (a looped stack:
``num_hidden_layers`` layers run ``total_ut_steps`` times over one set of
weights): the arithmetic side of the yardstick for its cells, computed from
a configuration file's sizes and a traffic file's shapes, never from the
program. ``benchmark/ops.py`` keeps the peaks and the conventions (one
multiply-add is 2 operations; causal attention counted as causal; a lookup
is no matmul). Found by the configuration's ``family``
(``benchmark.ops_<family>``).

What the loop changes: a token passes every layer ``total_ut_steps`` times,
so the layers' operations count that many times for one set of parameters;
no on-chip memory holds 4.9 GB of weights between passes, so a decode step
streams the layers' weights once a pass; and a cached position holds keys
and values for every (pass, layer), ``total_ut_steps`` times a plain stack's.
The head runs once a token. Four norm scales a layer (the sandwich norm),
the final norm and the exit gate ``Linear(hidden -> 1)`` with its bias.
"""

from __future__ import annotations

from benchmark import ops


def sizes(config: dict) -> dict:
    return dict(ops.sizes(config), loops=config["total_ut_steps"])


def param_counts(config: dict) -> dict:
    """Parameters by part; ``total`` is the published model's."""
    z, p = sizes(config), ops.param_counts(config)
    layer = p["layer_matmul"] + 4 * z["d"]
    gate = z["d"] + 1
    return {"layer_matmul": p["layer_matmul"], "layer": layer,
            "embed": p["embed"], "head": p["head"],
            "head_matmul": p["head_matmul"], "gate": gate,
            "total": z["layers"] * layer + p["embed"] + p["head"]
            + z["d"] + gate}


def forward_ops_per_token(config: dict, seq: int) -> dict:
    """Forward operations for one token of a sequence of ``seq`` tokens:
    ``ops.forward_ops_per_token`` with the layers and their attention
    counted once a pass, the gate once a pass and the head once."""
    z, once = sizes(config), ops.forward_ops_per_token(config, seq)
    layers, attention = once["layers"] * z["loops"], \
        once["attention"] * z["loops"]
    gate = 2 * z["d"] * z["loops"]
    return {"layers": layers, "attention": attention, "head": once["head"],
            "gate": gate,
            "total": layers + attention + once["head"] + gate}


def kv_bytes_a_position(config: dict) -> int:
    """Keys and values of one cached position of one row, bfloat16: a slot
    for every (pass, layer)."""
    z = sizes(config)
    return 2 * z["loops"] * z["layers"] * z["kvh"] * z["hd"] * 2


def generate_least_seconds(config: dict, rows: int, prompt: int, new: int,
                           weight_dtype: str, device_kind: str) -> dict:
    """Least time for one ``generate`` call as it is issued, as
    ``ops.generate_least_seconds`` counts it for a plain stack. Prefill:
    operations over peak, or the bytes (the layers' weights once a pass,
    the head, the keys and values written) over bandwidth, whichever is
    larger. Each decode step: the layers' weights ``total_ut_steps`` times
    and the head's once, plus the keys and values of the positions so far
    in all ``total_ut_steps x layers`` slots, against the step's
    operations."""
    z, p, pk = sizes(config), param_counts(config), ops.peaks(device_kind)
    wbytes = ops._DTYPE_BYTES[weight_dtype]
    weights = (p["layer_matmul"] * z["layers"] * z["loops"]
               + p["head_matmul"]) * wbytes
    fwd = forward_ops_per_token(config, prompt)
    prefill_ops = rows * prompt * (fwd["layers"] + fwd["attention"]
                                   + fwd["gate"]) + rows * fwd["head"]
    kv = kv_bytes_a_position(config)
    prefill_bytes = weights + rows * prompt * kv
    t_prefill = max(prefill_ops / pk["bf16_flops_per_s"],
                    prefill_bytes / pk["hbm_bytes_per_s"])
    t_decode = decode_ops = decode_bytes = kv_read = 0.0
    for step in range(new):
        pos = prompt + step                       # attends to pos + 1 keys
        step_ops = rows * (fwd["layers"] + fwd["gate"] + fwd["head"]
                           + 4 * (pos + 1) * z["h"] * z["hd"] * z["layers"]
                           * z["loops"])
        step_bytes = weights + rows * (pos + 1) * kv
        decode_ops += step_ops
        decode_bytes += step_bytes
        kv_read += rows * (pos + 1) * kv
        t_decode += max(step_ops / pk["bf16_flops_per_s"],
                        step_bytes / pk["hbm_bytes_per_s"])
    return {"seconds": t_prefill + t_decode, "prefill_seconds": t_prefill,
            "decode_seconds": t_decode, "prefill_ops": prefill_ops,
            "decode_ops": decode_ops, "decode_bytes": decode_bytes,
            "weight_bytes_a_step": weights, "kv_bytes_read": kv_read,
            "cache_bytes": rows * (prompt + new) * kv,
            "bound": "prefill compute, decode memory"
            if prefill_ops / pk["bf16_flops_per_s"]
            >= prefill_bytes / pk["hbm_bytes_per_s"] else "memory"}

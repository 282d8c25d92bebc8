"""What the two language-model apps share: how a configuration file becomes
the program's ``TransformerConfig``, how its parameter tree becomes the
plain reference's ``Weights``, seeds, and the compile counter."""

from __future__ import annotations

import hashlib
import importlib
import json
import os


def fold_seed(seed: int) -> int:
    """Any whole number -> 31 bits. The driver's seeds pass 2**31, and
    ``jax.random.PRNGKey`` and numpy's legacy seeding stop at 32 bits."""
    digest = hashlib.blake2s(str(int(seed)).encode(), digest_size=4).digest()
    return int.from_bytes(digest, "big") & 0x7FFFFFFF


def effective_traffic(traffic: dict, rehearse: bool) -> dict:
    """A traffic file as it is run: under ``--rehearse`` its ``rehearse``
    group (toy shapes for the CPU) overrides the rest."""
    out = {k: v for k, v in traffic.items() if k != "rehearse"}
    if rehearse:
        out.update(traffic.get("rehearse", {}))
    return out


def effective_config(config: dict, rehearse: bool) -> dict:
    """A configuration as it is run: under ``--rehearse`` the toy sizes of
    ``benchmark/rehearse/<family>.json`` replace its own."""
    if not rehearse:
        return dict(config)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "rehearse", config["family"] + ".json")
    with open(path) as f:
        toy = json.load(f)
    return {**config, **{k: v for k, v in toy.items() if k != "doc"}}


def model_kwargs(config: dict, seq: int, attn_impl: str) -> dict:
    """Hugging Face key names -> ``TransformerConfig`` fields (dtypes as
    strings: this dict crosses a process boundary)."""
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config.get("num_key_value_heads")
        or config["num_attention_heads"],
        d_ff=config["intermediate_size"], max_seq=seq,
        rope_theta=float(config["rope_theta"]),
        tied_embeddings=bool(config.get("tie_word_embeddings", False)),
        param_dtype=config["param_dtype"], attn_impl=attn_impl,
        rms_norm_eps=float(config["rms_norm_eps"]))


def transformer_config(kwargs: dict, remat: bool):
    import dataclasses

    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig
    kwargs = dict(kwargs)
    if "rms_norm_eps" not in {f.name for f in
                              dataclasses.fields(TransformerConfig)}:
        del kwargs["rms_norm_eps"]     # fixed in the program: see
        #                                program_rms_norm_eps()
    kwargs["param_dtype"] = jnp.dtype(kwargs["param_dtype"])
    head_dim = kwargs["d_model"] // kwargs["n_heads"]
    if head_dim * kwargs["n_heads"] != kwargs["d_model"]:
        raise ValueError("the program's block has head_dim = d_model / heads")
    return TransformerConfig(**kwargs, remat=remat)


def program_rms_norm_eps(cfg) -> float:
    """The epsilon the program's RMSNorm runs: its configuration's, once
    ``TransformerConfig`` has such a field (``transformer_config`` then
    hands it the published one); today the fixed default of
    ``models/transformer._rmsnorm``."""
    eps = getattr(cfg, "rms_norm_eps", None)
    if eps is None:
        import inspect

        from ray_tpu.models import transformer
        eps = inspect.signature(
            transformer._rmsnorm).parameters["eps"].default
    return float(eps)


def over_their_limits(judged: dict, says: dict) -> list:
    """``judged``: ``{name: [value, limit]}`` -> one reason for every value
    over its limit (a value that is not a number is over): the check's
    name, what it says, the number and the limit."""
    return [f"{name}: {says[name]}: {value:.6g} is over the limit "
            f"{limit:.6g}" for name, (value, limit) in judged.items()
            if not value <= limit]       # NaN compares false


def reference_module(config: dict):
    return importlib.import_module(
        "benchmark.reference." + config["family"])


def reference_weights(params: dict, config: dict):
    """The program's parameter tree as the reference's plain matrices: the
    same arrays, reshaped ([d, H, hd] -> [d, H*hd]); layers sliced lazily so
    that one layer at a time is upcast."""
    ref = reference_module(config)
    layers = params["layers"]
    d = params["embed"].shape[1]

    def layer(i: int) -> dict:
        a, m = layers["attn"], layers["mlp"]
        return {"wq": a["wq"][i].reshape(d, -1),
                "wk": a["wk"][i].reshape(d, -1),
                "wv": a["wv"][i].reshape(d, -1),
                "wo": a["wo"][i].reshape(-1, d),
                "w1": m["w1"][i], "w3": m["w3"][i], "w2": m["w2"][i],
                "ln1": layers["ln1"][i], "ln2": layers["ln2"][i]}

    head = params["embed"].T if "lm_head" not in params else params["lm_head"]
    return ref.Weights(embed=params["embed"], layer=layer,
                       n_layers=int(layers["ln1"].shape[0]),
                       final_norm=params["final_norm"], lm_head=head)


def device_facts() -> dict:
    import jax
    devs = jax.devices()
    return {"pid": os.getpid(), "platform": devs[0].platform,
            "kind": devs[0].device_kind, "count": len(devs)}


def require_chips(facts: dict, chips: int, rehearse: bool) -> None:
    if rehearse:
        return
    if facts["platform"] != "tpu" or facts["count"] != chips:
        raise RuntimeError(
            f"leased {chips} TPU chip(s) but jax sees {facts['count']} "
            f"device(s) of platform {facts['platform']!r}")


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) in this process
    through jax's own monitoring events; the window must see none."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _seconds: float, **_kw) -> None:
        if event.endswith(("backend_compile_duration",
                           "cache_retrieval_time_sec")):
            self.count += 1


def compiled_peak(compiled) -> dict:
    """What the compiler says one execution of ``compiled`` holds on a
    chip. The runtime's ``peak_bytes_in_use`` leaves a program's temporaries
    out (PERF.md, PR 21), so the larger of the two is reported."""
    mem = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "peak_memory_in_bytes")
    return {f: int(getattr(mem, f, 0) or 0) for f in fields} if mem else {}


def memory_report(devs, compiled_stats: dict, what: str) -> dict:
    """Peak bytes on the fullest chip: the larger of what the runtime saw
    and what the compiler says the cell's program holds."""
    runtime_peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        runtime_peak = max(runtime_peak, int(
            stats.get("peak_bytes_in_use") or stats.get("bytes_in_use") or 0))
    program_peak = compiled_stats.get("peak_memory_in_bytes", 0)
    return {"runtime_peak_bytes": runtime_peak, "compiled": compiled_stats,
            "peak_bytes": max(runtime_peak, program_peak),
            "peak_is": f"the compiler's peak_memory_in_bytes of {what}"
            if program_peak > runtime_peak
            else "the runtime's peak_bytes_in_use"}

"""Headline benchmark: flagship Transformer LM training on one TPU chip.

Primary metric: tokens/sec/chip with the Pallas flash-attention fast path
(ops/flash.py) enabled, plus model FLOPs utilization (MFU, PaLM convention:
(6*N + 12*L*d*S) FLOPs per token over the chip's peak bf16 rate).

vs_baseline: MFU / 0.40. The reference publishes no in-repo LM throughput
(BASELINE.md: its release gates are pass/fail); 40% single-chip MFU is the
credible floor a tuned single-chip LM stack must clear, so >1.0 means the
TPU compute plane is doing its job. The round-1 ResNet-50 metric
(images/sec/chip vs the ~2500 A100-DDP figure) is reported alongside in the
same JSON line for continuity.

Process model (a chip belongs to one process at a time):
  - pre-flight: sweep stale sessions, then probe the chip in a
    SUBPROCESS with a hard deadline — a dead backend fails fast instead
    of hanging the harness;
  - every phase runs in its own subprocess with its own time budget;
  - the parent process never imports jax, so it never holds the chip;
  - phases share jax's persistent compilation cache
    (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).

A run on anything but a TPU, a failed probe or a failed phase exits
non-zero and prints NO metric line: a number under a device metric's name
comes from the device or not at all. Otherwise prints exactly ONE JSON line
on stdout (progress goes to stderr):
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# Peak dense bf16 TFLOP/s by device kind (public spec sheets).
PEAK_BF16 = {
    "v6e": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v3": 123e12,
}
MFU_FLOOR = 0.40
MFU_GATE = 0.50     # regression gate: headline S=2048 MFU must clear this
BASELINE_IMG_PER_SEC_PER_CHIP = 2500.0

# Per-phase wall budgets (seconds): generous headroom over compile plus
# measured phase times.
PHASE_BUDGETS = {
    "probe": 300,
    "lm2048": 900,
    "lm8192": 600,
    "resnet": 540,
    "decode": 420,
}


def _phase_budget(name: str) -> int:
    """Host-aware wall budget: small CI hosts (fewer than 4 CPUs) time-slice
    the cluster's daemons, workers, and the phase subprocess onto the same
    cores, roughly doubling wall time — same scaling as tests/test_examples
    applies to its example timeouts."""
    scale = min(2, max(1, 4 // max(os.cpu_count() or 1, 1)))
    return PHASE_BUDGETS[name] * scale


def _peak_flops() -> float:
    from ray_tpu.tpu.topology import generation

    return PEAK_BF16[generation()]  # unknown device kind: an error


def phase_probe() -> dict:
    """Is the chip reachable and computing? A tiny jit round-trip. The
    matmul is deliberately minuscule (64x64): the probe times backend
    bring-up, not compute."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    devs = jax.devices()
    x = jnp.ones((64, 64), jnp.bfloat16)
    y = float(jax.jit(lambda a: (a @ a).sum())(x))
    return {"devices": len(devs), "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "probe_s": round(time.perf_counter() - t0, 1),
            "probe_value": y}


def bench_lm(seq: int = 2048, batch_per_chip: int = 8) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import TransformerConfig
    from ray_tpu.ops.flash import autotune_blocks
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_lm_train_step

    n = jax.device_count()
    # one-time on-chip block tuning at the REAL workload shape
    autotune_blocks(seq, head_dim=2048 // 16, heads=16,
                    batch=batch_per_chip * n)
    # ~0.74B params: the largest llama-style config whose f32 params
    # + adam moments + f32 grads (16 bytes/param) plus activations fit
    # a 16G v5e chip with per-layer remat. batch_per_chip*seq is held
    # at 16k tokens across the sweep so the long-context point isn't
    # memory-starved.
    cfg = TransformerConfig(
        vocab_size=32768, d_model=2048, n_layers=10, n_heads=16,
        n_kv_heads=16, max_seq=seq, attn_impl="flash",
        tied_embeddings=True, remat=True)
    batch = batch_per_chip * n
    mesh = build_mesh(MeshSpec(dp=n))
    init_fn, step_fn, place_batch = make_lm_train_step(cfg, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))

    rng = np.random.default_rng(0)
    batch_data = place_batch({
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)})
    for _ in range(3):  # compile + settle
        state, metrics = step_fn(state, batch_data)
    float(jax.device_get(metrics["loss"]))

    steps = 20
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step_fn(state, batch_data)
        float(jax.device_get(metrics["loss"]))
        best = min(best, time.perf_counter() - t0)
    tok_per_sec = steps * batch * seq / best
    flops_per_token = 6 * n_params + 12 * cfg.n_layers * cfg.d_model * seq
    mfu = tok_per_sec / n * flops_per_token / _peak_flops()
    return {
        "tokens_per_sec_per_chip": round(tok_per_sec / n, 1),
        "mfu": round(mfu, 4),
        "lm_params_b": round(n_params / 1e9, 3),
    }


def bench_decode() -> dict:
    """KV-cache autoregressive decode throughput (models/generate.py):
    tokens/sec/chip at batch 8 — the serving-side half of the LM story
    (the training numbers above are the other half)."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import TransformerConfig, generate, transformer_init

    cfg = TransformerConfig(
        vocab_size=32768, d_model=2048, n_layers=10, n_heads=16,
        n_kv_heads=16, max_seq=2048, attn_impl="flash",
        tied_embeddings=True, remat=False)
    batch, prompt_len, new = 8, 128, 256
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (batch, prompt_len)), jnp.int32)
    gen = jax.jit(partial(generate, cfg=cfg, max_new_tokens=new,
                          temperature=0.0))
    jax.device_get(gen(params, prompt))          # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.device_get(gen(params, prompt))
        best = min(best, time.perf_counter() - t0)
    # Single-device program (unsharded decode): the per-chip figure IS the
    # one device's throughput — no device_count scaling.
    return {"decode_tokens_per_sec_per_chip":
            round(batch * new / best, 1)}


def bench_resnet() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_resnet_train_step

    n = jax.device_count()
    mesh = build_mesh(MeshSpec(dp=n))
    per_chip_batch, image_size, steps = 256, 224, 30
    batch_size = per_chip_batch * n

    init_fn, step_fn, place_batch = make_resnet_train_step(
        mesh, num_classes=1000, image_size=image_size, learning_rate=0.1)
    state = init_fn(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    batch = place_batch({
        "image": jnp.asarray(
            rng.normal(size=(batch_size, image_size, image_size, 3)),
            jnp.float32),
        "label": jnp.asarray(rng.integers(0, 1000, (batch_size,)),
                             jnp.int32),
    })
    # Warmup (compile), synced via device_get of the final loss (the whole
    # chain must complete).
    for _ in range(3):
        state, metrics = step_fn(state, batch)
    float(jax.device_get(metrics["loss"]))

    best = float("inf")
    for _ in range(2):  # two windows; keep the best (first may recompile)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step_fn(state, batch)
        float(jax.device_get(metrics["loss"]))
        best = min(best, time.perf_counter() - t0)
    return {"resnet50_images_per_sec_per_chip":
            round(steps * batch_size / best / n, 2)}


_PHASES = {
    "probe": phase_probe,
    "lm2048": lambda: bench_lm(seq=2048, batch_per_chip=8),
    "lm8192": lambda: bench_lm(seq=8192, batch_per_chip=2),
    "resnet": bench_resnet,
    "decode": bench_decode,
}


def _run_phase_subprocess(name: str, scratch_dir: str) -> dict:
    """Run one phase in its own process under its budget; a hang or crash
    comes back as {"error": ...}."""
    from ray_tpu.tpu.topology import default_compile_cache_dir

    budget = _phase_budget(name)
    out_path = os.path.join(scratch_dir, f"{name}.json")
    print(f"[bench] phase {name} (budget {budget}s) ...",
          file=sys.stderr, flush=True)
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", default_compile_cache_dir())
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--phase", name, "--out", out_path],
        stdout=sys.stderr, stderr=subprocess.STDOUT, env=env)
    try:
        rc = proc.wait(timeout=budget)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        print(f"[bench] phase {name} TIMED OUT after {budget}s",
              file=sys.stderr, flush=True)
        return {"error": f"timeout after {budget}s"}
    dt = time.perf_counter() - t0
    if os.path.exists(out_path):
        with open(out_path) as f:
            result = json.load(f)
        print(f"[bench] phase {name} done in {dt:.0f}s: {result}",
              file=sys.stderr, flush=True)
        return result
    return {"error": f"phase exited rc={rc} without a result"}


def main() -> int:
    # Pre-flight hygiene: reclaim whatever previous runs stranded (the
    # round-4 bench found the chip held by orphans of an earlier suite).
    try:
        from ray_tpu.cluster import hygiene
        swept = hygiene.sweep_stale()
        if swept:
            print(f"[bench] pre-flight swept {len(swept)} stale artifacts",
                  file=sys.stderr, flush=True)
    except Exception as e:  # noqa: BLE001 - sweep is best-effort
        print(f"[bench] sweep failed: {e!r}", file=sys.stderr, flush=True)

    import tempfile
    scratch = tempfile.mkdtemp(prefix="bench-phases-")

    probe = _run_phase_subprocess("probe", scratch)
    if probe.get("platform") != "tpu":
        print(f"[bench] no TPU to measure on, no metric line: {probe}",
              file=sys.stderr, flush=True)
        return 1

    lm = _run_phase_subprocess("lm2048", scratch)
    lm8k = _run_phase_subprocess("lm8192", scratch)
    rn = _run_phase_subprocess("resnet", scratch)
    dec = _run_phase_subprocess("decode", scratch)
    errors = {k: v["error"] for k, v in
              (("lm2048", lm), ("lm8192", lm8k), ("resnet", rn),
               ("decode", dec)) if "error" in v}
    if errors:
        print(f"[bench] phases failed, no metric line: {errors}",
              file=sys.stderr, flush=True)
        return 1

    mfu = lm["mfu"]
    mfu_gate_pass = mfu >= MFU_GATE
    print(json.dumps({
        "metric": "lm_train_tokens_per_sec_per_chip",
        "value": lm["tokens_per_sec_per_chip"],
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / MFU_FLOOR, 4),
        "mfu": mfu,
        "lm_params_b": lm["lm_params_b"],
        "mfu_gate": f">= {MFU_GATE}",
        "mfu_gate_pass": mfu_gate_pass,
        "platform": probe["platform"],
        "device_kind": probe["device_kind"],
        "s8192_tokens_per_sec_per_chip": lm8k["tokens_per_sec_per_chip"],
        "s8192_mfu": lm8k["mfu"],
        "decode_tokens_per_sec_per_chip":
            dec["decode_tokens_per_sec_per_chip"],
        "resnet50_images_per_sec_per_chip":
            rn["resnet50_images_per_sec_per_chip"],
        "resnet_vs_a100_ddp": round(
            rn["resnet50_images_per_sec_per_chip"]
            / BASELINE_IMG_PER_SEC_PER_CHIP, 4),
        "probe": probe,
    }))
    # Regression gate AFTER the JSON line: a headline-MFU regression below
    # the gate fails the run visibly.
    return 0 if mfu_gate_pass else 1


def _phase_main(name: str, out_path: str) -> int:
    result = _PHASES[name]()
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, out_path)
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=sorted(_PHASES))
    ap.add_argument("--out")
    args = ap.parse_args()
    if args.phase:
        sys.exit(_phase_main(args.phase, args.out))
    sys.exit(main())

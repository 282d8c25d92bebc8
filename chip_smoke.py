"""Chip smoke: the runtime's main path, once, on the TPU this machine has.

    python chip_smoke.py

Through the entry points a user calls, at the full width of the flagship LM
(vocab 32,768, d_model 2,048, 10 layers, 16 heads x 128, SwiGLU 8,192, tied
embeddings: 0.738 B parameters, random weights from a seed):

  kernels  a ``num_tpus=1`` task checks the Pallas flash kernels (forward,
           backward, GQA, bf16) against a highest-precision reference at the
           flagship head shape with the default block table;
  train    ``JaxTrainer`` + ``ScalingConfig(use_tpu=True)`` leases every chip
           to one worker, which compiles ``make_lm_train_step`` (flash
           attention, S = 2,048, 8 sequences per chip) and takes 4 steps on
           a fixed batch; with >= 2 chips over a ``dp`` mesh and again over
           an ``fsdp`` one;
  serve    after that worker has exited, a ``num_tpus=1`` serve replica
           holds the same model and a jitted ``generate``; requests go
           through the HTTP proxy;
  pair     with >= 2 chips: two ``num_tpus=1`` actors alive at once, each on
           a chip of its own.

This process never opens a JAX backend (a chip belongs to one process: the
workers the daemon spawns for TPU leases). Anything wrong — no TPU, a kernel
off its reference, loss not falling, a non-200 reply, a phase that raised —
is a non-zero exit with the reason; only a run in which every phase passed
prints, as the last line of stdout,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``--cpu-dry-run`` drives the same calls at a toy size on the CPU, to debug
this command without a chip. Every line it prints says so, it skips what
needs the device, and it always exits 3 without the result line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import sys
import tempfile
import time
import urllib.request

FLAGSHIP = dict(vocab_size=32768, d_model=2048, n_layers=10, n_heads=16,
                n_kv_heads=16, d_ff=8192, max_seq=2048, tied_embeddings=True)
FULL = dict(model=dict(FLAGSHIP, attn_impl="flash"), seq=2048, seqs_per_chip=8,
            prompt_len=128, new_tokens=64, serve_batch=8)
# Toy size for --cpu-dry-run only ("auto" attention: a jnp form on the CPU).
TOY = dict(model=dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=4, d_ff=128, max_seq=64, tied_embeddings=True,
                      attn_impl="auto"),
           seq=64, seqs_per_chip=2, prompt_len=16, new_tokens=8, serve_batch=2)
TRAIN_STEPS = 4
DEADLINE_S = 1150   # the whole run, compilation included

_label = "[chip_smoke]"


def say(msg: str) -> None:
    print(f"{_label} {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# What runs inside the chip-owning workers (shipped to them by value).
# ---------------------------------------------------------------------------

def _device_facts() -> dict:
    import jax
    devs = jax.devices()
    return {"pid": os.getpid(), "platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "devices": len(devs),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS")}


def kernel_phase() -> dict:
    """Flash kernels vs attention_reference on the chip, flagship head shape
    (16 heads x 128, S = 2,048), default block table. Tolerances are set
    against a highest-precision gold: the default-precision XLA reference
    itself deviates ~4e-3 from it on a TPU, so flash must stay within 2x of
    the reference's own deviation — checking flash straight against the
    default-precision reference would conflate MXU rounding with bugs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import attention_reference
    from ray_tpu.ops.flash import _default_blocks, flash_attention

    b, s, h, d = 1, FLAGSHIP["max_seq"], FLAGSHIP["n_heads"], 128
    rng = np.random.default_rng(0)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32) * 0.5

    q, k, v = rand(b, s, h, d), rand(b, s, h, d), rand(b, s, h, d)
    out = dict(_device_facts(), blocks=_default_blocks(s, s, d, True),
               errors={})

    def record(name, err_flash, err_ref):
        out["errors"][name] = [err_flash, err_ref]
        if not err_flash < max(2 * err_ref, 1e-4):
            raise AssertionError(
                f"flash {name}: error {err_flash:.3e} vs the reference's own "
                f"{err_ref:.3e} from the highest-precision gold")

    def maxerr(a, b):
        return float(jnp.max(jnp.abs(a - b)))

    for causal in (True, False):
        def ref_fn(q, k, v):
            return attention_reference(q, k, v, causal=causal)

        def flash_fn(q, k, v):
            return flash_attention(q, k, v, causal=causal)

        with jax.default_matmul_precision("highest"):
            gold = jax.jit(ref_fn)(q, k, v)
            g_gold = jax.jit(jax.grad(
                lambda *a: jnp.sum(ref_fn(*a) ** 2), argnums=(0, 1, 2)))(
                    q, k, v)
        record(f"fwd causal={causal}",
               maxerr(jax.jit(flash_fn)(q, k, v), gold),
               maxerr(jax.jit(ref_fn)(q, k, v), gold))
        g_ref = jax.jit(jax.grad(
            lambda *a: jnp.sum(ref_fn(*a) ** 2), argnums=(0, 1, 2)))(q, k, v)
        g_fl = jax.jit(jax.grad(
            lambda *a: jnp.sum(flash_fn(*a) ** 2), argnums=(0, 1, 2)))(q, k, v)
        for name, fl, rf, gd in zip("qkv", g_fl, g_ref, g_gold):
            scale = float(jnp.max(jnp.abs(gd))) + 1e-9
            record(f"d{name} causal={causal}", maxerr(fl, gd) / scale,
                   maxerr(rf, gd) / scale)

    kg, vg = rand(b, s, h // 2, d), rand(b, s, h // 2, d)   # GQA 16/8
    with jax.default_matmul_precision("highest"):
        gold = jax.jit(attention_reference)(q, kg, vg)
    record("fwd gqa", maxerr(jax.jit(flash_attention)(q, kg, vg), gold),
           maxerr(jax.jit(attention_reference)(q, kg, vg), gold))

    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    err = maxerr(jax.jit(flash_attention)(qb, kb, vb).astype(jnp.float32),
                 jax.jit(attention_reference)(qb, kb, vb).astype(jnp.float32))
    out["errors"]["fwd bf16 vs reference"] = [err, 3e-2]
    if not err < 3e-2:
        raise AssertionError(f"flash bf16 forward off by {err:.3e}")
    return out


def train_loop(config: dict) -> None:
    """The JaxTrainer worker's loop: mesh over its chips, the flagship
    train step compiled once, TRAIN_STEPS steps on a fixed seeded batch."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.air import session
    from ray_tpu.models import TransformerConfig
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_lm_train_step

    facts = _device_facts()
    devs = jax.devices()
    n = len(devs)
    cfg = TransformerConfig(**config["model"], remat=True)
    mesh = build_mesh(MeshSpec(**{config["mesh_axis"]: n}))
    init_fn, step_fn, place_batch = make_lm_train_step(cfg, mesh)
    t0 = time.perf_counter()
    state = init_fn(jax.random.PRNGKey(0))
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (config["seqs_per_chip"] * n, config["seq"]))
    batch = place_batch({"tokens": jnp.asarray(tokens, jnp.int32)})
    leaves = jax.tree.leaves(state.params)
    start_stats = [d.memory_stats() or {} for d in devs]
    facts.update(
        mesh={a: s for a, s in mesh.shape.items() if s > 1},
        mesh_coords=[list(getattr(d, "coords", ()))
                     for d in mesh.devices.flat],
        n_params=sum(x.size for x in leaves),
        state_device_sets=sorted({len(x.sharding.device_set)
                                  for x in jax.tree.leaves(state)}),
        bytes_in_use_at_start=[s.get("bytes_in_use") for s in start_stats],
        batch_devices=len(batch["tokens"].sharding.device_set),
        init_s=round(init_s, 2))

    t0 = time.perf_counter()
    compiled = step_fn.lower(state, batch).compile()
    facts["compile_s"] = round(time.perf_counter() - t0, 2)
    hlo = compiled.as_text()
    mosaic = [ln for ln in hlo.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    # q/k/v/o/grad shapes of each Mosaic call (results and operand layout
    # constraints): [batch*heads, S, D] as one device sees them
    facts["mosaic_calls"] = len(mosaic)
    facts["mosaic_shapes"] = sorted({
        m for ln in mosaic for m in re.findall(r"bf16\[\d+,\d+,\d+\]", ln)})
    facts["all_gathers"] = len(re.findall(r" all-gather(?:-start)?\(", hlo))

    for _ in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready((state, metrics))
        step_s = time.perf_counter() - t0
        stats = [d.memory_stats() or {} for d in devs]
        session.report(dict(
            facts, loss=float(metrics["loss"]), step_s=round(step_s, 4),
            bytes_in_use=[s.get("bytes_in_use") for s in stats],
            peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats]))


class LMReplica:
    """The serve replica: the flagship model and a jitted greedy generate."""

    def __init__(self, sizes: dict):
        from functools import partial

        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import (TransformerConfig, generate,
                                    transformer_apply, transformer_init)
        from ray_tpu.models.generate import prefill

        self.np, self.jnp = np, jnp
        self.sizes = sizes
        self.cfg = cfg = TransformerConfig(**sizes["model"], remat=False)
        t0 = time.perf_counter()
        self.params = jax.jit(partial(transformer_init, cfg=cfg))(
            jax.random.PRNGKey(0))
        jax.block_until_ready(self.params)
        self.gen = jax.jit(partial(generate, cfg=cfg, temperature=0.0,
                                   max_new_tokens=sizes["new_tokens"]))
        t1 = time.perf_counter()
        prompt = self._prompt(0)
        first = np.asarray(self.gen(self.params, prompt))[:, 0]
        t2 = time.perf_counter()
        # The KV-cache path against the plain forward on the same prompt:
        # last-position logits agree, and greedy's first token is their
        # argmax.
        cached = jax.jit(partial(
            prefill, cfg=cfg,
            max_len=sizes["prompt_len"] + sizes["new_tokens"]))(
                self.params, prompt)[0]
        plain = jax.jit(partial(transformer_apply, cfg=cfg))(
            self.params, prompt)[:, -1]
        self.init = {
            "init_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
            "prefill_vs_forward_max_err": float(jnp.max(jnp.abs(
                cached - plain))),
            "first_token_is_argmax": bool(
                (first == np.asarray(jnp.argmax(cached, -1))).all()),
        }

    def _prompt(self, seed: int):
        s = self.sizes
        return self.jnp.asarray(self.np.random.default_rng(seed).integers(
            0, self.cfg.vocab_size, (s["serve_batch"], s["prompt_len"])),
            self.jnp.int32)

    def __call__(self, seed: int = 0):
        import zlib
        t0 = time.perf_counter()
        toks = self.np.asarray(self.gen(self.params, self._prompt(int(seed))))
        return dict(
            _device_facts(), init=self.init, seed=seed,
            generate_s=round(time.perf_counter() - t0, 4),
            compiles=self.gen._cache_size(),
            tokens_shape=list(toks.shape),
            tokens_in_vocab=bool(((toks >= 0)
                                  & (toks < self.cfg.vocab_size)).all()),
            tokens_crc=zlib.crc32(toks.tobytes()),
            tokens_head=toks[0, :8].tolist())


class ChipProbe:
    def look(self) -> dict:
        import jax.numpy as jnp
        facts = _device_facts()
        x = jnp.ones((256, 256), jnp.float32)
        facts["matmul_sum"] = float((x @ x).sum())
        time.sleep(2.0)   # stay alive while the other actor opens its chip
        return facts


# ---------------------------------------------------------------------------
# The driver: leases, checks, prints. Never touches a JAX backend.
# ---------------------------------------------------------------------------

def check_device(facts: dict, want_chips: int, dry: bool, who: str) -> None:
    say(f"{who}: platform={facts['platform']} "
        f"device_kind={facts['device_kind']!r} devices={facts['devices']} "
        f"pid={facts['pid']} visible_chips={facts['visible_chips']}")
    if dry:
        return
    from ray_tpu.tpu.topology import generation_of
    check(facts["platform"] == "tpu", f"{who} ran on {facts['platform']}")
    generation_of(facts["device_kind"])   # raises on a kind not in the table
    check(facts["devices"] == want_chips,
          f"{who} sees {facts['devices']} devices, was leased {want_chips}")


def run_kernels(rt) -> None:
    res = rt.get(rt.remote(kernel_phase).options(num_tpus=1).remote(),
                 timeout=600)
    check_device(res, 1, False, "kernels")
    say(f"kernels: default blocks {res['blocks']}; max error "
        "[flash, reference-or-bound] vs highest-precision gold:")
    for name, (e_fl, e_ref) in res["errors"].items():
        say(f"kernels:   {name}: {e_fl:.3e} vs {e_ref:.3e}")


def run_train(sizes: dict, chips: int, dry: bool, storage: str,
              mesh_axis: str = "dp") -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    who = f"train[{mesh_axis}]"
    scaling = ScalingConfig(num_workers=1) if dry else ScalingConfig(
        num_workers=1, use_tpu=True, tpus_per_worker=chips)
    result = JaxTrainer(
        train_loop,
        train_loop_config=dict(sizes, steps=TRAIN_STEPS,
                               mesh_axis=mesh_axis),
        scaling_config=scaling,
        run_config=RunConfig(name="chip_smoke", storage_path=storage,
                             stop={"training_iteration": TRAIN_STEPS})).fit()
    if result.error is not None:
        raise SmokeFailure(f"JaxTrainer failed: {result.error}")
    hist = result.metrics_history
    last = hist[-1]
    check_device(last, chips, dry, who)
    losses = [m["loss"] for m in hist]
    say(f"{who}: mesh={last['mesh']} coords={last['mesh_coords']} "
        f"params={last['n_params'] / 1e9:.3f}B init_s={last['init_s']} "
        f"compile_s={last['compile_s']} "
        f"step_s={[m['step_s'] for m in hist]}")
    say(f"{who}: loss={[round(x, 4) for x in losses]} "
        f"mosaic_calls={last['mosaic_calls']} "
        f"mosaic_shapes={last['mosaic_shapes']} "
        f"all_gathers={last['all_gathers']}")
    say(f"{who}: bytes_in_use={last['bytes_in_use']} "
        f"peak_bytes_in_use={last['peak_bytes_in_use']} "
        f"bytes_in_use_at_start={last['bytes_in_use_at_start']} "
        f"state_device_sets={last['state_device_sets']} "
        f"batch_devices={last['batch_devices']}")
    check(len(hist) == TRAIN_STEPS, f"{len(hist)} steps reported")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    vocab = sizes["model"]["vocab_size"]
    check(abs(losses[0] - math.log(vocab)) < 1.0,
          f"first loss {losses[0]} is not near ln(vocab) = {math.log(vocab)}")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"loss not decreasing on the fixed batch: {losses}")
    check(last["state_device_sets"] == [last["devices"]]
          and last["batch_devices"] == last["devices"],
          "params, optimizer state or batch are not spread over every device")
    if dry:
        return last
    check(last["mosaic_calls"] > 0,
          "no Mosaic custom call in the compiled train step")
    # Flash runs per shard: [seqs_per_chip * heads, S, head_dim] operands,
    # never the global batch (and so no all-gather can be feeding it).
    per_shard = "bf16[%d,%d,%d]" % (
        sizes["seqs_per_chip"] * sizes["model"]["n_heads"], sizes["seq"],
        sizes["model"]["d_model"] // sizes["model"]["n_heads"])
    check(last["mosaic_shapes"] == [per_shard],
          f"Mosaic calls take {last['mosaic_shapes']}, not the per-shard "
          f"{per_shard}")
    for key in ("bytes_in_use_at_start", "bytes_in_use", "peak_bytes_in_use"):
        check(max(last[key]) - min(last[key]) <= 0.05 * max(last[key]),
              f"per-device {key} is unbalanced: {last[key]}")
    return last


def wait_gone(pid: int, who: str, dry: bool = False,
              timeout: float = 30.0) -> None:
    """A chip-owning worker dies with its lease; until it has, the chip is
    not free for the next owner."""
    if dry:
        return   # CPU workers own no chip and go back to the pool
    deadline = time.monotonic() + timeout
    while os.path.exists(f"/proc/{pid}"):
        check(time.monotonic() < deadline,
              f"{who} (pid {pid}) still alive {timeout}s after its lease "
              "ended: its chip is not free")
        time.sleep(0.1)
    say(f"{who} (pid {pid}) has exited")


def run_serve(rt, serve, sizes: dict, dry: bool, trainer_pid: int) -> None:
    LM = serve.deployment(
        LMReplica, name="lm", route_prefix="/lm", init_grace_s=600.0,
        ray_actor_options={"num_tpus": 0 if dry else 1})
    handle = serve.run(LM.bind(sizes), http_host="127.0.0.1")
    # Model build + compile happen in the replica's __init__; a direct call
    # waits for it without the proxy's per-request deadline.
    t0 = time.perf_counter()
    warm = rt.get(handle.remote(seed=0), timeout=900)
    check_device(warm, 1, dry, "serve")
    check(warm["pid"] != trainer_pid, "the replica is the trainer's process")
    say(f"serve: replica ready after {time.perf_counter() - t0:.1f}s: "
        f"{warm['init']}")
    check(warm["init"]["prefill_vs_forward_max_err"] < 5e-2
          and warm["init"]["first_token_is_argmax"],
          f"KV-cache path disagrees with the plain forward: {warm['init']}")
    replies = []
    for seed in (1, 2, 1, 2):
        req = urllib.request.Request(
            f"http://127.0.0.1:{handle.http_port}/lm",
            data=json.dumps({"seed": seed}).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=120) as resp:
            check(resp.status == 200, f"HTTP {resp.status} from the proxy")
            body = json.loads(resp.read())
        body["latency_s"] = round(time.perf_counter() - t0, 4)
        replies.append(body)
        say(f"serve: seed={seed} latency_s={body['latency_s']} "
            f"generate_s={body['generate_s']} compiles={body['compiles']} "
            f"platform={body['platform']} "
            f"device_kind={body['device_kind']!r} pid={body['pid']} "
            f"tokens={body['tokens_shape']} head={body['tokens_head']}")
    want_shape = [sizes["serve_batch"], sizes["new_tokens"]]
    for r in replies:
        check(r["pid"] == warm["pid"], "replies came from another process")
        check(r["platform"] == warm["platform"], "platform changed")
        check(r["tokens_shape"] == want_shape and r["tokens_in_vocab"],
              f"bad tokens: {r['tokens_shape']}")
        check(r["compiles"] == 1,
              f"generate recompiled for a shape it had seen: {r['compiles']}")
    check(replies[0]["tokens_crc"] == replies[2]["tokens_crc"]
          and replies[1]["tokens_crc"] == replies[3]["tokens_crc"],
          "greedy decoding of the same prompt gave different tokens")
    check(replies[0]["tokens_crc"] != replies[1]["tokens_crc"],
          "different prompts gave identical tokens")
    serve.shutdown()
    wait_gone(warm["pid"], "serve replica", dry)


def run_pair(rt) -> None:
    Probe = rt.remote(ChipProbe)
    actors = [Probe.options(num_tpus=1).remote() for _ in range(2)]
    looks = rt.get([a.look.remote() for a in actors], timeout=300)
    for i, facts in enumerate(looks):
        check_device(facts, 1, False, f"pair[{i}]")
        check(facts["matmul_sum"] == 256.0 ** 3, "wrong matmul on the chip")
    check(looks[0]["pid"] != looks[1]["pid"]
          and looks[0]["visible_chips"] != looks[1]["visible_chips"],
          f"two one-chip actors share a process or a chip: {looks}")
    for a in actors:
        rt.kill(a)
    for facts in looks:
        wait_gone(facts["pid"], "pair actor")


def main() -> int:
    global _label
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="toy size on the CPU to debug this command; "
                         "never a result, always exits 3")
    dry = ap.parse_args().cpu_dry_run
    if dry:
        _label = "[chip_smoke CPU-DRY-RUN, NOT A CHIP RESULT]"
    sizes = TOY if dry else FULL

    def on_alarm(*_):
        raise SmokeFailure(f"not finished after {DEADLINE_S}s")
    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)

    import ray_tpu as rt
    from ray_tpu import serve

    storage = tempfile.mkdtemp(prefix="chip_smoke-")
    t_start = time.perf_counter()
    try:
        rt.init()
        chips = int(rt.cluster_resources().get("TPU", 0))
        say(f"rt.init(): TPU chips advertised = {chips} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
        if not dry:
            check(chips > 0, "no TPU: rt.init() found no chip on this "
                  "machine (jax falls back to, or is pinned to, the CPU)")
            run_kernels(rt)
        train = run_train(sizes, chips, dry, storage)
        wait_gone(train["pid"], "trainer worker", dry)
        if chips >= 2:   # the same step with params sharded, not replicated
            sharded = run_train(sizes, chips, dry, storage, "fsdp")
            wait_gone(sharded["pid"], "trainer worker", dry)
        run_serve(rt, serve, sizes, dry, train["pid"])
        if chips >= 2:
            run_pair(rt)
        if "jax" in sys.modules:
            from jax._src import xla_bridge
            check(not xla_bridge.backends_are_initialized(),
                  "the driver process opened a JAX backend")
    finally:
        signal.alarm(0)
        try:
            if rt.is_initialized():
                serve.shutdown()
        finally:
            rt.shutdown()
            shutil.rmtree(storage, ignore_errors=True)
    say(f"all phases done in {time.perf_counter() - t_start:.0f}s")
    if dry:
        say("dry run finished: nothing here was measured on a chip")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": train["platform"], "kind": train["device_kind"],
        "count": train["devices"]}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"{_label} FAILED: {e}", flush=True)
        sys.exit(1)

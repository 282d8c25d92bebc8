"""Pallas flash kernels without a chip: their numbers, and their compilation.

The kernels only run on a TPU (chip_smoke.py checks them there at the
flagship head shape). Here the same kernel bodies run through the Pallas
interpreter on the CPU against attention_reference, reached only by the
explicit ``interpret=True`` that no platform check ever selects; and libtpu
compiles them ahead of time for a v5e 2x2 topology, which needs no device
and applies the chip's real VMEM and HBM limits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops.flash import _flash_bwd, _flash_fwd, flash_attention


def _bhsd(x):  # [B, S, H, D] -> [B*H, S, D]
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


@pytest.mark.parametrize("causal,sq,sk,window,first", [
    (True, 256, 256, None, None),
    (False, 256, 256, None, None),
    (True, 128, 256, None, None),
    # a band, the queries the last of more keys: of one key, of few, of the
    # dots3 cell's (512 keys before the first query, its window 513)
    (True, 128, 256, 1, None),
    (True, 128, 256, 5, None),
    (True, 256, 768, 513, None),
    (True, 256, 256, 200, None),         # the sequence's own keys
    (True, 128, 256, 1000, None),        # wider than the keys: causal
    # a prompt's first chunk: the keys before position 0 do not exist, a
    # whole block of them and part of one
    (True, 256, 768, 513, 512),
    (True, 128, 256, 40, 100),
])
def test_flash_kernels_match_reference(causal, sq, sk, window, first):
    rng = np.random.default_rng(0)
    b, h, d = 1, 2, 128
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32) * 0.5
    k = jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32) * 0.5
    v = jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32) * 0.5
    g = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    kw = dict(causal=causal, scale=d ** -0.5, block_q=128, block_k=128,
              window=window, first=first, interpret=True)

    ref, vjp = jax.vjp(
        lambda q, k, v: attention_reference(
            q, k, v, causal=causal, window=window, first_key=first), q, k, v)
    out, lse = _flash_fwd(_bhsd(q), _bhsd(k), _bhsd(v), **kw)
    np.testing.assert_allclose(out, _bhsd(ref), atol=2e-5)

    grads = _flash_bwd((_bhsd(q), _bhsd(k), _bhsd(v), out, lse), _bhsd(g),
                       **kw)
    for got, want in zip(grads, vjp(g)):
        np.testing.assert_allclose(got, _bhsd(want), atol=2e-4)
    if first:       # a key that does not exist gets no gradient
        assert not np.asarray(grads[1])[:, :first].any()
        assert not np.asarray(grads[2])[:, :first].any()


def test_a_band_visits_only_the_key_blocks_it_reaches():
    """Which grid steps run, and which key block each fetches, at the dots3
    cell's chunk (4 blocks of 512 queries over 5 of 512 keys, window 513):
    a block of queries visits two key blocks, a step that does not run
    fetches the block its neighbour holds, and in a prompt's first chunk
    the key block before position 0 is never fetched."""
    from ray_tpu.ops import flash

    at = dict(block_q=512, block_k=512, q_offset=512, window=513)
    for first, fetched in ((0, [[0, 1, 1, 1, 1], [1, 1, 2, 2, 2],
                                [2, 2, 2, 3, 3], [3, 3, 3, 3, 4]]),
                           (512, [[1, 1, 1, 1, 1], [1, 1, 2, 2, 2],
                                  [2, 2, 2, 3, 3], [3, 3, 3, 3, 4]])):
        for i in range(4):
            span = flash._band_blocks(jnp.int32(i), first, **at)
            assert [int(jnp.clip(j, *span)) for j in range(5)] == fetched[i]
            runs = [j for j in range(5)
                    if flash._reaches(i, j, first, **at)]
            # the steps that run are those that fetch a block of their own
            assert runs == sorted(set(fetched[i])) == [
                j for j in range(5) if fetched[i][j] == j]


def test_without_a_window_the_program_is_the_one_without_the_argument(
        monkeypatch):
    """``window=None`` traces, forward and gradient, the jaxpr that the
    call without the argument traces: the cells that pass no window
    compile what they compiled before there was one."""
    from ray_tpu.ops import flash

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    q = jax.ShapeDtypeStruct((2, 1024, 4, 192), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 2048, 2, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 2048, 2, 128), jnp.bfloat16)

    def traced(**band):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, **band)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return str(jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(
            q, k, v))

    bare = traced()
    assert traced(window=None, first_key=None) == bare
    assert all(name in bare for name in ("rt_flash_fwd", "rt_flash_dkv",
                                         "rt_flash_dq"))
    assert traced(window=513) != bare
    with pytest.raises(ValueError, match="a window is a causal band"):
        flash_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="a window is a causal band"):
        flash_attention(q, k, v, first_key=3)


def test_flash_attention_raises_off_tpu():
    q = jnp.zeros((1, 128, 2, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        flash_attention(q, q, q)
    from ray_tpu.ops.attention import mha
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        mha(q, q, q, impl="flash")


def test_flash_compiles_for_v5e_per_shard(monkeypatch):
    """Mosaic accepts fwd + bwd at the flagship head shape with the default
    block table, and under a 4-chip data mesh each chip runs the kernel on
    its own batch shard: GSPMD cannot partition a Mosaic call, so without
    the shard_map in flash_attention this does not even lower."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.ops import flash

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    # Lowering targets the topology, not this process's CPU backend.
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4, 1),
                ("dcn_dp", "dp", "tp"))
    q = jax.ShapeDtypeStruct((8, 2048, 16, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, mesh=mesh)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 3                    # fwd, dkv, dq
    # [B*H, S, D] operands carry the shard's batch (2), not the global 8
    assert all("bf16[32,2048,128]" in ln for ln in calls)
    assert "bf16[128,2048,128]" not in hlo and "all-gather" not in hlo

    # The split is the caller's rules', not flash's own: batch over tp and
    # heads over dp here, 4 sequences x 4 heads -> 2 x 2 per chip.
    from ray_tpu.parallel.sharding import DEFAULT_RULES
    rules = DEFAULT_RULES.extend({"batch": "tp", "heads": "dp"})
    mesh = Mesh(np.array(topo.devices).reshape(1, 2, 2),
                ("dcn_dp", "dp", "tp"))
    q = jax.ShapeDtypeStruct((4, 256, 4, 128), jnp.bfloat16,
                             sharding=NamedSharding(
                                 mesh, P("tp", None, "dp")))
    hlo = jax.jit(lambda q: flash_attention(
        q, q, q, mesh=mesh, rules=rules)).lower(q).compile().as_text()
    call, = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert "bf16[4,256,128]" in call
    assert "all-gather" not in hlo and "all-to-all" not in hlo


def test_delta_rule_kernels_compile_for_v5e_per_shard(monkeypatch):
    """Mosaic accepts the gated delta rule's forward and backward kernels at
    the Qwen3-Next cell's shape (2 x 8192, 32 value heads on 16 key heads
    of 128) within the chip's VMEM, and under a 4-chip mesh each chip runs
    them on its own rows and heads (tests/test_qwen3_next.py holds their
    numbers, through the interpreter)."""
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from ray_tpu.ops import gated_delta
    from ray_tpu.parallel.sharding import DEFAULT_RULES

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    monkeypatch.setattr(gated_delta, "_on_tpu", lambda: True)

    def shapes(b, s, at):
        def like(shape, dtype, spec):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=at(spec))
        spec = P("dp", None, "tp")      # rows over dp, heads over tp
        return (like((b, s, 16, 128), jnp.bfloat16, spec),
                like((b, s, 16, 128), jnp.bfloat16, spec),
                like((b, s, 32, 128), jnp.bfloat16, spec),
                like((b, s, 32), jnp.float32, spec),
                like((b, s, 32), jnp.float32, spec))

    def kernel_calls(mesh, operands):
        def loss(*a):
            out = gated_delta.gated_delta_rule_over(mesh, DEFAULT_RULES, *a)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *operands).compile().as_text()
        return hlo, [ln for ln in hlo.splitlines()
                     if 'custom_call_target="tpu_custom_call"' in ln]

    one = SingleDeviceSharding(topo.devices[0])
    _, calls = kernel_calls(None, shapes(2, 8192, lambda spec: one))
    assert len(calls) == 2                    # forward (saving), backward
    assert all("bf16[2,8192,2048]" in ln for ln in calls)   # q, k as made

    mesh = Mesh(np.array(topo.devices).reshape(1, 2, 2),
                ("dcn_dp", "dp", "tp"))
    hlo, calls = kernel_calls(
        mesh, shapes(4, 512, lambda spec: NamedSharding(mesh, spec)))
    assert len(calls) == 2
    # half of the rows and half of the heads a chip: 8 key heads of 128
    assert all("bf16[2,512,1024]" in ln for ln in calls)
    assert "all-gather" not in hlo and "all-to-all" not in hlo


@pytest.mark.parametrize("bias", [False, True], ids=["gdn", "ssd"])
def test_convolution_kernels_compile_for_v5e_per_shard(monkeypatch, bias):
    """Mosaic accepts the sequence convolution's forward and backward
    kernels at the Qwen3-Next cell's shape (2 x 8192 positions of 8192
    channels, bfloat16, K = 4; with a bias: a state-space mixer's) within
    the chip's VMEM, and under a 4-chip mesh each chip runs them on its own
    rows of the batch; a served prompt of 128 positions compiles to no
    Mosaic call (tests/test_gdn_conv_kernel.py holds their numbers, through
    the interpreter)."""
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from ray_tpu.ops import gated_delta
    from ray_tpu.parallel.sharding import DEFAULT_RULES

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    monkeypatch.setattr(gated_delta, "_on_tpu", lambda: True)

    def kernel_calls(mesh, b, s, at):
        def like(shape, spec):
            return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                        sharding=at(spec))
        operands = (like((b, s, 8192), P("dp")), like((8192, 4), P())) \
            + ((like((8192,), P()),) if bias else ())

        def loss(*a):
            out = gated_delta.causal_conv_over(mesh, DEFAULT_RULES, *a)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        hlo = jax.jit(jax.grad(loss, argnums=tuple(range(len(operands))))
                      ).lower(*operands).compile().as_text()
        return hlo, [ln for ln in hlo.splitlines()
                     if 'custom_call_target="tpu_custom_call"' in ln]

    one = SingleDeviceSharding(topo.devices[0])
    _, calls = kernel_calls(None, 2, 8192, lambda spec: one)
    assert [name for ln in calls for name in
            ("rt_gdn_conv_fwd", "rt_gdn_conv_bwd") if name in ln] == [
                "rt_gdn_conv_fwd", "rt_gdn_conv_bwd"]
    assert all("bf16[2,8192,8192]" in ln for ln in calls)
    _, calls = kernel_calls(None, 48, 128, lambda spec: one)
    assert not calls

    mesh = Mesh(np.array(topo.devices).reshape(1, 4, 1),
                ("dcn_dp", "dp", "tp"))
    hlo, calls = kernel_calls(mesh, 8, 1024,
                              lambda spec: NamedSharding(mesh, spec))
    assert len(calls) == 2
    assert all("bf16[2,1024,8192]" in ln for ln in calls)   # a chip's rows
    assert "all-gather" not in hlo and "all-to-all" not in hlo


@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_sharded_flash_splits_gqa_heads_like_the_reference(monkeypatch,
                                                           kv_heads):
    """The shard_map around the kernel, with the kernel itself replaced by
    the reference: kv heads that split over tp stay grouped with their q
    heads, and fewer kv heads than tp shards (kv_heads=1, tp=4) still
    work."""
    from jax.sharding import Mesh

    from ray_tpu.ops import flash

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    monkeypatch.setattr(
        flash, "_flash_bshd",
        lambda q, k, v, *, causal, scale, block_q, block_k, window:
        attention_reference(q, k, v, causal=causal, scale=scale))
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 128, 8, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 128, kv_heads, 128)),
                        jnp.float32) for _ in range(2))
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, mesh=mesh))(
        q, k, v)
    np.testing.assert_allclose(out, attention_reference(q, k, v), atol=1e-5)


def test_a_looped_generate_keeps_one_cache_layout_on_v5e(monkeypatch):
    """The stacked KV cache of a looped stack goes from prefill to the
    decode loop without a copy of its own size: at the published widths
    (16 KV heads of 128, 16 rows x 384 positions; two layers run four
    times, so eight slots) no instruction of the compiled ``generate``
    copies a cache-shaped array, and the program's temporaries are the two
    stacks and little more. Written straight from the layer's body
    (``lax.dynamic_update_slice`` in prefill's ``attend``) the compiler
    gives prefill's carry another layout than decode's and copies both
    stacks whole: 18.85 of a chip's 15.75 GiB at 48 layers.

    The token loop's eight segments each read a prefix of a slot (160, 192,
    ... 384 positions): the slice fuses into the scores' fusion, whose
    operand is the whole stack, so no slab is written out either."""
    import re
    from functools import partial

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, generate_with_stats,
                                transformer_init)

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = TransformerConfig(
        vocab_size=49152, d_model=2048, n_layers=2, n_heads=16,
        n_kv_heads=16, d_ff=5632, max_seq=384, loop_steps=4,
        sandwich_norm=True, param_dtype=jnp.bfloat16, remat=False)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(partial(transformer_init, cfg=cfg),
                       jax.random.PRNGKey(0)))
    prompts = jax.ShapeDtypeStruct((16, 128), jnp.int32, sharding=one)

    def compiled():
        return jax.jit(partial(
            generate_with_stats, cfg=cfg, max_new_tokens=256)).lower(
                params, prompts).compile()

    stack = r"bf16\[8,16,384,16,128\]"
    copied = stack + r"\S* copy\("
    stacks = 2 * 8 * 16 * 384 * 16 * 128 * 2
    sound = compiled()
    text = sound.as_text()
    assert re.search(stack, text)
    assert not re.search(copied, text)
    assert sound.memory_analysis().temp_size_in_bytes < 1.5 * stacks
    shape_of = dict(re.findall(r"^\s*%(\S+) = (\w+\[[\d,]*\])", text, re.M))
    for extent in range(160, 385, 32):
        scores = [ln for ln in text.splitlines()
                  if re.search(rf"= bf16\[16,{extent},16\]\S* fusion\(", ln)
                  and "rt.loop.cache/bokgd,btkd->bkgt" in ln]
        assert len(scores) == 1, extent
        operands = re.findall(r"%([\w.\-]+)",
                              scores[0].split(" fusion(")[1].split(")")[0])
        assert "bf16[8,16,384,16,128]" in [shape_of[o] for o in operands]
    # the guard bites: the straight write brings the copies back
    import sys
    from jax import lax
    monkeypatch.setattr(
        sys.modules["ray_tpu.models.generate"], "_write_prompt",
        lambda cache, l, new: lax.dynamic_update_slice(
            cache, new[None], (l, 0, 0, 0, 0)))
    straight = compiled()
    assert len(re.findall(copied, straight.as_text())) == 2
    assert straight.memory_analysis().temp_size_in_bytes > 1.9 * stacks


def test_sparse_attend_compiles_for_v5e_at_the_cells_block():
    """Mosaic accepts the kernel at the dots3 cell's prefill block (2 x 128
    queries of 128 heads over 2,048 of 32,896 rows of 576) and grants it
    the VMEM it asks for: one batch row's cache and the working set, a
    slice of the next query's fetch unrolled into each slice of a query."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import sparse_attend

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    b, s, h, t, topk = 2, 128, 128, 32896, 2048
    compiled = jax.jit(
        lambda q, keys, at, real: sparse_attend.sparse_attend(
            q, sparse_attend.pack(keys, 512), at, real, v=512,
            scale=192 ** -0.5)).lower(
        shape((b, s, h, 576), jnp.bfloat16),
        shape((b, t, 576), jnp.bfloat16), shape((b, s, topk), jnp.int32),
        shape((b, s, topk), jnp.bool_)).compile()
    hlo = compiled.as_text()
    call, = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert "bf16[2,128,128,512]" in call            # only o_latent leaves
    assert "bf16[2,128,2048,576]" not in hlo        # no copy of the rows
    asked = t * 384 * 4 + sparse_attend.WORK_VMEM_BYTES
    assert asked <= 100 << 20                       # of a core's 128 MiB
    assert f'"size":"{asked}"' in call              # the limit it compiled to
    # what the kernel declares is inside it: the cache, the two buffers of
    # rows (one read while the other is filled), the scores and the value
    # parts of the two halves (that a slice's halves and the blocks fit
    # beside them is Mosaic's to refuse, above)
    assert (t * 384 * 4 + 2 * topk * 384 * 4 + h * topk * 4
            + 2 * topk * 256 * 2) < asked
    # no score and no row among the program's temporaries: the packed
    # cache (2 x 50.5 MB) and what packing it holds
    assert compiled.memory_analysis().temp_size_in_bytes < 320 << 20


def test_a_windows_flash_compiles_for_v5e_at_the_cells_chunk(monkeypatch):
    """Mosaic accepts the forward kernel with a window at the dots3 cell's
    prefill chunk (2 rows x 64 heads of 2,048 queries over 2,560 keys in
    position order, q and k 256 wide, v 128, window 513, the first key a
    traced scalar), in blocks of 512 x 512, and no score leaves it; under a
    gradient the rule is whole: three kernels, each with the band."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import flash

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    chip = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    assert flash._default_blocks(2048, 2560, 256, True, 513) == (512, 512)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def attend(q, k, v, first):
        return flash_attention(q, k, v, causal=True, scale=256 ** -0.5,
                               window=513, first_key=first)

    b, s, t, h = 2, 2048, 2560, 64
    operands = (shape((b, s, h, 256)), shape((b, t, h, 256)),
                shape((b, t, h, 128)), shape((), jnp.int32))
    compiled = jax.jit(attend).lower(*operands).compile()
    hlo = compiled.as_text()
    call, = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert "rt_flash_fwd" in call and "bf16[128,2048,128]" in call
    assert not re.search(r"f32\[[\d,]*,(2560|2568)\]", hlo)     # no scores
    # q, k, v laid out for the kernel, out and the log-sum-exp: no more
    assert compiled.memory_analysis().temp_size_in_bytes < 700 << 20

    def loss(q, k, v, first):
        return jnp.sum(attend(q, k, v, first).astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *operands).compile().as_text()
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(re.search(r"rt_flash_[a-z]+", ln).group() for ln in calls) \
        == ["rt_flash_dkv", "rt_flash_dq", "rt_flash_fwd"]


def test_a_hybrid_generate_keeps_one_state_on_v5e(monkeypatch):
    """Mosaic accepts the gated delta rule's kernels at the Olmo-Hybrid
    cell's widths (30 heads of 96 / 192: the forward kernel on operands
    padded to 128 / 256 with its final state, the one-position kernel on
    the packed state, the convolution's step), and the compiled
    ``generate`` of two periods at the cell's 48 rows x (128 + 384) holds
    ONE state stack: the step kernels' output is the stack they read, and
    no instruction copies an array of its shape. XLA's form of the step
    (cut the slot out, step, write it back) copies the whole stack twice a
    layer and step: the guard bites on it."""
    import re
    from functools import partial

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, generate_with_stats,
                                transformer_init)
    from ray_tpu.ops import gated_delta

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = TransformerConfig(
        vocab_size=12544, d_model=3840, n_layers=8, n_heads=30,
        n_kv_heads=30, d_ff=11008, max_seq=512,
        layer_types=("linear", "linear", "linear", "full"),
        linear_key_heads=30, linear_value_heads=30, linear_key_dim=96,
        linear_value_dim=192, linear_beta_scale=2.0, post_norm_only=True,
        qk_norm_whole=True, partial_rotary_factor=0.0,
        param_dtype=jnp.bfloat16, remat=False)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(partial(transformer_init, cfg=cfg),
                       jax.random.PRNGKey(0)))
    prompts = jax.ShapeDtypeStruct((48, 128), jnp.int32, sharding=one)

    def compiled():
        return jax.jit(partial(
            generate_with_stats, cfg=cfg, max_new_tokens=384)).lower(
                params, prompts).compile()

    monkeypatch.setattr(gated_delta, "_on_tpu", lambda: True)
    state = r"f32\[6,48,15,96,384\]"           # nothing padded: 3 x 128 lanes
    copied = state + r"\S* copy\("
    sound = compiled()
    text = sound.as_text()
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    for name, shape in (("rt_gdn_fwd", "bf16[48,128,7680]"),     # 30 x 256
                        ("rt_gdn_step", "f32[6,48,15,96,384]"),
                        ("rt_gdn_conv_step", "bf16[6,3,48,11520]")):
        calls = [ln for ln in kernels if name in ln]
        assert calls and all(shape in ln for ln in calls), name
    assert "f32[48,30,128,256]" in "".join(
        ln for ln in kernels if "rt_gdn_fwd" in ln)         # the final state
    assert re.search(state, text) and not re.search(copied, text)
    stacks = 4 * 6 * 48 * 15 * 96 * 384 + 2 * 2 * 2 * 48 * 512 * 30 * 128 \
        + 2 * 6 * 3 * 48 * 11520
    assert sound.memory_analysis().temp_size_in_bytes < stacks + (3 << 30)
    # the guard bites: off the kernels, the step copies the stack
    monkeypatch.setattr(gated_delta, "gated_delta_step_at",
                        lambda states, slot, *a: (lambda o, s: (
                            o, jax.lax.dynamic_update_slice(
                                states, s[None], (slot, 0, 0, 0, 0))))(
                            *gated_delta.gated_delta_step(
                                jax.lax.dynamic_index_in_dim(
                                    states, slot, 0, keepdims=False), *a)))
    assert re.search(copied, compiled().as_text())


def test_a_parallel_generate_keeps_one_state_on_v5e(monkeypatch):
    """Mosaic accepts the state-space step at the Falcon-H1 cell's widths
    (32 heads of [256, 128] float32: the delta rule's step kernel without
    its correction on a block of 4 MiB a row, and the convolution's step
    with a bias over 5,120 channels), and the compiled ``generate`` of two
    parallel layers at the cell's 64 rows x (128 + 384) holds ONE state
    stack beside its keys and values: no instruction copies an array of
    the state's shape. (XLA's form of THIS step, cut the slot out, step,
    write it back, compiles without such a copy too, where the delta
    rule's did not: it reads its slot once, with no ``S^T k`` before the
    update. The kernel is kept for the one pass over a row's state: on
    the chip XLA's form takes 4.15 s of the cell's call under
    ``rt.ssd.step`` where the kernel takes 2.92, PERF.md PR 55.)"""
    import re
    from functools import partial

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, generate_with_stats,
                                transformer_init)
    from ray_tpu.ops import flash, gated_delta, ssd

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = TransformerConfig(
        vocab_size=4096, d_model=5120, n_layers=2, n_heads=20, n_kv_heads=4,
        head_width=128, d_ff=21504, max_seq=512, rope_theta=1e11,
        norm_eps=1e-5, layer_types=("parallel",), linear_transition="ssd",
        linear_key_heads=2, linear_value_heads=32, linear_key_dim=256,
        linear_value_dim=128, param_dtype=jnp.bfloat16, remat=False)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(partial(transformer_init, cfg=cfg),
                       jax.random.PRNGKey(0)))
    prompts = jax.ShapeDtypeStruct((64, 128), jnp.int32, sharding=one)

    def compiled():
        return jax.jit(partial(
            generate_with_stats, cfg=cfg, max_new_tokens=384)).lower(
                params, prompts).compile()

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    monkeypatch.setattr(gated_delta, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd, "_on_tpu", lambda: True)
    state = r"f32\[2,64,32,256,128\]"
    copied = state + r"\S* copy\("
    sound = compiled()
    text = sound.as_text()
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    for name, shape in (("rt_ssd_step", "f32[2,64,32,256,128]"),
                        ("rt_gdn_conv_step", "bf16[2,3,64,5120]")):
        calls = [ln for ln in kernels if name in ln]
        assert calls and all(shape in ln for ln in calls), name
    assert not any("rt_gdn_step" in ln for ln in kernels)
    assert re.search(state, text) and not re.search(copied, text)
    stacks = 4 * 2 * 64 * 32 * 256 * 128 + 2 * 2 * 2 * 64 * 512 * 4 * 128 \
        + 2 * 2 * 3 * 64 * 5120
    assert sound.memory_analysis().temp_size_in_bytes < stacks + (3 << 30)


# the three cells' expert layers and a decode step's of the dots3 cell:
# (tokens, D, F, experts, held, top_k, scoring, under a gradient)
EXPERT_LAYERS = {
    "dots3_prefill_chunk": (4096, 5120, 1536, 256, 32, 8, "sigmoid", False),
    "dots3_decode_step": (2, 5120, 1536, 256, 32, 8, "sigmoid", False),
    "qwen3next_step": (16384, 2048, 512, 512, 32, 10, "softmax", True),
}


@pytest.mark.parametrize("which", sorted(EXPERT_LAYERS))
def test_expert_layer_walk_compiles_for_v5e(which):
    """The expert layer at the cells' own sizes, compiled for a v5e: no
    gather, select, multiply, cast, add or scatter-add over the whole
    [laid, D] buffer outside a loop's body (tests/test_moe_walk.py holds its
    numbers, and this check, at a toy size on the CPU, where a scatter is a
    loop of the compiler's own), a decode step's buffer passed over with no
    loop, and every grouped matmul's kernel under ``rt.moe.experts`` by the
    benchmark's own reading of the text: the loops take what the kernels
    wrote as operands, which carry no scope."""
    import os
    import sys
    from types import SimpleNamespace

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import moe
    from test_moe_walk import passes_over
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if checkout not in sys.path:
        sys.path.insert(0, checkout)
    from benchmark import trace_scopes

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    one = SingleDeviceSharding(topo.devices[0])
    n, d, f, experts, held, top_k, scoring, grad = EXPERT_LAYERS[which]
    cfg = SimpleNamespace(
        expert_top_k=top_k, held=held, num_experts=experts, first_expert=0,
        router_scoring=scoring, norm_topk_prob=True,
        routed_scaling_factor=1.0)
    pd = jnp.float32 if grad else jnp.bfloat16
    like = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)
    p = {"router": like((d, experts), pd), "w1": like((held, d, f), pd),
         "w3": like((held, d, f), pd), "w2": like((held, f, d), pd)}
    if scoring == "sigmoid":
        p["router_bias"] = like((experts,), pd)
    h = like((1, n, d), jnp.bfloat16)

    def loss(p, h):
        y, stats = jax.checkpoint(lambda p, h: moe.moe_apply(cfg, p, h))(p, h)
        return (y.astype(jnp.float32) ** 2).mean(), stats
    fn = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True) if grad \
        else lambda p, h: moe.moe_apply(cfg, p, h)
    text = jax.jit(fn).lower(p, h).compile().as_text()

    rows = moe.buffer_rows(cfg, n)
    laid = -(-rows // moe.ROW_MULTIPLE) * moe.ROW_MULTIPLE
    loops = [ln for ln in text.splitlines()
             if " while(" in ln and "rt.moe.experts" in ln]
    if n <= moe.FEW_TOKENS:
        assert moe.walk_block(laid, d) == laid and not loops
    else:
        assert laid // moe.walk_block(laid, d) >= 16
        assert len(loops) == (5 if grad else 2)     # forward twice: remat
        assert passes_over(text, (laid, d)) == []
    scopes = trace_scopes.scope_map(text)
    kernels = [trace_scopes.INSTRUCTION.match(ln).group(1)
               for ln in text.splitlines() if trace_scopes.INHERITS[0] in ln]
    assert len(kernels) >= 3
    assert {scopes.get(k) for k in kernels} == {"rt.moe.experts"}

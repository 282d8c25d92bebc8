"""Multi-chip SPMD LM training on a dp x tp mesh, always on 8 virtual CPU
devices — it never opens a chip (run: python
examples/02_train_lm_multichip.py). chip_smoke.py trains on real ones."""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax

# Whatever JAX_PLATFORMS says: the mesh below is 8 virtual CPU devices.
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import TransformerConfig
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.train import make_lm_train_step

mesh = build_mesh(MeshSpec(dp=4, tp=2))       # 8 devices: 4-way data, 2-way tensor
cfg = TransformerConfig(vocab_size=1024, d_model=128, n_layers=2, n_heads=4,
                        max_seq=128, attn_impl="reference", dtype=jnp.float32)
init_fn, step_fn, place_batch = make_lm_train_step(cfg, mesh)
state = init_fn(jax.random.PRNGKey(0))

rng = np.random.default_rng(0)
batch = place_batch({"tokens": jnp.asarray(
    rng.integers(0, 1024, (8, 128)), jnp.int32)})
for step in range(5):
    state, metrics = step_fn(state, batch)
    print(f"step {step}: loss={float(metrics['loss']):.4f}")
print("param sharding example:",
      jax.tree_util.tree_leaves(state.params)[0].sharding)

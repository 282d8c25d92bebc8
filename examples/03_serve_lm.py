"""Serve the in-tree LM with KV-cache generation over HTTP (run:
JAX_PLATFORMS=cpu python examples/03_serve_lm.py)."""
import json
import urllib.request

import ray_tpu as rt
from ray_tpu import serve

rt.init(num_cpus=8)  # explicit size: actors HOLD their CPU, so
# leave headroom for tasks scheduled alongside them


@serve.deployment(route_prefix="/generate", init_grace_s=300.0)
class LM:
    def __init__(self):
        from functools import partial

        import jax
        import jax.numpy as jnp

        from ray_tpu.models import (TransformerConfig, generate,
                                    transformer_init)
        self.jnp = jnp
        cfg = TransformerConfig(vocab_size=258, d_model=64, n_layers=2,
                                n_heads=4, n_kv_heads=2, max_seq=128,
                                attn_impl="reference", dtype=jnp.float32)
        self.params = transformer_init(jax.random.PRNGKey(0), cfg)
        # generate keeps its KV cache as one stacked pair [loop steps x L,
        # B, T_max, KVH, D]: a slot a layer, and for a looped stack
        # (cfg.loop_steps > 1) a slot for every (loop step, layer), since
        # a step's query sees the keys that step's own state gave.
        self._gen = jax.jit(partial(generate, cfg=cfg, max_new_tokens=16,
                                    temperature=0.8, top_k=40))

    def __call__(self, prompt=None):
        import numpy as np

        from ray_tpu.data import ByteTokenizer
        tok = ByteTokenizer()
        ids = tok.encode(prompt or "hello")[:-1]      # keep it open-ended
        arr = self.jnp.asarray(np.asarray([ids], np.int32))
        out = np.asarray(self._gen(self.params, arr))[0]
        return {"prompt": prompt, "generated_tokens": out.tolist(),
                "text": tok.decode(out)}


handle = serve.run(LM.bind(), http_host="127.0.0.1")
req = urllib.request.Request(
    f"http://127.0.0.1:{handle.http_port}/generate",
    data=json.dumps({"prompt": "tpu"}).encode(),
    headers={"Content-Type": "application/json"})
print(json.loads(urllib.request.urlopen(req, timeout=120).read()))
serve.shutdown()
rt.shutdown()

"""TPU-tuned ops: attention family, fused layers, Pallas kernels.

The reference has no in-tree attention/sequence-parallel kernels (SURVEY.md
§5 "Long-context / sequence parallelism: absent"); these are first-class
here. Public surface:

- attention: reference softmax attention + memory-efficient blockwise
  (online-softmax lax.scan) attention, differentiable on any backend.
- flash (Pallas): fused MXU flash-attention kernels for TPU.
- sparse_attend (Pallas): latent attention of each query over the cached
  rows an indexer selected for it, the rows fetched once from a cache held
  in VMEM, a query's beside the products of the query before it
  (models/latent.py calls it for a prefill block).
- ring_attention: sequence parallelism over an ICI ring (shard_map +
  ppermute), blockwise-causal.
- ulysses: all-to-all sequence parallelism (seq-sharded <-> head-sharded).
"""

from ray_tpu.ops.attention import (
    attention_reference,
    blockwise_attention,
    mha,
)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses import ulysses_attention

__all__ = [
    "attention_reference",
    "blockwise_attention",
    "mha",
    "ring_attention",
    "ulysses_attention",
]

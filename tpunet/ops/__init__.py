"""TPU kernels and attention ops for tpunet's hot paths.

- ``attention``: dense / blockwise / ring / Ulysses attention. Ring
  (K/V shards rotate over a mesh axis via ppermute with online-softmax
  accumulation) and Ulysses (all-to-all head resharding around a
  blockwise core) are the sequence-parallel primitives backing
  long-context support in the attention-based model families.
- ``flash``: Pallas TPU flash-attention kernels — the fused MXU form of
  the same online-softmax math (scores never leave VMEM), forward and
  backward, and the grouped / windowed prefill the serve engine calls.
- ``paged_decode``: Pallas TPU kernel for the engine's width-1 decode
  step over a paged K/V pool.
- ``partition``: splits a kernel call over a mesh with ``shard_map``.
- ``vocab_ce``: cross-entropy over a vocabulary sharded on the model
  axis.
"""

from tpunet.ops.attention import (blockwise_attention, dense_attention,
                                  ring_attention, ring_self_attention,
                                  ulysses_attention, ulysses_self_attention)
from tpunet.ops.flash import flash_attention

__all__ = [
    "blockwise_attention",
    "dense_attention",
    "flash_attention",
    "ring_attention",
    "ring_self_attention",
    "ulysses_attention",
    "ulysses_self_attention",
]

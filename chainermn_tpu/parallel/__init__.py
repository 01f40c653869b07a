"""Parallelism strategies beyond the reference's scope.

The reference (2018-era ChainerMN) ships DP, coarse model parallelism and the
``alltoall`` primitive (SURVEY.md §2.3); long-context sequence/context
parallelism postdates it.  This package supplies the TPU-native versions as
first-class citizens:

* :mod:`ring_attention` — ring/context parallelism: blockwise attention with
  K/V rotating around the mesh ring via ``ppermute`` (Liu et al., Ring
  Attention; flash-style online softmax).
* :mod:`ulysses` — all-to-all sequence parallelism (DeepSpeed-Ulysses style):
  re-shard sequence↔heads with ``all_to_all`` around any local attention.
* :mod:`zigzag` — load-balanced CAUSAL context parallelism: rank i owns
  sequence chunks (i, 2S-1-i), equalizing causal work across the ring
  (the plain ring leaves ~half the flops idle under causal masking).
* :mod:`moe` — expert parallelism: capacity-based top-k token dispatch over an
  ``expert`` mesh axis via ``all_to_all`` (built on the same primitive the
  reference exposed as ``chainermn.functions.alltoall``).
* :mod:`held_experts` — that layer minus its exchange, dropless: a chip told
  which contiguous range of a layer's experts it holds routes over all of
  them, sorts the held (token, choice) pairs by expert and runs a grouped
  matmul over them (one chip's share of an expert-parallel group).
"""

from chainermn_tpu.parallel.ring_attention import (
    ring_attention,
    ring_flash_self_attention,
    ring_self_attention,
)
from chainermn_tpu.parallel.ulysses import ulysses_attention
from chainermn_tpu.parallel.zigzag import (
    zigzag_attention,
    zigzag_ring_self_attention,
    zigzag_shard,
    zigzag_unshard,
)
from chainermn_tpu.parallel.moe import MoELayer, moe_combine, moe_dispatch
from chainermn_tpu.parallel.held_experts import (
    held_experts_ffn,
    held_range,
    sigmoid_topk_route,
)

__all__ = [
    "ring_attention",
    "ring_flash_self_attention",
    "ring_self_attention",
    "ulysses_attention",
    "zigzag_attention",
    "zigzag_ring_self_attention",
    "zigzag_shard",
    "zigzag_unshard",
    "moe_dispatch",
    "moe_combine",
    "MoELayer",
    "held_experts_ffn",
    "held_range",
    "sigmoid_topk_route",
]

"""Paged single-position decode attention (``ops/decode_attention.py``),
one call: every live slot reads its own context out of the block pool.

Needed: each resident (position, kv head) row of K and of V is read once;
each is used by the ``heads / kv_heads`` query heads of its group in one
multiply-add per element for the scores and one for the values.  Reading
whole blocks past a context's end, or parking blocks of idle slots, is the
kernel's overhead, not the algorithm's need."""

from __future__ import annotations

from typing import Dict


def call(context_tokens: float, slots: int, heads: int, kv_heads: int,
         head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """``context_tokens``: resident positions summed over the live slots."""
    kv = 2.0 * context_tokens * kv_heads * head_dim * itemsize
    qo = 2.0 * slots * heads * head_dim * itemsize
    return {"flops": 4.0 * context_tokens * heads * head_dim,
            "bytes": kv + qo}

"""Causal flash attention (``ops/flash_attention.py``), one call on
(B, T, H, Dh) queries and (B, T, KH, Dh) keys/values.

One "matmul unit" is one T x T x Dh product over the causal half:
``2 * Dh * T(T+1)/2`` FLOPs per batch row and query head.  The forward needs
two (scores; weighted values).  The backward needs five (scores again, dP,
dV, dK, dQ) however the program splits it into kernels, so its two kernels
(dK/dV and dQ) are judged together."""

from __future__ import annotations

from typing import Dict


def unit_flops(batch: int, heads: int, seq: int, head_dim: int) -> float:
    return 2.0 * head_dim * (seq * (seq + 1) / 2.0) * batch * heads


def forward(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int,
            itemsize: int = 2) -> Dict[str, float]:
    q = batch * seq * heads * head_dim * itemsize
    kv = 2 * batch * seq * kv_heads * head_dim * itemsize
    lse = batch * seq * heads * 4
    return {"flops": 2 * unit_flops(batch, heads, seq, head_dim),
            "bytes": float(q + kv + q + lse)}


def backward(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int,
             itemsize: int = 2) -> Dict[str, float]:
    q = batch * seq * heads * head_dim * itemsize
    kv = 2 * batch * seq * kv_heads * head_dim * itemsize
    lse = 2 * batch * seq * heads * 4  # lse and delta
    # reads q, k, v, o, do, lse, delta; writes dq, dk, dv
    return {"flops": 5 * unit_flops(batch, heads, seq, head_dim),
            "bytes": float(3 * q + kv + lse + q + kv)}

"""Model FLOPs of a dense decoder-only LM, per token, forward + backward.

What the forward and backward passes *require*: 2 FLOPs per multiply-add,
backward twice the forward, nothing for rematerialised forwards.  Matmuls
only (projections, feed-forward, head, attention scores and values);
elementwise work, norms, softmax and the optimizer are left out, as is usual
for MFU.  The head is counted as the program has it: a separate (untied)
``lm_head``; the embedding lookup is a gather, not a matmul."""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(m: Dict[str, Any]) -> int:
    """Parameters that sit in a matmul applied to every token."""
    D, H, F, V, L = (m["d_model"], m["n_heads"], m["d_ff"], m["vocab"],
                     m["n_layers"])
    KH = m.get("n_kv_heads") or H
    Dh = D // H
    attn = D * H * Dh + 2 * D * KH * Dh + H * Dh * D
    return L * (attn + 2 * D * F) + D * V


def attention_flops_per_token(m: Dict[str, Any], seq: int, causal: bool = True) -> float:
    """Forward FLOPs per token of scores + weighted values, all layers:
    2 matmuls x 2 FLOPs x head_dim x heads x (keys seen per query)."""
    D, H, L = m["d_model"], m["n_heads"], m["n_layers"]
    keys = (seq + 1) / 2.0 if causal else float(seq)
    return L * 2 * 2 * (D // H) * H * keys


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    fwd = 2.0 * matmul_params(m) + attention_flops_per_token(m, seq)
    return 3.0 * fwd

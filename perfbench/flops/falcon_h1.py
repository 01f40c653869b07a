"""What the Falcon-H1 stack (``HybridLM``'s ``F`` layers) needs, from the
shapes.

Training — ``train_flops_per_token(model, seq_len)``, forward + backward,
every matmul the model *needs*, 2 FLOPs a multiply-add, backward twice the
forward, nothing elementwise: a layer's Mamba-2 mixer (input and output
projections, the four products of the chunked scan, as ``nemotron_h.py``
counts an ``M`` layer), its attention (q, k, v and output projections, scores
and weighted values over the keys a causal query sees), its SwiGLU (three
matmuls), and the untied head.  No cell trains this model; the count is what
``train_mfu`` would multiply a rate by.

Serving — ``tick_bytes(model, live, context_tokens)``: the bytes one
decode tick must move, which is what bounds it: every weight once (the
embedding is a gather of ``live`` rows, not a stream), the live slots'
recurrent state read and written, their convolution tails, and the keys and
values of the resident positions read once.
"""

from __future__ import annotations

from typing import Any, Dict

F32, BF16 = 4, 2


def layer_flops_per_token(m: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward FLOPs a token of one layer's three parts, and of the head."""
    D = m["d_model"]
    H, P, G, N = m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"]
    inner, bc, Q = H * P, G * N, min(m["ssm_chunk"], seq)
    mamba = (2.0 * D * (2 * inner + 2 * bc + H) + 2.0 * inner * D
             + 2.0 * Q * N * G          # C . B^T
             + 2.0 * Q * P * H          # scores . values
             + 2.0 * N * P * H          # the chunk's addition to the state
             + 2.0 * N * P * H)         # the carried state's contribution
    A, KH, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    attention = (2.0 * D * (A + 2 * KH) * Dh + 2.0 * A * Dh * D
                 + 2 * 2.0 * Dh * A * (seq + 1) / 2.0)
    return {"mamba": mamba, "attention": attention,
            "ffn": 3 * 2.0 * D * m["d_ff"], "head": 2.0 * D * m["vocab"]}


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    per = layer_flops_per_token(m, seq)
    layer = per["mamba"] + per["attention"] + per["ffn"]
    return 3.0 * (m["n_layers"] * layer + per["head"])


def layer_params(m: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer's matrices, by part (the vectors — norms,
    convolution, ``dt_bias``, ``A_log``, ``D`` — are a few thousand)."""
    D = m["d_model"]
    H, P, G, N = m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"]
    inner, bc = H * P, G * N
    A, KH, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"in_proj": D * (2 * inner + 2 * bc + H), "out_proj": inner * D,
            "attention": D * (A + 2 * KH) * Dh + A * Dh * D,
            "ffn": 3 * D * m["d_ff"]}


def state_bytes_per_slot(m: Dict[str, Any], itemsize: int = BF16) -> Dict[str, int]:
    """One slot's recurrent state (float32) and convolution tail (the
    compute dtype), all layers."""
    H, P, G, N = m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"]
    width = H * P + 2 * G * N
    return {"ssm": m["n_layers"] * H * P * N * F32,
            "conv": m["n_layers"] * (m["conv_kernel"] - 1) * width * itemsize}


def kv_bytes_per_token(m: Dict[str, Any], itemsize: int = BF16) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def tick_bytes(m: Dict[str, Any], live: float, context_tokens: float,
               itemsize: int = BF16) -> Dict[str, float]:
    """Bytes one decode tick of ``live`` slots over ``context_tokens``
    resident positions must move."""
    weights = (m["n_layers"] * sum(layer_params(m).values())
               + m["d_model"] * m["vocab"]) * itemsize
    per_slot = state_bytes_per_slot(m, itemsize)
    return {"weights": float(weights),
            "state": 2.0 * live * (per_slot["ssm"] + per_slot["conv"]),
            "kv": float(context_tokens * kv_bytes_per_token(m, itemsize))}

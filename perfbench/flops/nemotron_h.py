"""Model FLOPs of the Nemotron-H stack (``HybridLM``), per token, forward +
backward: every matmul the model *needs*, 2 FLOPs a multiply-add, backward
twice the forward, nothing for rematerialised forwards, nothing elementwise.

* ``M``: the input projection to ``[z | xBC | dt]``, the output projection,
  and the four products of the chunked scan (``C.B^T`` a group; scores times
  values, the chunk's addition to the state, the carried state's
  contribution, a head) — the chunked form is what a chip needs; the
  recurrence over time needs fewer FLOPs and no matmul.
* ``*``: q, k, v and output projections; scores and weighted values over
  the keys a causal query sees.
* ``E``: the router over every expert of the layer, the shared expert, and
  the routed pairs a token sends to the experts held here:
  ``experts_per_tok * held / all`` of them on average.
* the untied head over the vocabulary held; the embedding is a gather.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_flops_per_token(m: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward FLOPs a token of one layer of each kind, and of the head."""
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
    n_all = m["experts_held"] * m["ep_of"]
    pairs = m["experts_per_tok"] * m["experts_held"] / n_all
    experts = (2.0 * D * n_all + 2 * 2.0 * D * m["d_shared"]
               + pairs * 2 * 2.0 * D * m["d_expert"])
    return {"M": mamba, "*": attention, "E": experts,
            "head": 2.0 * D * m["vocab"]}


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    per = layer_flops_per_token(m, seq)
    kinds = m["layer_kinds"][:m["n_layers"]]
    return 3.0 * (sum(per[k] for k in kinds) + per["head"])

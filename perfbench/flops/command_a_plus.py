"""What the Command A+ stack (``HybridLM``'s ``W`` / ``G`` layers) needs, from
the shapes.

Training — ``train_flops_per_token(model, seq_len)``, forward + backward,
every matmul the model *needs*, 2 FLOPs a multiply-add, backward twice the
forward, nothing elementwise: a layer's attention (q, k, v and output
projections; scores and weighted values over the keys a query sees — the last
``window`` of them in a ``W`` layer, all before it in a ``G`` layer), its
router, the experts a token takes of those this chip holds
(``experts_per_tok / ep_of`` on average, three matmuls each), the shared
experts (three matmuls of width ``d_shared``), and the tied head.  No cell
trains this model; the count is what ``train_mfu`` would multiply a rate by.

Serving — ``tick_bytes(model, live, context_tokens)``: the bytes one decode
tick must move, which is what bounds it: every weight once (the held experts'
among them: an expert no row chose still has a tile, ``ops/grouped_matmul.py
aligned_groups``), the full layers' resident keys and values, and the window
layers' — at most ``window`` positions a slot.
"""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2


def layer_params(m: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer's matrices, by part."""
    D, A, KH, Dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"attention": D * (A + 2 * KH) * Dh + A * Dh * D,
            "router": D * m["experts_held"] * m["ep_of"],
            "experts": m["experts_held"] * 3 * D * m["d_expert"],
            "shared": 3 * D * m["d_shared"]}


def n_params(m: Dict[str, Any]) -> int:
    """As cut: the layers' matrices and the embedding once (tied)."""
    return (m["n_layers"] * sum(layer_params(m).values())
            + m["vocab"] * m["d_model"])


def layer_flops_per_token(m: Dict[str, Any], seq: int, kind: str
                          ) -> Dict[str, float]:
    D, A, KH, Dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    seen = (seq + 1) / 2.0
    if kind == "W":
        w = min(m["window"], seq)
        seen = w - w * (w - 1) / (2.0 * seq)  # mean of min(i + 1, w)
    return {"attention": (2.0 * D * (A + 2 * KH) * Dh + 2.0 * A * Dh * D
                          + 2 * 2.0 * Dh * A * seen),
            "router": 2.0 * D * m["experts_held"] * m["ep_of"],
            "experts": (m["experts_per_tok"] / m["ep_of"]
                        * 3 * 2.0 * D * m["d_expert"]),
            "shared": 3 * 2.0 * D * m["d_shared"]}


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    layers = sum(sum(layer_flops_per_token(m, seq, kind).values())
                 for kind in m["layer_kinds"][:m["n_layers"]])
    return 3.0 * (layers + 2.0 * m["d_model"] * m["vocab"])


def kv_bytes_per_token(m: Dict[str, Any], itemsize: int = BF16) -> int:
    """One layer's keys and values of one position."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def tick_bytes(m: Dict[str, Any], contexts, itemsize: int = BF16
               ) -> Dict[str, float]:
    """Bytes one decode tick must move for live slots of ``contexts``
    resident positions each."""
    kinds = m["layer_kinds"][:m["n_layers"]]
    per = kv_bytes_per_token(m, itemsize)
    return {"weights": float(n_params(m) * itemsize),
            "kv_full": float(kinds.count("G") * per * sum(contexts)),
            "kv_window": float(kinds.count("W") * per
                               * sum(min(c, m["window"]) for c in contexts))}

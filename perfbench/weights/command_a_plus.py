"""The weight tree of ``HybridLM`` with ``W`` / ``G`` layers (the Command A+
parallel block: attention and a gated mixture of experts side by side of one
LayerNorm), written down from the configuration's fields; a tier-1 test pins
it against the program's own tree at a small size.

**The init is chosen so that the check's limits test something** (PR 44's
lesson: under a normal 0.02 draw the logits have a spread of ~0.01).  Every
matrix is drawn normal with ``std = gain / sqrt(fan_in)``, so a unit-RMS input
gives an output of RMS ``gain`` at any width (the rehearsal preset has the
published model's statistics), and the gains are set so that

* **logits have a spread of one through the tied head**: ``logits =
  LN(h) . E^T`` with ``logit_scale`` 1, so the embedding's rows are drawn at
  ``1 / sqrt(d_model)`` (a LayerNorm's output has RMS one).  The embedding
  therefore enters the residual stream small (RMS 1/64 at the published
  width) and the first layer's branches carry the token on, which a LayerNorm
  in front of both does not mind;
* attention scores have a spread of 1.2 (``q`` gain 1.1, the fused ``k | v``
  kernel 1.1: ``q . k / sqrt(head_dim)`` of independent rows); the output
  projection's gain 16 stands against a softmax-weighted mean of values, whose
  RMS over the ~1,000 effective keys of a 4,096-wide window is ~0.035: the
  branch enters the stream at ~0.5;
* a gated expert's ``silu(gate) * up`` has RMS 0.6 (``gate`` and ``up`` gain
  1); ``down`` gain 4 puts the routed sum of eight experts with weights that
  add to one at ~0.85 — of which this chip's share, one chosen expert in
  eight held, is ~0.3 a token; the shared experts' fused ``down`` (fan-in
  ``n_shared x`` an expert's width) has gain 4, divided by ``n_shared`` by the
  model: ~0.6;
* the router's logits have a spread of 1.5, so the sigmoid scores spread over
  (0.1, 0.9) and the eighth and ninth largest of 128 lie ~0.01 apart — no
  ties to a float32 router.

Norm scales get a small spread so that a path that drops one is seen.
"""

from __future__ import annotations

import math
from typing import Any, Dict

KINDS = "WG"
GAINS = {"q": 1.1, "kv": 1.1, "proj": 16.0, "router": 1.5,
         "experts_gate_up": 1.0, "experts_down": 4.0,
         "shared_gate_up": 1.0, "shared_down": 4.0}


def param_specs(m: Dict[str, Any]) -> Dict[str, Any]:
    D, V, L, F = m["d_model"], m["vocab"], m["n_layers"], m["d_expert"]
    kinds = m["layer_kinds"][:L]
    if set(kinds) - set(KINDS) or len(kinds) != L:
        raise ValueError(f"layer_kinds {m['layer_kinds']!r}: this tree is of "
                         f"{L} layers of {KINDS!r}")
    A, KH, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    E, S = m["experts_held"], m["d_shared"]
    n_all = E * m["ep_of"]

    def matrix(name, shape, fan_in):
        return (shape, 0.0, GAINS[name] / math.sqrt(fan_in))

    block = {
        "norm": ((D,), 1.0, 0.05),
        "q": {"kernel": matrix("q", (D, A, Dh), D)},
        "kv": {"kernel": matrix("kv", (D, 2, KH, Dh), D)},
        "proj": {"kernel": matrix("proj", (A, Dh, D), A * Dh)},
        "router": matrix("router", (D, n_all), D),
        "experts_gate_up": matrix("experts_gate_up", (E, D, 2 * F), D),
        "experts_down": matrix("experts_down", (E, F, D), F),
        "shared_gate_up": {"kernel": matrix("shared_gate_up", (D, 2 * S), D)},
        "shared_down": {"kernel": matrix("shared_down", (S, D), S)},
    }
    tree: Dict[str, Any] = {f"block_{i}": dict(block) for i in range(L)}
    tree["embed"] = {"embedding": ((V, D), 0.0, 1.0 / math.sqrt(D))}
    tree["norm_f"] = ((D,), 1.0, 0.05)
    return tree

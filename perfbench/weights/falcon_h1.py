"""The weight tree of ``HybridLM`` with ``F`` layers (the Falcon-H1 block:
Mamba-2 and attention side by side, then a SwiGLU), written down from the
configuration's fields; a tier-1 test pins it against the program's own tree
at a small size.

**The init is chosen against the muP multipliers**, not the usual normal
0.02: under them a 0.02 draw gives attention scores of ~0 and logits with a
spread of ~0.01, and a comparison of logits would test nothing.  Every matrix
is drawn normal with ``std = gain / sqrt(fan_in)``, so a unit-RMS input gives
an output of RMS ``gain`` at any width (the rehearsal preset has the published
model's statistics), and the gains are set so that after its multiplier

* the embedding enters the residual stream at RMS 1
  (``gain = 1 / embedding_multiplier``);
* ``in_proj`` (gain 24, input times ``ssm_in_multiplier`` 0.25, segments times
  ``ssm_multipliers``) gives z 2.1, x 1.5, B 1.1, C 3.0 and a ``dt`` term of
  2.1 around ``dt_bias`` (normal -4.6 +- 0.5): steps of ~1e-3 .. 1e-1 after
  the softplus; ``A_log`` normal log 4 +- 0.7, decays ``A`` of -1 .. -16, as
  the family's code draws both ranges; ``D`` 1 +- 0.1; convolution taps
  normal 0.3 (PyTorch's default for a fan-in of 4 is uniform +-0.5), bias 0.1;
* attention scores have a spread of ``16 * 8 * key_multiplier`` = 1.4 (q gain
  16, the fused k/v kernel gain 8);
* each branch enters the residual stream at an RMS of order one (meant
  ~0.5; on the chip the stream reads RMS 6.9 after six layers): ``out_proj`` gain 5.66
  times ``ssm_out_multiplier`` 0.0884; ``proj`` gain 14 on a weighted mean of
  values times ``attention_out_multiplier`` 0.0375; the FFN's gate at 1 before
  the SiLU (gain 5.66 times 0.1768), ``up`` gain 8, ``down`` gain 9.3 times
  ``mlp_multipliers[1]`` 0.01116;
* logits have a spread of 1: ``lm_head`` gain 128 times
  ``lm_head_multiplier`` 1/128.

Norm scales get a small spread so that a path that drops one is seen.
"""

from __future__ import annotations

import math
from typing import Any, Dict

KIND = "F"
GAINS = {"in_proj": 24.0, "out_proj": 5.66, "q": 16.0, "kv": 8.0,
         "proj": 14.0, "gate": 5.66, "up": 8.0, "down": 9.3,
         "lm_head": 128.0}


def param_specs(m: Dict[str, Any]) -> Dict[str, Any]:
    D, V, L, F = m["d_model"], m["vocab"], m["n_layers"], m["d_ff"]
    kinds = m["layer_kinds"][:L]
    if set(kinds) != {KIND} or len(kinds) != L:
        raise ValueError(f"layer_kinds {m['layer_kinds']!r}: this tree is of "
                         f"{L} {KIND!r} layers")
    H, P = m["ssm_heads"], m["ssm_head_dim"]
    inner, bc = H * P, m["ssm_groups"] * m["ssm_state"]
    A, KH, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]

    def norm(n):
        return ((n,), 1.0, 0.05)

    def matrix(name, shape, fan_in):
        return (shape, 0.0, GAINS[name] / math.sqrt(fan_in))

    block = {
        "norm": norm(D),
        "in_proj": {"kernel": matrix("in_proj", (D, 2 * inner + 2 * bc + H), D)},
        "conv_kernel": ((m["conv_kernel"], inner + 2 * bc), 0.0, 0.3),
        "conv_bias": ((inner + 2 * bc,), 0.0, 0.1),
        "dt_bias": ((H,), -4.6, 0.5),
        "A_log": ((H,), math.log(4.0), 0.7),
        "D": ((H,), 1.0, 0.1),
        "gate_norm": norm(inner),
        "out_proj": {"kernel": matrix("out_proj", (inner, D), inner)},
        "q": {"kernel": matrix("q", (D, A, Dh), D)},
        "kv": {"kernel": matrix("kv", (D, 2, KH, Dh), D)},
        "proj": {"kernel": matrix("proj", (A, Dh, D), A * Dh)},
        "norm_ff": norm(D),
        "gate": {"kernel": matrix("gate", (D, F), D)},
        "up": {"kernel": matrix("up", (D, F), D)},
        "down": {"kernel": matrix("down", (F, D), F)},
    }
    tree: Dict[str, Any] = {f"block_{i}": dict(block) for i in range(L)}
    tree["embed"] = {"embedding": ((V, D), 0.0, 1.0 / m["embedding_multiplier"])}
    tree["norm_f"] = norm(D)
    tree["lm_head"] = {"kernel": matrix("lm_head", (D, V), D)}
    return tree

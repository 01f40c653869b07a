"""The weight tree of ``HybridLM`` (the Nemotron-H stack), written down from
the configuration's fields; a tier-1 test pins it against the program's own
tree at a small size.

Normal 0.02 everywhere a matrix projects (the published
``initializer_range``), the projections back into the residual stream
divided by sqrt(layers) (``rescale_prenorm_residual``).  The state-space
layer's own leaves are drawn where the published ``time_step_min`` / ``max``
and the family's initialisation put them: ``dt_bias`` around the inverse
softplus of 0.01 (steps of 0.002-0.05), ``A_log`` around log 4 (decays ``a``
of -1.5 to -11), ``D`` around 1, convolution taps of 0.3 (PyTorch's default
for a fan-in of 4 is uniform +-0.5).  Norm scales get a small spread so that
a path that drops one is seen.
"""

from __future__ import annotations

import math
from typing import Any, Dict

KINDS = "M*E"


def param_specs(m: Dict[str, Any]) -> Dict[str, Any]:
    D, V, L = m["d_model"], m["vocab"], m["n_layers"]
    kinds = m["layer_kinds"][:L]
    w = 0.02
    res = w / math.sqrt(L)
    H, P = m["ssm_heads"], m["ssm_head_dim"]
    inner, bc = H * P, m["ssm_groups"] * m["ssm_state"]
    n_all = m["experts_held"] * m["ep_of"]

    def norm(n):
        return ((n,), 1.0, w)

    def block(kind):
        if kind == "M":
            return {
                "norm": norm(D),
                "in_proj": {"kernel": ((D, 2 * inner + 2 * bc + H), 0.0, w)},
                "conv_kernel": ((m["conv_kernel"], inner + 2 * bc), 0.0, 0.3),
                "conv_bias": ((inner + 2 * bc,), 0.0, w),
                "dt_bias": ((H,), -4.6, 0.8),
                "A_log": ((H,), math.log(4.0), 0.5),
                "D": ((H,), 1.0, 0.1),
                "gate_norm": norm(inner),
                "out_proj": {"kernel": ((inner, D), 0.0, res)},
            }
        if kind == "*":
            A, KH, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
            return {
                "norm": norm(D),
                "q": {"kernel": ((D, A, Dh), 0.0, w)},
                "kv": {"kernel": ((D, 2, KH, Dh), 0.0, w)},
                "proj": {"kernel": ((A, Dh, D), 0.0, res)},
            }
        if kind == "E":
            E, F, S = m["experts_held"], m["d_expert"], m["d_shared"]
            return {
                "norm": norm(D),
                "router": ((D, n_all), 0.0, w),
                "experts_up": ((E, D, F), 0.0, w),
                "experts_down": ((E, F, D), 0.0, res),
                "shared_up": {"kernel": ((D, S), 0.0, w)},
                "shared_down": {"kernel": ((S, D), 0.0, res)},
            }
        raise ValueError(f"layer kind {kind!r}: expected one of {KINDS!r}")

    tree: Dict[str, Any] = {f"block_{i}": block(k) for i, k in enumerate(kinds)}
    tree["embed"] = {"embedding": ((V, D), 0.0, w)}
    tree["norm_f"] = norm(D)
    tree["lm_head"] = {"kernel": ((D, V), 0.0, w)}
    return tree

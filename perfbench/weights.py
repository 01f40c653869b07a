"""Seeded weights for ``TransformerLM``-shaped configurations.

The benchmark makes the weights, not the program: one jitted call draws every
leaf on the device from ``--seed`` in the dtype it is served or trained in.
The tree (names, shapes) is written down here from the configuration's fields
and is what both the program and the plain reference are handed; a tier-1
test pins it against ``TransformerLM.init``'s own tree at a tiny size.

Scales follow GPT-2's published initialisation (normal 0.02, residual
projections divided by sqrt(2·layers)); biases and LayerNorm parameters are
given small non-zero values so that a path that drops one is seen.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

Spec = Tuple[Tuple[int, ...], float, float]  # shape, mean, std


def param_specs(m: Dict[str, Any]) -> Dict[str, Any]:
    """Nested ``{name: (shape, mean, std)}`` for the model fields ``m``
    (``vocab, n_layers, d_model, n_heads, n_kv_heads, d_ff, max_len,
    pos_enc``)."""
    D, H, F, V = m["d_model"], m["n_heads"], m["d_ff"], m["vocab"]
    KH = m.get("n_kv_heads") or H
    Dh = D // H
    L = m["n_layers"]
    w, b = 0.02, 0.01
    res = w / float(np.sqrt(2.0 * L))

    def ln():
        return {"scale": ((D,), 1.0, w), "bias": ((D,), 0.0, b)}

    def block():
        out = {
            "ln1": ln(), "ln2": ln(),
            "proj": {"kernel": ((H, Dh, D), 0.0, res), "bias": ((D,), 0.0, b)},
            "ff1": {"kernel": ((D, F), 0.0, w), "bias": ((F,), 0.0, b)},
            "ff2": {"kernel": ((F, D), 0.0, res), "bias": ((D,), 0.0, b)},
        }
        if KH == H:
            out["qkv"] = {"kernel": ((D, 3, H, Dh), 0.0, w),
                          "bias": ((3, H, Dh), 0.0, b)}
        else:
            out["q"] = {"kernel": ((D, H, Dh), 0.0, w),
                        "bias": ((H, Dh), 0.0, b)}
            out["kv"] = {"kernel": ((D, 2, KH, Dh), 0.0, w),
                         "bias": ((2, KH, Dh), 0.0, b)}
        return out

    tree: Dict[str, Any] = {f"block_{i}": block() for i in range(L)}
    tree["embed"] = {"embedding": ((V, D), 0.0, w)}
    tree["ln_f"] = ln()
    tree["lm_head"] = {"kernel": ((D, V), 0.0, w), "bias": ((V,), 0.0, b)}
    if m.get("pos_enc", "learned") == "learned":
        tree["pos"] = ((m["max_len"], D), 0.0, b)
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def seed_key(seed: int, stream: int = 0):
    """A raw threefry key from any whole-number seed (the driver's seeds pass
    2**31, which ``PRNGKey`` under 32-bit ints refuses)."""
    import jax.numpy as jnp

    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32
    )
    return jnp.asarray(words, jnp.uint32)


def n_params(m: Dict[str, Any]) -> int:
    import jax

    leaves = jax.tree_util.tree_leaves(param_specs(m), is_leaf=_is_spec)
    return int(sum(int(np.prod(s[0])) for s in leaves))


def make_params(m: Dict[str, Any], seed: int, dtype, sharding=None):
    """All weights in one jitted call, on the device, in ``dtype``."""
    import jax
    import jax.numpy as jnp

    specs = param_specs(m)
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    # One draw per distinct (shape, mean, std), stacked over the leaves that
    # share it (the layers), then sliced: a few dozen random ops instead of
    # one per leaf, which the TPU compiler takes minutes over (16 s against
    # 200 s for gpt2-xl; chip compiler and my chip run, PR 23).
    groups: Dict[Spec, list] = {}
    for i, spec in enumerate(leaves):
        groups.setdefault(spec, []).append(i)

    def draw(key):
        out = [None] * len(leaves)
        for g, ((shape, mean, std), members) in enumerate(groups.items()):
            x = jax.random.normal(jax.random.fold_in(key, g),
                                  (len(members),) + shape, jnp.float32)
            x = (mean + std * x).astype(dtype)
            for j, i in enumerate(members):
                out[i] = x[j]
        return jax.tree_util.tree_unflatten(treedef, out)

    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(draw, **kw)(seed_key(seed))

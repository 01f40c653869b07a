"""``optax.adafactor`` with its documented defaults.

After the first update its second-moment decay is 1 - 1^-0.8 = 0, so the
state holds exactly the statistics of the first gradient the optimizer got:
``v = g^2 + eps`` for an unfactored leaf, ``v_row = mean(g^2 + eps)`` over the
largest axis for a factored one.  Summing gives the leaf's squared norm."""

from __future__ import annotations

from typing import Dict

from perfbench.reference.adafactor import EPS, factored_dims


def make(lr: float):
    import optax

    return optax.adafactor(lr)


def first_grad_norms(opt_state, params) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    fs = next(s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "v_row"))
        if hasattr(s, "v_row"))

    def norms(v_row, v_col, v, p):
        def one(r, c, full, leaf):
            dims = factored_dims(leaf.shape)
            if dims is None:
                sq = jnp.sum(full.astype(jnp.float32))
            else:
                sq = jnp.sum(r.astype(jnp.float32)) * leaf.shape[dims[1]]
            return jnp.sqrt(jnp.maximum(sq - EPS * leaf.size, 0.0))

        return jax.tree_util.tree_map(one, v_row, v_col, v, p)

    return leaf_dict(jax.jit(norms)(fs.v_row, fs.v_col, fs.v, params))


def leaf_dict(tree) -> Dict[str, float]:
    import jax

    return {
        "/".join(str(getattr(k, "key", k)) for k in path): float(x)
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
    }

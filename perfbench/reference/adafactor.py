"""Plain Adafactor (Shazeer & Stern 2018) in float32, leaf by leaf, with the
settings ``optax.adafactor(learning_rate)`` documents as its defaults:
factored second moments for leaves whose two largest axes are both >= 128,
decay 1 - (t+1)^-0.8, eps 1e-30, update clipped to block rms 1, scaled by the
learning rate and by max(rms(param), 1e-3); no momentum, no weight decay.
The new parameter is rounded once, to the storage dtype."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MIN_DIM = 128
EPS = 1e-30


def factored_dims(shape) -> Optional[Tuple[int, int]]:
    if len(shape) < 2:
        return None
    order = np.argsort(shape)
    if shape[order[-2]] < MIN_DIM:
        return None
    return int(order[-2]), int(order[-1])


def init_leaf(p) -> Dict[str, Any]:
    dims = factored_dims(p.shape)
    if dims is None:
        return {"v": jnp.zeros(p.shape, jnp.float32)}
    d1, d0 = dims
    return {"v_row": jnp.zeros(np.delete(p.shape, d0), jnp.float32),
            "v_col": jnp.zeros(np.delete(p.shape, d1), jnp.float32)}


def update_leaf(p, g, st, count, lr: float):
    """``(new_p, new_state)``; ``count`` is the number of steps taken before
    this one."""
    g = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    beta = 1.0 - (jnp.asarray(count, jnp.float32) + 1.0) ** -0.8
    sq = jnp.square(g) + EPS
    dims = factored_dims(p.shape)
    if dims is None:
        v = beta * st["v"] + (1.0 - beta) * sq
        u = g * v ** -0.5
        new = {"v": v}
    else:
        d1, d0 = dims
        v_row = beta * st["v_row"] + (1.0 - beta) * jnp.mean(sq, axis=d0)
        v_col = beta * st["v_col"] + (1.0 - beta) * jnp.mean(sq, axis=d1)
        rd1 = d1 - 1 if d1 > d0 else d1
        row = (v_row / jnp.mean(v_row, axis=rd1, keepdims=True)) ** -0.5
        u = g * jnp.expand_dims(row, d0) * jnp.expand_dims(v_col ** -0.5, d1)
        new = {"v_row": v_row, "v_col": v_col}
    u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(jnp.square(u))))
    u = u * lr * jnp.sqrt(jnp.maximum(jnp.mean(jnp.square(pf)), 1e-6))
    return (pf - u).astype(p.dtype), new


def init(tree):
    return jax.tree_util.tree_map(init_leaf, tree)


@jax.jit
def _update(tree, grads, state, count, lr):
    leaves, tdef = jax.tree_util.tree_flatten(tree)
    gl = tdef.flatten_up_to(grads)
    sl = tdef.flatten_up_to(state)
    out = [update_leaf(p, g, s, count, lr) for p, g, s in zip(leaves, gl, sl)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]))


def update(tree, grads, state, count: int, lr: float):
    return _update(tree, grads, state, jnp.int32(count), jnp.float32(lr))

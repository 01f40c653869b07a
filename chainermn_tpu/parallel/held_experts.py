"""The expert-parallel layer minus its exchange: a chip that holds a
contiguous range of a layer's routed experts routes every token over ALL of
them, computes the part of the result its own experts give, and drops none.

Where :mod:`chainermn_tpu.parallel.moe` scatters tokens into per-expert
capacity buffers (and drops what overflows), this layer sorts the (token,
choice) pairs by expert, lays the held experts' rows out one expert after
another on tile boundaries and runs one grouped matmul a projection over
them (:mod:`chainermn_tpu.ops.grouped_matmul`): the tiles carry each
expert's fill, whatever the imbalance.  Pairs whose expert lives on another
chip are neither gathered nor multiplied nor added: what the absent experts
would have given is left out of the sum, and an all-to-all over the
``expert`` axis (not here) is what a multi-chip layer would put around
:func:`held_experts_ffn`.

Shapes are static and nothing is dropped: the row buffer is ``row_bound``
rows (three times what the held experts draw on average is
``HybridLM``'s choice) and, when a batch routes more than fit, the same arithmetic runs
over a buffer that holds every pair (``lax.cond``: the exact fallback).

Routing is the sigmoid / bias-corrected top-k of the DeepSeek-V3 family:
scores ``s = sigmoid(u . W_g)`` in float32, the ``k`` largest of ``s +
e_bias`` chosen, weights ``s`` of the chosen over their sum times ``scale``;
experts are ``relu(.)**2`` MLPs, not gated — or, with
``activation=swiglu``, gated ones whose gate and up projections are one
``(held, D, 2 F)`` product.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops.grouped_matmul import aligned_groups, grouped_matmul


def held_range(index: int, of: int, held: int) -> Tuple[int, int]:
    """Experts ``[lo, hi)`` that shard ``index`` of ``of`` holds, ``held``
    each: a contiguous range by rank."""
    if not 0 <= index < of:
        raise ValueError(f"shard index {index} outside 0..{of - 1}")
    return index * held, (index + 1) * held


def relu2(x):
    """``relu(x) ** 2``, the family's expert activation (not gated)."""
    return jnp.square(jax.nn.relu(x))


def swiglu(h):
    """``silu(gate) * up`` of a fused ``[gate | up]`` product (..., 2 F):
    the gated expert's activation, which halves the width."""
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


def sigmoid_topk_route(u, w_gate, e_bias, k: int, *, scale: float = 1.0):
    """``(experts, weights)`` of shape (N, k) for tokens ``u`` (N, D): the
    router runs in float32 at the highest matmul precision over every
    column of ``w_gate`` (D, E), whoever holds the experts; the chosen
    scores are divided by their sum."""
    s = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32),
                               w_gate.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    _, experts = lax.top_k(s + e_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, experts, axis=-1)
    return experts, w / jnp.sum(w, axis=-1, keepdims=True) * scale


def held_experts_ffn(x, experts, weights, w_up, w_down, *, lo: int,
                     tile: int = 128, row_bound: Optional[int] = None,
                     activation=relu2,
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The held experts' part of the routed sum for tokens ``x`` (N, D).

    ``experts`` / ``weights`` (N, k) are the router's choices over all the
    layer's experts; ``w_up`` (held, D, F) and ``w_down`` (held, F, D) are
    the experts ``lo .. lo + held - 1``.  Returns ``(y, counters)`` with
    ``y`` (N, D) float32: ``sum over chosen held e of weight * down_e .
    activation(up_e . x)`` — :func:`relu2`, or :func:`swiglu` over a
    ``w_up`` that is ``[gate | up]`` (held, D, 2 F).  ``tile`` rows of the buffer belong to one expert (a
    multiple of 8); ``row_bound`` sizes the buffer of the usual case (all
    ``N * k`` pairs when ``None``).
    """
    N, k = experts.shape
    D, P, held = x.shape[1], N * k, w_up.shape[0]
    with jax.named_scope("moe.dispatch"):
        local = experts.reshape(-1) - lo
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        # held pairs first, grouped by expert; the others behind them
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None], axis=0,
                        dtype=jnp.int32)
        starts = jnp.cumsum(sizes) - sizes
        tiles_needed = jnp.sum(jnp.maximum(-(-sizes // tile), 1))
    def part(n_tiles: int, x, flat_w, w_up, w_down):
        """The layer over a buffer of ``n_tiles`` tiles."""
        with jax.named_scope("moe.dispatch"):
            tile_group, n_active, first_row = aligned_groups(
                sizes, tile, n_tiles)
            r = jnp.arange(n_tiles * tile)
            g = tile_group[r // tile]
            offset = r - first_row[g]
            valid = (offset < sizes[g]) & (r // tile < n_active[0])
            pair = order[jnp.clip(starts[g] + offset, 0, P - 1)]
            token = pair // k
            # a group's last tile is filled up with zeros; tiles past the
            # last one in use are not computed, and nothing is taken of them
            rows = jnp.where(valid[:, None], x[token], 0)
        with jax.named_scope("moe.experts"):
            h = grouped_matmul(rows, w_up.astype(x.dtype), tile_group,
                               n_active, tile)
            out = grouped_matmul(activation(h), w_down.astype(x.dtype), tile_group,
                                 n_active, tile)
        with jax.named_scope("moe.combine"):
            gate = jnp.where(valid, flat_w[pair], 0)
            part_y = jnp.where(valid[:, None], out, 0).astype(jnp.float32)
            y = jnp.zeros((N, D), jnp.float32).at[token].add(
                part_y * gate[:, None])
        return y, jnp.sum(valid)

    every = -(-P // tile) + held  # tiles that hold any routing at all
    usual = every if row_bound is None else min(-(-row_bound // tile) + held,
                                                every)
    operands = (x, weights.reshape(-1), w_up, w_down)
    if usual == every:
        y, covered = part(every, *operands)
    else:
        # Each branch keeps only its operands for the backward and works its
        # own forward out again there: differentiated as it stands, a
        # ``cond`` saves the union of its branches' residuals, and the
        # branch taken writes zeros for the other's (1.5 ms a call at the
        # benchmark's shape, PERF.md §6).
        y, covered = lax.cond(
            tiles_needed <= usual,
            jax.checkpoint(functools.partial(part, usual)),
            jax.checkpoint(functools.partial(part, every)), *operands)
    n_held = jnp.sum(sizes)
    counters = {
        "moe_pairs_held": n_held.astype(jnp.float32),
        "moe_rows_max_over_mean": jnp.max(sizes) * held
        / jnp.maximum(n_held, 1),
        # pairs routed to a held expert that no row of the buffer took up
        "moe_pairs_dropped": (jnp.sum(mine) - covered).astype(jnp.float32),
    }
    return y, counters

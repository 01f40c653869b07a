"""Grouped matmul over tile-aligned groups — the expert layer's two
projections as Pallas kernels.

``rows`` (R, K) holds the rows of ``G`` groups one after another, every group
starting on a multiple of ``tm`` rows (:func:`aligned_groups` lays them out
so); group ``g``'s rows are multiplied by ``w[g]`` (K, N).  Because a tile of
``tm`` rows belongs to one group, the kernels need no masks: a tile's step is
one ``(tm, K) x (K, N)`` product against the weights of ``tile_group[t]``,
which stay in VMEM while consecutive tiles share a group, and tiles past
``n_active`` cost neither a DMA nor a product.  Rows of a group's last tile
beyond its size must be zeros on the way in (they come out as zeros' product,
and add nothing to the weights' gradient); tiles past ``n_active`` are not
written at all.

Three kernels, one a product of the backward: ``grouped_matmul`` (``x . W``,
and with the weights read transposed ``dy . W^T``) and ``grouped_matmul_dw``
(``x^T . dy`` summed over a group's tiles in a float32 accumulator).  XLA's
own ``lax.ragged_dot`` computes the same on unaligned groups; on a v5e it
took 9.6 ms for an expert layer's two products at the benchmark's shape
(16 groups, ~6,000 rows, 2688 x 1856) against 0.7 ms for a dense batched
einsum of the same FLOPs, and megablox's ``gmm`` (3.1 ms) declares no
``vma`` for its outputs, so it cannot run under ``shard_map(check_vma=True)``
(PERF.md §6, PR 38).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import _use_interpret, _vma_union

#: the weights of one group, double-buffered, may take 20 MB (2688 x 1856 in
#: bf16); Mosaic's default scoped limit is 16 MiB of the v5e's 128 MiB
VMEM_LIMIT = 64 * 2**20


def _tile(t, active):
    """The tile a grid step works on: a step past the last tile in use
    stays on that one (no DMA, no product)."""
    return jnp.minimum(t, active[0] - 1)


def _xla_twin(tile_group, n_active) -> bool:
    """Off the TPU the kernels run in Pallas's interpreter, which cannot
    index prefetched scalars that vary over a ``shard_map``'s mesh
    (``check_vma=True``: the train step).  There, and only there, the same
    arithmetic is written in plain ``jax.numpy`` (:func:`_mm_xla`,
    :func:`_dw_xla`); on the chip a kernel is opaque to the type check."""
    return _use_interpret() and bool(_vma_union(tile_group, n_active))


def _tiles(x, tm):
    return x.reshape(x.shape[0] // tm, tm, x.shape[1])


def _mm_xla(x, w, tile_group, n_active, tm, transposed):
    rule = "trb,tab->tra" if transposed else "tra,tab->trb"
    out = jnp.einsum(rule, _tiles(x, tm), w[tile_group],
                     preferred_element_type=jnp.float32)
    return out.reshape(x.shape[0], -1).astype(x.dtype)


def _dw_xla(x, dy, tile_group, n_active, tm, n_groups):
    used = (jnp.arange(x.shape[0] // tm) < n_active[0])[:, None, None]
    per_tile = jnp.einsum("tra,trb->tab", _tiles(x, tm),
                          jnp.where(used, _tiles(dy, tm), 0),
                          preferred_element_type=jnp.float32)
    return jax.ops.segment_sum(per_tile, tile_group,
                               num_segments=n_groups).astype(x.dtype)


def _lane_tile(d: int, cap: int = 1024) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``d``; ``d``
    itself where none does (a block's last axis is a multiple of 128 lanes
    or the whole axis)."""
    for t in range(cap - cap % 128, 0, -128):
        if d % t == 0:
            return t
    return d


def aligned_groups(sizes: jax.Array, tm: int, n_tiles: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Where ``G`` groups of ``sizes`` rows lie in a buffer of ``n_tiles``
    tiles of ``tm`` rows when every group starts on a tile: ``(tile_group,
    n_active, first_row)`` — the group of each tile (n_tiles,), the tiles in
    use (1,), and each group's first row (G,).  An empty group still takes
    one tile (of zeros), so that the weights' gradient is written for it.
    The layout holds only if ``n_active[0] <= n_tiles``."""
    tiles = jnp.maximum(-(-sizes // tm), 1).astype(jnp.int32)
    ends = jnp.cumsum(tiles)
    group = jnp.searchsorted(ends, jnp.arange(n_tiles, dtype=jnp.int32),
                             side="right")
    group = jnp.minimum(group, sizes.shape[0] - 1).astype(jnp.int32)
    return group, ends[-1:], (ends - tiles) * tm


# ------------------------------------------------------------- x . W
def _mm_kernel(group_ref, active_ref, x_ref, w_ref, o_ref, *, transposed):
    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transposed else \
            (((1,), (0,)), ((), ()))
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[...], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _mm(x, w, tile_group, n_active, tm: int, transposed: bool):
    """``x`` (R, A) times group weights ``w`` (G, A, B) — or, ``transposed``,
    ``w`` (G, B, A) read as its transpose — to (R, B)."""
    R, A = x.shape
    B = w.shape[1] if transposed else w.shape[2]
    tn = _lane_tile(B)
    n_tiles = R // tm
    if _xla_twin(tile_group, n_active):
        return _mm_xla(x, w, tile_group, n_active, tm, transposed)
    tile = _tile

    w_block = (None, tn, A) if transposed else (None, A, tn)

    def w_index(j, t, group, active):
        g = group[tile(t, active)]
        return (g, j, 0) if transposed else (g, 0, j)

    return pl.pallas_call(
        functools.partial(_mm_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B // tn, n_tiles),
            in_specs=[
                pl.BlockSpec((tm, A),
                             lambda j, t, group, active: (tile(t, active), 0)),
                pl.BlockSpec(w_block, w_index),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, t, group, active: (tile(t, active), j)),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (R, B), x.dtype, vma=_vma_union(x, w, tile_group, n_active)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_use_interpret(),
        name="grouped_matmul",
    )(tile_group, n_active, x, w)


# ----------------------------------------------------------- x^T . dy
def _dw_kernel(group_ref, active_ref, x_ref, dy_ref, o_ref, acc_ref):
    t, active = pl.program_id(2), active_ref[0]
    g = group_ref[jnp.minimum(t, active - 1)]
    before = group_ref[jnp.maximum(t - 1, 0)]
    after = group_ref[jnp.minimum(t + 1, active - 1)]

    @pl.when(t < active)
    def _():
        @pl.when((t == 0) | (before != g))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when((t == active - 1) | (after != g))
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dw(x, dy, tile_group, n_active, tm: int, n_groups: int):
    """``sum over a group's tiles of x_tile^T . dy_tile`` -> (G, A, B)."""
    R, A = x.shape
    B = dy.shape[1]
    ta, tb = _lane_tile(A), _lane_tile(B)
    if _xla_twin(tile_group, n_active):
        return _dw_xla(x, dy, tile_group, n_active, tm, n_groups)
    tile = _tile

    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(A // ta, B // tb, R // tm),
            in_specs=[
                pl.BlockSpec((tm, ta), lambda a, b, t, group, active:
                             (tile(t, active), a)),
                pl.BlockSpec((tm, tb), lambda a, b, t, group, active:
                             (tile(t, active), b)),
            ],
            out_specs=pl.BlockSpec(
                (None, ta, tb), lambda a, b, t, group, active:
                (group[tile(t, active)], a, b)),
            scratch_shapes=[pltpu.VMEM((ta, tb), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_groups, A, B), x.dtype,
            vma=_vma_union(x, dy, tile_group, n_active)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_use_interpret(),
        name="grouped_matmul_dw",
    )(tile_group, n_active, x, dy)


# ------------------------------------------------------------ the op
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_group, n_active, tm: int):
    """``out[r] = x[r] . w[group of r's tile]`` for ``x`` (R, K) in tiles of
    ``tm`` rows (``R`` a multiple of ``tm``, ``tm`` of 8) and ``w``
    (G, K, N); ``tile_group`` and ``n_active`` from :func:`aligned_groups`.
    Rows of tiles past ``n_active`` are left unwritten."""
    if x.shape[0] % tm or tm % 8:
        raise ValueError(f"{x.shape[0]} rows in tiles of {tm}: the rows must "
                         f"be a multiple of the tile, the tile of 8")
    return _mm(x, w, tile_group, n_active, tm, transposed=False)


def _gm_fwd(x, w, tile_group, n_active, tm):
    return grouped_matmul(x, w, tile_group, n_active, tm), \
        (x, w, tile_group, n_active)


def _gm_bwd(tm, res, dy):
    x, w, tile_group, n_active = res
    dx = _mm(dy, w, tile_group, n_active, tm, transposed=True)
    dw = _dw(x, dy, tile_group, n_active, tm, w.shape[0])
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)

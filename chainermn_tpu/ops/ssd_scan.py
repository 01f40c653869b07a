"""Chunked state-space scan (the SSD form of Mamba-2) and the short causal
depthwise convolution that feeds it.

The recurrence, a head ``h`` of width ``P`` with a state of ``N`` columns::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t . C_t

is evaluated a chunk of ``Q`` positions at a time (Dao & Gu 2024, "state
space duality"): inside a chunk it is a masked ``(Q, Q)`` product, like
attention with a decay for a mask; between chunks only the ``(P, N)`` state
is carried, over ``T / Q`` steps instead of ``T``.  Four matmuls a chunk:

1. ``C . B^T`` — the group's ``(Q, Q)`` scores (``G`` groups share ``B`` and
   ``C`` among ``H / G`` heads each);
2. ``(scores * decay) . (dt x)`` — the chunk's own contribution;
3. ``B^T . (decay-to-the-chunk's-end * dt x)`` — what the chunk adds to the
   state;
4. ``C . S`` — what the state it was handed contributes.

Matmul operands are in the compute dtype with float32 accumulation; the
log-decays, their cumulative sums, every ``exp`` and the recurrence over
chunk states are float32.

One algorithm, two realisations; :func:`ssd_scan` picks from what it can see
of the call and a caller says nothing (the rule is stated once, in
:func:`_kernels_take`):

* *The kernels* (``ssd_fwd``, ``ssd_bwd``: Pallas, behind a
  ``jax.custom_vjp``) — on the TPU, a whole sequence of more than one chunk
  (no ``initial_state``, no ``return_state``) whose shapes fill the kernels'
  tiles: the training call.  A grid step is one chunk of one group of
  ``R = H / G`` heads; the ``(Q, Q)`` decay tiles and scores live in
  registers and the ``(N, R * P)`` float32 state in VMEM from chunk to
  chunk, so neither sees HBM.  ``ssd_bwd`` needs the inputs alone: one
  launch walks a group's chunks first to last to work the chunk states out
  again into VMEM, then last to first with the state's gradient carried.
  The running sums, ``A``'s and ``D``'s reductions stay ``jax.numpy``
  around the kernels (2 MB arrays).  A position's ``dt`` is folded into the
  scores and into ``B^T`` (a row there) where the ``jax.numpy`` body scales
  ``x``: same products, same dtypes, bfloat16 rounded at another place.
* *Plain* ``jax.numpy`` (:func:`_ssd_scan_xla`), autodiff's backward —
  everything else: any backend but the TPU, serving's prefill chunk (ONE
  chunk from a slot's state to a slot's state), a shape that does not tile.
  It is also the oracle the kernels are tested against.

Serving carries the recurrence across calls: a prefill chunk is one
:func:`ssd_scan` from a slot's state to a slot's state (``initial_state=`` /
``return_state=``; a position given ``dt = 0`` decays by one and adds
nothing, so the rows past a short tail leave the state alone to the bit),
a decode step one position of it a row (:func:`ssd_step`), and the
convolution's last ``K - 1`` inputs ride beside the state
(``causal_depthwise_conv(tail=)``, :func:`conv_step`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import _use_interpret, _vma_union
from chainermn_tpu.utils import pvary_to_match


def causal_depthwise_conv(x: jax.Array, kernel: jax.Array,
                          bias: Optional[jax.Array] = None,
                          tail: Optional[jax.Array] = None) -> jax.Array:
    """``out[t, c] = sum_j kernel[j, c] * x[t - (K - 1) + j, c] (+ bias[c])``
    over ``x`` (B, T, C) with ``kernel`` (K, C): each channel sees its own
    last ``K`` positions; before the first, zeros, or ``tail`` (B, K - 1, C),
    the inputs the sequence had before ``x``.  ``K`` shifted multiplies
    (K is 4 in the published models), which XLA fuses into one pass."""
    K = kernel.shape[0]
    T = x.shape[1]
    if tail is None:
        padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = sum(padded[:, j:j + T] * kernel[j] for j in range(K))
    return out if bias is None else out + bias


def conv_tail(x: jax.Array, tail: jax.Array, length) -> jax.Array:
    """The ``K - 1`` inputs that precede position ``length`` of the
    sequence ``tail ++ x``'s part ``x`` (B, T, C): what the next call's
    ``tail`` is after ``length`` (traced, ``<= T``) real positions of a
    chunk.  ``length = 0`` hands ``tail`` back."""
    return lax.dynamic_slice_in_dim(
        jnp.concatenate([tail.astype(x.dtype), x], axis=1), length,
        tail.shape[1], axis=1)


def conv_step(tail: jax.Array, x: jax.Array, kernel: jax.Array,
              bias: Optional[jax.Array] = None):
    """One position of :func:`causal_depthwise_conv` a row: ``tail``
    (B, K - 1, C) the row's last inputs, ``x`` (B, C) the new one.  Returns
    ``(out (B, C), new tail)``."""
    window = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    out = jnp.sum(window * kernel, axis=1)
    return (out if bias is None else out + bias), window[:, 1:]


def ssd_step(state: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
             B: jax.Array, C: jax.Array, D: Optional[jax.Array] = None):
    """One position of the recurrence a row, in float32: ``state``
    (batch, H, P, N), ``x`` (batch, H, P), ``dt`` (batch, H), ``A`` (H,),
    ``B`` and ``C`` (batch, G, N).  Returns ``(y (batch, H, P) float32,
    new state)``.  A row whose ``dt`` is 0 keeps its state to the bit."""
    Bsz, H, P, N = state.shape
    G = B.shape[1]
    f32 = jnp.float32
    s = state.astype(f32).reshape(Bsz, G, H // G, P, N)
    dtf = dt.astype(f32).reshape(Bsz, G, H // G)
    xf = x.astype(f32).reshape(Bsz, G, H // G, P)
    decay = jnp.exp(dtf * A.astype(f32).reshape(G, H // G))
    s = (s * decay[..., None, None]
         + (xf * dtf[..., None])[..., None]
         * B.astype(f32)[:, :, None, None, :])
    y = jnp.sum(s * C.astype(f32)[:, :, None, None, :], axis=-1)
    if D is not None:
        y = y + xf * D.astype(f32).reshape(G, H // G)[..., None]
    return y.reshape(Bsz, H, P), s.reshape(Bsz, H, P, N)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int, D: Optional[jax.Array] = None,
             initial_state: Optional[jax.Array] = None,
             return_state: bool = False):
    """The recurrence above over ``x`` (batch, T, H, P).

    ``dt`` (batch, T, H) float32 and positive (after the softplus), ``A``
    (H,) float32 and negative, ``B`` and ``C`` (batch, T, G, N) with ``G``
    dividing ``H``; ``T`` a multiple of ``chunk``.  ``D`` (H,) adds the skip
    ``D * x``.  ``initial_state`` (batch, H, P, N) float32 is the state
    before the first position (zeros by default); with ``return_state`` the
    result is ``(y, final_state)``.  ``y`` is float32.  Which body runs is
    :func:`_kernels_take`'s to say.
    """
    T, H = x.shape[1:3]
    G = B.shape[2]
    if T % chunk or H % G:
        raise ValueError(f"T={T} must be a multiple of chunk={chunk} and "
                         f"H={H} of the groups G={G}")
    if _kernels_take(x, B, C, chunk=chunk, initial_state=initial_state,
                     return_state=return_state):
        return _ssd_scan_kernels(x, dt, A, B, C, D, chunk)
    return _ssd_scan_xla(x, dt, A, B, C, chunk=chunk, D=D,
                         initial_state=initial_state,
                         return_state=return_state)


def _ssd_scan_xla(x, dt, A, B, C, *, chunk, D=None, initial_state=None,
                  return_state=False):
    """:func:`ssd_scan` in plain ``jax.numpy``, its backward autodiff's
    (under the block's ``jax.checkpoint`` the ``(H, Q, Q)`` decay tiles live
    only while one block is differentiated)."""
    Bsz, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Q = chunk
    nC, R = T // Q, H // G
    cd, f32 = x.dtype, jnp.float32

    # log-decay of every position and its running sum inside the chunk
    a = (dt.astype(f32) * A.astype(f32)).reshape(Bsz, nC, Q, G, R)
    # (a running sum as a float32 product with a triangle of ones: XLA's
    # cumsum over a middle axis took 1.9 ms a layer on the v5e, PERF.md §6)
    upto = jnp.tril(jnp.ones((Q, Q), f32))
    cs = jnp.einsum("qs,bcsgr->bcqgr", upto, a,     # (b, c, q, g, r)
                    precision=lax.Precision.HIGHEST)
    total = cs[:, :, -1]                            # (b, c, g, r)
    xf = x.astype(f32).reshape(Bsz, nC, Q, G, R, P)
    dtc = dt.astype(f32).reshape(Bsz, nC, Q, G, R)
    Bc = B.reshape(Bsz, nC, Q, G, N)
    Cc = C.reshape(Bsz, nC, Q, G, N)

    # 1. scores of a group, 2. the chunk's own part
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc,
                    preferred_element_type=f32)
    csh = jnp.moveaxis(cs, 2, -1)                   # (b, c, g, r, q)
    diff = csh[..., :, None] - csh[..., None, :]    # (b, c, g, r, q, s)
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    scores = (cb[:, :, :, None] * decay).astype(cd)
    xdt = (xf * dtc[..., None]).astype(cd)
    y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", scores, xdt,
                   preferred_element_type=f32)

    # 3. what each chunk adds to the state.  (States as (.., r, p, n): XLA
    # turns the 134 MB of them over once a pass for the fourth product, a
    # copy with no op_name; kept (.., n, r, p) that copy goes and the scan's
    # forward + backward takes 9.6 ms against 7.8 a layer — PERF.md §6, PR 38.)
    to_end = jnp.exp(total[:, :, None] - cs)        # (b, c, q, g, r)
    xend = (xf * (dtc * to_end)[..., None]).astype(cd)
    added = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bc, xend,
                       preferred_element_type=f32)

    # the recurrence over chunk states, float32
    if initial_state is None:
        s0 = jnp.zeros((Bsz, G, R, P, N), f32)
    else:
        s0 = initial_state.astype(f32).reshape(Bsz, G, R, P, N)
    s0 = pvary_to_match(s0, added, total)  # inside a shard_map: vary alike

    def step(s, inp):
        dec, add = inp
        return s * jnp.exp(dec)[..., None, None] + add, s

    final, before = lax.scan(
        step, s0, (jnp.moveaxis(total, 1, 0), jnp.moveaxis(added, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)             # (b, c, g, r, p, n)

    # 4. what the state a chunk was handed contributes
    carried = jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cc, before.astype(cd),
                         preferred_element_type=f32)
    y = y + carried * jnp.exp(cs)[..., None]
    if D is not None:
        y = y + xf * D.astype(f32).reshape(G, R)[..., None]
    y = y.reshape(Bsz, T, H, P)
    if return_state:
        return y, final.reshape(Bsz, H, P, N)
    return y


# ------------------------------------------------------------ the kernels
#: lanes of a register: what a chunk, a group's state columns and a group's
#: ``R * P`` columns of ``x`` must be multiples of for the kernels' tiles
LANES = 128

#: what a group's chunk states may take of VMEM in the backward launch (the
#: v5e has 128 MiB; Mosaic's default scoped limit is 16)
STATES_VMEM = 24 * 2**20

_NT = (((1,), (1,)), ((), ()))   # a . b^T
_TN = (((0,), (0,)), ((), ()))   # a^T . b


def _kernels_take(x, B, C, *, chunk, initial_state, return_state) -> bool:
    """THE rule, from what :func:`ssd_scan` can see of its call: the
    kernels when the backend is the TPU, the call is a whole sequence (no
    ``initial_state``, no ``return_state``) of more than one chunk, and the
    shapes fill the kernels' tiles — a chunk, ``N`` and a group's ``R * P``
    columns multiples of 128 lanes, one matmul dtype, a group's chunk
    states within ``STATES_VMEM``; the ``jax.numpy`` body otherwise."""
    T, H, P = x.shape[1:]
    G, N = B.shape[2:]
    nC, RP = T // chunk, H // G * P
    return (not _use_interpret()
            and initial_state is None and not return_state and nC > 1
            and chunk % LANES == 0 and N % LANES == 0 and RP % LANES == 0
            and P % 8 == 0
            and x.dtype == B.dtype == C.dtype
            and x.dtype in (jnp.bfloat16, jnp.float32)
            and nC * N * RP * 4 <= STATES_VMEM)


def _decay(lead, lag, mask):
    """``exp(lead - lag)`` where ``mask``, 0 elsewhere: a ``(Q, Q)`` tile."""
    return jnp.exp(jnp.where(mask, lead - lag, -jnp.inf))


def _wide(column, n):
    """A ``(rows, 1)`` column along ``n`` lanes."""
    return jnp.broadcast_to(column, (column.shape[0], n))


def _causal(Q):
    """``[q, s]``: position ``s`` is not after ``q``."""
    return (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


def _heads_a_tile(R, P):
    """Heads side by side in one stretch of lanes: two of 64 columns fill
    128 lanes, so a tile's elementwise work and its stores are whole
    registers (a matmul 64 wide costs the MXU what one 128 wide does: each
    head's product is taken over the tile and its own columns kept)."""
    hp = LANES // P if P < LANES and LANES % P == 0 else 1
    return hp if R % hp == 0 else 1


def _tiles(R, P):
    """``(columns, heads)`` of every stretch of lanes a group's heads are
    worked in."""
    hp = _heads_a_tile(R, P)
    return [(slice(j * hp * P, (j + 1) * hp * P),
             range(j * hp, (j + 1) * hp)) for j in range(R // hp)]


def _own(parts, P):
    """Columns ``[h P, (h + 1) P)`` of ``parts[h]``, side by side: a tile
    ``(rows, len(parts) * P)`` of each head's own."""
    out = parts[0]
    if len(parts) > 1:
        head = lax.broadcasted_iota(jnp.int32, (1, out.shape[1]), 1) // P
        for h, part in enumerate(parts[1:], 1):
            out = jnp.where(head == h, part, out)
    return out


def _head_sums(m, heads, R, P):
    """``(R, Q)``: row ``r`` of ``heads`` holds the sums of ``m`` (Q, W)
    over head ``r``'s own ``P`` columns, the other rows 0 — on the MXU
    against a 0/1 matrix, in ``m``'s dtype (a sum along lanes is the XLU's
    slowest, and its result a column where the gradients are rows)."""
    W = m.shape[1]
    pick = (lax.broadcasted_iota(jnp.int32, (R, W), 0)
            == heads[0] + lax.broadcasted_iota(jnp.int32, (R, W), 1) // P
            ).astype(m.dtype)
    return lax.dot_general(pick, m, _NT, preferred_element_type=jnp.float32)


def _rect_sums(m, earlier, causal):
    """``(1, Q)``: entry ``t`` is the sum of ``m[q, s]`` (Q, Q) float32 over
    ``s < t <= q`` — what ``d/da_t`` of ``L[q, s] = exp(a_{s+1} + ... +
    a_q)`` collects.  The sum along the lanes is a product with the 0/1
    triangle ``earlier`` (``[s, t]``: ``s < t``, in the compute dtype; ``m``
    goes through the MXU in two halves of it, the rounded value and what
    rounding left, and keeps float32's digits), the one down the sublanes
    the VPU's."""
    f32, cd = jnp.float32, earlier.dtype
    hi = m.astype(cd)
    part = jnp.dot(hi, earlier, preferred_element_type=f32)
    if cd != f32:
        lo = (m - hi.astype(f32)).astype(cd)
        part = part + jnp.dot(lo, earlier, preferred_element_type=f32)
    return jnp.sum(jnp.where(causal, part, 0.0), axis=0, keepdims=True)


def _states_step(x_ref, b_ref, csr_ref, dtr_ref, s_ref, *, R, P):
    """Product 3 alone: ``s_ref`` (the state transposed, ``(N, R * P)``)
    taken across one chunk."""
    f32 = jnp.float32
    Bc = b_ref[...]
    Q = Bc.shape[0]
    cs_row, dt_row = csr_ref[...], dtr_ref[...]
    to_end = dt_row * jnp.exp(cs_row[:, Q - 1:] - cs_row)       # (R, Q)
    b_t = Bc.T.astype(f32)                                      # (N, Q)
    for at, heads in _tiles(R, P):
        xt = x_ref[:, at]
        added = [jnp.dot((b_t * to_end[r:r + 1, :]).astype(Bc.dtype), xt,
                         preferred_element_type=f32) for r in heads]
        total = [_wide(cs_row[r:r + 1, Q - 1:], xt.shape[1]) for r in heads]
        s_ref[:, at] = (s_ref[:, at] * jnp.exp(_own(total, P))
                        + _own(added, P))


def _fwd_kernel(x_ref, b_ref, c_ref, csr_ref, csc_ref, dtr_ref, d_ref,
                y_ref, s_ref, *, R, P):
    """One chunk of one group: the four products of the module docstring
    for its ``R`` heads, the state — transposed, ``(N, R * P)`` float32 — in
    ``s_ref`` from chunk to chunk."""
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    Bc, Cc = b_ref[...], c_ref[...]
    cd = Bc.dtype
    Q = Bc.shape[0]
    cs_row, cs_col, dt_row = csr_ref[...], csc_ref[...], dtr_ref[...]
    causal = _causal(Q)
    cb = lax.dot_general(Cc, Bc, _NT, preferred_element_type=f32)
    for at, heads in _tiles(R, P):
        xt = x_ref[:, at]
        carried = jnp.dot(Cc, s_ref[:, at].astype(cd),
                          preferred_element_type=f32)
        own, lead = [], []
        for r in heads:
            row = slice(r, r + 1)
            lead.append(_wide(cs_col[:, row], xt.shape[1]))
            scores = (cb * _decay(_wide(cs_col[:, row], Q), cs_row[row, :],
                                  causal) * dt_row[row, :]).astype(cd)
            own.append(jnp.dot(scores, xt, preferred_element_type=f32))
        y_ref[:, at] = (_own(own, P) + carried * jnp.exp(_own(lead, P))
                        + xt.astype(f32) * d_ref[:, at])
    _states_step(x_ref, b_ref, csr_ref, dtr_ref, s_ref, R=R, P=P)


def _bwd_kernel(x_ref, b_ref, c_ref, csr_ref, csc_ref, dtr_ref, d_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dcs_ref,
                dd_ref, ds_ref, before_ref, *, R, P):
    """A group's chunks walked twice in one launch.  First to last, the
    state every chunk is handed is worked out again (product 3 alone) and
    kept — ``before_ref``, ``(chunks, N, R * P)`` float32 in VMEM, 16 MB at
    the hybrid cell's shape: it never sees HBM.  Then last to first, the
    gradients, ``ds_ref`` holding that of the (transposed) state a chunk
    hands on; ``dd_ref`` sums ``dy * x`` over the chunks."""
    step, nC = pl.program_id(2), before_ref.shape[0]

    @pl.when((step == 0) | (step == nC))
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    @pl.when(step < nC)
    def _():
        before_ref[step] = ds_ref[...]
        _states_step(x_ref, b_ref, csr_ref, dtr_ref, ds_ref, R=R, P=P)

    @pl.when(step == nC)
    def _():
        dd_ref[...] = jnp.zeros_like(dd_ref)

    @pl.when(step >= nC)
    def _():
        _bwd_step(x_ref, b_ref, c_ref, csr_ref, csc_ref, dtr_ref, d_ref,
                  dy_ref, before_ref.at[2 * nC - 1 - step], dx_ref,
                  db_ref, dc_ref, ddt_ref, da_ref, dcs_ref, dd_ref, ds_ref,
                  R=R, P=P)


def _bwd_step(x_ref, b_ref, c_ref, csr_ref, csc_ref, dtr_ref, d_ref, dy_ref,
              before_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dcs_ref,
              dd_ref, ds_ref, *, R, P):
    """One chunk of the walk back: every input's gradient there.  With
    ``W = cb * L * dt`` the scores and ``E = B^T * to_end`` (both by head),
    the forward is ``y = W x + exp(cs) (C S) + D x`` and ``S' = exp(total) S
    + E x``.  A running sum enters ``L[q, s] = exp(cs_q - cs_s)`` twice, so
    its gradient through ``L`` is a difference — ``dW W`` summed along a
    tile's lanes where it leads, down its sublanes where it lags — of two
    sums that all but cancel, and in bfloat16 ``A``'s gradient drowns in
    their rounding.  So that part is taken with respect to the log-decays
    ``a`` themselves (``da_ref``, :func:`_rect_sums`: no two terms cancel);
    ``dcs_ref`` holds the rest, which :func:`_scan_bwd` sums back to
    ``a``."""
    f32 = jnp.float32
    Bc, Cc = b_ref[...], c_ref[...]
    cd = Bc.dtype
    Q, N = Bc.shape
    cs_row, cs_col, dt_row = csr_ref[...], csc_ref[...], dtr_ref[...]
    tail = jnp.exp(cs_row[:, Q - 1:] - cs_row)                  # (R, Q)
    to_end = dt_row * tail
    causal = _causal(Q)
    earlier = (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
               < lax.broadcasted_iota(jnp.int32, (Q, Q), 1)).astype(cd)
    at_end = lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    cb = lax.dot_general(Cc, Bc, _NT, preferred_element_type=f32)
    b_t, c_t = Bc.T.astype(f32), Cc.T                           # (N, Q)
    hp = _heads_a_tile(R, P)
    W = hp * P
    head = lax.broadcasted_iota(jnp.int32, (1, W), 1) // P
    dcb = jnp.zeros((Q, Q), f32)
    dbt = jnp.zeros((N, Q), f32)
    dc = jnp.zeros((Q, N), f32)
    through = jnp.zeros((R, Q), f32)
    for at, heads in _tiles(R, P):
        xt, dyt, skip = x_ref[:, at], dy_ref[:, at], d_ref[:, at]
        xf = xt.astype(f32)
        before, ds = before_ref[:, at], ds_ref[:, at]           # (N, W)
        dy_c, ds_c = dyt.astype(cd), ds.astype(cd)
        kept = jnp.sum(ds * before, axis=0, keepdims=True)      # (1, W)
        dxs, lead, total = [], [], []
        for h, r in enumerate(heads):
            row = slice(r, r + 1)
            own = head == h
            lead.append(_wide(cs_col[:, row], W))
            total.append(_wide(cs_col[Q - 1:, row], W))
            ld = _decay(_wide(cs_col[:, row], Q), cs_row[row, :], causal)
            w0 = cb * ld
            ld = ld * dt_row[row, :]
            w = (w0 * dt_row[row, :]).astype(cd)
            b_e = (b_t * to_end[row, :]).astype(cd)
            # dW = dy . x^T and dE = dS' . x^T over the head's own columns
            x_own = xt if hp == 1 else jnp.where(own, xt, jnp.zeros_like(xt))
            dw = lax.dot_general(dy_c, x_own, _NT,
                                 preferred_element_type=f32)    # (Q, Q)
            db_e = lax.dot_general(ds_c, x_own, _NT,
                                   preferred_element_type=f32)  # (N, Q)
            # x's: W^T . dy + E^T . dS'
            dxs.append(
                lax.dot_general(w, dy_c, _TN, preferred_element_type=f32)
                + lax.dot_general(b_e, ds_c, _TN,
                                  preferred_element_type=f32))
            dcb = dcb + dw * ld
            dbt = dbt + db_e * to_end[row, :]
            m0 = dw * w0
            by_s = jnp.sum(m0, axis=0, keepdims=True)           # (1, Q)
            d_end = jnp.sum(db_e * b_t, axis=0, keepdims=True)  # (1, Q)
            d_total = (
                jnp.exp(cs_col[Q - 1:, row]) * jnp.sum(
                    jnp.where(own, kept, 0.0), axis=1, keepdims=True)
                + jnp.sum(d_end * to_end[row, :], axis=1, keepdims=True))
            ddt_ref[row, :] = by_s + d_end * tail[row, :]
            da_ref[row, :] = _rect_sums(m0 * dt_row[row, :], earlier, causal)
            dcs_ref[row, :] = (jnp.where(at_end, d_total, 0.0)
                               - d_end * to_end[row, :])
        dz = (dyt * jnp.exp(_own(lead, P))).astype(cd)
        dc = dc + lax.dot_general(dz, before.astype(cd), _NT,
                                  preferred_element_type=f32)
        ds_ref[:, at] = (ds * jnp.exp(_own(total, P))
                         + jnp.dot(c_t, dz, preferred_element_type=f32))
        dx_ref[:, at] = (_own(dxs, P) + dyt * skip).astype(dx_ref.dtype)
        dd_ref[:, at] += jnp.sum(dyt * xf, axis=0, keepdims=True)
        # where a running sum leads the carried part, exp(cs) (C . S): no
        # two terms cancel, so the compute dtype's digits do, as for dz
        through = through + _head_sums(
            (dyt * jnp.dot(Cc, before.astype(cd), preferred_element_type=f32)
             ).astype(cd), heads, R, P)
    dcs_ref[...] += through * jnp.exp(cs_row)
    # the group's scores: cb = C . B^T
    dcb = dcb.astype(cd)
    dc_ref[...] = (dc + jnp.dot(dcb, Bc, preferred_element_type=f32)
                   ).astype(dc_ref.dtype)
    db_ref[...] = (dbt.T + lax.dot_general(
        dcb, Cc, _TN, preferred_element_type=f32)).astype(db_ref.dtype)


def _launch(kernel, name, operands, outs, dims, *, back=False):
    """``kernel`` on the grid ``(batch, group, step)``, the steps innermost
    and in order, with a ``(N, R * P)`` float32 scratch carried across them.
    Forward a step is a chunk.  ``back``: twice the chunks — first to last,
    then last to first — with scratch for the group's states; an operand of
    kind ``…@back`` is read on the way back alone and stays on the last
    chunk before, as every result does.
    ``operands`` and ``outs`` are ``(array or shape-and-dtype, kind)`` pairs;
    a kind says how a step finds its block: ``wide`` (batch, T, a group's
    columns), ``row`` (batch, G, R, T), ``col`` (batch, G, T, R), ``skip``
    (1, H * P), ``sum`` (batch, 1, H * P; one block a group, summed over
    its chunks)."""
    Bsz, G, nC, Q, R, P, N = dims
    last = 2 * nC - 1

    def chunk(kind):
        if not back:
            return lambda c: c
        if kind.endswith("@back"):
            return lambda c: jnp.minimum(nC - 1, last - c)
        return lambda c: jnp.minimum(c, last - c)

    def spec(a, kind):
        at = chunk(kind)
        return {
            "wide": lambda: pl.BlockSpec(
                (None, Q, a.shape[2] // G), lambda b, g, c: (b, at(c), g)),
            "row": lambda: pl.BlockSpec(
                (None, None, R, Q), lambda b, g, c: (b, g, 0, at(c))),
            "col": lambda: pl.BlockSpec(
                (None, None, Q, R), lambda b, g, c: (b, g, at(c), 0)),
            "skip": lambda: pl.BlockSpec(
                (1, R * P), lambda b, g, c: (0, g)),
            "sum": lambda: pl.BlockSpec(
                (None, 1, R * P), lambda b, g, c: (b, 0, g)),
        }[kind.split("@")[0]]()

    scratch = [(N, R * P)]
    if back:
        scratch += [(nC, N, R * P)]
    vma = _vma_union(*(a for a, _ in operands))
    return pl.pallas_call(
        functools.partial(kernel, R=R, P=P),
        grid=(Bsz, G, 2 * nC if back else nC),
        in_specs=[spec(a, k) for a, k in operands],
        out_specs=[spec(a, k + "@back") for a, k in outs],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype, vma=vma)
                   for a, _ in outs],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(STATES_VMEM + 16 * 2**20) if back else None),
        interpret=_use_interpret(),
        name=name,
    )(*(a for a, _ in operands))


def _dims(Q, x, B, cs_row):
    Bsz, T, _ = x.shape
    _, G, R, _ = cs_row.shape
    return Bsz, G, T // Q, Q, R, x.shape[2] // (G * R), B.shape[2] // G


def _in_chunk_sums(v, Q, *, to_the_end=False):
    """``v`` (batch, G, R, T) summed inside its chunk: up to each position
    or, ``to_the_end``, from it on (the first's transpose).  A float32
    product with a triangle of ones: XLA's cumsum over a middle axis took
    1.9 ms a layer on the v5e (PERF.md §6)."""
    Bsz, G, R, T = v.shape
    return jnp.einsum(
        "bgrcq,qs->bgrcs" if to_the_end else "bgrcs,qs->bgrcq",
        v.reshape(Bsz, G, R, T // Q, Q), jnp.tril(jnp.ones((Q, Q), v.dtype)),
        precision=lax.Precision.HIGHEST).reshape(v.shape)


def _running_sums(a_row, Q):
    """The log-decays ``a_row`` (batch, G, R, T) summed up inside their
    chunk (2 MB at the hybrid cell's shape, so the backward works them out
    again), laid out both ways a kernel reads them: a position's scalar
    along the lanes (batch, G, R, T) and down the sublanes (batch, G, T,
    R)."""
    cs_row = _in_chunk_sums(a_row, Q)
    return cs_row, jnp.swapaxes(cs_row, 2, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(Q, x, B, C, a_row, dt_row, skip):
    """Kernel ``ssd_fwd``: ``y`` (batch, T, H * P) float32 of ``x`` (batch,
    T, H * P), ``B`` and ``C`` (batch, T, G * N), the log-decays ``dt * A``
    and ``dt`` by row (batch, G, R, T) and ``D`` spread over its head's
    columns (1, H * P)."""
    cs_row, cs_col = _running_sums(a_row, Q)
    y, = _launch(_fwd_kernel, "ssd_fwd",
                 [(x, "wide"), (B, "wide"), (C, "wide"), (cs_row, "row"),
                  (cs_col, "col"), (dt_row, "row"), (skip, "skip")],
                 [(jax.ShapeDtypeStruct(x.shape, jnp.float32), "wide")],
                 _dims(Q, x, B, cs_row))
    return y


def _scan_fwd(Q, *operands):
    return _scan(Q, *operands), operands


def _scan_bwd(Q, res, dy):
    """Kernel ``ssd_bwd``: every input's gradient in one launch, which
    needs the inputs alone (it works the chunk states out again in VMEM)."""
    x, B, C, a_row, dt_row, skip = res
    cs_row, cs_col = _running_sums(a_row, Q)
    dims = _dims(Q, x, B, cs_row)
    f32 = jnp.float32
    dx, dB, dC, ddt, da, dcs, dskip = _launch(
        _bwd_kernel, "ssd_bwd",
        [(x, "wide"), (B, "wide"), (C, "wide@back"), (cs_row, "row"),
         (cs_col, "col@back"), (dt_row, "row"), (skip, "skip"),
         (dy.astype(f32), "wide@back")],
        [(jax.ShapeDtypeStruct(x.shape, x.dtype), "wide"),
         (jax.ShapeDtypeStruct(B.shape, B.dtype), "wide"),
         (jax.ShapeDtypeStruct(C.shape, C.dtype), "wide"),
         (jax.ShapeDtypeStruct(dt_row.shape, f32), "row"),
         (jax.ShapeDtypeStruct(a_row.shape, f32), "row"),
         (jax.ShapeDtypeStruct(a_row.shape, f32), "row"),
         (jax.ShapeDtypeStruct((dims[0], 1, x.shape[2]), f32), "sum")],
        dims, back=True)
    # what is left of the running sums' gradient, back to the log-decays: a
    # position counts in every running sum from it to its chunk's end
    da = da + _in_chunk_sums(dcs, Q, to_the_end=True)
    return dx, dB, dC, da, ddt, jnp.sum(dskip, axis=0)


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _ssd_scan_kernels(x, dt, A, B, C, D, chunk):
    """:func:`ssd_scan`'s whole-sequence call on the kernels; one ``jit``,
    so that a model's layers of one shape share one lowering.  Outside the
    kernels, in ``jax.numpy`` and differentiated by JAX: ``dt`` and the
    log-decays ``dt * A`` laid out by row (2 MB each at the hybrid cell's
    shape), and ``D`` spread over a head's columns."""
    Bsz, T, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    f32 = jnp.float32
    dt_row = dt.astype(f32).reshape(Bsz, T, G, R).transpose(0, 2, 3, 1)
    a_row = dt_row * A.astype(f32).reshape(G, R)[:, :, None]
    skip = (jnp.zeros((1, H * P), f32) if D is None
            else jnp.repeat(D.astype(f32), P)[None])
    y = _scan(chunk, x.reshape(Bsz, T, H * P), B.reshape(Bsz, T, G * N),
              C.reshape(Bsz, T, G * N), a_row, dt_row, skip)
    return y.reshape(Bsz, T, H, P)

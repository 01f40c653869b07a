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
chunk states are float32.  Plain ``jax.numpy``: the backward is autodiff's,
meant to run under the block's ``jax.checkpoint`` (the ``(H, Q, Q)`` decay
tiles live only while one block is differentiated).

Serving carries the recurrence across calls: a prefill chunk is one
:func:`ssd_scan` from a slot's state to a slot's state (``initial_state=`` /
``return_state=``; a position given ``dt = 0`` decays by one and adds
nothing, so the rows past a short tail leave the state alone to the bit),
a decode step one position of it a row (:func:`ssd_step`), and the
convolution's last ``K - 1`` inputs ride beside the state
(``causal_depthwise_conv(tail=)``, :func:`conv_step`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

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
    result is ``(y, final_state)``.  ``y`` is float32.
    """
    Bsz, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Q = chunk
    if T % Q or H % G:
        raise ValueError(f"T={T} must be a multiple of chunk={Q} and "
                         f"H={H} of the groups G={G}")
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


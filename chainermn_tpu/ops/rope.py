"""Rotary position embeddings (RoPE, Su et al. 2021) — the modern
positional scheme (GPT-NeoX/Llama style half-split rotation).

TPU-first shape: the rotation is a pure elementwise map over the projected
``(B, T, H, D)`` q/k — applied OUTSIDE the flash kernel, where XLA fuses it
into the projection epilogue (one HBM round trip, no kernel change);
angles are computed in fp32 regardless of the activation dtype (bf16 loses
the high position bits past ~4k tokens).

Positions are explicit — ``(T,)`` or per-row ``(B, T)`` — so the same
function serves the full training path (``arange``), packed rows
(per-document restart positions), and KV-cache decode (the write
position), and the relative-attention property
``<rope(q, m), rope(k, n)> = f(m − n)`` holds across all of them.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_tables(positions: jnp.ndarray, head_dim: int,
                theta: float = 10000.0):
    """Precomputed ``(cos, sin)`` rotation tables, each ``(..., T, 1,
    head_dim//2)`` — compute ONCE per step and share across layers (every
    decoder block rotates by the same positions; per-block recomputation
    would redo the transcendentals n_layers times, and under remat again
    in the backward)."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head dim, got {head_dim}")
    half = head_dim // 2
    # (half,) inverse frequencies; fp32 throughout the angle math.
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray = None,
               theta: float = 10000.0, tables=None,
               interleaved: bool = False) -> jnp.ndarray:
    """Rotate ``x`` (..., T, H, D) by its ``positions`` ((T,) or (..., T)
    int) — NeoX half-split convention: feature pairs are ``(i, i + D/2)``;
    ``interleaved`` pairs neighbours ``(2i, 2i + 1)`` instead (GPT-J's
    convention, ``rope_gptj``), pair ``i`` turning by the same angle.
    Pass ``tables`` (from :func:`rope_tables`) to reuse precomputed
    cos/sin across layers.

    Returns the same shape/dtype as ``x``.
    """
    D = x.shape[-1]
    if D % 2:
        raise ValueError(f"RoPE needs an even head dim, got {D}")
    half = D // 2
    if tables is None:
        tables = rope_tables(positions, D, theta)
    cos, sin = tables
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)

"""Plain float32 reference of the Falcon-H1 stack (``HybridLM``'s ``F``
layers), written from the published configuration's keys and importing
nothing of the program.  ``u``, ``v`` are RMS norms with eps ``norm_eps``;
no bias but the convolution's::

    x0 = embed[tok] * embedding_multiplier
    u  = RMSNorm_in(h)
    -- Mamba-2 (H heads of P, G groups, state N, convolution of K taps)
    p  = in_proj(u * ssm_in_multiplier) * mup
         mup = ssm_multipliers on the segments [z | x | B | C | dt]
    z, xBC, dt = split(p);  xBC = silu(causal depthwise conv(xBC) + bias)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t . C_t + D x_t
    m  = out_proj(RMSNorm_groups=G(y * silu(z))) * ssm_out_multiplier
    -- attention (grouped queries, RoPE of base rope_theta over the whole head)
    q = Wq(u * attention_in_multiplier);  k = Wk(..) * key_multiplier;  v = Wv(..)
    a  = Wo(softmax(rope(q) rope(k)^T / sqrt(head_dim)) v) * attention_out_multiplier
    h  = h + m + a
    v_ = RMSNorm_ff(h)
    h  = h + down(silu(gate(v_) * mlp_multipliers[0]) * up(v_)) * mlp_multipliers[1]
    logits = head(RMSNorm_f(h)) * lm_head_multiplier

**The recurrence is a sequential ``lax.scan`` over positions**, never the
chunked form the program runs and never a cache; attention is a full masked
softmax.  Layer-streamed like ``transformer_lm.py``: parameters stay in their
storage dtype, one block at a time is upcast, the embedding is gathered
before it is upcast and the head is applied a block of the vocabulary at a
time, so the published model's 10.5 GB of bfloat16 weights, one layer in
float32 and one row's logits fit one chip.  Every matmul is at
``Precision.HIGHEST``.  ``quant`` is the lower-precision control: the
operands of every projection, FFN and head matmul pass through
``transformer_lm.mm``'s fake ``"fp8"`` / ``"int8"``; the recurrence and
attention's scores stay float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.transformer_lm import HI, NEG, mm

VOCAB_BLOCK = 32768  # head columns upcast at once, at most about this many

#: the fields of ``model`` a block's arithmetic depends on (the rest is in
#: the parameters' shapes)
GEOMETRY = ("n_heads", "n_kv_heads", "head_dim", "ssm_heads", "ssm_head_dim",
            "ssm_groups", "ssm_state", "norm_eps", "rope_theta",
            "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
            "attention_in_multiplier", "key_multiplier",
            "attention_out_multiplier", "mlp_multipliers")


def rms(x, w, eps, groups: int = 1):
    g = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return g.reshape(x.shape) * w


# ------------------------------------------------------------ Mamba-2
def recurrence(x, delta, a, B, C, D):
    """``x`` (b, T, H, P), ``delta`` (b, T, H), ``a`` (H,), ``B`` / ``C``
    (b, T, G, N), ``D`` (H,) -> ``y`` (b, T, H, P): one position a step,
    the state (b, H, P, N) from zero."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]

    def one(s, inp):
        x_t, d_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, H // G, axis=1)          # (b, H, N)
        c_h = jnp.repeat(c_t, H // G, axis=1)
        s = (jnp.exp(d_t * a)[..., None, None] * s
             + (d_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return s, jnp.sum(s * c_h[:, :, None, :], axis=-1)

    seq = tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, B, C))
    _, y = jax.lax.scan(jax.checkpoint(one),
                        jnp.zeros((b, H, P, N), jnp.float32), seq)
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


def conv(x, kernel, bias):
    """Causal depthwise: ``out[t] = sum_j kernel[j] x[t - (K-1) + j] + b``."""
    K, T = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * kernel[j] for j in range(K)) + bias


def mamba(p, u, g: Dict[str, Any], quant):
    b, T, _ = u.shape
    H, P, G, N = (g["ssm_heads"], g["ssm_head_dim"], g["ssm_groups"],
                  g["ssm_state"])
    inner, bc = H * P, G * N
    mup = jnp.concatenate([
        jnp.full((w,), m, jnp.float32)
        for w, m in zip((inner, inner, bc, bc, H), g["ssm_multipliers"])])
    proj = mm(u * g["ssm_in_multiplier"], p["in_proj"]["kernel"], 1, quant) * mup
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * bc], -1)
    xbc = jax.nn.silu(conv(xbc, p["conv_kernel"], p["conv_bias"]))
    x, B, C = jnp.split(xbc, [inner, inner + bc], -1)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x.reshape(b, T, H, P), delta, -jnp.exp(p["A_log"]),
                   B.reshape(b, T, G, N), C.reshape(b, T, G, N), p["D"])
    y = rms(y.reshape(b, T, inner) * jax.nn.silu(z), p["gate_norm"],
            g["norm_eps"], groups=G)
    return mm(y, p["out_proj"]["kernel"], 1, quant) * g["ssm_out_multiplier"]


# ---------------------------------------------------------- attention
def rope(x, theta: float):
    """NeoX half-split rotation of ``x`` (b, T, H, Dh) by positions 0..T-1."""
    T, half = x.shape[1], x.shape[-1] // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, u, g: Dict[str, Any], quant):
    b, T, _ = u.shape
    H, KH, Dh = g["n_heads"], g["n_kv_heads"], g["head_dim"]
    u = u * g["attention_in_multiplier"]
    q = mm(u, p["q"]["kernel"], 1, quant)             # (b, T, H, Dh)
    kv = mm(u, p["kv"]["kernel"], 1, quant)           # (b, T, 2, KH, Dh)
    k, v = kv[:, :, 0] * g["key_multiplier"], kv[:, :, 1]
    q, k = rope(q, g["rope_theta"]), rope(k, g["rope_theta"])
    qg = q.reshape(b, T, KH, H // KH, Dh)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k, precision=HI) / math.sqrt(Dh)
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    w = jax.nn.softmax(jnp.where(mask, s, NEG), axis=-1)
    a = jnp.einsum("bkgts,bskd->btkgd", w, v, precision=HI)
    return (mm(a.reshape(b, T, H, Dh), p["proj"]["kernel"], 2, quant)
            * g["attention_out_multiplier"])


# ------------------------------------------------------------ a layer
def block(p, h, *, geometry: Tuple, quant=None):
    """One layer on float32 ``h`` (b, T, D); ``p`` in any dtype."""
    g = dict(geometry)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    u = rms(h, p["norm"], g["norm_eps"])
    h = h + mamba(p, u, g, quant) + attention(p, u, g, quant)
    v = rms(h, p["norm_ff"], g["norm_eps"])
    gate_m, down_m = g["mlp_multipliers"]
    y = (jax.nn.silu(mm(v, p["gate"]["kernel"], 1, quant) * gate_m)
         * mm(v, p["up"]["kernel"], 1, quant))
    return h + mm(y, p["down"]["kernel"], 1, quant) * down_m


def _geometry(model: Dict[str, Any]) -> Tuple:
    kinds = model["layer_kinds"][:model["n_layers"]]
    if set(kinds) != {"F"} or len(kinds) != model["n_layers"]:
        raise ValueError(f"layer_kinds {model['layer_kinds']!r}: this "
                         f"reference is of {model['n_layers']} 'F' layers")
    return tuple((k, tuple(model[k]) if isinstance(model[k], list)
                  else model[k]) for k in GEOMETRY)


def _embed(embedding, tokens, multiplier):
    return embedding[tokens].astype(jnp.float32) * multiplier


@functools.lru_cache(maxsize=None)
def _jitted(geometry: Tuple, quant: Optional[str]):
    return jax.jit(functools.partial(block, geometry=geometry, quant=quant))


def _vocab_blocks(V: int) -> int:
    return next(n for n in range(max(1, V // VOCAB_BLOCK), V + 1) if V % n == 0)


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, multiplier: float, width: int,
                 quant: Optional[str]):
    def columns(norm_f, kernel, h, start):
        """Logits of vocabulary columns ``start .. start + width``."""
        part = jax.lax.dynamic_slice_in_dim(kernel, start, width, axis=1)
        x = rms(h, norm_f.astype(jnp.float32), eps)
        return mm(x, part.astype(jnp.float32), 1, quant) * multiplier

    return jax.jit(columns)


def head_logits(params, h, model: Dict[str, Any], quant=None):
    kernel = params["lm_head"]["kernel"]
    V = kernel.shape[1]
    width = V // _vocab_blocks(V)
    cols = _jitted_head(model["norm_eps"], model["lm_head_multiplier"],
                        width, quant)
    return jnp.concatenate(
        [cols(params["norm_f"], kernel, h, np.int32(s))
         for s in range(0, V, width)], axis=-1)


def hidden(params, tokens, model: Dict[str, Any], quant=None):
    geometry = _geometry(model)
    h = jax.jit(_embed)(params["embed"]["embedding"], tokens,
                        model["embedding_multiplier"])
    for i in range(model["n_layers"]):
        h = _jitted(geometry, quant)(params[f"block_{i}"], h)
    return h


def forward_logits(params, tokens, model: Dict[str, Any], *,
                   quant: Optional[str] = None):
    """Float32 logits (b, T, V) of a full forward, one layer at a time."""
    return head_logits(params, hidden(params, tokens, model, quant), model,
                       quant)


def loss_and_grads(params, tokens, targets, model: Dict[str, Any], *,
                   quant: Optional[str] = None, on_layer_grads=None,
                   offload: bool = False):
    """Mean next-token loss over the targets that are not negative, and its
    gradient by autodiff of the same forward, handed to
    ``on_layer_grads(name, grads)`` one top-level entry of the tree at a
    time.  Whole-tree autodiff in float32: for the sizes of a test — the
    model is served, and no cell trains it (``offload`` is accepted and
    changes nothing)."""
    geometry = _geometry(model)

    def loss_fn(p):
        h = _embed(p["embed"]["embedding"], tokens,
                   model["embedding_multiplier"])
        for i in range(model["n_layers"]):
            h = block(p[f"block_{i}"], h, geometry=geometry, quant=quant)
        x = rms(h, p["norm_f"].astype(jnp.float32), model["norm_eps"])
        logits = (mm(x, p["lm_head"]["kernel"].astype(jnp.float32), 1, quant)
                  * model["lm_head_multiplier"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        mask = (targets >= 0).astype(jnp.float32)
        return jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    if on_layer_grads is not None:
        for name in grads:
            on_layer_grads(name, grads[name])
    return float(loss)

"""Plain float32 reference of the decoder-only LM both configurations run.

Pre-norm blocks, LayerNorm (eps as the program's flax default, 1e-6),
tanh-GELU, biases everywhere, multi-head or grouped-query causal attention,
learned positions or NeoX-style rotary positions (base 10000), and a separate
``lm_head`` with bias.  Where that departs from a model's published config
(tied head, rope base, eps 1e-5) the departure is the program's, listed in the
configuration's file, and mirrored here: the reference computes what the
program is meant to compute, from the same parameters.

Everything is layer-streamed: parameters stay in their storage dtype and one
block at a time is upcast to float32, so a 3B model fits beside nothing else
on one 16 GB chip.  ``quant`` is the lower-precision *control*: the operands of every
projection, feed-forward and head matmul pass through symmetric per-row
absmax ``"int8"``, or scaled ``"fp8"`` (e4m3), with a straight-through
gradient; attention itself stays float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6
ROPE_THETA = 10000.0
NEG = -1e30


def _fake_int8(x, axes):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127.0, 127.0) * s
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x, axes):
    """float8 e4m3 (3 mantissa bits) with a per-row scale that puts the
    row's largest magnitude at the format's largest, 448."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


_FAKE = {"int8": _fake_int8, "fp8": _fake_fp8}


def mm(x, w, n: int, quant: Optional[str]):
    """Contract the last ``n`` axes of ``x`` with the first ``n`` of ``w``."""
    if quant is not None:
        if quant not in _FAKE:
            raise ValueError(f"unknown control precision {quant!r}")
        x = _FAKE[quant](x, tuple(range(x.ndim - n, x.ndim)))
        w = _FAKE[quant](w, tuple(range(n)))
    return jnp.tensordot(x, w, axes=n, precision=HI)


def layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rope(x, positions):
    """NeoX half-split rotation of ``x`` (B, T, H, Dh) by ``positions`` (T,)."""
    half = x.shape[-1] // 2
    inv = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv  # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend_group(q, k, v):
    """One kv head: q (B, T, G, Dh), k/v (B, T, Dh) -> (B, T, G, Dh)."""
    T, Dh = q.shape[1], q.shape[-1]
    s = jnp.einsum("btgd,bsd->bgts", q, k, precision=HI) / math.sqrt(Dh)
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(mask[None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgts,bsd->btgd", p, v, precision=HI)


def attention(q, k, v):
    """Causal attention, q (B, T, H, Dh), k/v (B, T, KH, Dh); one kv head
    at a time so the (T, T) scores of all heads never live at once."""
    B, T, H, Dh = q.shape
    KH = k.shape[2]
    qg = jnp.moveaxis(q.reshape(B, T, KH, H // KH, Dh), 2, 0)
    out = jax.lax.map(
        lambda a: jax.checkpoint(_attend_group)(*a),
        (qg, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)),
    )  # (KH, B, T, G, Dh)
    return jnp.moveaxis(out, 0, 2).reshape(B, T, H, Dh)


def block(p, h, *, use_rope: bool, quant: Optional[str] = None):
    """One decoder block on float32 ``h`` (B, T, D); ``p`` in any dtype."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x = layer_norm(h, p["ln1"])
    if "qkv" in p:
        qkv = mm(x, p["qkv"]["kernel"], 1, quant) + p["qkv"]["bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    else:
        q = mm(x, p["q"]["kernel"], 1, quant) + p["q"]["bias"]
        kv = mm(x, p["kv"]["kernel"], 1, quant) + p["kv"]["bias"]
        k, v = kv[:, :, 0], kv[:, :, 1]
    if use_rope:
        pos = jnp.arange(h.shape[1])
        q, k = rope(q, pos), rope(k, pos)
    a = attention(q, k, v)
    h = h + mm(a, p["proj"]["kernel"], 2, quant) + p["proj"]["bias"]
    x = layer_norm(h, p["ln2"])
    y = gelu_tanh(mm(x, p["ff1"]["kernel"], 1, quant) + p["ff1"]["bias"])
    return h + mm(y, p["ff2"]["kernel"], 1, quant) + p["ff2"]["bias"]


def embed(params, tokens):
    h = params["embed"]["embedding"].astype(jnp.float32)[tokens]
    if "pos" in params:
        h = h + params["pos"].astype(jnp.float32)[None, : tokens.shape[1]]
    return h


def head_logits(ln_f, lm_head, h, quant: Optional[str] = None):
    ln_f, lm_head = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), (ln_f, lm_head))
    x = layer_norm(h, ln_f)
    return mm(x, lm_head["kernel"], 1, quant) + lm_head["bias"]


def _n_layers(params) -> int:
    return sum(1 for k in params if k.startswith("block_"))


@functools.lru_cache(maxsize=None)
def _jitted(use_rope: bool, quant: Optional[str]):
    blk = functools.partial(block, use_rope=use_rope, quant=quant)
    fwd = jax.jit(blk)

    def bwd(p, h, ct):
        _, vjp = jax.vjp(blk, p, h)
        gp, gh = vjp(ct)
        return gp, gh

    def head_loss(ln_f, lm_head, h, targets):
        logits = head_logits(ln_f, lm_head, h, quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        mask = (targets >= 0).astype(jnp.float32)
        return jnp.sum((lse - picked) * mask)

    head = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
    logits = jax.jit(functools.partial(head_logits, quant=quant))
    return fwd, jax.jit(bwd), head, logits


def forward_logits(params, tokens, *, use_rope: bool,
                   quant: Optional[str] = None, keep_from: int = 0):
    """Float32 logits (B, T - keep_from, V) of a full forward over
    ``tokens`` (B, T), one block at a time."""
    fwd, _, _, logits = _jitted(use_rope, quant)
    h = jax.jit(embed)(
        {k: params[k] for k in ("embed", "pos") if k in params}, tokens)
    for i in range(_n_layers(params)):
        h = fwd(params[f"block_{i}"], h)
    return logits(params["ln_f"], params["lm_head"], h[:, keep_from:])


def _sq(tree):
    return jax.tree_util.tree_map(
        lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), tree)


def loss_and_grads(params, tokens, targets, *, use_rope: bool,
                   quant: Optional[str] = None, on_layer_grads=None,
                   offload: bool = False):
    """Mean next-token loss over all rows and, leaf by leaf, the gradient
    of it — handed to ``on_layer_grads(name, grads)`` one top-level entry at
    a time (``block_i``, ``ln_f``, ``lm_head``, ``embed``, ``pos``) so that
    no more than one block's float32 gradients ever live at once.  Rows
    are processed one at a time ("in blocks of rows"); ``offload`` keeps the
    stored block inputs in host memory (several rows of a 3B model do not
    fit beside the parameters on one chip)."""
    fwd, bwd, head, _ = _jitted(use_rope, quant)
    B, T = tokens.shape
    L = _n_layers(params)
    n_tok = float(np.sum(np.asarray(targets) >= 0))
    emb = jax.jit(embed)
    emb_p = {k: params[k] for k in ("embed", "pos") if k in params}
    acts: List[List[Any]] = []
    keep = (lambda h: np.asarray(h)) if offload else (lambda h: h)
    for r in range(B):
        h = emb(emb_p, tokens[r:r + 1])
        hs = [keep(h)]
        for i in range(L):
            h = fwd(params[f"block_{i}"], h)
            hs.append(keep(h) if i < L - 1 else h)
        acts.append(hs)
    loss = 0.0
    cts, g_lnf, g_head = [], None, None
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    scale = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x / n_tok, t))
    for r in range(B):
        l, (a, b, ct) = head(params["ln_f"], params["lm_head"], acts[r][L],
                             targets[r:r + 1])
        loss += float(l)
        g_lnf = a if g_lnf is None else add(g_lnf, a)
        g_head = b if g_head is None else add(g_head, b)
        cts.append(ct)
        acts[r][L] = None
    if on_layer_grads is not None:
        on_layer_grads("ln_f", scale(g_lnf))
        on_layer_grads("lm_head", scale(g_head))
    del g_lnf, g_head
    for i in reversed(range(L)):
        g = None
        for r in range(B):
            gp, cts[r] = bwd(params[f"block_{i}"], jnp.asarray(acts[r][i]),
                             cts[r])
            g = gp if g is None else add(g, gp)
            acts[r][i] = None
        if on_layer_grads is not None:
            on_layer_grads(f"block_{i}", scale(g))
        del g
    if on_layer_grads is not None:
        V, D = params["embed"]["embedding"].shape

        def embed_grad(toks, ct):
            return jnp.zeros((V, D), jnp.float32).at[toks.reshape(-1)].add(
                ct.reshape(-1, D)) / n_tok

        on_layer_grads("embed", {"embedding": jax.jit(embed_grad)(
            tokens, jnp.concatenate(cts, 0))})
        if "pos" in params:
            g = sum(cts)[0] / n_tok
            pad = params["pos"].shape[0] - T
            on_layer_grads("pos", jnp.pad(g, ((0, pad), (0, 0))))
    return loss / n_tok

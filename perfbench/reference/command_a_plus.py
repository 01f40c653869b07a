"""Plain float32 reference of the Command A+ stack (``HybridLM``'s ``W`` /
``G`` layers), written from the published configuration's keys and importing
nothing of the program.  For layer ``l`` of kind ``layer_kinds[l]``::

    u   = LN(h): (h - mean) * rsqrt(var + layer_norm_eps) * w   (no bias)
    -- attention (128 query / 8 KV heads of 128, no bias, no q/k norm)
    q = u Wq;  k = u Wk;  v = u Wv;  scores = q k^T / sqrt(head_dim)
    W (sliding_attention): q, k rotated over the whole head in INTERLEAVED
       pairs (2i, 2i+1) at base rope_theta (rope_gptj); query i sees keys
       i - sliding_window < j <= i
    G (full_attention): no positional encoding; query i sees j <= i
    a   = softmax(scores) v, regrouped, Wo
    -- experts, of the SAME u
    s   = sigmoid(u Wr) in float32 over all experts_held * ep_of columns
    the num_experts_per_tok largest s chosen (no bias term)
    w   = s_chosen / sum s_chosen                        (norm_topk_prob)
    routed = sum over the chosen experts THIS SHARE HOLDS of
             w_e Wdown_e( silu(Wgate_e u) * Wup_e u )
    shared = 1/n_shared sum_j Wdown_j( silu(Wgate_j u) * Wup_j u )
    ffn = routed + shared
    h   = h + a + ffn
    logits = logit_scale * LN(h) E^T                     (tie_word_embeddings)

The share is the model's: ``experts_held`` of ``experts_held * ep_of``
experts, the contiguous range of ``ep_index``, and the sliced vocabulary; what
the absent experts would have added is left out of the sum, here as in the
program.  Gate and up of an expert are the halves ``[gate | up]`` of one
``(D, 2 F)`` matrix, the shared experts' the same with the four laid side by
side in each half; the reference takes them apart again and runs one expert
at a time.

**It has to fit beside the bfloat16 parameters of the published width**
(9.5 GB of a 16 GB chip, for one ``(1, 14336)`` row): parameters stay in their
storage dtype and one matrix at a time is upcast — an expert at a time, never
a layer (4.6 GB in float32) — and the queries go a block of ``QUERY_BLOCK``
at a time and a KV head at a time (a row's scores whole are 128 x 14336^2
floats); a window layer's block reads the ``sliding_window + QUERY_BLOCK``
keys it can see and no others.  Every matmul is at ``Precision.HIGHEST``.
``quant`` is the lower-precision control: the operands of every projection,
expert and head matmul pass through ``transformer_lm.mm``'s fake ``"fp8"`` /
``"int8"``; the router and attention's scores stay float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from perfbench.reference.transformer_lm import HI, NEG, mm

QUERY_BLOCK = 256

#: the fields of ``model`` a block's arithmetic depends on (the rest is in
#: the parameters' shapes)
GEOMETRY = ("n_heads", "n_kv_heads", "head_dim", "window", "rope_theta",
            "norm_eps", "experts_held", "ep_of", "ep_index",
            "experts_per_tok", "d_expert", "n_shared")

F32 = jnp.float32


def layer_norm(x, w, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rope_interleaved(x, positions, theta: float):
    """``x`` (T, H, Dh) rotated in pairs ``(2i, 2i + 1)`` by ``positions``
    (T,): pair ``i`` turns by ``position * theta ** (-2 i / Dh)``."""
    half = x.shape[-1] // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


# ---------------------------------------------------------- attention
def attention(p, u, g: Dict[str, Any], windowed: bool, quant):
    """``u`` (T, D) -> (T, D): the queries a block of ``QUERY_BLOCK`` at a
    time, a KV head at a time."""
    T, D = u.shape
    H, KH, Dh = g["n_heads"], g["n_kv_heads"], g["head_dim"]
    G, W = H // KH, (g["window"] if windowed else 0)
    wq, wkv, wo = (p["q"]["kernel"], p["kv"]["kernel"], p["proj"]["kernel"])
    kv = mm(u, wkv.astype(F32), 1, quant)              # (T, 2, KH, Dh)
    k, v = kv[:, 0], kv[:, 1]
    if windowed:
        k = rope_interleaved(k, jnp.arange(T), g["rope_theta"])
    QB = min(QUERY_BLOCK, T)
    n_blocks = -(-T // QB)
    pad = n_blocks * QB - T
    # the keys a block sees: all of them (G), or the span of W - 1 + QB that
    # ends at the block's last query (W) — keys laid out behind W - 1 rows
    # that stand for positions below 0 and are masked as such
    span = W - 1 + QB if windowed else n_blocks * QB
    lead = W - 1 if windowed else 0
    k = jnp.pad(k, ((lead, pad), (0, 0), (0, 0)))
    v = jnp.pad(v, ((lead, pad), (0, 0), (0, 0)))
    up = jnp.pad(u, ((0, pad), (0, 0)))
    wq, wo = wq.astype(F32), wo.astype(F32)

    def block(i):
        q0 = i * QB
        q_pos = q0 + jnp.arange(QB)
        q = mm(jax.lax.dynamic_slice_in_dim(up, q0, QB), wq, 1, quant)
        if windowed:
            q = rope_interleaved(q, q_pos, g["rope_theta"])
        start = q0 if windowed else 0
        ks = jax.lax.dynamic_slice_in_dim(k, start, span)
        vs = jax.lax.dynamic_slice_in_dim(v, start, span)
        k_pos = start - lead + jnp.arange(span)
        seen = (k_pos[None] <= q_pos[:, None]) & (k_pos[None] >= 0)
        if windowed:
            seen &= k_pos[None] > q_pos[:, None] - W

        def head(h):
            qh = jax.lax.dynamic_slice_in_dim(q, h * G, G, axis=1)
            s = jnp.einsum("tgd,sd->gts", qh, ks[:, h], precision=HI) \
                / math.sqrt(Dh)
            w = jax.nn.softmax(jnp.where(seen[None], s, NEG), axis=-1)
            return jnp.einsum("gts,sd->tgd", w, vs[:, h], precision=HI)

        a = jax.lax.map(head, jnp.arange(KH))          # (KH, QB, G, Dh)
        a = jnp.moveaxis(a, 0, 1).reshape(QB, H, Dh)
        return mm(a, wo, 2, quant)

    out = jax.lax.map(block, jnp.arange(n_blocks))
    return out.reshape(n_blocks * QB, D)[:T]


# ------------------------------------------------------------ experts
def gated(u, gate_up, down, quant):
    """One gated expert: ``down( silu(gate u) * up u )`` with ``gate_up``
    (D, 2 F) holding ``[gate | up]``."""
    F = gate_up.shape[1] // 2
    gate = mm(u, gate_up[:, :F], 1, quant)
    up = mm(u, gate_up[:, F:], 1, quant)
    return mm(jax.nn.silu(gate) * up, down, 1, quant)


def experts(p, u, g: Dict[str, Any], quant):
    """``routed + shared`` for ``u`` (T, D): the held experts one at a time,
    each over every token and weighted by the router's choice of it (0 for a
    token that did not choose it), then the shared experts one at a time."""
    held, k = g["experts_held"], g["experts_per_tok"]
    lo = g["ep_index"] * held
    s = jax.nn.sigmoid(jnp.dot(u, p["router"].astype(F32), precision=HI))
    top, chosen = jax.lax.top_k(s, k)
    w = top / jnp.sum(top, axis=-1, keepdims=True)

    def one(acc, e):
        w_e = jnp.sum(jnp.where(chosen == lo + e, w, 0.0), axis=-1)
        y = gated(u, p["experts_gate_up"][e].astype(F32),
                  p["experts_down"][e].astype(F32), quant)
        return acc + w_e[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))

    n, F = g["n_shared"], g["d_expert"]
    gate_up, down = p["shared_gate_up"]["kernel"], p["shared_down"]["kernel"]

    def shared(acc, j):
        cols = jnp.concatenate([
            jax.lax.dynamic_slice_in_dim(gate_up, j * F, F, axis=1),
            jax.lax.dynamic_slice_in_dim(gate_up, (n + j) * F, F, axis=1),
        ], axis=1)
        rows = jax.lax.dynamic_slice_in_dim(down, j * F, F, axis=0)
        return acc + gated(u, cols.astype(F32), rows.astype(F32), quant), None

    total, _ = jax.lax.scan(shared, jnp.zeros_like(u), jnp.arange(n))
    return routed + total / n


# ------------------------------------------------------------ a layer
def block(p, h, *, geometry: Tuple, windowed: bool, quant=None):
    """One layer on float32 ``h`` (T, D); ``p`` in its storage dtype."""
    g = dict(geometry)
    u = layer_norm(h, p["norm"].astype(F32), g["norm_eps"])
    return h + attention(p, u, g, windowed, quant) + experts(p, u, g, quant)


def _geometry(model: Dict[str, Any]) -> Tuple:
    kinds = model["layer_kinds"][:model["n_layers"]]
    if set(kinds) - set("WG") or len(kinds) != model["n_layers"]:
        raise ValueError(f"layer_kinds {model['layer_kinds']!r}: this "
                         f"reference is of {model['n_layers']} 'W' / 'G' "
                         "layers")
    for key, want in (("norm", "layer"), ("rope_interleaved", True),
                      ("tie_embeddings", True)):
        if model.get(key) != want:
            raise ValueError(f"model.{key} = {model.get(key)!r}: this "
                             f"reference is written for {want!r}")
    if model["d_shared"] != model["n_shared"] * model["d_expert"]:
        raise ValueError("the shared experts are n_shared experts of the "
                         "routed experts' width, fused")
    return tuple((k, model.get(k, 0)) for k in GEOMETRY)


@functools.lru_cache(maxsize=None)
def _jitted(geometry: Tuple, windowed: bool, quant: Optional[str]):
    return jax.jit(functools.partial(block, geometry=geometry,
                                     windowed=windowed, quant=quant))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, scale: float, quant: Optional[str]):
    def logits(norm_f, embedding, h):
        x = layer_norm(h, norm_f.astype(F32), eps)
        return mm(x, embedding.astype(F32).T, 1, quant) * scale

    return jax.jit(logits)


def hidden(params, tokens, model: Dict[str, Any], quant=None):
    """The rows of ``tokens`` (b, T) one after another, a layer at a time."""
    geometry = _geometry(model)
    kinds = model["layer_kinds"]
    rows = []
    for row in tokens:
        h = params["embed"]["embedding"][row].astype(F32)
        for i in range(model["n_layers"]):
            h = _jitted(geometry, kinds[i] == "W", quant)(
                params[f"block_{i}"], h)
        rows.append(h)
    return jnp.stack(rows)


def forward_logits(params, tokens, model: Dict[str, Any], *,
                   quant: Optional[str] = None):
    """Float32 logits (b, T, V) of a full forward, one layer at a time."""
    head = _jitted_head(model["norm_eps"],
                        float(model.get("lm_head_multiplier", 1)), quant)
    h = hidden(params, tokens, model, quant)
    return jnp.stack([head(params["norm_f"], params["embed"]["embedding"], x)
                      for x in h])


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
    kinds = model["layer_kinds"]

    def row_loss(p, row, want):
        h = p["embed"]["embedding"][row].astype(F32)
        for i in range(model["n_layers"]):
            h = block(p[f"block_{i}"], h, geometry=geometry,
                      windowed=kinds[i] == "W", quant=quant)
        x = layer_norm(h, p["norm_f"].astype(F32), model["norm_eps"])
        logits = (mm(x, p["embed"]["embedding"].astype(F32).T, 1, quant)
                  * model.get("lm_head_multiplier", 1))
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(want, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - picked) * (want >= 0))

    def loss_fn(p):
        total = sum(row_loss(p, r, t) for r, t in zip(tokens, targets))
        return total / jnp.maximum(jnp.sum(targets >= 0), 1)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    if on_layer_grads is not None:
        for name in grads:
            on_layer_grads(name, grads[name])
    return float(loss)

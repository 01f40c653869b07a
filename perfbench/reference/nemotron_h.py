"""Plain float32 reference of the Nemotron-H stack (``HybridLM``'s
equations, written from the published description and importing nothing of
the program).

Every layer is ``h <- h + mixer(RMSNorm(h))``, eps from the model's
``norm_eps``, no bias but the convolution's; the kind of mixer is the
layer's letter in ``layer_kinds``:

* ``M`` — Mamba-2: ``[z | xBC | dt] = u . W_in``; ``xBC <- silu(causal
  depthwise conv(xBC) + b)``; ``x`` (H heads of P), ``B``, ``C`` (G groups
  of N, a group shared by H / G heads); ``delta = softplus(dt + dt_bias)``,
  ``a = -exp(A_log)``; **the recurrence over time**, one position a step of
  a ``lax.scan`` — ``S_t = exp(delta_t a) S_{t-1} + delta_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t`` — never the chunked form the program runs;
  ``y <- RMSNorm_groups(y * silu(z)) * w``; ``out = y . W_out``.
* ``*`` — causal grouped-query attention, no positional encoding.
* ``E`` — ``s = sigmoid(u . W_g)`` over all ``experts_held * ep_of``
  experts; the ``k`` largest of ``s + e_bias``; weights ``s`` of the chosen
  over their sum, times ``routed_scale``; expert ``e``: ``W_down,e .
  relu(W_up,e . u)^2``, **one held expert at a time over every token, with
  a mask** — no sort, no grouped matmul; plus the shared expert.  It is
  given the same share as the program: the experts ``ep_index * held ..``
  are summed, the others left out.  ``e_bias`` is the constant
  ``0.02 sin(0.5 + 1.7 layer + 2.3 j)``.

Layer-streamed like ``transformer_lm.py``: parameters stay in their storage
dtype, one block at a time is upcast.  The time scan is rematerialised by
segments and attention by blocks of query rows, so the backward fits at
T = 8192.  ``quant`` is the lower-precision control: the operands of every
projection, expert and head matmul pass through ``transformer_lm.mm``'s
fake ``"fp8"`` / ``"int8"``; the router, the recurrence and attention's
scores stay float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.transformer_lm import HI, NEG, mm

SCAN_SEGMENT = 128   # positions of the time scan kept per remat segment
QUERY_BLOCK = 1024   # query rows of attention scored at once

#: the fields of ``model`` a block's arithmetic depends on (the rest is in
#: the parameters' shapes)
GEOMETRY = ("n_heads", "n_kv_heads", "head_dim", "ssm_heads", "ssm_head_dim",
            "ssm_groups", "ssm_state", "experts_held", "ep_of", "ep_index",
            "experts_per_tok", "routed_scale", "norm_eps")


def router_bias(layer: int, n_experts: int):
    j = jnp.arange(n_experts, dtype=jnp.float32)
    return 0.02 * jnp.sin(0.5 + 1.7 * layer + 2.3 * j)


def rms(x, w, eps, groups: int = 1):
    g = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return g.reshape(x.shape) * w


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ---------------------------------------------------------------- M
def recurrence(x, delta, a, B, C, D):
    """``x`` (b, T, H, P), ``delta`` (b, T, H), ``a`` (H,), ``B`` / ``C``
    (b, T, G, N), ``D`` (H,) -> ``y`` (b, T, H, P); the state (b, H, P, N)
    starts at zero."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    seg = math.gcd(T, SCAN_SEGMENT)

    def one(s, inp):
        x_t, d_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, H // G, axis=1)          # (b, H, N)
        c_h = jnp.repeat(c_t, H // G, axis=1)
        s = (jnp.exp(d_t * a)[..., None, None] * s
             + (d_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return s, jnp.sum(s * c_h[:, :, None, :], axis=-1)

    @jax.checkpoint
    def segment(s, inp):
        return jax.lax.scan(one, s, inp)

    seq = tuple(jnp.moveaxis(v, 1, 0).reshape((T // seg, seg) + v.shape[:1]
                                              + v.shape[2:])
                for v in (x, delta, B, C))
    _, y = jax.lax.scan(segment, jnp.zeros((b, H, P, N), jnp.float32), seq)
    y = jnp.moveaxis(y.reshape((T, b, H, P)), 0, 1)
    return y + D[:, None] * x


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
    zxbcdt = mm(u, p["in_proj"]["kernel"], 1, quant)
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], -1)
    xbc = jax.nn.silu(conv(xbc, p["conv_kernel"], p["conv_bias"]))
    x, B, C = jnp.split(xbc, [inner, inner + bc], -1)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x.reshape(b, T, H, P), delta, -jnp.exp(p["A_log"]),
                   B.reshape(b, T, G, N), C.reshape(b, T, G, N), p["D"])
    y = rms(y.reshape(b, T, inner) * jax.nn.silu(z), p["gate_norm"],
            g["norm_eps"], groups=G)
    return mm(y, p["out_proj"]["kernel"], 1, quant)


# ---------------------------------------------------------------- *
def _attend_rows(q, k, v, q0):
    """Query rows ``q0 ..`` of one kv head: q (b, tq, G, Dh), k / v
    (b, T, Dh)."""
    tq, T, Dh = q.shape[1], k.shape[1], q.shape[-1]
    s = jnp.einsum("btgd,bsd->bgts", q, k, precision=HI) / math.sqrt(Dh)
    mask = (q0 + jnp.arange(tq))[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, NEG), axis=-1)
    return jnp.einsum("bgts,bsd->btgd", p, v, precision=HI)


def attention(p, u, g: Dict[str, Any], quant):
    b, T, _ = u.shape
    H, KH, Dh = g["n_heads"], g["n_kv_heads"], g["head_dim"]
    q = mm(u, p["q"]["kernel"], 1, quant)             # (b, T, H, Dh)
    kv = mm(u, p["kv"]["kernel"], 1, quant)           # (b, T, 2, KH, Dh)
    k, v = kv[:, :, 0], kv[:, :, 1]
    tq = math.gcd(T, QUERY_BLOCK)
    # (kv head, block of rows, b, tq, group, Dh)
    qg = jnp.moveaxis(q.reshape(b, T // tq, tq, KH, H // KH, Dh), (3, 1), (0, 1))
    starts = jnp.arange(T // tq) * tq

    def head(args):
        q_h, k_h, v_h = args
        return jax.lax.map(
            lambda qa: jax.checkpoint(_attend_rows)(qa[0], k_h, v_h, qa[1]),
            (q_h, starts))

    out = jax.lax.map(head, (qg, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    a = jnp.moveaxis(out, (0, 1), (3, 1)).reshape(b, T, H, Dh)
    return mm(a, p["proj"]["kernel"], 2, quant)


# ---------------------------------------------------------------- E
def route(p, flat, e_bias, g: Dict[str, Any]):
    """``(experts, weights)`` (n, k): float32 at the highest precision,
    whatever ``quant`` is."""
    s = jax.nn.sigmoid(jnp.dot(flat, p["router"], precision=HI))
    _, chosen = jax.lax.top_k(s + e_bias, g["experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / jnp.sum(w, -1, keepdims=True) * g["routed_scale"]


def experts(p, u, e_bias, g: Dict[str, Any], quant):
    b, T, D = u.shape
    flat = u.reshape(b * T, D)
    chosen, w = route(p, flat, e_bias, g)
    lo = g["ep_index"] * g["experts_held"]

    @jax.checkpoint
    def one(y, args):
        e, up, down = args
        gate = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)   # (n,)
        return y + gate[:, None] * mm(relu2(mm(flat, up, 1, quant)), down,
                                      1, quant), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(flat),
        (lo + jnp.arange(g["experts_held"]), p["experts_up"],
         p["experts_down"]))
    y = y + mm(relu2(mm(flat, p["shared_up"]["kernel"], 1, quant)),
               p["shared_down"]["kernel"], 1, quant)
    return y.reshape(b, T, D)


# ------------------------------------------------------------ a layer
def block(p, h, e_bias, *, kind: str, geometry: Tuple, quant=None):
    """One layer on float32 ``h`` (b, T, D); ``p`` in any dtype."""
    g = dict(geometry)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    u = rms(h, p["norm"], g["norm_eps"])
    if kind == "M":
        return h + mamba(p, u, g, quant)
    if kind == "*":
        return h + attention(p, u, g, quant)
    if kind == "E":
        return h + experts(p, u, e_bias, g, quant)
    raise ValueError(f"layer kind {kind!r}: expected one of 'M', '*', 'E'")


def head_logits(norm_f, lm_head, h, eps, quant=None):
    norm_f, lm_head = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), (norm_f, lm_head))
    return mm(rms(h, norm_f, eps), lm_head["kernel"], 1, quant)


def _embed(embedding, tokens):
    return embedding.astype(jnp.float32)[tokens]


def _shape(model: Dict[str, Any]):
    kinds = model["layer_kinds"][:model["n_layers"]]
    if len(kinds) != model["n_layers"]:
        raise ValueError("layer_kinds is shorter than n_layers")
    return kinds, tuple((k, model[k]) for k in GEOMETRY)


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, geometry: Tuple, quant: Optional[str]):
    blk = functools.partial(block, kind=kind, geometry=geometry, quant=quant)

    def bwd(p, h, e_bias, ct):
        _, vjp = jax.vjp(lambda p_, h_: blk(p_, h_, e_bias), p, h)
        return vjp(ct)

    return jax.jit(blk), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, quant: Optional[str]):
    def head_loss(norm_f, lm_head, h, targets):
        logits = head_logits(norm_f, lm_head, h, eps, quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        mask = (targets >= 0).astype(jnp.float32)
        return jnp.sum((lse - picked) * mask)

    return (jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1, 2))),
            jax.jit(functools.partial(head_logits, eps=eps, quant=quant)))


def _n_experts(model) -> int:
    return model["experts_held"] * model["ep_of"]


def forward_logits(params, tokens, model: Dict[str, Any], *,
                   quant: Optional[str] = None):
    """Float32 logits (b, T, V) of a full forward, one layer at a time."""
    kinds, geometry = _shape(model)
    h = jax.jit(_embed)(params["embed"]["embedding"], tokens)
    for i, kind in enumerate(kinds):
        fwd, _ = _jitted(kind, geometry, quant)
        h = fwd(params[f"block_{i}"], h, router_bias(i, _n_experts(model)))
    _, logits = _jitted_head(model["norm_eps"], quant)
    return logits(params["norm_f"], params["lm_head"], h)


def loss_and_grads(params, tokens, targets, model: Dict[str, Any], *,
                   quant: Optional[str] = None, on_layer_grads=None,
                   offload: bool = False):
    """Mean next-token loss over all rows and the gradient of it, handed to
    ``on_layer_grads(name, grads)`` one top-level entry of the tree at a
    time (``norm_f``, ``lm_head``, ``block_i`` from the last to the first,
    ``embed``).  Rows go through one at a time; ``offload`` keeps the stored
    layer inputs in host memory."""
    kinds, geometry = _shape(model)
    L = len(kinds)
    biases = [router_bias(i, _n_experts(model)) for i in range(L)]
    head, _ = _jitted_head(model["norm_eps"], quant)
    B, T = tokens.shape
    n_tok = float(np.sum(np.asarray(targets) >= 0))
    emb = jax.jit(_embed)
    keep = (lambda h: np.asarray(h)) if offload else (lambda h: h)
    acts: List[List[Any]] = []
    for r in range(B):
        h = emb(params["embed"]["embedding"], tokens[r:r + 1])
        hs = [keep(h)]
        for i, kind in enumerate(kinds):
            h = _jitted(kind, geometry, quant)[0](
                params[f"block_{i}"], h, biases[i])
            hs.append(keep(h) if i < L - 1 else h)
        acts.append(hs)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    scale = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x / n_tok, t))
    loss, cts, g_norm, g_head = 0.0, [], None, None
    for r in range(B):
        l, (a, b, ct) = head(params["norm_f"], params["lm_head"], acts[r][L],
                             targets[r:r + 1])
        loss += float(l)
        g_norm = a if g_norm is None else add(g_norm, a)
        g_head = b if g_head is None else add(g_head, b)
        cts.append(ct)
        acts[r][L] = None
    if on_layer_grads is not None:
        on_layer_grads("norm_f", scale(g_norm))
        on_layer_grads("lm_head", scale(g_head))
    del g_norm, g_head
    for i in reversed(range(L)):
        bwd = _jitted(kinds[i], geometry, quant)[1]
        g = None
        for r in range(B):
            gp, cts[r] = bwd(params[f"block_{i}"], jnp.asarray(acts[r][i]),
                             biases[i], cts[r])
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
    return loss / n_tok

"""A decoder-only LM whose depth is a string of layer kinds, one letter a
published layer.  The Nemotron-H family's shape: every layer is
``h <- h + mixer(RMSNorm(h))`` with ONE mixer, its kind read from
``layer_kinds[i]``:

* ``M`` — a Mamba-2 mixer: one input projection to ``[z | xBC | dt]``, a
  causal depthwise convolution and SiLU over ``xBC``, the state-space
  recurrence through :func:`~chainermn_tpu.ops.ssd_scan.ssd_scan`, a gated
  grouped RMS norm and the output projection;
* ``*`` — causal grouped-query attention with a free ``head_dim`` and no
  positional encoding (the Mamba layers carry position), on the flash kernel;
* ``E`` — a mixture of experts: sigmoid router over all
  ``experts_held * ep_of`` experts, the ``experts_held`` this chip holds
  through :func:`~chainermn_tpu.parallel.held_experts.held_experts_ffn`
  (dropless), plus a shared expert every token takes; ``relu(.)**2`` MLPs.
  The routed experts elsewhere are left out of the sum: ``ep_of = 1`` holds
  them all.

And the Falcon-H1 family's, whose layer runs TWO mixers side by side:

* ``F`` — a whole Falcon-H1 block: ``h <- h + m + a`` with ``m`` the
  Mamba-2 mixer of ``M`` and ``a`` grouped-query attention with RoPE over
  the whole head, both of one ``RMSNorm(h)``; then a SwiGLU,
  ``h <- h + down(silu(gate(v)) * up(v))`` of a second norm ``v``.  The
  family's fourteen muP multipliers (``embedding_multiplier``,
  ``ssm_in_multiplier``, the five ``ssm_multipliers`` on the input
  projection's segments ``[z | x | B | C | dt]``, ``ssm_out_multiplier``,
  ``attention_in_multiplier``, ``key_multiplier``,
  ``attention_out_multiplier``, the two ``mlp_multipliers`` on the gate and
  on the FFN's result, ``lm_head_multiplier``) are fields whose default, 1,
  adds no operation.

And the Command A+ family's, whose layer runs attention and its experts side
by side:

* ``W`` / ``G`` — one **parallel block**, ``h <- h + a + ffn`` with both
  halves of one LayerNorm ``u`` (``norm="layer"``): grouped-query attention
  — ``W`` over the last ``window`` positions with RoPE in interleaved pairs
  (``rope_interleaved``), ``G`` over the whole context with no positional
  encoding — and a mixture of experts in ``E``'s shape with a **gated**
  activation: sigmoid router over all ``experts_held * ep_of`` experts with
  no bias, the held range through ``held_experts_ffn`` with gate and up as
  one ``(held, D, 2 F)`` product, and ``n_shared`` shared experts as one
  fused SwiGLU of width ``d_shared`` whose result is divided by
  ``n_shared`` (their average).  ``tie_embeddings`` reads the head from the
  embedding.

RMS norms (a LayerNorm where ``norm`` says so), no bias anywhere but the
convolution's, an untied bias-free head unless tied.
Trains through :func:`~chainermn_tpu.models.lm_loss_chunked` like
:class:`~chainermn_tpu.models.TransformerLM` (``return_hidden=True``, the
head read from ``lm_head/kernel``); each block is under ``jax.checkpoint``
when ``remat``.

**Decode path** (``F``, ``W`` and ``G`` layers; the calling convention
:class:`~chainermn_tpu.serving.DecodeEngine` uses for ``TransformerLM``):
``cache`` holds one entry a layer — the paged ``{"kv"}`` pool attention
writes and reads through ``block_tables``
(:mod:`chainermn_tpu.ops.decode_attention`), and beside it the layer's
**recurrent state by slot**, ``{"ssm": (slots, H, P, N) float32, "conv":
(slots, K - 1, inner + 2 G N)}`` (:meth:`HybridLM.state_shapes`).  Decode
rows (``T == 1``, ``decode_pos`` per row) are the slots in order: row ``r``
advances slot ``r``'s state by one position
(:func:`~chainermn_tpu.ops.ssd_scan.ssd_step`, scope ``ssm.step``), a row
``slot_mask`` leaves out keeps it to the bit.  A prefill chunk
(``T > 1``, one scalar ``decode_pos``, one table) and the ``chunk_rows``
that ride a decode step are ONE sequence of slot ``state_slot``: the
chunked scan from that slot's state to that slot's state (``ssm.scan``),
over the first ``chunk_len`` rows — the rows past a short tail are given
``dt = 0`` and the convolution's tail is taken at the last real position.
A chunk that starts at position 0 starts from zeros, inside the program:
that is what makes a used slot's next request, and an evicted request's
recompute, right.  A ``G`` layer's entry is the paged ``{"kv"}`` alone; a
``W`` layer keeps no blocks of the pool but a **ring by slot**, ``{"ring":
(slots, R, block_len, KH * 2 * Dh)}`` (:meth:`HybridLM.ring_shapes`;
:func:`~chainermn_tpu.ops.decode_attention.ring_attend`): O(window) a slot
whatever the context, its table worked out inside the program from the
positions, masked by absolute position and so never zeroed.  Decode rows and
a riding chunk's rows go through ONE call of the expert layer; rows
``slot_mask`` leaves out are routed to no expert.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.ops.decode_attention import (
    paged_attend,
    pool_shapes,
    pool_write,
    ring_attend,
    ring_blocks,
    ring_write,
)
from chainermn_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
    resolve_attention,
)
from chainermn_tpu.ops.rope import apply_rope, rope_tables
from chainermn_tpu.ops.ssd_scan import (
    causal_depthwise_conv,
    conv_step,
    conv_tail,
    ssd_scan,
    ssd_step,
)
from chainermn_tpu.models.transformer import _wants_paged_kernel
from chainermn_tpu.parallel.held_experts import (
    held_experts_ffn,
    held_range,
    relu2,
    sigmoid_topk_route,
    swiglu,
)

LAYER_KINDS = "M*EFWG"
#: the kinds with a decode path
CACHED_KINDS = "FWG"


def _times(x, m):
    """``x * m``; a multiplier left at 1 adds no operation."""
    return x if m == 1 else x * m


def router_bias(layer: int, n_experts: int) -> jax.Array:
    """The ``e_bias`` buffer of layer ``layer``: a constant outside the
    parameter tree (the published models update it by a balancing rule that
    is no part of the loss; here it is a fixed small offset that moves the
    choice without swamping the scores)."""
    j = jnp.arange(n_experts, dtype=jnp.float32)
    return 0.02 * jnp.sin(0.5 + 1.7 * layer + 2.3 * j)


def rms_norm(x, scale, eps: float, groups: int = 1):
    """float32 RMS norm over the last axis, in ``groups`` equal parts."""
    with jax.named_scope("rms_norm"):
        x = x.astype(jnp.float32)
        g = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
        return g.reshape(x.shape) * scale.astype(jnp.float32)


def layer_norm(x, scale, eps: float):
    """float32 LayerNorm over the last axis: a scale and no bias."""
    with jax.named_scope("layer_norm"):
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, -1, keepdims=True)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
        return x * scale.astype(jnp.float32)


class _Decode(NamedTuple):
    """What a block's decode path is handed besides its cache entry (see the
    module docstring): plain data, ``chunk_rows`` and ``paged_kernel``
    static."""

    decode_pos: Any       # scalar or (B,), as the model was given it
    q_pos: Any            # (B, T) the position of every token
    block_tables: Any     # (B, max_blocks)
    slot_mask: Any        # (B,) bool or None
    chunk_rows: int
    state_slot: Any       # the slot the chunk's rows are a sequence of
    chunk_len: Any        # how many of the chunk's rows hold text
    paged_kernel: bool


class _HybridBlock(nn.Module):
    kind: str
    layer: int
    cfg: Any  # the HybridLM's fields (:class:`_Fields`): the geometry

    def _dense(self, features, name, axis=-1):
        c = self.cfg
        return nn.DenseGeneral(features, axis=axis, use_bias=False,
                               dtype=c.dtype, param_dtype=c.param_dtype,
                               kernel_init=nn.initializers.normal(0.02),
                               name=name)

    @nn.compact
    def __call__(self, h, rope=None, cache=None, dec=None):
        c = self.cfg
        scale = self.param("norm", nn.initializers.ones, (c.d_model,),
                           c.param_dtype)
        u = (layer_norm if c.norm == "layer" else rms_norm)(
            h, scale, c.norm_eps)
        if self.kind == "F":
            return self._falcon(h, u, rope, cache, dec)
        if self.kind in "WG":
            return self._parallel(h, u, rope, cache, dec)
        if cache is not None:
            raise NotImplementedError(
                f"layer kind {self.kind!r} has no decode path: only "
                f"{CACHED_KINDS!r} layers keep a cache")
        mixer = {"M": self._mamba, "*": self._attention, "E": self._experts}
        return h + mixer[self.kind](u).astype(h.dtype)

    # ------------------------------------------------------------ F
    def _falcon(self, h, u, rope, cache, dec):
        """Both mixers of one norm, then the gated FFN of a second."""
        c = self.cfg
        m, state = self._mamba(u, cache, dec)
        a, kv = self._attention(u, rope, cache, dec)
        h = (h + _times(m, c.ssm_out_multiplier).astype(h.dtype)
             + _times(a, c.attention_out_multiplier).astype(h.dtype))
        scale = self.param("norm_ff", nn.initializers.ones, (c.d_model,),
                           c.param_dtype)
        x = rms_norm(h, scale, c.norm_eps).astype(c.dtype)
        gate_m, down_m = c.mlp_multipliers
        # gate, up, down and the residual add read as ONE layer in a device
        # trace, as TransformerLM's FFN does
        with jax.named_scope("ffn"):
            g = _times(self._dense(c.d_ff, "gate")(x), gate_m)
            y = self._dense(c.d_model, "down")(
                jax.nn.silu(g) * self._dense(c.d_ff, "up")(x))
            h = h + _times(y, down_m).astype(h.dtype)
        return h if cache is None else (h, {**kv, **state})

    # ------------------------------------------------------------ M
    def _mamba(self, u, cache=None, dec=None):
        """The mixer's output; called from an ``F`` layer, ``(output, the
        cache entry's ``{"ssm", "conv"}`` as they are after)`` — nothing
        where there is no cache."""
        c = self.cfg
        B, T, _ = u.shape
        H, P, G, N = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
        inner, bc = H * P, G * N
        with jax.named_scope("ssm.in_proj"):
            zxbcdt = self._dense(2 * inner + 2 * bc + H, "in_proj")(
                _times(u, c.ssm_in_multiplier).astype(c.dtype))
            if any(m != 1 for m in c.ssm_multipliers):
                widths = (inner, inner, bc, bc, H)  # z, x, B, C, dt
                zxbcdt = zxbcdt * jnp.concatenate([
                    jnp.full((w,), m, c.dtype)
                    for w, m in zip(widths, c.ssm_multipliers)])
            z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], -1)
        kernel = self.param("conv_kernel", nn.initializers.normal(0.02),
                            (c.conv_kernel, inner + 2 * bc), c.param_dtype)
        bias = self.param("conv_bias", nn.initializers.zeros,
                          (inner + 2 * bc,), c.param_dtype)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,),
                             c.param_dtype)
        A_log = self.param("A_log", nn.initializers.zeros, (H,),
                           c.param_dtype)
        D = self.param("D", nn.initializers.ones, (H,), c.param_dtype)
        state = {}
        if cache is None:
            with jax.named_scope("ssm.conv"):
                xbc = jax.nn.silu(causal_depthwise_conv(
                    xbc, kernel.astype(c.dtype), bias.astype(c.dtype)))
                x, Bm, Cm = jnp.split(xbc, [inner, inner + bc], -1)
            with jax.named_scope("ssm.scan"):
                delta = jax.nn.softplus(dt.astype(jnp.float32)
                                        + dt_bias.astype(jnp.float32))
                y = ssd_scan(x.reshape(B, T, H, P), delta,
                             -jnp.exp(A_log.astype(jnp.float32)),
                             Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N),
                             chunk=min(c.ssm_chunk, T), D=D)
        else:
            y, state = self._recur(xbc, dt, cache, dec, kernel.astype(c.dtype),
                                   bias.astype(c.dtype), dt_bias, A_log, D)
        with jax.named_scope("ssm.gate_out"):
            gate = self.param("gate_norm", nn.initializers.ones, (inner,),
                              c.param_dtype)
            y = y.reshape(B, T, inner) * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y, gate, c.norm_eps, groups=G)
            out = self._dense(c.d_model, "out_proj")(y.astype(c.dtype))
        return out if self.kind == "M" else (out, state)

    def _recur(self, xbc, dt, cache, dec, kernel, bias, dt_bias, A_log, D):
        """The convolution and the recurrence against the slots' state:
        ``(y (rows, T, H, P) float32, {"ssm", "conv"} as they are after)``.
        The decode rows step their slots' state (``ssm.step``); the chunk's
        rows — a prefill chunk's ``T``, or the last ``chunk_rows`` — scan
        from slot ``state_slot``'s state to it (``ssm.scan``)."""
        c = self.cfg
        H, P, G, N = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
        inner, bc = H * P, G * N
        rows, T = xbc.shape[:2]
        C = dec.chunk_rows if T == 1 else T
        S = rows - C if T == 1 else 0
        if T > 1 and rows != 1:
            raise ValueError(
                f"a prefill chunk is one sequence of one slot, got {rows} rows")
        ssm, conv = cache["ssm"], cache["conv"]
        f32 = jnp.float32
        A = -jnp.exp(A_log.astype(f32))
        dt = dt.astype(f32) + dt_bias.astype(f32)
        ys = []
        if S:
            live = (jnp.ones((S,), bool) if dec.slot_mask is None
                    else dec.slot_mask[:S].astype(bool))
            with jax.named_scope("ssm.conv"):
                out, tail = conv_step(conv, xbc[:S, 0], kernel, bias)
                conv = jnp.where(live[:, None, None], tail, conv)
                x, Bm, Cm = jnp.split(jax.nn.silu(out), [inner, inner + bc], -1)
            with jax.named_scope("ssm.step"):
                delta = jnp.where(live[:, None],
                                  jax.nn.softplus(dt[:S, 0]), 0.0)
                y, ssm = ssd_step(ssm, x.reshape(S, H, P), delta, A,
                                  Bm.reshape(S, G, N), Cm.reshape(S, G, N), D)
            ys.append(y.reshape(S, 1, H, P))
        if C:
            def seq(v):  # the chunk's rows as one sequence (1, C, ...)
                return jnp.swapaxes(v[S:], 0, 1) if T == 1 else v

            slot = 0 if dec.state_slot is None else dec.state_slot
            n = C if dec.chunk_len is None else dec.chunk_len
            fresh = dec.q_pos[S, 0] == 0
            with jax.named_scope("ssm.conv"):
                xc = seq(xbc)
                tail = jnp.where(fresh, 0, jax.lax.dynamic_index_in_dim(
                    conv, slot, 0, keepdims=True)).astype(conv.dtype)
                out = causal_depthwise_conv(xc, kernel, bias, tail=tail)
                conv = jax.lax.dynamic_update_slice_in_dim(
                    conv, conv_tail(xc, tail, n).astype(conv.dtype), slot, 0)
                x, Bm, Cm = jnp.split(jax.nn.silu(out), [inner, inner + bc], -1)
            with jax.named_scope("ssm.scan"):
                s0 = jnp.where(fresh, 0.0, jax.lax.dynamic_index_in_dim(
                    ssm, slot, 0, keepdims=True))
                delta = jnp.where((jnp.arange(C) < n)[None, :, None],
                                  jax.nn.softplus(seq(dt)), 0.0)
                Q = C if C <= c.ssm_chunk else c.ssm_chunk
                y, s1 = ssd_scan(x.reshape(1, C, H, P), delta, A,
                                 Bm.reshape(1, C, G, N), Cm.reshape(1, C, G, N),
                                 chunk=Q, D=D, initial_state=s0,
                                 return_state=True)
                ssm = jax.lax.dynamic_update_slice_in_dim(
                    ssm, s1.astype(ssm.dtype), slot, 0)
            ys.append(jnp.swapaxes(y, 0, 1) if T == 1 else y)
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=0)
        return y, {"ssm": ssm, "conv": conv}

    # ------------------------------------------------------------ *
    def _attention(self, u, rope=None, cache=None, dec=None):
        """The mixer's output; from ``F``, ``(output, the cache entry's new
        ``{"kv"}``)``: keys and values go into the paged pool and the read
        is :func:`~chainermn_tpu.ops.decode_attention.paged_attend`'s."""
        c = self.cfg
        T = u.shape[1]
        u = _times(u, c.attention_in_multiplier).astype(c.dtype)
        with jax.named_scope("attn_qkv"):
            q = self._dense((c.n_heads, c.head_dim), "q")(u)
            kv = self._dense((2, c.n_kv_heads, c.head_dim), "kv")(u)
            k, v = _times(kv[:, :, 0], c.key_multiplier), kv[:, :, 1]
            if rope is not None and c.rope_interleaved:
                q, k = (apply_rope(x, tables=rope, interleaved=True)
                        for x in (q, k))
            elif rope is not None:
                # before the pool's write: a cached key keeps its rotation
                q, k = apply_rope(q, tables=rope), apply_rope(k, tables=rope)
        pool = {}
        window = c.window if self.kind == "W" else 0
        if cache is not None and self.kind == "W":
            # rows are the slots in order; the chunk's rows are one slot's
            B = u.shape[0]
            S = B - dec.chunk_rows if T == 1 else 0
            slots = jnp.concatenate([
                jnp.arange(S, dtype=jnp.int32),
                jnp.full((B - S,), 0 if dec.state_slot is None
                         else dec.state_slot, jnp.int32)])
            pool = ring_write({"ring": cache["ring"]}, k, v, slots,
                              dec.q_pos, dec.slot_mask)
            a = ring_attend(q, pool, slots, dec.q_pos, dec.slot_mask,
                            window=window, kernel=dec.paged_kernel,
                            chunk_rows=dec.chunk_rows)
        elif cache is not None:
            pool = pool_write({"kv": cache["kv"]}, k, v, None,
                              dec.block_tables, dec.q_pos, dec.slot_mask)
            a = paged_attend(q, pool, dec.block_tables, dec.decode_pos,
                             dec.q_pos, dec.slot_mask,
                             kernel=dec.paged_kernel,
                             chunk_rows=dec.chunk_rows)
        elif resolve_attention(c.attention, T) == "flash":
            with jax.named_scope("attn.flash"):
                a = flash_attention(q, k, v, causal=True,
                                    **({"window": window} if window else {}))
        else:
            with jax.named_scope("attn.xla"):
                a = reference_attention(
                    q, k, v, causal=True,
                    **({"window": window} if window else {})).astype(q.dtype)
        with jax.named_scope("attn_out"):
            out = self._dense(c.d_model, "proj", axis=(-2, -1))(a)
        return out if self.kind == "*" else (out, pool)

    # ------------------------------------------------------------ W, G
    def _parallel(self, h, u, rope, cache, dec):
        """Attention and the gated experts side by side, of one norm."""
        a, kv = self._attention(u, rope if self.kind == "W" else None,
                                cache, dec)
        rows = None
        if dec is not None and dec.slot_mask is not None:
            rows = jnp.broadcast_to(dec.slot_mask.astype(bool)[:, None],
                                    u.shape[:2])
        f = self._gated_experts(u, rows)
        h = h + a.astype(h.dtype) + f.astype(h.dtype)
        return h if cache is None else (h, kv)

    def _gated_experts(self, u, rows=None):
        """``E``'s layer with gated experts and no router bias; ``rows``
        (B, T) bool: the rows that hold text — the others are routed to no
        expert (nothing of them is gathered, multiplied or counted)."""
        c = self.cfg
        B, T, D = u.shape
        N, n_all, F = B * T, c.experts_held * c.ep_of, c.d_expert
        lo, _ = held_range(c.ep_index, c.ep_of, c.experts_held)
        flat = u.reshape(N, D)
        x = flat.astype(c.dtype)
        init = nn.initializers.normal(0.02)
        with jax.named_scope("moe.route"):
            w_gate = self.param("router", init, (D, n_all), c.param_dtype)
            experts, weights = sigmoid_topk_route(
                flat, w_gate, jnp.zeros((n_all,), jnp.float32),
                c.experts_per_tok, scale=c.routed_scale)
            if rows is not None:
                experts = jnp.where(rows.reshape(N, 1), experts, -1)
        w_up = self.param("experts_gate_up", init,
                          (c.experts_held, D, 2 * F), c.param_dtype)
        w_down = self.param("experts_down", init,
                            (c.experts_held, F, D), c.param_dtype)
        # A tile of twice the rows a held expert draws on average, and the
        # buffer that holds every pair: no conditional between two buffers
        # (on the chip a fence no prefetch crosses), and at a decode step's
        # few rows the every-pair buffer is small.
        mean = N * c.experts_per_tok // n_all
        routed, counters = held_experts_ffn(
            x, experts, weights, w_up, w_down, lo=lo,
            tile=min(128, max(16, 16 * -(-2 * mean // 16))),
            row_bound=None, activation=swiglu)
        held = lo + jnp.arange(c.experts_held)
        counters["moe_experts_touched"] = jnp.sum(jnp.any(
            experts.reshape(-1, 1) == held[None], axis=0)).astype(jnp.float32)
        counters["moe_layers"] = jnp.ones((), jnp.float32)
        for name, value in counters.items():
            self.sow("intermediates", name, value)
        with jax.named_scope("moe.shared"):
            y = self._dense(D, "shared_down")(
                swiglu(self._dense(2 * c.d_shared, "shared_gate_up")(x)))
            y = y.astype(jnp.float32) / c.n_shared
        with jax.named_scope("moe.combine"):
            return (routed + y).reshape(B, T, D)

    # ------------------------------------------------------------ E
    def _experts(self, u):
        c = self.cfg
        B, T, D = u.shape
        n_all = c.experts_held * c.ep_of
        lo, _ = held_range(c.ep_index, c.ep_of, c.experts_held)
        flat = u.reshape(B * T, D)
        x = flat.astype(c.dtype)
        init = nn.initializers.normal(0.02)
        with jax.named_scope("moe.route"):
            w_gate = self.param("router", init, (D, n_all), c.param_dtype)
            experts, weights = sigmoid_topk_route(
                flat, w_gate, router_bias(self.layer, n_all),
                c.experts_per_tok, scale=c.routed_scale)
        w_up = self.param("experts_up", init,
                          (c.experts_held, D, c.d_expert), c.param_dtype)
        w_down = self.param("experts_down", init,
                            (c.experts_held, c.d_expert, D), c.param_dtype)
        # The buffer of the usual case: three times the rows the held
        # experts draw on average.  Rows of one text repeat their tokens (a
        # row of the benchmark's traffic has ~350 distinct ones, the
        # commonest a sixth of it), so a layer near the embedding can send
        # this shard twice its share; past the bound the layer works over
        # all pairs, 1% of a step slower, so the bound sits where a seed
        # does not reach it (PERF.md §6, PR 38).
        routed, counters = held_experts_ffn(
            x, experts, weights, w_up, w_down, lo=lo,
            tile=min(128, 8 * -(-B * T // 8)),
            row_bound=3 * B * T * c.experts_per_tok // c.ep_of)
        for name, value in counters.items():
            self.sow("intermediates", name, value)
        with jax.named_scope("moe.shared"):
            y = self._dense(D, "shared_down")(
                relu2(self._dense(c.d_shared, "shared_up")(x)))
        with jax.named_scope("moe.combine"):
            return (routed + y.astype(jnp.float32)).reshape(B, T, D)


@dataclasses.dataclass(frozen=True)
class _Fields:
    """A :class:`HybridLM`'s own fields as plain data (a block cannot hold
    its parent module as an attribute)."""

    values: tuple

    def __getattr__(self, name):
        for key, value in self.values:
            if key == name:
                return value
        raise AttributeError(name)


class HybridLM(nn.Module):
    """See the module docstring.  ``layer_kinds`` has one letter a layer;
    the model runs its first ``n_layers``."""

    vocab: int
    n_layers: int
    d_model: int
    layer_kinds: str
    # * attention
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 64
    attention: str = "auto"
    # M state-space mixer
    ssm_heads: int = 8
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_chunk: int = 128
    conv_kernel: int = 4
    # E experts: ``experts_held`` of ``experts_held * ep_of`` live here, the
    # contiguous range of shard ``ep_index``
    experts_held: int = 8
    ep_of: int = 1
    ep_index: int = 0
    experts_per_tok: int = 2
    routed_scale: float = 1.0
    d_expert: int = 256
    d_shared: int = 256
    norm_eps: float = 1e-5
    # W / G parallel block: the window of a ``W`` layer, RoPE in interleaved
    # pairs, the shared experts averaged, a LayerNorm, a tied head — each
    # default adds no operation to a model without such a layer
    window: int = 0
    rope_interleaved: bool = False
    n_shared: int = 1
    norm: str = "rms"
    tie_embeddings: bool = False
    # F Falcon-H1 block: the gated FFN's width, RoPE's base, and the
    # family's muP multipliers (1 adds no operation)
    d_ff: int = 1024
    rope_theta: float = 10000.0
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Sequence[float] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    mlp_multipliers: Sequence[float] = (1.0, 1.0)
    #: whether paged decode rows may run the Pallas kernel ("fused") or
    #: take the gathered read ("einsum"), as ``TransformerLM``'s field
    decode_attention: str = "einsum"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: each block under ``jax.checkpoint``: O(n_layers) residuals only
    remat: bool = False

    #: what every ``E`` layer sows and :func:`lm_loss_chunked` reports in
    #: the step's metrics, each with how the layers' values are merged
    routing_counters = {"moe_pairs_held": jnp.mean,
                        "moe_rows_max_over_mean": jnp.max,
                        "moe_pairs_dropped": jnp.sum}

    @property
    def serve_counters(self):
        """What a served step of a model with ``W`` / ``G`` layers hands
        back beside its tokens, each summed over the layers (whole numbers:
        pairs, experts that drew a row, and the layers counted); nothing
        for any other model."""
        if not set(self.layer_kinds[:self.n_layers]) & set("WG"):
            return ()
        return ("moe_pairs_held", "moe_experts_touched", "moe_pairs_dropped",
                "moe_layers")

    def _kinds(self) -> str:
        kinds = self.layer_kinds[:self.n_layers]
        if len(kinds) != self.n_layers or set(kinds) - set(LAYER_KINDS):
            raise ValueError(
                f"layer_kinds={self.layer_kinds!r}: need {self.n_layers} "
                f"letters of {LAYER_KINDS!r}")
        return kinds

    def state_shapes(self):
        """What ONE slot keeps of each layer beside its paged keys and
        values, ``{name: (shape, dtype)}`` a layer: the recurrence's state
        in float32 (up to a context's length of steps compound in it) and
        the convolution's last ``conv_kernel - 1`` inputs in the compute
        dtype.  The serving pool is built from it
        (:class:`~chainermn_tpu.serving.kv_pool.PagedKVPool`)."""
        kinds = self._kinds()
        if set(kinds) - set(CACHED_KINDS):
            raise NotImplementedError(
                f"layer_kinds={kinds!r}: only {CACHED_KINDS!r} layers have "
                "a decode path")
        width = (self.ssm_heads * self.ssm_head_dim
                 + 2 * self.ssm_groups * self.ssm_state)
        state = {"ssm": ((self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                         jnp.float32),
                 "conv": ((self.conv_kernel - 1, width), self.dtype)}
        return [state if kind == "F" else {} for kind in kinds]

    def ring_shapes(self, slots: int, block_len: int, prefill_chunk: int):
        """What each layer keeps INSTEAD of blocks of the paged pool, a
        layer: ``None`` (it pages its whole context), or — a ``W`` layer —
        the ``(shape, dtype)`` of its ring by slot, ``(slots, R, block_len,
        KH * 2 * Dh)`` in the pool row's own format with ``R`` from
        :func:`~chainermn_tpu.ops.decode_attention.ring_blocks`: the window
        and one chunk, whatever the contexts are."""
        kinds = self._kinds()
        if "W" in kinds and self.window < 1:
            raise ValueError("a 'W' layer needs window >= 1")
        R = ring_blocks(self.window, prefill_chunk, block_len) \
            if "W" in kinds else 0
        row = pool_shapes(1, block_len, self.n_kv_heads, self.head_dim)[0]
        return [((slots, R) + row[1:], self.dtype) if kind == "W" else None
                for kind in kinds]

    @nn.compact
    def __call__(self, tokens, segment_ids=None, return_hidden: bool = False,
                 cache=None, decode_pos=None, block_tables=None,
                 slot_mask=None, chunk_rows: int = 0, state_slot=None,
                 chunk_len=None):
        """(B, T) int32 -> (B, T, vocab) float32 logits, or the pre-head
        hidden states with ``return_hidden``.  With ``cache`` (the module
        docstring's decode path; ``block_tables`` required) the result is
        ``(that, new_cache)``."""
        if segment_ids is not None:
            raise NotImplementedError(
                "HybridLM trains whole rows: packed documents would need the "
                "scan's state and the convolution reset at each boundary")
        kinds = self._kinds()
        B, T = tokens.shape
        dec = None
        if cache is not None:
            if block_tables is None:
                raise ValueError(
                    "HybridLM's cache is the serving engine's paged pool "
                    "with the slots' state: block_tables is required")
            self.state_shapes()  # refuses kinds without a decode path
            if jnp.ndim(decode_pos) == 0:
                q_pos = jnp.broadcast_to(
                    (decode_pos + jnp.arange(T))[None], (B, T))
            else:
                q_pos = decode_pos[:, None] + jnp.arange(T)[None]
            dec = _Decode(decode_pos, q_pos, block_tables, slot_mask,
                          chunk_rows, state_slot, chunk_len,
                          _wants_paged_kernel(self.decode_attention))
        elif chunk_rows:
            raise ValueError("chunk_rows needs the paged cache")
        with jax.named_scope("embed"):
            h = nn.Embed(self.vocab, self.d_model, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(tokens)
            h = _times(h, self.embedding_multiplier)
        rope = None
        if "F" in kinds or "W" in kinds:  # once, shared by every layer
            rope = rope_tables(jnp.arange(T) if dec is None else dec.q_pos,
                               self.head_dim, float(self.rope_theta))
        fields = _Fields(tuple(
            (f.name, tuple(v) if isinstance(v, list) else v)
            for f in dataclasses.fields(self)
            if f.name not in ("parent", "name")
            for v in (getattr(self, f.name),)))
        block = (nn.remat(_HybridBlock) if self.remat and cache is None
                 else _HybridBlock)
        new_cache = []
        for i, kind in enumerate(kinds):
            blk = block(kind=kind, layer=i, cfg=fields, name=f"block_{i}")
            if cache is not None:
                h, entry = blk(h, rope, cache[i], dec)
                new_cache.append(entry)
            elif kind in CACHED_KINDS:
                h = blk(h, rope)
            else:
                h = blk(h)
        scale = self.param("norm_f", nn.initializers.ones, (self.d_model,),
                           self.param_dtype)
        h = (layer_norm if self.norm == "layer" else rms_norm)(
            h, scale, self.norm_eps).astype(self.dtype)
        if not return_hidden and self.tie_embeddings:
            with jax.named_scope("head"):
                table = self.variables["params"]["embed"]["embedding"]
                h = _times(h.astype(jnp.float32)
                           @ table.astype(jnp.float32).T,
                           self.lm_head_multiplier)
        elif not return_hidden:
            with jax.named_scope("head"):
                h = _times(nn.Dense(
                    self.vocab, use_bias=False, dtype=jnp.float32,
                    param_dtype=self.param_dtype, name="lm_head")(h),
                    self.lm_head_multiplier)
        return h if cache is None else (h, new_cache)

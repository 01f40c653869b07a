"""Transformer LM — the long-context flagship of the model zoo.

Two tiers:

* :class:`TransformerLM` — Flax decoder-only LM for single-chip / pure-DP
  use, attention running on the Pallas flash kernel
  (:func:`chainermn_tpu.ops.flash_attention`).

* The functional *parallel* LM (`init_parallel_lm` / `ParallelLM`) — the
  5-way-parallel SPMD program composed from the framework's own pieces:
  data parallel over ``data``, GPipe microbatch pipelining over ``stage``
  (:class:`~chainermn_tpu.links.PipelineChain`), tensor-parallel attention
  heads + expert-parallel MoE FFN over ``model``
  (:class:`~chainermn_tpu.parallel.MoELayer`), and ring-attention context
  parallelism over ``seq``
  (:func:`~chainermn_tpu.parallel.ring_self_attention`).  This is the shape
  the reference could not express (its model parallelism was coarse
  rank-placement — ``multi_node_chain_list.py``; SP/EP absent, SURVEY.md
  §2.3) and the program `__graft_entry__.dryrun_multichip` exercises.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import flax.linen as nn

from chainermn_tpu.links.chain_list import PipelineChain
from chainermn_tpu.parallel.moe import MoELayer
from chainermn_tpu.parallel.ring_attention import ring_self_attention


def segment_positions(segment_ids: jax.Array) -> jax.Array:
    """Per-document position restart for packed rows: contiguous segments,
    so each token's offset is its index minus its segment's start (cummax
    of boundary indices).  Shared by the LM (learned table gather / RoPE
    rotation) and the seq2seq family's packed-pair path."""
    B, T = segment_ids.shape
    idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    is_new = jnp.concatenate(
        [
            jnp.ones((B, 1), bool),
            segment_ids[:, 1:] != segment_ids[:, :-1],
        ],
        axis=1,
    )
    starts = lax.cummax(jnp.where(is_new, idx, 0), axis=1)
    return idx - starts  # (B, T)


def _wants_paged_kernel(decode_attention: str) -> bool:
    """The one reading of the ``decode_attention`` field: whether paged
    decode steps may run the Pallas kernel.  Any string but the two is
    refused (the block's call and ``init_cache`` both ask)."""
    if decode_attention not in ("einsum", "fused"):
        raise ValueError(
            f"decode_attention={decode_attention!r}: expected 'einsum' or "
            "'fused'"
        )
    return decode_attention == "fused"


def _quantise_kv(k, v):
    """A chunk's ``(B, T, KH, Dh)`` keys and values as symmetric-absmax
    int8 plus one fp32 scale per (token, kv-head) row, ``(B, T, KH)``:
    ``(k_w, v_w, (k_scale, v_scale))``.  Both cache formats store these."""
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_scale = jnp.maximum(
        jnp.max(jnp.abs(kf), axis=-1), 1e-6
    ) / 127.0  # (B, T, KH)
    v_scale = jnp.maximum(
        jnp.max(jnp.abs(vf), axis=-1), 1e-6
    ) / 127.0
    k_w = jnp.clip(
        jnp.round(kf / k_scale[..., None]), -127, 127
    ).astype(jnp.int8)
    v_w = jnp.clip(
        jnp.round(vf / v_scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return k_w, v_w, (k_scale, v_scale)


# =====================================================================
# Flax tier (single-chip / DP)
# =====================================================================
class _DecoderBlock(nn.Module):
    """One pre-norm decoder block (attention + FFN residuals)."""

    d_model: int
    n_heads: int
    d_ff: int
    dtype: Any
    attention: str
    #: parameter STORAGE dtype (flax convention).  ``float32`` (default)
    #: is the classic master-weights layout; ``bfloat16`` halves the
    #: persistent params+grads bytes (T5-style: adafactor's factored stats
    #: follow the param dtype) — the storage lever for >2B-param configs
    #: on the 15.75 GB chip, where even 2.08B with fp32 params OOMs
    #: (result/lm_2085m_stdout.log).  The router stays fp32
    #: regardless — routing softmax numerics, GShard/Switch convention.
    param_dtype: Any = jnp.float32
    #: kv heads (grouped-query attention).  Equal to ``n_heads`` (the
    #: default, and the classic multi-head layout) keeps the fused ``qkv``
    #: projection and its parameter names; fewer kv heads split the
    #: projection into ``q`` + ``kv`` and shrink the KV cache by
    #: ``n_heads // n_kv_heads``.
    n_kv_heads: int = 0  # 0 → n_heads
    #: sliding-window size (0 → full attention): each position attends the
    #: last ``window`` positions only; the flash kernel skips out-of-window
    #: blocks (O(T·window) attention compute).
    window: int = 0
    #: :class:`TransformerLM`'s fields of the same names, handed to
    #: :func:`~chainermn_tpu.ops.decode_attention.paged_attend`.  The mesh
    #: is static (hashable), so it composes with flax's module dataclass
    #: and jit caching.
    decode_attention: str = "einsum"
    decode_mesh: Any = None
    #: "learned" (parent adds a position table to the embeddings) or
    #: "rope" (this block rotates q/k — the parent adds nothing to ``h``
    #: and passes shared per-step cos/sin ``rope`` tables instead).
    pos_enc: str = "learned"
    #: number of FFN experts (0 → the classic dense 2-layer FFN).  The
    #: single-chip counterpart of the EP tier (`parallel.moe.MoELayer` /
    #: ParallelLM): same capacity-based top-k routing (`_topk_dispatch`),
    #: but all experts live on this device as one stacked ``(E, ...)``
    #: weight and the "exchange" is a pair of batched einsums — no
    #: all_to_all.  ``d_ff`` becomes the PER-EXPERT hidden size (active
    #: FLOPs per token ≈ a dense FFN of ``moe_k * d_ff``).
    n_experts: int = 0
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    #: routing group size: tokens are routed in independent groups of this
    #: many, each with its own capacity.  The dispatch/combine einsums cost
    #: O(G²·k·cf·D) per group — per token that is G·cf/(2·d_ff) of the
    #: expert matmul cost, so small groups keep routing overhead a few
    #: percent while large groups would dominate (G=2048, d_ff=3072 →
    #: 42%).  GShard's group dimension, same reasoning.
    moe_group: int = 512

    @nn.compact
    def __call__(self, h, segment_ids=None, cache=None, decode_pos=None,
                 rope=None, rolling=False, block_tables=None,
                 slot_mask=None, chunk_rows=0):
        """Full path: ``h`` (B, T, D) → (B, T, D).  Decode path (``cache``
        given): ``h`` (B, 1, D) for position ``decode_pos``, attends against
        the KV cache, returns ``(h, new_cache)``.  Both paths create the
        identical parameters (Dense/LayerNorm shapes are length-free), so
        one set of weights serves training and generation.

        ``block_tables`` (``(B, max_blocks)`` int32) switches the decode
        path to the PAGED cache: the cache entry is one layer's physical
        block pool, shared by all rows, and each row's positions are
        mapped through its block table.  ``slot_mask`` (``(B,)`` bool)
        marks live decode slots — masked rows write nothing.  The pool's
        format, its write and its two reads are
        :mod:`chainermn_tpu.ops.decode_attention`'s, ``chunk_rows`` (the
        last rows are one slot's prefill chunk) among them: every row goes
        through the projections, the pool write and the FFN alike."""
        from chainermn_tpu.ops import (
            flash_attention,
            reference_attention,
            resolve_attention,
        )
        from chainermn_tpu.ops.decode_attention import (
            paged_attend,
            pool_write,
        )
        from chainermn_tpu.ops.rope import apply_rope

        T = h.shape[1]
        D, H = self.d_model, self.n_heads
        KH = self.n_kv_heads or H
        if not 0 < KH <= H or H % KH:
            # Fail fast with the real reason — otherwise the decode path
            # surfaces this as an opaque reshape error inside the scan.
            raise ValueError(
                f"n_kv_heads ({KH}) must divide n_heads ({H})"
            )
        if self.window < 0:
            # A negative window would mask EVERY pair on the xla/decode
            # paths — softmax over all-NEG_INF rows degenerates to uniform
            # (causality-violating) weights with no error.
            raise ValueError(f"window must be >= 0, got {self.window}")
        paged_kernel = _wants_paged_kernel(self.decode_attention)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype, name="ln1")(h)
        with jax.named_scope("attn_qkv"):
            if KH == H:
                qkv = nn.DenseGeneral(
                    (3, H, D // H), dtype=self.dtype, param_dtype=self.param_dtype,
                    name="qkv"
                )(x)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            else:
                q = nn.DenseGeneral(
                    (H, D // H), dtype=self.dtype,
                    param_dtype=self.param_dtype, name="q",
                )(x)
                kv = nn.DenseGeneral(
                    (2, KH, D // H), dtype=self.dtype, param_dtype=self.param_dtype,
                    name="kv"
                )(x)
                k, v = kv[:, :, 0], kv[:, :, 1]
        if cache is not None:
            # Incremental: write this chunk's k/v at decode_pos (T=1 per
            # generation step; T=P for the batched prompt prefill), attend
            # causally over the cache prefix (memory-bound — XLA, not
            # flash).  decode_pos may be a (B,) vector (ragged prompts:
            # each row writes at its own position, T must be 1) — per-row
            # causal masking then keeps the not-yet-overwritten pad slots
            # of shorter rows unattended.
            B = k.shape[0]
            paged = block_tables is not None
            if rolling and paged:
                # The ring-buffer slot arithmetic is the contiguous
                # cache's; a ring over a block table is not implemented.
                raise ValueError(
                    "rolling decode requires a non-paged cache (got "
                    "block_tables)"
                )
            if rolling:
                # Ring-buffer cache of size `window`: slot = pos mod W.
                # O(window) memory for unbounded streaming decode — slot s
                # holds the LATEST position ≡ s (mod W), which is exactly
                # the sliding window (pos − W, pos].
                if not self.window or cache["k"].shape[1] != self.window:
                    raise ValueError(
                        "rolling decode needs a window model and a "
                        f"window-sized cache (window={self.window}, cache "
                        f"len {cache['k'].shape[1]})"
                    )
                if T != 1:
                    raise ValueError(
                        "rolling decode is single-token (T == 1); prefill "
                        "through a full cache and convert (lm_generate "
                        f"does) — got T = {T}"
                    )
            if jnp.ndim(decode_pos) == 0:
                q_pos = jnp.broadcast_to(
                    (decode_pos + jnp.arange(T))[None], (B, T)
                )
            else:
                if rolling and T != 1:
                    raise ValueError(
                        "per-row decode_pos on the rolling cache requires "
                        f"single-token chunks (T == 1), got T = {T}"
                    )
                # (B, T): row r's chunk occupies positions
                # decode_pos[r] .. decode_pos[r] + T - 1 (per-row
                # speculative verify chunks; ragged prompts at T = 1).
                q_pos = decode_pos[:, None] + jnp.arange(T)[None]
            with jax.named_scope("attn_qkv"):
                if self.pos_enc == "rope":
                    # Rotate BEFORE the cache write: the cache stores
                    # position-rotated keys, so cached entries never need
                    # re-rotation (RoPE's relative property does the rest).
                    q = apply_rope(q, tables=rope)
                    k = apply_rope(k, tables=rope)
            # int8-quantized cache (``TransformerLM.kv_dtype=jnp.int8``,
            # detected by the scale entries ``init_cache`` adds): each
            # written (token, kv-head) row stores symmetric-absmax int8
            # values plus one fp32 scale — the HBM-RESIDENT cache is half
            # the bf16 bytes (decode is measured KV-bandwidth-bound:
            # result/decode_tpu_b64.json, decode_tpu_gqa.json), and twice
            # the context/batch fits.  Dequantization never materializes a
            # float cache: the k scale folds into the score einsum's
            # output, the v scale into the probability operand.
            quant = ("kv_scale" if paged else "k_scale") in cache
            k_w, v_w, scales = k, v, None
            if quant:
                with jax.named_scope("kv_write"):
                    k_w, v_w, scales = _quantise_kv(k, v)
            if paged:
                new_cache = pool_write(
                    cache, k_w, v_w, scales, block_tables, q_pos, slot_mask
                )
                a = paged_attend(
                    q, new_cache, block_tables, decode_pos, q_pos,
                    slot_mask, kernel=paged_kernel, window=self.window,
                    mesh=self.decode_mesh, chunk_rows=chunk_rows,
                )
            else:
                with jax.named_scope("kv_write"):
                    if quant:
                        k_scale, v_scale = scales
                    else:
                        # Float cache: cast to the cache's storage dtype
                        # (kv_dtype may differ from the compute dtype —
                        # e.g. store bf16 under fp32 compute).
                        kvd = cache["k"].dtype
                        k_w, v_w = k.astype(kvd), v.astype(kvd)
                    write_pos = (
                        decode_pos % self.window if rolling else decode_pos
                    )
                    if jnp.ndim(decode_pos) == 0:
                        kc = lax.dynamic_update_slice(
                            cache["k"], k_w, (0, write_pos, 0, 0)
                        )
                        vc = lax.dynamic_update_slice(
                            cache["v"], v_w, (0, write_pos, 0, 0)
                        )
                        if quant:
                            ks_c = lax.dynamic_update_slice(
                                cache["k_scale"], k_scale, (0, write_pos, 0)
                            )
                            vs_c = lax.dynamic_update_slice(
                                cache["v_scale"], v_scale, (0, write_pos, 0)
                            )
                    else:
                        # Per-row chunk scatter: row r writes its T slots
                        # starting at write_pos[r].
                        rows = jnp.arange(B)[:, None]
                        cols = write_pos[:, None] + jnp.arange(T)[None]
                        kc = cache["k"].at[rows, cols].set(k_w)
                        vc = cache["v"].at[rows, cols].set(v_w)
                        if quant:
                            ks_c = cache["k_scale"].at[rows, cols].set(k_scale)
                            vs_c = cache["v_scale"].at[rows, cols].set(v_scale)
                with jax.named_scope("attn.einsum"):
                    # Grouped attention against the (B, L, KH, Dh) cache: query
                    # head h reads kv head h // (H // KH).  KH == H reduces to
                    # classic multi-head (group axis of size 1).
                    G = H // KH
                    qg = q.reshape(q.shape[0], T, KH, G, D // H)
                    s = jnp.einsum(
                        "bqkgd,btkd->bkgqt", qg.astype(jnp.float32),
                        kc.astype(jnp.float32),
                    ) / math.sqrt(D // H)
                    if quant:
                        # Per-(t, kv-head) k scale commutes out of the head_dim
                        # contraction: apply it on the (b, k, g, q, t) scores.
                        s = s * jnp.transpose(
                            ks_c, (0, 2, 1)
                        )[:, :, None, None, :]
                    t_idx = jnp.arange(kc.shape[1])
                    if rolling:
                        # Slot s holds absolute position pos − ((pos − s) mod
                        # W): the latest position ≡ s that is ≤ pos.  Negative
                        # ⇒ the slot was never written (early steps) — mask
                        # it.  Window and causality are automatic: every held
                        # position lies in (pos − W, pos].
                        pos_b = q_pos[:, 0]  # (B,), T == 1
                        p_s = pos_b[:, None] - (
                            (pos_b[:, None] - t_idx[None, :]) % self.window
                        )
                        visible = (p_s >= 0)[:, None, None, None, :]
                    else:
                        visible = (
                            t_idx[None, None, None, None, :]
                            <= q_pos[:, None, None, :, None]
                        )
                        if self.window:
                            # Decode twin of the training-time sliding window:
                            # only the last `window` positions stay attendable.
                            visible &= (
                                t_idx[None, None, None, None, :]
                                > q_pos[:, None, None, :, None] - self.window
                            )
                    s = jnp.where(visible, s, -1e30)
                    p = jax.nn.softmax(s, axis=-1)
                    if quant:
                        # v scale folds into the probability operand (per t, kv
                        # head) — the int8 cache feeds the einsum directly.
                        p = p * jnp.transpose(
                            vs_c, (0, 2, 1)
                        )[:, :, None, None, :]
                    a = jnp.einsum(
                        "bkgqt,btkd->bqkgd", p, vc.astype(jnp.float32)
                    ).reshape(q.shape[0], T, H, D // H).astype(q.dtype)
                new_cache = (
                    {"k": kc, "v": vc, "k_scale": ks_c, "v_scale": vs_c}
                    if quant else {"k": kc, "v": vc}
                )
        else:
            if self.attention not in ("flash", "xla", "auto"):
                raise ValueError(
                    f"attention={self.attention!r}: expected 'flash', "
                    "'xla' or 'auto'"
                )
            with jax.named_scope("attn_qkv"):
                if self.pos_enc == "rope":
                    # Shared per-step tables from the parent (packed rows bake
                    # per-document restart positions into them).  Rotation is
                    # elementwise — XLA fuses it into the projection epilogue.
                    q = apply_rope(q, tables=rope)
                    k = apply_rope(k, tables=rope)
            if resolve_attention(self.attention, T) == "flash":
                # Library-default blocks: largest sweep-winning
                # power-of-2 divisors of T (flash needs T % block == 0);
                # natural lengths work without upstream padding.  'auto'
                # picks flash/xla by the measured on-chip crossover
                # (ops.FLASH_MIN_SEQ).
                with jax.named_scope("attn.flash"):
                    block = None
                    a = flash_attention(q, k, v, causal=True,
                                        segment_ids=segment_ids, block_q=block,
                                        block_k=block,
                                        window=self.window or None)
            else:
                with jax.named_scope("attn.xla"):
                    a = reference_attention(
                        q, k, v, causal=True, segment_ids=segment_ids,
                        window=self.window or None,
                    ).astype(q.dtype)
        with jax.named_scope("attn_out"):
            o = nn.DenseGeneral(
                D, axis=(-2, -1), dtype=self.dtype,
                param_dtype=self.param_dtype, name="proj",
            )(a)
            h = h + o
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype, name="ln2")(h)
        # ff1 -> activation -> ff2 and the residual add read as ONE layer in
        # a device trace (a fusion carries its root's scope, and the root of
        # the ff2 fusion is the residual add).
        with jax.named_scope("ffn"):
            if self.n_experts:
                y = self._moe_ffn(x)
            else:
                y = nn.Dense(self.d_ff, dtype=self.dtype,
                             param_dtype=self.param_dtype, name="ff1")(x)
                y = nn.Dense(D, dtype=self.dtype, param_dtype=self.param_dtype,
                             name="ff2")(nn.gelu(y))
            h = h + y
        return (h, new_cache) if cache is not None else h

    def _moe_ffn(self, x):
        """Single-device mixture-of-experts FFN.

        Routing reuses :func:`~chainermn_tpu.parallel.moe._topk_dispatch`
        (identical capacity/renormalization semantics to the EP tier, so a
        model measured here behaves the same routed over an ``expert`` mesh
        axis), applied per group of ``moe_group`` tokens.  Expert compute is
        two ``(E, ·, D)x(E, D, F)`` batched einsums — E MXU matmuls per
        step, no gather/scatter, fully static shapes.

        Sows (collected by ``lm_loss``/``lm_loss_chunked``):
        ``moe_aux`` — the Switch load-balance loss;
        ``moe_dropped`` — fraction of (token, choice) routings that lost
        the capacity race and fell through on the residual.
        """
        from chainermn_tpu.parallel.moe import _topk_dispatch

        D, E, F = self.d_model, self.n_experts, self.d_ff
        B, T = x.shape[0], x.shape[1]
        N = B * T
        flat = x.reshape(N, D)
        # Largest group <= moe_group that divides N keeps shapes static
        # without padding (all production shapes are powers of two).
        G = min(self.moe_group, N)
        while N % G:
            G -= 1
        n_groups = N // G
        C = max(1, math.ceil(
            self.moe_k * self.moe_capacity_factor * G / E
        ))
        router = self.param(
            "router", nn.initializers.normal(0.02), (D, E), jnp.float32
        )
        w1 = self.param(
            "moe_w1", nn.initializers.lecun_normal(batch_axis=(0,)),
            (E, D, F), self.param_dtype,
        )
        b1 = self.param("moe_b1", nn.initializers.zeros, (E, F),
                        self.param_dtype)
        w2 = self.param(
            "moe_w2", nn.initializers.lecun_normal(batch_axis=(0,)),
            (E, F, D), self.param_dtype,
        )
        b2 = self.param("moe_b2", nn.initializers.zeros, (E, D),
                        self.param_dtype)

        with jax.named_scope("moe.dispatch"):
            xg = flat.reshape(n_groups, G, D)
            probs = jax.nn.softmax(
                (xg.astype(jnp.float32) @ router), axis=-1
            )  # (g, G, E)
            dispatch, combine, first = jax.vmap(
                lambda p: _topk_dispatch(p, C, self.moe_k)
            )(probs)
            # Switch load-balance loss, averaged over groups; dropped rate =
            # routings that lost the capacity race (they fall through on the
            # residual with weight 0 in `combine`).
            f_e = jnp.mean(first, axis=1)  # (g, E)
            p_e = jnp.mean(probs, axis=1)
            aux = E * jnp.mean(jnp.sum(f_e * p_e, axis=-1))
            dropped = 1.0 - jnp.sum(dispatch) / (N * self.moe_k)
            self.sow("intermediates", "moe_aux", aux)
            self.sow("intermediates", "moe_dropped", dropped)

            # Dispatch einsum in the compute dtype: each (e, c) output slot has
            # AT MOST ONE nonzero term over n (dispatch is one-hot in (e, c)
            # per routing), so there is no accumulation to lose — unlike the
            # EP wire in moe.py, no fp32 pass is needed for exactness.
            send = jnp.einsum(
                "gnec,gnd->egcd", dispatch.astype(self.dtype),
                xg.astype(self.dtype),
            ).reshape(E, n_groups * C, D)
        with jax.named_scope("moe.experts"):
            hmid = nn.gelu(
                jnp.einsum("exd,edf->exf", send, w1.astype(self.dtype))
                + b1[:, None, :].astype(self.dtype)
            )
            out = (
                jnp.einsum("exf,efd->exd", hmid, w2.astype(self.dtype))
                + b2[:, None, :].astype(self.dtype)
            ).reshape(E, n_groups, C, D)
        with jax.named_scope("moe.combine"):
            # Combine accumulates k expert outputs per token — fp32, as the EP
            # tier's combine einsum does.
            y = jnp.einsum(
                "gnec,egcd->gnd", combine, out.astype(jnp.float32)
            )
            return y.reshape(B, T, D).astype(self.dtype)


class TransformerLM(nn.Module):
    """Decoder-only LM; attention runs on the Pallas flash kernel."""

    vocab: int
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    d_ff: int = 1024
    max_len: int = 1024
    dtype: Any = jnp.bfloat16
    #: parameter STORAGE dtype.  ``bfloat16`` halves persistent
    #: params(+grads) HBM — with adafactor's factored stats following it,
    #: the T5-style all-bf16 layout sized to fit a 2.6B model's optimizer
    #: state on the one 15.75 GB chip (even 2.08B with fp32 params OOMs,
    #: ``result/lm_2085m_stdout.log``).  The
    #: MoE router and the LayerNorm/lm_head COMPUTE stay fp32 either way.
    param_dtype: Any = jnp.float32
    #: "flash" (Pallas kernel), "xla" (materialized-scores oracle — the
    #: switch the LM benchmark uses to measure the kernel's end-to-end
    #: value), or "auto" (default): flash from the measured on-chip
    #: crossover length up (``ops.FLASH_MIN_SEQ``), xla below it, where
    #: short sequences don't amortize the block machinery
    #: (result/seq2seq_tpu.json vs result/lm_tpu.json).
    attention: str = "auto"
    #: kv heads for grouped-query attention (0 → ``n_heads``, classic MHA;
    #: 1 → multi-query).  Must divide ``n_heads``; shrinks the generation
    #: KV cache (and the k/v projection) by ``n_heads // n_kv_heads``.
    n_kv_heads: int = 0
    #: KV-cache STORAGE dtype (decode only; ``None`` → the compute dtype).
    #: ``jnp.int8`` stores each written (token, kv-head) row as
    #: symmetric-absmax int8 plus one fp32 scale: the HBM-resident cache
    #: halves vs bf16 (decode throughput is measured KV-bandwidth-bound —
    #: ``result/decode_tpu_b64.json``/``decode_tpu_gqa.json``), and twice
    #: the context or decode batch fits.  Composes with GQA (`n_kv_heads`)
    #: multiplicatively.  Training is untouched — quantization happens at
    #: cache-write time, never on the flash/xla training paths.
    kv_dtype: Any = None
    #: sliding-window attention size (0 → full): each position attends only
    #: the previous ``window`` positions, in training (flash kernel skips
    #: out-of-window blocks — O(T·window)) AND in KV-cache decode (same
    #: mask, so generation bit-matches training semantics).
    window: int = 0
    #: whether PAGED decode steps (the serving engine's, ``block_tables``
    #: given) may run the Pallas kernel
    #: (:func:`~chainermn_tpu.ops.paged_decode_attention`).  "fused": yes,
    #: wherever it applies — single-token steps and speculative verify
    #: chunks of a full-attention model; prefill chunks and window models
    #: take the gathered read.  "einsum" (default): the gathered read
    #: everywhere — the reference path tests compare the kernel with.
    #: Nothing else reads it: the contiguous cache (``init_cache``,
    #: ``lm_generate``, ``rolling``) and training are the same under both.
    decode_attention: str = "einsum"
    #: tensor-parallel serving mesh (``jax.sharding.Mesh``, 1-D) or None.
    #: Set by ``serving.sharding.attach_decode_mesh`` on mesh-sharded
    #: engines: "fused" decode steps then run the Pallas kernel per
    #: shard under ``shard_map`` (KV-head cut, no new collectives)
    #: instead of the gathered einsum.  Threads straight through to
    #: :class:`_DecoderBlock`; single-device use leaves it ``None``.
    decode_mesh: Any = None
    #: Rematerialize each block in the backward pass (``jax.checkpoint``):
    #: activation memory drops from O(n_layers) residuals+intermediates to
    #: O(n_layers) residuals only, for one extra forward of compute — the
    #: standard HBM lever for deep/long-context configs (pairs with the
    #: optimizers' ``accum_steps``).
    remat: bool = False
    #: "learned" (GPT-2-style position table added to the embeddings,
    #: length-capped at ``max_len``) or "rope" (rotary q/k rotation in
    #: every block — no table, no length cap beyond memory; packed rows
    #: restart rotation per document exactly like the learned restart).
    pos_enc: str = "learned"
    #: FFN experts per block (0 → dense FFN).  When set, ``d_ff`` is the
    #: PER-EXPERT hidden size; active FLOPs per token match a dense FFN of
    #: ``moe_k * d_ff``.  ``lm_loss``/``lm_loss_chunked`` collect the sown
    #: load-balance aux loss (weighted ``moe_aux_weight``) and report the
    #: dropped-routing rate in the step metrics.  See
    #: :meth:`_DecoderBlock._moe_ffn`.
    n_experts: int = 0
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_group: int = 512
    moe_aux_weight: float = 0.01

    @nn.compact
    def __call__(self, tokens, segment_ids=None, return_hidden: bool = False,
                 cache=None, decode_pos=None, rolling: bool = False,
                 block_tables=None, slot_mask=None, chunk_rows: int = 0):
        """(B, T) int32 → (B, T, vocab) fp32 logits; with
        ``return_hidden=True``, the pre-head (B, T, d_model) hidden states
        instead (for :func:`lm_loss_chunked`, which streams the head, and
        the serving engine's prefill, which applies the head at one
        position only; on the decode path the updated cache still rides
        along: ``(hidden, new_cache)``).

        ``segment_ids`` (``(B, T)`` int32, from
        :func:`~chainermn_tpu.datasets.pack_sequences`) trains PACKED rows:
        attention masked within each document and positional encodings
        restarting at each document boundary — a packed document computes
        exactly what it would alone.

        Decode path (``cache`` from :meth:`init_cache`, ``decode_pos``
        scalar): ``tokens`` is the (B, 1) token at that position; returns
        ``(logits, new_cache)``.  See :func:`lm_generate`.

        ``block_tables``/``slot_mask`` switch the decode path to the PAGED
        cache (``cache`` entries are the serving engine's physical block
        pools; see :class:`_DecoderBlock.__call__` and
        ``chainermn_tpu/serving``).  ``chunk_rows`` (static) says the last
        that many of the ``B`` single-token rows are one slot's prefill
        chunk riding the decode rows' step: consecutive positions of one
        sequence in ``decode_pos``, that slot's table in every such row of
        ``block_tables``, ``slot_mask`` false past the text's end.  Only
        attention's read tells them from decode rows
        (:func:`~chainermn_tpu.ops.decode_attention.paged_attend`)."""
        B, T = tokens.shape
        D = self.d_model
        if chunk_rows and block_tables is None:
            raise ValueError("chunk_rows needs the paged cache (block_tables)")
        if self.pos_enc not in ("learned", "rope"):
            raise ValueError(
                f"pos_enc={self.pos_enc!r}: expected 'learned' or 'rope'"
            )
        with jax.named_scope("embed"):
            h = nn.Embed(self.vocab, D, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(tokens)
            positions = None
            if segment_ids is not None and cache is None:
                # Per-document position restart (shared helper; both schemes:
                # the learned table gathers at these positions, RoPE rotates
                # by them).
                positions = segment_positions(segment_ids)
            if self.pos_enc == "learned":
                pos = self.param(
                    "pos", nn.initializers.normal(0.02), (self.max_len, D),
                    self.param_dtype,
                )
                if cache is not None:
                    if jnp.ndim(decode_pos) == 0:
                        h = h + lax.dynamic_slice(
                            pos, (decode_pos, 0), (T, D)
                        )[None].astype(self.dtype)
                    else:
                        # Per-row positions: row r's chunk occupies
                        # decode_pos[r] .. decode_pos[r] + T - 1 (ragged-prompt
                        # decode at T = 1; per-row speculative verify chunks).
                        gather = decode_pos[:, None] + jnp.arange(T)[None]
                        h = h + pos[gather].astype(self.dtype)
                elif positions is None:
                    h = h + pos[None, :T].astype(self.dtype)
                else:
                    h = h + pos[positions].astype(self.dtype)
        # RoPE adds nothing to h; compute the cos/sin tables ONCE here and
        # share them across every block (n_layers × 2 rotations reuse one
        # set of transcendentals — also under remat, where blocks would
        # otherwise redo them in the backward).
        rope = None
        if self.pos_enc == "rope":
            from chainermn_tpu.ops.rope import rope_tables

            if cache is None:
                pos_arr = (
                    jnp.arange(T) if positions is None else positions
                )
            elif jnp.ndim(decode_pos) == 0:
                pos_arr = decode_pos + jnp.arange(T)
            else:
                # (B, T) per-row chunk positions.
                pos_arr = decode_pos[:, None] + jnp.arange(T)[None]
            rope = rope_tables(pos_arr, D // self.n_heads)
        # Remat is a TRAINING memory lever; the decode path never needs it
        # (no backward), and rematting it would also trace the static
        # `rolling` flag into a TracerBool error.
        block_cls = (
            nn.remat(_DecoderBlock)
            if self.remat and cache is None
            else _DecoderBlock
        )
        new_cache = []
        for i in range(self.n_layers):
            blk = block_cls(
                d_model=D, n_heads=self.n_heads, d_ff=self.d_ff,
                dtype=self.dtype, attention=self.attention,
                n_kv_heads=self.n_kv_heads, window=self.window,
                pos_enc=self.pos_enc, n_experts=self.n_experts,
                moe_k=self.moe_k,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_group=self.moe_group,
                decode_attention=self.decode_attention,
                decode_mesh=self.decode_mesh,
                param_dtype=self.param_dtype, name=f"block_{i}",
            )
            if cache is not None:
                h, c = blk(h, None, cache[i], decode_pos, rope=rope,
                           rolling=rolling, block_tables=block_tables,
                           slot_mask=slot_mask, chunk_rows=chunk_rows)
                new_cache.append(c)
            else:
                h = blk(h, segment_ids, rope=rope)
        h = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                         name="ln_f")(h)
        if return_hidden:
            return (h, new_cache) if cache is not None else h
        with jax.named_scope("head"):
            logits = nn.Dense(self.vocab, dtype=jnp.float32,
                              param_dtype=self.param_dtype, name="lm_head")(h)
        return (logits, new_cache) if cache is not None else logits

    def init_cache(self, batch: int, max_len: int = None):
        """Zeroed KV cache: per layer ``{"k","v"}`` of shape
        ``(batch, max_len, kv_heads, head_dim)`` in the compute dtype —
        ``n_heads // n_kv_heads``-fold smaller under grouped-query
        attention (the main GQA payoff: longer contexts / bigger decode
        batches fit in HBM).  With ``kv_dtype=jnp.int8`` the entries are
        int8 plus per-(token, kv-head) fp32 ``{"k_scale","v_scale"}`` of
        shape ``(batch, max_len, kv_heads)`` — half the bf16 bytes (the
        scale adds 2/head_dim fp32 words per row).  The one contiguous
        layout, whatever ``decode_attention`` says."""
        _wants_paged_kernel(self.decode_attention)  # refuse a bad string
        L = max_len or self.max_len
        kvh = self.n_kv_heads or self.n_heads
        shape = (batch, L, kvh, self.d_model // self.n_heads)
        kvd = self.kv_dtype if self.kv_dtype is not None else self.dtype
        if jnp.dtype(kvd) == jnp.int8:
            return [
                {"k": jnp.zeros(shape, jnp.int8),
                 "v": jnp.zeros(shape, jnp.int8),
                 "k_scale": jnp.zeros(shape[:3], jnp.float32),
                 "v_scale": jnp.zeros(shape[:3], jnp.float32)}
                for _ in range(self.n_layers)
            ]
        if not jnp.issubdtype(jnp.dtype(kvd), jnp.floating):
            raise ValueError(
                f"kv_dtype must be a float dtype or jnp.int8, got {kvd}"
            )
        return [
            {"k": jnp.zeros(shape, kvd),
             "v": jnp.zeros(shape, kvd)}
            for _ in range(self.n_layers)
        ]


def lm_head_logits(model, params, h):
    """float32 logits of hidden rows ``h`` (..., d_model) through the
    model's own head, for the callers that run a model headless
    (``return_hidden=True``) and apply the head at a few rows only — the
    serving engine's prefill and mixed step: ``lm_head``'s kernel, its bias
    where the model has one, and the model's ``lm_head_multiplier`` where it
    scales its logits (:class:`~chainermn_tpu.models.HybridLM`) — or, for a
    model whose head is tied (``tie_embeddings``), the embedding read
    transposed."""
    if getattr(model, "tie_embeddings", False):
        table = params["embed"]["embedding"]
        logits = h.astype(jnp.float32) @ table.astype(jnp.float32).T
        scale = getattr(model, "lm_head_multiplier", 1)
        return logits if scale == 1 else logits * scale
    head = params["lm_head"]
    logits = h.astype(jnp.float32) @ head["kernel"].astype(jnp.float32)
    if "bias" in head:
        logits = logits + head["bias"].astype(jnp.float32)
    scale = getattr(model, "lm_head_multiplier", 1)
    return logits if scale == 1 else logits * scale


def _check_generation_length(model: "TransformerLM", P: int,
                             n_new: int) -> int:
    """Shared decode-entry contract (``lm_generate`` and
    ``decoding.lm_beam_search``): only the learned position table caps
    generation length — RoPE has no table, so the cache (sized to the
    request) is the only limit.  Returns ``P + n_new``."""
    total = P + n_new
    if total > model.max_len and model.pos_enc == "learned":
        raise ValueError(
            f"prompt ({P}) + n_new ({n_new}) exceeds max_len "
            f"{model.max_len}"
        )
    return total


def lm_generate(
    model: "TransformerLM",
    params,
    prompt,
    n_new: int,
    temperature: float = 0.0,
    rng=None,
    top_k: int = 0,
    top_p: float = 1.0,
    prompt_lengths=None,
    rolling: bool = False,
):
    """Autoregressive generation with the KV cache, one ``lax.scan`` over
    positions (prefill + generation in a single compiled program — the
    TPU-idiomatic decode loop; no Python per-token dispatch).

    Args:
      prompt: ``(B, P)`` int32 prompt tokens (``P >= 1``).  Without
        ``prompt_lengths`` every row must be a FULL-length (un-padded)
        prompt — the prefill conditions on ``prompt[:, -1]`` for all rows.
      n_new: tokens to generate per row.
      temperature: ``0`` = greedy argmax; ``> 0`` = softmax sampling
        (requires ``rng``).
      top_k: with sampling, keep only the ``k`` most likely tokens
        (``0`` = no truncation).
      top_p: with sampling, nucleus truncation — keep the smallest set of
        tokens whose cumulative probability reaches ``top_p``
        (``1.0`` = no truncation).  Composes with ``top_k``.
      prompt_lengths: optional ``(B,)`` int32 per-row real lengths for
        RIGHT-PADDED ragged prompts (``1 <= length <= P``).  Each row
        conditions on its own last real token and generates at positions
        ``length, length+1, …``; the generated KVs overwrite the pad slots
        progressively, so per-row causal masking keeps pads unattended.
      rolling: sliding-window models only (``model.window > 0``) — use a
        RING-BUFFER cache of ``window`` slots instead of ``P + n_new``:
        O(window) memory however long the generation runs (streaming
        decode).  Prefill still runs batched through a prompt-sized cache,
        then collapses to the ring.  Token-identical to the full cache up
        to fp32 summation order (the ring permutes slot order, so a
        near-tie in greedy argmax could in principle flip); the window
        mask hides everything a ring evicts.  Not compatible with
        ``prompt_lengths``.

    Returns ``(B, n_new)`` int32 generated tokens (row ``i``'s tokens at
    positions ``length_i … length_i + n_new - 1`` when ragged).
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    B, P = prompt.shape
    if n_new < 1:
        return jnp.zeros((B, 0), jnp.int32)
    total = _check_generation_length(model, P, n_new)
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if rolling:
        if not model.window:
            raise ValueError(
                "rolling=True needs a sliding-window model (window > 0)"
            )
        if prompt_lengths is not None:
            raise ValueError(
                "rolling=True does not support ragged prompts: pad slots "
                "written during prefill would alias real ring positions"
            )
    # Host (numpy) params are fine to pass in — the scan indexes the
    # positional table with a traced position, which needs device arrays.
    params = jax.tree_util.tree_map(jnp.asarray, params)
    # Cache sized to the live positions, not max_len: attention cost and
    # cache memory are O(P + n_new) per step (masking is shape-agnostic).
    # Under `rolling` the steady-state cache is the W-slot ring; prefill
    # uses a prompt-sized cache and collapses below.
    cache = model.init_cache(B, P if rolling else total)

    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")

    def truncate(scaled):
        """top-k then nucleus filtering on TEMPERATURE-SCALED (B, V) logits
        (the nucleus must cover top_p of the distribution actually sampled
        from).  One descending sort serves both filters."""
        V = scaled.shape[-1]
        sorted_l = jnp.sort(scaled, axis=-1)[:, ::-1]
        if top_k:
            k = min(top_k, V)  # top_k > vocab = keep all (HF convention)
            kth = sorted_l[:, k - 1][:, None]
            scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
            sorted_l = jnp.where(
                jnp.arange(V)[None, :] < k, sorted_l, -jnp.inf
            )
        if top_p < 1.0:
            probs = jax.nn.softmax(sorted_l, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # A token is kept while the mass BEFORE it is < top_p — keeps
            # every token up to and including the one that crosses top_p.
            keep = (cum - probs) < top_p
            thresh = jnp.min(
                jnp.where(keep, sorted_l, jnp.inf), axis=-1
            )[:, None]  # smallest KEPT logit
            scaled = jnp.where(scaled < thresh, -jnp.inf, scaled)
        return scaled

    def pick(logits, key):
        if temperature > 0:
            key, sub = jax.random.split(key)
            scaled = logits / temperature
            if top_k or top_p < 1.0:
                scaled = truncate(scaled)
            nxt = jax.random.categorical(sub, scaled, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(jnp.int32), key

    if prompt_lengths is not None:
        lengths = jnp.asarray(prompt_lengths, jnp.int32)
        if lengths.shape != (B,):
            raise ValueError(
                f"prompt_lengths must be ({B},), got {lengths.shape}"
            )
        try:  # concrete values (the usual case): enforce 1 <= length <= P
            lv = np.asarray(lengths)
        except Exception:  # traced under jit — contract is documented
            lv = None
        if lv is not None and (lv.min() < 1 or lv.max() > P):
            raise ValueError(
                f"prompt_lengths must be in [1, {P}], got range "
                f"[{lv.min()}, {lv.max()}] (length 0 would wrap to the "
                "last pad position under negative indexing)"
            )

    # Batched prefill: ONE (B, P) forward populates the whole prompt's
    # cache (MXU-friendly), instead of P serialized single-token steps.
    key = rng if rng is not None else jax.random.PRNGKey(0)
    logits, cache = model.apply(
        {"params": params}, prompt, cache=cache, decode_pos=0
    )
    if prompt_lengths is None:
        tok0, key = pick(logits[:, -1], key)
    else:
        # Each row conditions on its own last real token's logits; pad-slot
        # prefill logits are simply never read.
        tok0, key = pick(logits[jnp.arange(B), lengths - 1], key)

    if n_new == 1:
        return tok0[:, None]

    if rolling:
        # Collapse the prompt-sized cache into the W-slot ring: slot s
        # takes the LAST prompt position ≡ s (mod W) — a deterministic
        # gather (never a duplicate-index scatter).  Slots no prompt
        # position reached (P < W) hold clamped junk that the decode-time
        # ``p_s >= 0`` mask hides until a real write lands there.
        W = model.window
        sl = jnp.arange(W)
        pos_s = (P - 1) - ((P - 1 - sl) % W)
        safe = jnp.clip(pos_s, 0, P - 1)
        cache = [
            {n: c[n][:, safe] for n in c} for c in cache
        ]

    def body(carry, i):
        tok, cache, key = carry
        step_pos = (P + i) if prompt_lengths is None else (lengths + i)
        logits, cache = model.apply(
            {"params": params}, tok[:, None], cache=cache,
            decode_pos=step_pos, rolling=rolling,
        )
        nxt, key = pick(logits[:, 0], key)
        return (nxt, cache, key), tok

    (last, _, _), fed = lax.scan(
        body, (tok0, cache, key), jnp.arange(n_new - 1)
    )
    # ``fed`` holds the tokens at positions P .. P+n_new-2; ``last`` is the
    # final prediction (position P+n_new-1).
    return jnp.concatenate(
        [jnp.transpose(fed, (1, 0)), last[:, None]], axis=1
    )


#: what a capacity-MoE ``TransformerLM`` sows, merged over its blocks
_MOE_STATS = {"moe_aux": jnp.mean, "moe_dropped": jnp.mean}


def _sown_counters(mutables, merges):
    """``{name: merge(values sown under name, one a layer)}`` (sow stores
    per-call tuples; one forward -> one entry each)."""
    from flax import traverse_util

    flat = traverse_util.flatten_dict(mutables.get("intermediates", {}))
    out = {}
    for name, merge in merges.items():
        sown = [v for k, vs in flat.items() if k[-1] == name for v in vs]
        if sown:
            out[name] = merge(jnp.stack(sown))
    return out


def lm_loss(model: nn.Module):
    """``loss_fn(params, (tokens, targets)) -> (loss, aux)`` for the DP
    optimizer (targets = next tokens, -1 = padding/ignore).  A 3-tuple batch
    ``(tokens, targets, segment_ids)`` trains packed rows (see
    :func:`~chainermn_tpu.datasets.pack_sequences`).

    MoE models (``model.n_experts > 0``) add the sown load-balance loss
    (weighted ``model.moe_aux_weight``) and report ``moe_aux`` /
    ``moe_dropped`` in the metrics; ``ppl_log`` stays CE-only."""
    import optax

    def loss_fn(params, batch):
        tokens, targets, *rest = batch
        seg = rest[0] if rest else None
        moe = getattr(model, "n_experts", 0)
        if moe:
            logits, mut = model.apply(
                {"params": params}, tokens, segment_ids=seg,
                mutable=["intermediates"],
            )
        else:
            logits = model.apply({"params": params}, tokens, segment_ids=seg)
        with jax.named_scope("ce"):
            mask = (targets >= 0).astype(jnp.float32)
            safe = jnp.maximum(targets, 0)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
            loss = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        metrics = {"ppl_log": loss}
        if moe:
            metrics.update(_sown_counters(mut, _MOE_STATS))
            loss = loss + model.moe_aux_weight * metrics["moe_aux"]
        return loss, metrics

    return loss_fn


def lm_loss_chunked(model: nn.Module, chunk_size: int = 4096):
    """Same contract as :func:`lm_loss`, but the LM head is streamed through
    :func:`~chainermn_tpu.ops.chunked_softmax_cross_entropy` — the
    ``(B, T, vocab)`` logits are never materialized (working memory
    ``O(B·T·chunk_size)``).  The head params (``lm_head/kernel|bias``) are
    read from the tree, so the same initialized params serve both losses."""
    from chainermn_tpu.ops import chunked_softmax_cross_entropy

    if getattr(model, "lm_head_multiplier", 1) != 1:
        raise NotImplementedError(
            "lm_loss_chunked streams lm_head's kernel itself and knows no "
            "lm_head_multiplier: train this model through lm_loss"
        )

    def loss_fn(params, batch):
        tokens, targets, *rest = batch
        seg = rest[0] if rest else None
        moe = getattr(model, "n_experts", 0)
        # a model whose expert layers sow routing counters names them, each
        # with how the layers' values merge (``HybridLM.routing_counters``)
        counters = getattr(model, "routing_counters", {})
        if moe or counters:
            hidden, mut = model.apply(
                {"params": params}, tokens, segment_ids=seg,
                return_hidden=True, mutable=["intermediates"],
            )
        else:
            hidden = model.apply(
                {"params": params}, tokens, segment_ids=seg,
                return_hidden=True,
            )
        head = params["lm_head"]
        # Match nn.Dense(dtype=fp32): inputs cast to fp32 before the matmul
        # (the chunk einsum accumulates fp32 regardless).
        with jax.named_scope("ce"):
            ce = chunked_softmax_cross_entropy(
                hidden.astype(jnp.float32), head["kernel"], targets,
                bias=head.get("bias"), chunk_size=chunk_size,
            )
            mask = (targets >= 0).astype(jnp.float32)
            loss = jnp.sum(ce) / jnp.maximum(jnp.sum(mask), 1.0)
        metrics = {"ppl_log": loss}
        if moe:
            metrics.update(_sown_counters(mut, _MOE_STATS))
            loss = loss + model.moe_aux_weight * metrics["moe_aux"]
        if counters:
            metrics.update(_sown_counters(mut, counters))
        return loss, metrics

    return loss_fn


# =====================================================================
# Functional tier: DP x PP x TP x SP x EP parallel LM
# =====================================================================
class ParallelLMConfig(NamedTuple):
    vocab: int
    n_stages: int  # one transformer block per pipeline stage
    d_model: int
    n_heads: int  # global head count; sharded over `model`
    d_ff: int  # per-expert hidden size
    max_len: int
    n_experts: int  # == size of the `model` axis
    moe_k: int = 2
    capacity_factor: float = 0.0  # 0 → ample (no drops; exact vs dense oracle)
    #: "learned" (seq-sharded slice of a position table) or "rope" (rotary
    #: q/k rotation at GLOBAL positions — each seq shard rotates by
    #: ``seq_rank·T_local + arange``, so the ring-circulated keys carry
    #: their true positions and relative attention is exact across shards).
    pos_enc: str = "learned"
    #: ring-local attention impl: "auto" (default — flash-block ring when
    #: the local shard length clears ``ops.FLASH_MIN_SEQ``, XLA blocks
    #: below), or force "flash"/"xla".  Both exact; perf-only.
    attention: str = "auto"
    #: grouped-query attention: 0 (default) = dense (kv heads == heads);
    #: else the kv head count — must divide ``n_heads``, and the TP
    #: sharding additionally needs it divisible by the ``model`` axis
    #: extent (kv heads shard over ``model`` like q heads).
    n_kv_heads: int = 0


def _check_pos_enc(cfg: ParallelLMConfig) -> None:
    """Fail fast on a bad ``pos_enc`` (the TransformerLM contract): any
    string other than 'rope' would otherwise silently run the learned
    branch."""
    if cfg.pos_enc not in ("learned", "rope"):
        raise ValueError(
            f"pos_enc={cfg.pos_enc!r}: expected 'learned' or 'rope'"
        )


def init_parallel_lm(rng: np.random.RandomState, cfg: ParallelLMConfig) -> Dict:
    """Host-side init of the stage-stacked parameter pytree."""
    _check_pos_enc(cfg)
    S, D, H, F, E = (
        cfg.n_stages, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.n_experts
    )
    Dh = D // H

    def g(*shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (rng.normal(size=shape) * scale).astype(np.float32)

    KH = cfg.n_kv_heads or H
    if KH != H:
        qkv_leaves = {
            "wq": g(S, D, H, Dh, scale=1.0 / math.sqrt(D)),
            "wkv": g(S, D, 2, KH, Dh, scale=1.0 / math.sqrt(D)),
        }
    else:
        qkv_leaves = {"wqkv": g(S, D, 3, H, Dh, scale=1.0 / math.sqrt(D))}
    tree = {
        "embed": g(cfg.vocab, D, scale=0.02),
        "pos": g(cfg.max_len, D, scale=0.02),
        "stages": {
            "ln1_scale": np.ones((S, D), np.float32),
            "ln1_bias": np.zeros((S, D), np.float32),
            **qkv_leaves,
            "wo": g(S, H, Dh, D, scale=1.0 / math.sqrt(D)),
            "ln2_scale": np.ones((S, D), np.float32),
            "ln2_bias": np.zeros((S, D), np.float32),
            "router": g(S, D, E, scale=1.0 / math.sqrt(D)),
            "w1": g(S, E, D, F, scale=1.0 / math.sqrt(D)),
            "w2": g(S, E, F, D, scale=1.0 / math.sqrt(F)),
        },
        "ln_f_scale": np.ones((D,), np.float32),
        "ln_f_bias": np.zeros((D,), np.float32),
        "lm_head": g(D, cfg.vocab, scale=1.0 / math.sqrt(D)),
    }
    if cfg.pos_enc == "rope":
        del tree["pos"]  # rotary: no table, no max_len cap
    return tree


def parallel_lm_specs(cfg: ParallelLMConfig):
    """PartitionSpecs matching :func:`init_parallel_lm`'s pytree."""
    from jax.sharding import PartitionSpec as P

    _check_pos_enc(cfg)
    if cfg.n_kv_heads and cfg.n_kv_heads != cfg.n_heads:
        qkv_specs = {
            "wq": P("stage", None, "model", None),
            "wkv": P("stage", None, None, "model", None),  # kv heads TP too
        }
    else:
        qkv_specs = {
            "wqkv": P("stage", None, None, "model", None),  # heads TP
        }
    specs = {
        "embed": P(),
        "pos": P(),
        "stages": {
            "ln1_scale": P("stage", None),
            "ln1_bias": P("stage", None),
            **qkv_specs,
            "wo": P("stage", "model", None, None),
            "ln2_scale": P("stage", None),
            "ln2_bias": P("stage", None),
            "router": P("stage", None, None),
            "w1": P("stage", "model", None, None),  # experts EP-sharded
            "w2": P("stage", "model", None, None),
        },
        "ln_f_scale": P(),
        "ln_f_bias": P(),
        "lm_head": P(),
    }
    if cfg.pos_enc == "rope":
        del specs["pos"]
    return specs


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


class ParallelLM:
    """The 5-way-parallel LM program.  Call :meth:`apply` inside a
    ``shard_map`` over a mesh with axes ``("data", "stage", "model",
    "seq")``; parameter leaves follow :func:`parallel_lm_specs`, tokens /
    targets are ``P("data", "seq")``.
    """

    def __init__(self, cfg: ParallelLMConfig, stage_comm, n_microbatches: int):
        _check_pos_enc(cfg)
        # Fail fast on a bad attention impl too — otherwise the
        # resolve_attention ValueError surfaces mid-trace inside
        # jit+shard_map, buried in a trace stack.
        from chainermn_tpu.ops import resolve_attention

        resolve_attention(cfg.attention, 1)
        if cfg.n_kv_heads and (
            not 0 < cfg.n_kv_heads <= cfg.n_heads
            or cfg.n_heads % cfg.n_kv_heads
        ):
            raise ValueError(
                f"n_kv_heads ({cfg.n_kv_heads}) must be in (0, n_heads] "
                f"and divide n_heads ({cfg.n_heads})"
            )
        self.cfg = cfg
        self.scomm = stage_comm
        self.n_micro = n_microbatches

    # --------------------------------------------------- stage (one block)
    def _stage_apply(self, p, h, rope=None):
        # p: this device's (stage, model) shard of the stacked stage params
        # (leading stage axis 1; expert/head axes local).  h: (B, Tl, D).
        cfg = self.cfg
        B, Tl, D = h.shape
        x = _layer_norm(h, p["ln1_scale"][0], p["ln1_bias"][0])
        if "wkv" in p:
            # GQA: fewer kv heads (TP-sharded like q heads).  k/v stay
            # COMPACT here — both rings consume them directly (the XLA
            # ring expands per visiting block at attend time, the flash
            # kernel streams shared kv natively), so the ring circulates
            # H/KH× fewer kv bytes.
            q = jnp.einsum("btd,dhe->bthe", x, p["wq"][0])
            kv = jnp.einsum("btd,dche->btche", x, p["wkv"][0])
            k, v = kv[:, :, 0], kv[:, :, 1]
        else:
            qkv = jnp.einsum("btd,dche->btche", x, p["wqkv"][0])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if rope is not None:
            # Rotation at GLOBAL positions happens BEFORE the ring: the
            # keys each shard circulates already carry their true
            # positions, so cross-shard relative attention is exact.
            from chainermn_tpu.ops.rope import apply_rope

            q = apply_rope(q, tables=rope)
            k = apply_rope(k, tables=rope)
        # SP ring.  The measured auto policy picks the flash-block ring
        # when the LOCAL shard length clears the crossover (that's the
        # block length each ring step attends at); both rings are
        # oracle-exact, so this is purely a perf selection.
        from chainermn_tpu.ops import resolve_attention

        if resolve_attention(cfg.attention, Tl) == "flash":
            from chainermn_tpu.parallel import ring_flash_self_attention

            a = ring_flash_self_attention(q, k, v, "seq", causal=True)
        else:
            a = ring_self_attention(q, k, v, "seq", causal=True)
        o = jnp.einsum("bthe,hed->btd", a, p["wo"][0])
        o = lax.psum(o, "model")  # TP contraction over head shards
        h = h + o

        x = _layer_norm(h, p["ln2_scale"][0], p["ln2_bias"][0])
        E = cfg.n_experts
        N = B * Tl
        toks = x.reshape(N, D)
        # After the TP psum the activations are replicated over `model`; MoE
        # expects tokens SHARDED over the expert axis (moe.py layout), so
        # each rank dispatches only its 1/E slice and the outputs are
        # re-assembled with an all_gather — identical numerics, E× less
        # expert compute and dispatch traffic than routing the full set
        # everywhere.
        if N % E:
            raise ValueError(f"local tokens {N} not divisible by experts {E}")
        mrank = lax.axis_index("model")
        mine = lax.dynamic_slice_in_dim(toks, mrank * (N // E), N // E, axis=0)

        def expert_apply(ep, t):
            w1, w2 = ep  # local shards (1, D, F), (1, F, D)
            return jax.nn.gelu(t @ w1[0]) @ w2[0]

        cap_f = cfg.capacity_factor if cfg.capacity_factor > 0 else float(E)
        moe = MoELayer(expert_apply, "model", k=cfg.moe_k,
                       capacity_factor=cap_f)
        y, aux = moe(p["router"][0], (p["w1"][0], p["w2"][0]), mine)
        # Reassemble the expert outputs as an offset-placed psum rather
        # than all_gather: numerically identical (each rank contributes
        # only its own slice), but psum output is TYPED model-invarying,
        # so check_vma=True can verify the stage output's replication —
        # all_gather stays varying-typed and would force the checker off
        # (this JAX has no all_gather_invariant).  Costs ~2x the wire
        # bytes of an all_gather; acceptable for the debug guarantee.
        y_full = lax.dynamic_update_slice_in_dim(
            jnp.zeros((N, D), y.dtype), y, mrank * (N // E), axis=0
        )
        y = lax.psum(y_full, "model")  # (N, D), model-invarying
        h = h + y.reshape(B, Tl, D)
        return h

    # ------------------------------------------------------------ forward
    def apply(self, params, tokens):
        """tokens: (B_local, T_local) int32 → logits (B_local, T_local, V)."""
        cfg = self.cfg
        B, Tl = tokens.shape
        seq_rank = lax.axis_index("seq")
        h = params["embed"][tokens]
        rope = None
        if cfg.pos_enc == "rope":
            from chainermn_tpu.ops.rope import rope_tables

            # Global positions for THIS seq shard; one set of tables
            # shared by every pipeline stage.
            rope = rope_tables(
                seq_rank * Tl + jnp.arange(Tl), cfg.d_model // cfg.n_heads
            )
        else:
            pos = lax.dynamic_slice_in_dim(
                params["pos"], seq_rank * Tl, Tl, axis=0
            )
            h = h + pos[None]
        pipe = PipelineChain(
            lambda p, x: self._stage_apply(p, x, rope=rope),
            self.scomm, self.n_micro,
        )
        h = pipe(params["stages"], h)
        h = _layer_norm(h, params["ln_f_scale"], params["ln_f_bias"])
        return h @ params["lm_head"]

    def loss(self, params, batch):
        """This rank's SHARE of the global masked CE.

        The numerator is local but the denominator is the GLOBAL
        valid-token count (shards hold unequal mask counts, so a
        mean-of-local-means would be biased).  The replica convention then
        depends on the checker mode, discriminated at trace time by the
        tokens' vma type:

        * ``check_vma=True`` — the vma-aware transpose seeds ONE cotangent
          per logical value (the share is typed invarying over
          stage/model), so the share needs no correction; the global loss
          is ``utils.psum_over_varying`` of the shares.
        * ``check_vma=False`` — everything is untyped; ``value_and_grad``
          seeds a cotangent on each of the stage×model identical copies,
          so the share is pre-divided by that replica count to keep the
          seeded mass at ``L``; the global loss is the psum of shares over
          ALL mesh axes.

        Both modes are pinned to the dense single-device oracle (loss AND
        reduced grads) by ``test_parallel_loss_and_grads_match_dense``.
        """
        tokens, targets = batch
        logits = self.apply(params, tokens)
        mask = (targets >= 0).astype(jnp.float32)
        safe = jnp.maximum(targets, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        n_total = lax.psum(jnp.sum(mask), ("data", "seq"))
        share = jnp.sum(ce * mask) / jnp.maximum(n_total, 1.0)
        if jax.typeof(tokens).vma:
            # check_vma=True: the vma-aware transpose seeds ONE cotangent
            # per logical value (the loss is typed invarying over
            # stage/model, where every rank holds an identical copy), so
            # no replica correction exists or is needed — and the global
            # loss is the psum of shares over the axes the share VARIES
            # over (utils.psum_over_varying), not over all axes.
            return share
        # check_vma=False: every value is untyped, value_and_grad seeds a
        # cotangent on each of the stage×model identical copies, so the
        # share is pre-divided to keep the total seeded mass at L — and
        # the global loss is the psum of shares over ALL mesh axes.
        replicas = lax.axis_size("stage") * lax.axis_size("model")
        return share / replicas

    # ------------------------------------------------------ grad reduction
    def grad_reduce(self, grads, axes=("data", "stage", "model", "seq")):
        """Per-leaf cross-device gradient reduction.

        With :meth:`loss` seeding the global loss exactly once across the
        mesh, shard_map AD already yields ∂L/∂(this copy) for every
        parameter copy; a tied (replicated) parameter's gradient is then the
        SUM of its copies' gradients.  So each leaf psums over exactly the
        axes its PartitionSpec does NOT shard — e.g. ``embed`` (fully
        replicated; grads live only on stage-0 ranks where the pipeline
        consumes its input) sums over all axes, while ``wqkv`` (sharded over
        stage and model) sums over data/seq only.
        """
        specs = parallel_lm_specs(self.cfg)
        # Mode discriminator: under check_vma=True the AD transpose has
        # ALREADY reduced the cotangent of any leaf whose primal was
        # replicated (the vma type forces it), so summing again would
        # multiply by the axis size — reduce only over axes the grad still
        # VARIES on.  Under check_vma=False everything is untyped (vma
        # empty on every leaf) and each free axis needs the explicit psum.
        vma_on = any(
            jax.typeof(l).vma for l in jax.tree_util.tree_leaves(grads)
        )

        def reduce_leaf(g, spec):
            used = set()
            for entry in spec:
                if entry is None:
                    continue
                if isinstance(entry, (tuple, list)):
                    used.update(entry)
                else:
                    used.add(entry)
            free = tuple(a for a in axes if a not in used)
            if vma_on:
                from chainermn_tpu.utils import psum_over_varying

                return psum_over_varying(g, free)
            return lax.psum(g, free) if free else g

        # NB: is_leaf keys on the grads tree (arrays), so the matching specs
        # subtree (a PartitionSpec, itself a tuple) is passed through whole.
        return jax.tree_util.tree_map(
            reduce_leaf, grads, specs, is_leaf=lambda x: hasattr(x, "shape")
        )


def dense_lm_reference(params_host: Dict, cfg: ParallelLMConfig, tokens):
    """Single-device oracle: identical math, no parallelism (for tests and
    parity checks).  ``params_host`` is the :func:`init_parallel_lm` pytree.
    """
    p = jax.tree_util.tree_map(jnp.asarray, params_host)
    B, T = tokens.shape
    D = cfg.d_model
    h = p["embed"][tokens]
    rope = None
    if cfg.pos_enc == "rope":
        from chainermn_tpu.ops.rope import rope_tables

        rope = rope_tables(jnp.arange(T), D // cfg.n_heads)
    else:
        h = h + p["pos"][None, :T]
    for s in range(cfg.n_stages):
        st = {k: v[s] for k, v in p["stages"].items()}
        x = _layer_norm(h, st["ln1_scale"], st["ln1_bias"])
        if "wkv" in st:
            q = jnp.einsum("btd,dhe->bthe", x, st["wq"])
            kv = jnp.einsum("btd,dche->btche", x, st["wkv"])
            k, v = kv[:, :, 0], kv[:, :, 1]
            G = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        else:
            qkv = jnp.einsum("btd,dche->btche", x, st["wqkv"])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if rope is not None:
            from chainermn_tpu.ops.rope import apply_rope

            q = apply_rope(q, tables=rope)
            k = apply_rope(k, tables=rope)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s_ = jnp.einsum("bqhe,bkhe->bhqk", q, k) * scale
        s_ = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s_, -jnp.inf)
        a = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(s_, axis=-1), v)
        h = h + jnp.einsum("bthe,hed->btd", a, st["wo"])

        x = _layer_norm(h, st["ln2_scale"], st["ln2_bias"])
        toks = x.reshape(B * T, D)
        probs = jax.nn.softmax(toks @ st["router"], axis=-1)
        # dense top-k with renormalized gates (matches MoELayer w/ ample cap)
        k_ = cfg.moe_k
        top = jax.lax.top_k(probs, k_)[1]
        sel = jax.nn.one_hot(top, cfg.n_experts).sum(axis=1)  # (N, E)
        gates = probs * sel
        gates = gates / jnp.maximum(
            gates.sum(-1, keepdims=True), jnp.finfo(jnp.float32).tiny
        )
        expert_out = jnp.stack(
            [jax.nn.gelu(toks @ st["w1"][e]) @ st["w2"][e]
             for e in range(cfg.n_experts)], axis=1,
        )  # (N, E, D)
        y = jnp.einsum("ne,ned->nd", gates, expert_out)
        h = h + y.reshape(B, T, D)
    h = _layer_norm(h, p["ln_f_scale"], p["ln_f_bias"])
    return h @ p["lm_head"]

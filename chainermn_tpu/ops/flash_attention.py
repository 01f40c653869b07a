"""Flash attention — Pallas TPU kernel with custom VJP.

The single-chip hot op under every attention layer in the model zoo, and the
local block kernel for the sequence-parallel strategies
(:mod:`chainermn_tpu.parallel.ulysses` runs it unmodified on full-length
sequences; ring attention composes the same online-softmax recurrence across
chips).  O(T·block) memory instead of O(T²): scores never hit HBM.

Forward: grid ``(batch·heads, T/block_q)``; each program streams K/V blocks
through VMEM, maintaining the online-softmax state (running max ``m``,
normalizer ``l``, fp32 accumulator) in scratch, and writes the output block
plus the per-row logsumexp (LSE) for the backward.

Backward (custom VJP, flash-style recomputation): ``delta = rowsum(dO·O)`` in
XLA, then one kernel over K/V blocks accumulating ``dK``/``dV`` across the Q
loop (and across the query heads that share a KV head, inside the launch),
and one over Q blocks accumulating ``dQ`` across the K loop — the standard
dataflow that keeps every intermediate in VMEM.

On non-TPU backends the same kernels run in Pallas interpret mode (tests), so
numerics are identical everywhere.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30  # finite stand-in: -inf breaks m==NEG_INF rescue on all-masked rows


def _use_interpret() -> bool:
    """Pallas interpret mode off-TPU, Mosaic on it — decided from the
    platform alone; a backend that fails to initialise raises here."""
    return jax.default_backend() != "tpu"


def reference_attention(q, k, v, causal: bool = False,
                        segment_ids=None, kv_segment_ids=None,
                        window=None) -> jax.Array:
    """Plain-XLA softmax attention over ``(B, T, H, D)`` — the single
    correctness oracle every flash test/benchmark compares against (one
    implementation, so the CPU interpret tests and the on-chip harness can
    never validate against diverging references).  Computed in fp32, cast
    back to the input dtype.  ``k``/``v`` may have a different length
    (cross-attention; ``causal`` then requires equal lengths) and fewer
    heads than ``q`` (grouped-query attention; ``q`` heads must be a
    multiple of kv heads).  ``window`` masks to ``|q - k| < window``
    (sliding-window / local attention)."""
    return _reference_attention_lse(
        q, k, v, causal, segment_ids, kv_segment_ids, window
    )[0]


def _reference_attention_lse(q, k, v, causal: bool = False,
                             segment_ids=None, kv_segment_ids=None,
                             window=None):
    """:func:`reference_attention` + per-row logsumexp ``(B, H, T)`` — the
    XLA twin of :func:`flash_attention_lse` (used as its vma-checked
    interpret-mode fallback)."""
    B, T, H, D = q.shape
    S = k.shape[1]
    # Same contracts as the flash path — the oracle must never silently
    # compute something the kernel would reject.
    if causal and S != T:
        raise ValueError(
            f"causal attention needs equal q/kv lengths, got {T} vs {S}"
        )
    if segment_ids is not None and kv_segment_ids is None and S != T:
        raise ValueError(
            "cross-attention with segment_ids needs explicit "
            "kv_segment_ids (kv length differs from q)"
        )
    kv_heads = k.shape[2]
    if kv_heads != H:
        if H % kv_heads:
            raise ValueError(
                f"q heads {H} must be a multiple of kv heads {kv_heads}"
            )
        # GQA expansion in the oracle only — the kernel streams shared kv
        # blocks via its index maps instead of materializing the repeat.
        k = jnp.repeat(k, H // kv_heads, axis=2)
        v = jnp.repeat(v, H // kv_heads, axis=2)
    if window is not None:
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if S != T:
            raise ValueError(
                f"sliding-window attention needs equal q/kv lengths, got "
                f"{T} vs {S}"
            )
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool))
        s = jnp.where(mask, s, NEG_INF)
    if window is not None:
        # |q - k| < window (non-causal) / q - window < k <= q (causal — the
        # upper side is the causal mask above).
        qi = jnp.arange(T)[:, None]
        ki = jnp.arange(S)[None, :]
        local = (qi - ki < window) & (ki - qi < window)
        s = jnp.where(local, s, NEG_INF)
    if segment_ids is not None or kv_segment_ids is not None:
        if segment_ids is None:
            segment_ids = jnp.zeros((B, T), jnp.int32)
        if kv_segment_ids is None:
            kv_segment_ids = segment_ids
        seg = (segment_ids[:, :, None] == kv_segment_ids[:, None, :])
        s = jnp.where(seg[:, None, :, :], s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # (B, H, T)
    # Match the kernel's fully-masked-row contract: rows where every key is
    # NEG_INF emit zeros + lse = NEG_INF ("no mass"), and the p mask also
    # zeroes their q/k/v gradients under AD (the kernel's bwd guard twin).
    alive = jnp.max(s, axis=-1) > NEG_INF * 0.5  # (B, H, T)
    p = jnp.exp(s - lse[..., None]) * alive[..., None]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    lse = jnp.where(alive, lse, NEG_INF)
    return o.transpose(0, 2, 1, 3).astype(q.dtype), lse


# ----------------------------------------------------------- shared masks
# One definition each for the causal/window position masks and the
# block-skipping loop bounds: the forward and both backward kernels must
# agree on these EXACTLY or gradients silently diverge from the forward.

def _mask_scores(s, q0, k0, causal, window, q_axis=0):
    """Apply causal (``q >= k``) and sliding-window (``|q - k| < window``)
    masks to a score block whose rows start at absolute q position ``q0``
    and columns at k position ``k0`` — or, with ``q_axis=1``, the transposed
    block the dK/dV kernel builds (rows are keys, columns queries): the same
    comparisons on the same absolute positions."""
    if not causal and window is None:
        return s
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    if causal:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if window is not None:
        local = (q_pos - k_pos < window) & (k_pos - q_pos < window)
        s = jnp.where(local, s, NEG_INF)
    return s


def _k_block_range(qi, bq, block_k, n_k, causal, window, kv_off=0):
    """``[k_lo, k_hi)`` kv-block bounds visited by the q block starting at
    ``qi * bq`` (forward and dQ kernels).  Blocks fully outside the causal
    triangle or the window are skipped, not just masked.  ``kv_off`` is the
    static absolute position of the kv array's first row (nonzero when the
    sequence is VMEM-chunked, :func:`_stage_chunk`); block indices stay
    LOCAL to the chunk.  Bounds may cross (empty range → zero loop trips)."""
    last_q = (qi + 1) * bq - 1
    if causal:
        k_hi = jnp.clip((last_q - kv_off) // block_k + 1, 0, n_k)
    elif window is not None:
        k_hi = jnp.clip((last_q + window - 1 - kv_off) // block_k + 1, 0, n_k)
    else:
        k_hi = n_k
    if window is not None:
        k_lo = jnp.maximum((qi * bq - window + 1 - kv_off) // block_k, 0)
    else:
        k_lo = 0
    return k_lo, k_hi


def _q_block_range(ki, bk, block_q, n_q, causal, window, q_off=0):
    """``[q_lo, q_hi)`` q-block bounds visited by the kv block starting at
    ``ki * bk`` (dK/dV kernel) — the transpose of :func:`_k_block_range`.
    ``q_off`` is the static absolute position of the q array's first row
    (nonzero when the q rows are VMEM-chunked); indices stay chunk-local."""
    first_k = ki * bk
    q_lo = jnp.clip((first_k - q_off) // block_q, 0, n_q) if causal else 0
    q_hi = n_q
    if window is not None:
        # q >= k_first - window + 1 and q <= k_last + window - 1.
        q_lo = jnp.maximum(q_lo, (first_k - window + 1 - q_off) // block_q)
        q_lo = jnp.maximum(q_lo, 0)
        q_hi = jnp.clip(
            (first_k + bk - 1 + window - 1 - q_off) // block_q + 1, 0, n_q
        )
    return q_lo, q_hi


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                block_k, causal, segmented, scale, window=None, kv_off=0):
    # q_ref: (1, BQ, D); k/v_ref: (1, T, D); o_ref: (1, BQ, D).
    # Per-row refs (lse, segments) carry a trailing singleton lane dim —
    # (1, BQ, 1) / (1, T, 1) — because Mosaic requires each block's last two
    # dims to be (divisible by 8, divisible by 128) or equal to the array's;
    # a (1, BQ) block over a (BH, T) array violates the sublane rule.
    if segmented:
        segq_ref, segk_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    T = k_ref.shape[1]
    D = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    seg_q = segq_ref[0, :, 0] if segmented else None  # (BQ,)

    n_k = T // block_k
    k_lo, n_k_eff = _k_block_range(qi, bq, block_k, n_k, causal, window,
                                   kv_off=kv_off)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        s = _mask_scores(s, qi * bq, ki * block_k + kv_off, causal, window)
        if segmented:
            seg_k = segk_ref[0, pl.ds(ki * block_k, block_k), 0]
            s = jnp.where(seg_q[:, None] == seg_k[None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(k_lo, n_k_eff, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    # A fully-masked row (every key NEG_INF — e.g. a query segment with no
    # matching kv id) leaves m at NEG_INF; the finite-NEG_INF rescue would
    # then make p = exp(0) = 1 for every key and o a uniform average of V.
    # Emit zeros and the canonical "no mass" lse = NEG_INF instead (exact
    # log-0 mass, so ring/blockwise merges weight these rows to zero).
    alive = m > NEG_INF * 0.5
    o_ref[0] = jnp.where(
        alive[:, None], acc / l_safe[:, None], 0.0
    ).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(alive, m + jnp.log(l_safe), NEG_INF)[:, None]



def _vma_union(*arrays):
    """Union of the inputs' varying-manual-axes (vma) types.

    Inside a ``check_vma=True`` ``shard_map``, ``pallas_call`` outputs must
    declare how they vary over the mesh (``ShapeDtypeStruct(vma=...)``);
    the kernel is per-device local compute, so outputs vary exactly as the
    union of the inputs do.  Outside shard_map this is the empty set."""
    out = frozenset()
    for a in arrays:
        out |= getattr(jax.typeof(a), "vma", frozenset())
    return out

def _kv_row(heads: int, kv_heads: int):
    """Flattened ``(batch·q_head) → (batch·kv_head)`` row map for GQA: query
    head ``h`` reads kv head ``h // group`` (consecutive query heads share).
    Identity when the head counts match (the common path compiles away the
    arithmetic: ``group == 1``)."""
    group = heads // kv_heads
    if group == 1:
        return lambda b: b
    return lambda b: (b // heads) * kv_heads + (b % heads) // group


#: VMEM budget (bytes) for a kernel's two double-buffered full-sequence
#: refs — k+v in the fwd/dQ kernels, q+do in the dK/dV kernel.  Half the
#: 16 MB of VMEM a kernel is given by default; the rest covers block tiles,
#: the score matrix, and accumulators.  Sequences whose staged refs exceed
#: this are transparently chunked (:func:`_stage_chunk`) and the partials
#: merged through their logsumexps — same math, unbounded T (the real chip
#: rejected the unchunked kernel at T=16384, D=128: 16.25 MB scoped > 16 MB).
_STAGE_BUDGET_BYTES = 8 * 1024 * 1024

#: VMEM budget (bytes) for the float32 dK and dV rows the dK/dV kernel keeps
#: resident while a KV head's whole query group passes over them (one
#: buffer each: they leave once a head).  4,096 rows at D=128; a longer kv
#: sequence takes a grid axis of such chunks.
_RESIDENT_BUDGET_BYTES = 4 * 1024 * 1024

#: Mosaic pads a block's last two dims to whole (8, 128) tiles.  A per-row
#: ref with a trailing singleton lane dim ((1, T, 1): the fwd/dQ kernels'
#: lse/delta blocks and their staged kv segment row) costs 512 bytes a f32
#: row, not 4 — the on-chip OOM that motivated this accounting: the dK/dV
#: kernel at T=16384, D=128 with q+do staged under a naive 2·2·D·itemsize
#: budget still allocated 17 MB, the extra ~8 MB being exactly the
#: double-buffered lane-padded lse+delta rows.  That kernel stages its
#: per-row refs lane-dense, ``(T // block_q, block_q)`` — a q block a
#: sublane row, read as a ``(1, block_q)`` row that broadcasts down the
#: transposed score tile: 4 bytes a row, 32 when a chunk of fewer than
#: eight q blocks still fills eight sublanes.
_LANE = 128
_SUBLANE = 8


def _row_bytes(depth, itemsize, n_dense=0, block=_LANE, segmented=False):
    """Double-buffered VMEM bytes per staged sequence row: two (row, depth)
    arrays (k+v or q+do), plus ``n_dense`` lane-dense 32-bit per-row refs in
    blocks of ``block`` rows (the dK/dV kernel's lse/delta/query segments,
    counted at eight sublanes a block), plus the lane-padded int32 kv
    segment row the fwd/dQ kernels stage when segmented."""
    b = 2 * 2 * depth * itemsize
    lanes = -(-block // _LANE) * _LANE
    b += -(-2 * n_dense * _SUBLANE * 4 * lanes // block)
    if segmented:
        b += 2 * _LANE * 4
    return b


def _stage_chunk(length, row_bytes, block, max_rows,
                 budget=_STAGE_BUDGET_BYTES):
    """Chunk length for the full-row staged refs: the largest divisor of
    ``length`` that is a multiple of ``block`` and fits ``budget`` (the
    stage budget) at ``row_bytes`` per row (:func:`_row_bytes`).  ``length``
    itself when it already fits — the chunk-free fast path, byte-identical
    to the unchunked kernel."""
    rows = budget // row_bytes
    if max_rows is not None:
        rows = min(rows, max_rows)
    if length <= rows:
        return length
    c = rows - rows % block
    while c >= block and length % c:
        c -= block
    if c < block:
        raise ValueError(
            f"sequence length {length} has no multiple-of-{block} divisor "
            f"within the {rows}-row VMEM stage budget: pad the sequence or "
            f"pass smaller block_q/block_k"
        )
    return c


def _merge_partials(o1, lse1, o2, lse2):
    """Exact two-partial softmax merge over disjoint key sets (the lse
    composition rule documented on :func:`flash_attention_lse`), honoring
    the fully-masked-row contract (zero rows, lse = NEG_INF).  Returns the
    merged output in fp32 so chained merges accumulate at full precision
    and round once at the end (the backward paths' policy).

    Siblings implementing the same rule in their own layouts/sentinels:
    ``parallel.ring_attention._merge_blocks`` ((B,T,H,D)/-inf) and
    ``parallel.zigzag._merge_flash_block`` (running unnormalized state) —
    a fix to the alive-row guard here likely applies there too."""
    m = jnp.maximum(lse1, lse2)
    alive = m > NEG_INF * 0.5
    m_safe = jnp.where(alive, m, 0.0)
    w1 = jnp.where(alive, jnp.exp(lse1 - m_safe), 0.0)
    w2 = jnp.where(alive, jnp.exp(lse2 - m_safe), 0.0)
    tot = jnp.maximum(w1 + w2, 1e-30)
    o = (o1.astype(jnp.float32) * (w1 / tot)[..., None]
         + o2.astype(jnp.float32) * (w2 / tot)[..., None])
    lse = jnp.where(alive, m_safe + jnp.log(tot), NEG_INF)
    return o, lse


def _fwd(q, k, v, seg_q, seg_kv, segmented, heads, kv_heads, causal, block_q,
         block_k, interpret, window=None, max_stage_rows=None):
    """Forward dispatch: single kernel call when k/v fit the VMEM stage
    budget, else kv-chunked calls (static position offsets into the masks
    and block-skip ranges) merged through their logsumexps."""
    S = k.shape[1]
    C = _stage_chunk(
        S, _row_bytes(k.shape[2], k.dtype.itemsize, segmented=segmented),
        block_k, max_stage_rows,
    )
    if C >= S:
        return _fwd_chunk(q, k, v, seg_q, seg_kv, segmented, heads, kv_heads,
                          causal, block_q, block_k, interpret, window, 0)
    o = lse = None
    for off in range(0, S, C):
        kc = jax.lax.slice_in_dim(k, off, off + C, axis=1)
        vc = jax.lax.slice_in_dim(v, off, off + C, axis=1)
        sc = (jax.lax.slice_in_dim(seg_kv, off, off + C, axis=1)
              if segmented else seg_kv)
        oc, lsec = _fwd_chunk(q, kc, vc, seg_q, sc, segmented, heads,
                              kv_heads, causal, block_q, block_k, interpret,
                              window, off)
        o, lse = (oc, lsec) if o is None else _merge_partials(o, lse, oc,
                                                              lsec)
    # The running merge stays fp32 across chunks; round once at the end.
    return o.astype(q.dtype), lse


def _fwd_chunk(q, k, v, seg_q, seg_kv, segmented, heads, kv_heads, causal,
               block_q, block_k, interpret, window, kv_off):
    BH, T, D = q.shape
    S = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    grid = (BH, T // block_q)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, segmented=segmented,
        scale=scale, window=window, kv_off=kv_off,
    )
    kvr = _kv_row(heads, kv_heads)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, S, D), lambda b, i: (kvr(b), 0, 0)),
        pl.BlockSpec((1, S, D), lambda b, i: (kvr(b), 0, 0)),
    ]
    args = [q, k, v]
    if segmented:
        # Segments stay (B, T)/(B, S) — every head of batch row b // heads
        # shares them (no H-fold copy): q-block view + full-row kv view.
        # Trailing singleton lane dim for Mosaic's block tiling rule.
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b // heads, i, 0)),
            pl.BlockSpec((1, S, 1), lambda b, i: (b // heads, 0, 0)),
        ]
        args += [seg_q[..., None], seg_kv[..., None]]
    # Outputs vary as the union of ALL inputs — including the segment
    # arrays (a device-varying packing mask alone makes outputs vary).
    vma = _vma_union(q, k, v, *(args[3:] if segmented else []))
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((BH, T, 1), jnp.float32, vma=vma),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    return o, lse[..., 0]


# --------------------------------------------------------------------- bwd
def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_q, causal, segmented, scale, window=None, q_off=0,
):
    # Written from the kv block's side.  Grid (batch·kv_head, kv chunk,
    # query head of the group, kv block of the chunk); one step takes the
    # block's k/v_ref (1, BK, D) past the staged q/do_ref (1, T, D) of ONE
    # query head.  Per-row refs (lse/delta/query segments) are lane-dense
    # (1, T // BQ, BQ) — q block ``qi`` is sublane row ``qi`` — and the kv
    # block's segments a (1, BK, 1) column.  dk/dv_ref (1, Sc, D) fp32 hold
    # the kv head's whole chunk: their block index ignores the two inner
    # axes, so they stay in VMEM while the group passes and leave once.
    if segmented:
        segq_ref, segk_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
    g = pl.program_id(2)
    i = pl.program_id(3)
    bk = k_ref.shape[1]
    T = q_ref.shape[1]
    D = k_ref.shape[2]
    ki = pl.program_id(1) * (dk_ref.shape[1] // bk) + i  # block of all S
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    seg_k = segk_ref[0] if segmented else None  # (BK, 1)

    n_q = T // block_q
    q_start_blk, q_end_blk = _q_block_range(
        ki, bk, block_q, n_q, causal, window, q_off=q_off
    )

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi, 1), :]  # (1, BQ)
        delta = delta_ref[0, pl.ds(qi, 1), :]
        # Scores and dP TRANSPOSED, (BK, BQ): k·qᵀ and v·doᵀ contract the
        # last dims like the dQ kernel's products, and pᵀ / dsᵀ then enter
        # the two accumulations as plain (BK, BQ)·(BQ, D) left operands —
        # no score tile is turned.
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        st = _mask_scores(st, qi * block_q + q_off, ki * bk, causal, window,
                          q_axis=1)
        if segmented:
            seg_q = segq_ref[0, pl.ds(qi, 1), :]  # (1, BQ)
            st = jnp.where(seg_k == seg_q, st, NEG_INF)
        # Exact softmax via saved LSE.  Rows with lse == NEG_INF carried no
        # mass in the forward (fully masked); s - lse would cancel the
        # finite NEG_INF there (p = 1), so mask them to zero explicitly.
        pt = jnp.where(lse > NEG_INF * 0.5, jnp.exp(st - lse), 0.0)
        dv_new = dv + jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, BQ)
        dst = pt * (dpt - delta)
        dk_new = dk + jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, D), jnp.float32)
    dv0 = jnp.zeros((bk, D), jnp.float32)
    dk, dv = jax.lax.fori_loop(q_start_blk, q_end_blk, body, (dk0, dv0))
    # dk = dsᵀ·(q·scale): the softmax scale flows in through the scaled q.
    # The group sums where it stands: the first query head's pass sets the
    # block's rows, the others add to them, all in fp32.
    rows = pl.ds(i * bk, bk)

    @pl.when(g == 0)
    def _():
        dk_ref[0, rows, :] = dk
        dv_ref[0, rows, :] = dv

    @pl.when(g > 0)
    def _():
        dk_ref[0, rows, :] += dk
        dv_ref[0, rows, :] += dv


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    block_k, causal, segmented, scale, window=None, kv_off=0,
):
    if segmented:
        segq_ref, segk_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    T = k_ref.shape[1]
    D = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    seg_q = segq_ref[0, :, 0] if segmented else None  # (BQ,)

    n_k = T // block_k
    k_lo, n_k_eff = _k_block_range(qi, bq, block_k, n_k, causal, window,
                                   kv_off=kv_off)

    def body(ki, dq):
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = _mask_scores(s, qi * bq, ki * block_k + kv_off, causal, window)
        if segmented:
            seg_k = segk_ref[0, pl.ds(ki * block_k, block_k), 0]
            s = jnp.where(seg_q[:, None] == seg_k[None, :], s, NEG_INF)
        # Same fully-masked-row guard as the dK/dV kernel.
        p = jnp.where(
            (lse > NEG_INF * 0.5)[:, None], jnp.exp(s - lse[:, None]), 0.0
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(k_lo, n_k_eff, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd(segmented, heads, kv_heads, causal, block_q, block_k, interpret,
         residuals, g, dlse=None, window=None, max_stage_rows=None):
    """Shared backward.  ``dlse`` (cotangent of the logsumexp output, used by
    the LSE-exposing API) folds into the kernels for free: ``∂lse_i/∂s_ij =
    p_ij``, so the lse cotangent just shifts the per-row delta —
    ``ds = p·(dp − (delta − dlse))`` — and both kernels run unchanged.

    Under GQA (``kv_heads < heads``) the dK/dV kernel owns a KV head's rows
    for its whole group: the query heads pass over them on an inner grid
    axis (each reading the shared kv block through the forward's index map)
    and add into one resident fp32 block, so what leaves the kernel is one
    ``(B·kv_heads, S, D)`` tensor each — no per-query-head gradient ever
    reaches HBM, and ``heads == kv_heads`` is the same body with a group
    axis of length one."""
    q, k, v, seg_q, seg_kv, o, lse = residuals
    do = g
    BH, T, D = q.shape
    BKH, S = k.shape[:2]
    group = heads // kv_heads
    scale = 1.0 / math.sqrt(D)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    kvr = _kv_row(heads, kv_heads)
    vma = _vma_union(q, k, v, do, lse, delta,
                     *([seg_q, seg_kv] if segmented else []))

    # kv rows whose fp32 dK + dV stay resident (single-buffered) at a time.
    Sc = _stage_chunk(S, 2 * D * 4, block_k, max_stage_rows,
                      budget=_RESIDENT_BUDGET_BYTES)
    n_i = Sc // block_k

    def dkv_call(q_c, do_c, lse_c, delta_c, seg_q_c, q_off):
        """fp32 dK/dV over ALL kv rows from one q-chunk (``(1, Tc, D)``
        staged q/do refs, once a query head and kv chunk; kv blocked
        through the grid)."""
        Tc = q_c.shape[1]
        dkv_kernel = functools.partial(
            _bwd_dkv_kernel, block_q=block_q, causal=causal,
            segmented=segmented, scale=scale, window=window, q_off=q_off,
        )

        def dense(x):  # (rows, Tc) → one q block a sublane row
            return x.reshape(x.shape[0], Tc // block_q, block_q)

        def qh(b, c, g, i):  # the group's g-th query head
            return (b * group + g, 0, 0)

        def kvb(b, c, g, i):  # kv block i of chunk c
            return (b, c * n_i + i, 0)

        dense_block = (1, Tc // block_q, block_q)
        stat = pl.BlockSpec(dense_block, qh)
        in_specs = [
            pl.BlockSpec((1, Tc, D), qh),          # q
            pl.BlockSpec((1, block_k, D), kvb),    # k
            pl.BlockSpec((1, block_k, D), kvb),    # v
            pl.BlockSpec((1, Tc, D), qh),          # do
            stat,                                  # lse
            stat,                                  # delta
        ]
        args = [q_c, k, v, do_c, dense(lse_c), dense(delta_c)]
        if segmented:
            in_specs += [
                pl.BlockSpec(dense_block,
                             lambda b, c, g, i: (b // kv_heads, 0, 0)),
                pl.BlockSpec((1, block_k, 1),
                             lambda b, c, g, i: (b // kv_heads,
                                                 c * n_i + i, 0)),
            ]
            args += [dense(seg_q_c), seg_kv[..., None]]
        resident = pl.BlockSpec((1, Sc, D), lambda b, c, g, i: (b, c, 0),
                                pipeline_mode=pl.Buffered(1))
        return pl.pallas_call(
            dkv_kernel,
            grid=(BKH, S // Sc, group, n_i),
            in_specs=in_specs,
            out_specs=[resident, resident],
            out_shape=[
                jax.ShapeDtypeStruct((BKH, S, D), jnp.float32, vma=vma),
                jax.ShapeDtypeStruct((BKH, S, D), jnp.float32, vma=vma),
            ],
            interpret=interpret,
            name="flash_bwd_dkv",
        )(*args)

    # The kernel accumulates and sums the group in fp32, so fp32 is what it
    # hands over: (B·kv_heads, S, D), the size of the final gradients × 2
    # against a bf16 wire.  The partials of a q-chunked sequence (long T,
    # :func:`_stage_chunk`) add in fp32 too; everything rounds once, here.
    Cq = _stage_chunk(
        T,
        _row_bytes(D, q.dtype.itemsize, n_dense=3 if segmented else 2,
                   block=block_q),
        block_q, max_stage_rows,
    )
    if Cq >= T:
        dk, dv = dkv_call(q, do, lse, delta, seg_q, 0)
    else:
        dk = dv = None
        for off in range(0, T, Cq):
            sl = functools.partial(jax.lax.slice_in_dim, start_index=off,
                                   limit_index=off + Cq, axis=1)
            dkc, dvc = dkv_call(
                sl(q), sl(do), sl(lse), sl(delta),
                sl(seg_q) if segmented else seg_q, off,
            )
            dk = dkc if dk is None else dk + dkc
            dv = dvc if dv is None else dv + dvc
    dk = dk.astype(k.dtype)
    dv = dv.astype(v.dtype)

    def dq_call(k_c, v_c, seg_kv_c, kv_off, out_dtype):
        """dQ over all q rows from one kv-chunk (``(1, Sc, D)`` staged k/v
        refs; q blocked through the grid)."""
        Sc = k_c.shape[1]
        dq_kernel = functools.partial(
            _bwd_dq_kernel, block_k=block_k, causal=causal,
            segmented=segmented, scale=scale, window=window, kv_off=kv_off,
        )
        in_specs = [
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),  # q
            pl.BlockSpec((1, Sc, D), lambda b, i: (kvr(b), 0, 0)),  # k
            pl.BlockSpec((1, Sc, D), lambda b, i: (kvr(b), 0, 0)),  # v
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),  # do
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),  # lse
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),  # delta
        ]
        args = [q, k_c, v_c, do, lse[..., None], delta[..., None]]
        if segmented:
            in_specs += [
                pl.BlockSpec((1, block_q, 1),
                             lambda b, i: (b // heads, i, 0)),   # seg (q blk)
                pl.BlockSpec((1, Sc, 1),
                             lambda b, i: (b // heads, 0, 0)),   # seg (k rows)
            ]
            args += [seg_q[..., None], seg_kv_c[..., None]]
        return pl.pallas_call(
            dq_kernel,
            grid=(BH, T // block_q),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, T, D), out_dtype, vma=vma),
            interpret=interpret,
            name="flash_bwd_dq",
        )(*args)

    Ck = _stage_chunk(
        S, _row_bytes(D, k.dtype.itemsize, segmented=segmented),
        block_k, max_stage_rows,
    )
    if Ck >= S:
        dq = dq_call(k, v, seg_kv, 0, q.dtype)
    else:
        dq = None
        for off in range(0, S, Ck):
            sl = functools.partial(jax.lax.slice_in_dim, start_index=off,
                                   limit_index=off + Ck, axis=1)
            dqc = dq_call(sl(k), sl(v),
                          sl(seg_kv) if segmented else seg_kv, off,
                          jnp.float32)
            dq = dqc if dq is None else dq + dqc
        dq = dq.astype(q.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------- api
@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13)
)
def _flash_lse(q, k, v, seg_q, seg_kv, segmented, heads, kv_heads, causal,
               block_q, block_k, interpret, window, max_stage_rows):
    return _fwd(q, k, v, seg_q, seg_kv, segmented, heads, kv_heads, causal,
                block_q, block_k, interpret, window=window,
                max_stage_rows=max_stage_rows)


def _flash_lse_fwd(q, k, v, seg_q, seg_kv, segmented, heads, kv_heads,
                   causal, block_q, block_k, interpret, window,
                   max_stage_rows):
    o, lse = _fwd(q, k, v, seg_q, seg_kv, segmented, heads, kv_heads, causal,
                  block_q, block_k, interpret, window=window,
                  max_stage_rows=max_stage_rows)
    return (o, lse), (q, k, v, seg_q, seg_kv, o, lse)


def _flash_lse_bwd(segmented, heads, kv_heads, causal, block_q, block_k,
                   interpret, window, max_stage_rows, residuals, g):
    do, dlse = g
    dq, dk, dv = _bwd(segmented, heads, kv_heads, causal, block_q, block_k,
                      interpret, residuals, do, dlse=dlse, window=window,
                      max_stage_rows=max_stage_rows)
    # Segments are integer-typed: their cotangent is the symbolic zero.
    return dq, dk, dv, None, None


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _default_block(length: int, cap: int) -> int:
    """Largest multiple of 8 ≤ ``cap`` that divides ``length`` — Mosaic's
    sublane constraint (block multiple of 8, or the full dim).

    When NO multiple of 8 divides (e.g. the ViT token grid T=196=4·49 —
    the real chip rejected the old chooser's block 4 there: a (1, 4, 64)
    block violates the (8, 128) tiling rule), fall back to the full dim,
    which the tiling rule always accepts — but only up to 1024, past which
    a full-dim scores tile blows the ~16 MB VMEM budget; longer awkward
    lengths must be padded upstream (error, with the padded size named).

    The on-chip sweep (result/flash_tpu.json, TPU v5 lite, T=2048) showed
    (block_q=128, block_k=128) — the old defaults — running 0.78× of XLA
    attention while (256, 512) runs 2.1× faster fwd+bwd: bigger kv blocks
    amortize the online-softmax rescale over more MXU work.  (512, 512),
    which that sweep left out at D=128, is faster again (PERF.md §6, PR 36:
    4.01 ms against 4.63 a layer's fwd+bwd at 24 / 2 heads of 128, T=4096,
    the dK/dV kernel 1.64 against 2.12), as result/flash_tpu_d64.json had
    it at D=64: a taller q block amortizes the dK/dV kernel's 2·block_k·D
    fp32 carry over twice the cells."""
    b = min(cap, length)
    b -= b % 8
    while b >= 8:
        if length % b == 0:
            return b
        b -= 8
    if length <= 1024:
        return length
    raise ValueError(
        f"no multiple-of-8 block size divides sequence length {length} and "
        f"a full-dim block would exceed VMEM: pad the sequence to a "
        f"multiple of 8 (e.g. {-(-length // 8) * 8}) with segment-id "
        f"masking, or pass block_q/block_k explicitly"
    )


#: Measured flash-vs-XLA crossover sequence length on the real chip
#: (TPU v5 lite, bf16) for CAUSAL / cross attention: XLA's
#: materialized-scores attention WINS below it — at T=512/D=64 flash ran
#: 0.86× of XLA end-to-end (result/seq2seq_tpu.json) because the block
#: machinery doesn't amortize — while flash wins 2.1–2.5× at T=2048
#: (result/flash_tpu{_d64,}.json) and 1.3–1.6× fwd+bwd at T=2048–4096
#: (result/longcontext_tpu.json).
FLASH_MIN_SEQ = 1024

#: Measured crossover for NON-CAUSAL UNMASKED self-attention (no mask
#: work, every block live): flash already wins at T=196 — the ViT-S/16
#: on-chip pair measured 2010.6 img/s (flash) vs 1919.4 (XLA) for the
#: full train step (result/bench_tpu_vit.json vs
#: result/bench_tpu_vit_auto.json).  The threshold sits AT the measured
#: point; below it is unmeasured and keeps the conservative XLA choice.
#: SEGMENT-MASKED non-causal rows (e.g. the packed seq2seq encoder) are a
#: different, unmeasured category — their call sites keep the generic
#: crossover (the T=512 seq2seq composite measured flash 0.86× overall).
FLASH_MIN_SEQ_NONCAUSAL = 196


def resolve_attention(impl: str, *lengths: int, causal: bool = True,
                      platform: Optional[str] = None) -> str:
    """Resolve an ``attention`` impl choice for the given sequence
    length(s): ``'auto'`` returns ``'flash'`` when every length clears the
    measured crossover AND tiles legally (a multiple-of-8 block divides it
    or a full-dim block fits — Mosaic's sublane rule), else ``'xla'``.
    Explicit ``'flash'``/``'xla'`` pass through unchanged.

    ``'auto'`` is BACKEND-AWARE: off-TPU (``platform`` defaults to the
    current JAX backend) it always resolves ``'xla'`` — the Pallas kernels
    run in interpret mode there, a numerics-testing vehicle, never a perf
    win.  It is also CAUSALITY-AWARE: pass ``causal=False`` for UNMASKED
    non-causal single-length self-attention (the ViT family measurement)
    to use the lower crossover :data:`FLASH_MIN_SEQ_NONCAUSAL`; causal,
    cross, and segment-masked rows use :data:`FLASH_MIN_SEQ` (callers
    with segment ids should keep the default ``causal=True`` resolution —
    that category is unmeasured below 1024)."""
    if impl not in ("flash", "xla", "auto"):
        raise ValueError(
            f"attention={impl!r}: expected 'flash', 'xla' or 'auto'"
        )
    if impl != "auto":
        return impl
    if platform is None:
        platform = jax.default_backend()
    if platform != "tpu":
        return "xla"
    min_seq = (
        FLASH_MIN_SEQ_NONCAUSAL
        if not causal and len(lengths) == 1
        else FLASH_MIN_SEQ
    )
    for n in lengths:
        if n < min_seq:
            return "xla"
        try:
            if _default_block(n, 512) < 8:
                return "xla"
        except ValueError:
            return "xla"
    return "flash"


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    max_stage_rows: Optional[int] = None,
):
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ``(B, H, T)`` — the merge state for blockwise/ring composition: two
    attention results over disjoint key sets combine exactly as

        ``lse = logaddexp(lse₁, lse₂);  o = (o₁·e^{lse₁−lse} + o₂·e^{lse₂−lse})``

    (see :func:`chainermn_tpu.parallel.ring_attention.ring_flash_self_attention`).
    Differentiable in both outputs.

    ``k``/``v`` may be a different length than ``q`` (cross-attention);
    ``causal`` then requires equal lengths.  They may also carry FEWER heads
    than ``q`` (grouped-query / multi-query attention, inferred from the
    shapes): query head ``h`` attends through kv head ``h // group`` where
    ``group = q_heads // kv_heads``.  The kernels stream each shared kv
    block once per query head via their index maps — no repeated kv copy is
    materialized in HBM — and dK/dV group-sum in fp32.  ``kv_segment_ids``
    (``(B, S)``) masks keys independently of the query segments — give pad
    keys an id no query uses; defaults to ``segment_ids`` (self-attention
    packing)."""
    B, T, H, D = q.shape
    S = k.shape[1]
    KH = k.shape[2] if k.ndim == 4 else H
    if k.shape != (B, S, KH, D) or v.shape != (B, S, KH, D):
        raise ValueError(
            f"k/v must be (B, S, kv_heads, D) = ({B}, S, *, {D}); got "
            f"{k.shape} / {v.shape}"
        )
    if KH != H and (KH == 0 or H % KH):
        raise ValueError(
            f"q heads {H} must be a multiple of kv heads {KH} "
            "(grouped-query attention)"
        )
    if causal and S != T:
        raise ValueError(
            f"causal attention needs equal q/kv lengths, got {T} vs {S}"
        )
    if window is not None:
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if S != T:
            raise ValueError(
                f"sliding-window attention needs equal q/kv lengths, got "
                f"{T} vs {S}"
            )
    if interpret is None:
        interpret = _use_interpret()
    # Sweep-informed defaults (see _default_block); explicit args win.
    block_q = _default_block(T, 512) if block_q is None else block_q
    block_k = _default_block(S, 512) if block_k is None else block_k
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    if T % block_q or S % block_k:
        # Validate BEFORE any fallback so CPU tests reject exactly the
        # block configs the TPU kernel would.
        raise ValueError(
            f"q len {T} / kv len {S} must be multiples of block sizes "
            f"({block_q}, {block_k})"
        )
    segmented = segment_ids is not None or kv_segment_ids is not None
    if segmented:
        if segment_ids is None:
            segment_ids = jnp.zeros((B, T), jnp.int32)
        if kv_segment_ids is None:
            if S != T:
                raise ValueError(
                    "cross-attention with segment_ids needs explicit "
                    "kv_segment_ids (kv length differs from q)"
                )
            kv_segment_ids = segment_ids
        if segment_ids.shape != (B, T):
            raise ValueError(
                f"segment_ids must be (batch, q_len) = {(B, T)}, got "
                f"{segment_ids.shape}"
            )
        if kv_segment_ids.shape != (B, S):
            raise ValueError(
                f"kv_segment_ids must be (batch, kv_len) = {(B, S)}, got "
                f"{kv_segment_ids.shape}"
            )
    if interpret and _vma_union(q, k, v):
        # Interpret-mode Pallas cannot be traced through shard_map's vma
        # checker (its kernel jaxpr mixes varying refs with invariant index
        # scalars and the checker rejects it — a JAX interpreter
        # limitation).  Off-TPU inside a checked shard_map, compute the
        # mathematically identical XLA form instead; the compiled kernel is
        # unaffected (opaque to the checker).
        return _reference_attention_lse(
            q, k, v, causal, segment_ids, kv_segment_ids, window
        )

    def to_bh(x):
        _, L, Hx, _ = x.shape
        return x.transpose(0, 2, 1, 3).reshape(B * Hx, L, D)

    # Segments stay (B, T)/(B, S): the kernels' index maps read row b // H,
    # so every head shares one copy (no H-fold materialization).
    if segmented:
        seg_q = segment_ids.astype(jnp.int32)
        seg_kv = kv_segment_ids.astype(jnp.int32)
    else:
        seg_q = seg_kv = jnp.zeros((1, 1), jnp.int32)  # unused placeholder
    o, lse = _flash_lse(
        to_bh(q), to_bh(k), to_bh(v), seg_q, seg_kv, segmented, H, KH,
        causal, block_q, block_k, interpret, window, max_stage_rows,
    )
    return (
        o.reshape(B, H, T, D).transpose(0, 2, 1, 3),
        lse.reshape(B, H, T),
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    max_stage_rows: Optional[int] = None,
) -> jax.Array:
    """Exact attention over ``(batch, seq, heads, head_dim)`` inputs; ``k``/
    ``v`` may use a different sequence length (cross-attention, non-causal).

    ``segment_ids`` (``(batch, q_len)`` int32) masks attention to
    same-segment pairs — packed sequences and padding (give pad positions
    their own id) without materialized masks; ``kv_segment_ids``
    (``(batch, kv_len)``) masks the key side independently (defaults to
    ``segment_ids``).  Requires lengths divisible by the block sizes (pad
    upstream; the data layer's bucketing keeps XLA-friendly static shapes
    anyway).  ``block_q``/``block_k`` default to the largest multiple-of-8
    divisors up to 512, the on-chip optimum (``result/flash_tpu_d64.json``;
    PERF.md §6, PR 36 at head dim 128); see ``_default_block``.  Pass
    explicit values to override.  Differentiable via the flash backward.
    ``interpret=None`` auto-selects interpret mode off-TPU.

    ``window`` enables sliding-window (local) attention: query ``i``
    attends only keys with ``|i - k| < window`` (with ``causal`` the usual
    Mistral-style "last ``window`` keys").  The kernels SKIP key/query
    blocks entirely outside the window, so compute and HBM reads scale
    O(T·window) instead of O(T²) — combine with ``segment_ids`` for packed
    local attention.

    Sequences too long for the kernels' full-row VMEM staging are
    transparently chunked and the partials merged through their logsumexps
    (``_stage_chunk``) — same math, unbounded T; ``max_stage_rows``
    tightens the per-chunk row budget below the VMEM-derived default
    (mainly a test hook).

    Thin facade over :func:`flash_attention_lse` (one custom-VJP path to
    maintain); the dropped lse output arrives in the backward as a zero
    cotangent, which folds away inside the shared kernels."""
    return flash_attention_lse(
        q, k, v, causal=causal, segment_ids=segment_ids,
        kv_segment_ids=kv_segment_ids, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window, max_stage_rows=max_stage_rows,
    )[0]

"""The paged KV pool: its row format, the write, the two reads.

The serving engine's cache (``chainermn_tpu/serving``) lives in a fixed
device-resident **block pool**, one array per layer, and this module is
the one place that knows what a pool row looks like:

* :func:`pool_shapes` — the shapes :mod:`~chainermn_tpu.serving.kv_pool`
  allocates;
* :func:`pool_write` — pack a chunk's tokens into rows and scatter them
  through the block tables (masked slots park on block 0);
* :func:`paged_decode_attention` — the Pallas kernel that walks a slot's
  table over the pool in HBM (below), and its ``shard_map`` twin;
* :func:`pool_context_attend` — the gathered read: a slot's blocks up to
  the chunk's last position (:func:`context_widths`), contracted as stored
  into float32 scores (prefill chunks, and the reference path the tests
  compare the kernel with);
* :func:`paged_attend` — the one place that chooses between the two reads.

The model's block (``models/transformer.py`` ``_DecoderBlock``) hands
over q, k, v, the tables and the positions and knows none of this.

:func:`paged_decode_attention` reads a pool that is token-major and
lane-dense — ``(num_blocks, block_len, KH * 2 * Dh)`` with each head's
key and value side by side in one lane group ``[k_h | v_h]`` (why that
layout and no other: :mod:`chainermn_tpu.serving.kv_pool`), an int8 pool
with its scale plane ``(num_blocks, KH, 2, block_len)`` beside it, and
physical block 0 the parking block no table maps — and each slot owns a
block table mapping logical cache blocks to physical pool blocks
(vLLM/PagedAttention, Kwon et al. 2023).  Grid ``(S,)``, one step a
slot, with the block tables and each slot's count of resident blocks
scalar-prefetched: the pool stays in HBM and the step loops over the
slot's OWN blocks, DMAing the whole contiguous row
``pool[table[s, i]]`` — every KV head of the block, 102 KB at GPT-2 XL's
25 heads of 64 in bf16 — into one of ``_ROWS_IN_VMEM`` buffers while
the rows before it are consumed.  The kernel walks the table directly, no gathered
contiguous copy is ever materialized, and a table entry past a slot's
length costs neither a DMA nor a loop iteration, an idle slot nothing
but its zeros.  Blocks accumulate through the online-softmax recurrence
(running max / normalizer / fp32 accumulator in VMEM scratch), so no
context length is too long for VMEM: it holds a few rows at a time.  How a
row's heads are handled follows from the static shapes: one query head
a KV head (MHA) as lane-dense VPU arithmetic over the whole row — the
slot's queries laid along the row's own lanes, one cross-lane sum a
head — because 25 matmuls of ``(1 x 64) . (64 x 16)`` a block leave the
MXU idle behind its own latency; grouped queries (and the int8 pool,
whose scale panels are per head) as a static loop of MXU matmuls over
the KV heads, where ``G * T`` query rows a head make the matmul worth
its push.  That second body's loop step is a **tile of 128 positions**
where a block holds fewer (:func:`blocks_a_step`: eight consecutive table
entries at ``block_len`` 16, their rows DMAed into consecutive slices of
one buffer): a table lists a slot's blocks in logical order, so the tile
is contiguous in everything the fold computes, and a head's two products
cost the MXU's round trip once for 128 positions instead of once a block
(PR 48).  A 4-D query ``(S, T, H, Dh)`` is the **multi-query verify
mode** (the serving engine's speculative decode): query offset ``t``
attends positions ``< valid_len + t`` — per-position causality inside
the verify chunk, one kernel launch for all ``k + 1`` positions
(``T <= MAX_VERIFY_T``; ``T == 1`` is bit-identical to the 3-D call).
On a chip a head's lane group ``2 * Dh`` has to be a multiple of 128
(:func:`paged_kernel_takes`); any other head width takes the gathered
read.

No reference counterpart (the reference has no incremental-decode stack;
SURVEY §2.9's examples are training-side) — this extends the repo's
Pallas hot-op family (``ops/flash_attention.py``) to the inference loop.
On non-TPU backends the kernel runs in Pallas interpret mode;
``tests/ops_tests/test_decode_attention.py`` pins its numerics against
an einsum oracle (MHA/GQA, ragged ``valid_len``, int8 pool + scales),
and the write and the gathered read against plain ``numpy``.

**Tensor-parallel (shard_map) entry point**: a Pallas kernel carries
no GSPMD partitioning rule, so a mesh-sharded caller cannot simply let
the partitioner propagate through ``pallas_call``.
:func:`sharded_paged_decode_attention` closes the gap by running the
kernel **per shard** under ``jax.shard_map`` over a 1-D mesh: queries
shard on the query-head axis, the pool on its LAST axis (whole
``[k_h | v_h]`` lane groups, so a cut on KV heads is a plain block cut),
block tables / lengths ride replicated, and each shard runs the
unmodified kernel over its local ``KH / n`` heads.
Attention is embarrassingly parallel across KV heads, so the sharded
output is bit-identical to the unsharded kernel's — no collective is
introduced; the row-parallel output projection's existing ``psum``
downstream completes the Megatron cut
(:mod:`chainermn_tpu.serving.sharding`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import NEG_INF, _use_interpret

#: query-position cap for :func:`paged_decode_attention`'s multi-query
#: (speculative-verify) mode: T query offsets multiply the per-program
#: row count (T·G rows vs G), so unbounded T would blow the scratch
#: budget — and verify chunks are k+1 ≤ a handful anyway.
#: :func:`paged_attend` takes the gathered read past it.
MAX_VERIFY_T = 16


def pool_shapes(num_blocks: int, block_len: int, kv_heads: int,
                head_dim: int):
    """``(row_shape, scale_shape)`` of one layer's pool: the ``"kv"`` array
    ``(num_blocks, block_len, KH * 2 * Dh)`` — a token's row is
    ``[k_0 | v_0 | k_1 | v_1 | ...]`` — and, for an int8 pool, the fp32
    ``"kv_scale"`` plane ``(num_blocks, KH, 2, block_len)`` that pairs a
    head's key and value scale the same way, positions minor-most."""
    return ((num_blocks, block_len, kv_heads * 2 * head_dim),
            (num_blocks, kv_heads, 2, block_len))


def pool_write(cache, k, v, scales, block_tables, q_pos, slot_mask=None):
    """Write a chunk's keys and values into the pool; returns the new
    cache entry (same keys as ``cache``).

    Each row's positions map through its block table to physical pool
    blocks; ONE scatter of whole token rows ``[k_0|v_0|k_1|v_1|...]`` into
    the token-major pool (serving/kv_pool.py says why that layout) — the
    same statement for decode (T = 1), verify and prefill chunks.  Masked
    (idle) slots redirect to the reserved parking block 0 and write back
    their own current value — duplicate indices then carry duplicate
    VALUES, keeping the scatter deterministic.

    Args:
      cache: ``{"kv": pool}``, an int8 pool with ``"kv_scale"`` beside it
        (:func:`pool_shapes`).
      k, v: ``(B, T, KH, Dh)`` — floats, stored at the pool's dtype, or
        already int8 with ``scales``.
      scales: ``None``, or the int8 values' ``(k_scale, v_scale)``, each
        ``(B, T, KH)`` fp32 — given iff the cache has a scale plane.
      block_tables: ``(B, max_blocks)`` int32.
      q_pos: ``(B, T)`` int32 — the position each token is written at.
      slot_mask: ``(B,)`` bool or ``None`` — rows that write nothing.
    """
    pool = cache["kv"]
    quant = scales is not None
    if quant != ("kv_scale" in cache):
        raise ValueError(
            "an int8 pool (a cache with 'kv_scale') is written with "
            f"scales and no other: cache has {sorted(cache)}, scales "
            f"{'given' if quant else 'None'}"
        )
    B, T, KH, Dh = k.shape
    with jax.named_scope("kv_write"):
        k, v = k.astype(pool.dtype), v.astype(pool.dtype)
        BL = pool.shape[1]
        pb = jnp.take_along_axis(
            block_tables, q_pos // BL, axis=1
        )  # (B, T) physical block per written position
        off = q_pos % BL
        row = jnp.concatenate([k, v], axis=-1).reshape(B, T, KH * 2 * Dh)
        if quant:
            # (B, T, KH, 2): a head's k and v scale, as the kernel's
            # (2, block_len) scale panel pairs them.
            srow = jnp.stack(scales, axis=-1)
        if slot_mask is not None:
            live = slot_mask.astype(bool)[:, None]
            pb = jnp.where(live, pb, 0)
            off = jnp.where(live, off, 0)
            row = jnp.where(live[..., None], row, pool[pb, off])
            if quant:
                srow = jnp.where(
                    live[..., None, None], srow,
                    cache["kv_scale"][pb, :, :, off],
                )
        new = {"kv": pool.at[pb, off].set(row)}
        if quant:
            new["kv_scale"] = cache["kv_scale"].at[pb, :, :, off].set(srow)
    return new

def paged_kernel_takes(head_dim: int) -> bool:
    """Whether :func:`paged_decode_attention` can read a pool of this
    head width where it runs: the kernel DMAs a pool block's whole
    ``(block_len, KH * 2 * Dh)`` row and slices it at the heads'
    ``[k | v]`` lane groups, so Mosaic wants a group's width ``2 * Dh`` a
    multiple of 128 lanes (Dh 64, 128, 192, 256 ...); the interpreter
    (every non-TPU backend) takes any width.  The shape decides — callers
    with another width use the gathered einsum path."""
    return (2 * head_dim) % 128 == 0 or _use_interpret()


def _group_sums(x, width):
    """Sum each ``width``-lane group of ``x``'s last axis, the sum left in
    every lane of its group (the groups are the heads' ``[k | v]`` lanes;
    a slice at a multiple of ``width`` is whole vregs on the chip, and the
    sum over it one cross-lane reduce a vreg)."""
    rows, lanes = x.shape
    return jnp.concatenate([
        jnp.broadcast_to(
            jnp.sum(x[:, h * width:(h + 1) * width], axis=1, keepdims=True),
            (rows, width))
        for h in range(lanes // width)
    ], axis=1)


#: buffers of the pool's rows in VMEM at once in
#: :func:`paged_decode_attention`, each one loop step's blocks (a block, or
#: a tile of :func:`blocks_a_step`): one in use and three in flight.  On a
#: v5e, a block a step (my chip runs, PR 28): two buffers left every block
#: waiting on its row — 0.40 us a block at 102 KB rows and at 16 KB rows
#: alike, so the DMA's latency and not its bytes — three 0.29, four 0.27
#: (the arithmetic's own time at GPT-2 XL's row), six and eight no better.
#: A tile of eight 32 KB rows a step (Falcon-H1's 64 slots of 36 blocks,
#: one launch, the fold still one head after another; my chip runs, PR 48):
#: two buffers 297 us, three 287, four 282, six 275 — a tile in flight
#: already hides the rows' latency, and four buffers are 1 MB of VMEM.  As
#: the fold ships (three passes over the heads) four read 218 us: 0.49 us a
#: tile and 0.94 us a slot, where the parent's 0.62 us a block came to 4.97
#: a tile.
_ROWS_IN_VMEM = 4

#: positions the per-head body folds a loop step where a block holds fewer
#: (:func:`blocks_a_step`): a ``(rows, 128)`` float32 score tile is whole
#: vregs, and a product's cost is the MXU's round trip, not its columns.
_TILE = 128


def _takes_rows(group: int, quant: bool, windowed: bool) -> bool:
    """Whether a call runs :func:`_paged_row_kernel`: one query head a KV
    head, a float pool, the whole context."""
    return group == 1 and not quant and not windowed


def blocks_a_step(block_len: int, dtype, group: int,
                  windowed: bool = False) -> int:
    """Consecutive table entries one loop step of the kernel folds, from
    the call's static shapes alone (the scheduler's ``kv_steps=`` counts
    with it what the kernel walks).

    The per-head body (:func:`_paged_head_kernel`) folds a tile of
    ``128 // block_len`` blocks where ``block_len`` divides 128, is under
    it, and is whole packed sublane tiles of the pool's dtype (16 rows of
    bfloat16, 8 of float32: a block's rows then land aligned in their
    slice of the step's buffer); at any other length one block.  One block
    too for an int8 pool — its scale panels ride ``(max_blocks, KH, 2,
    block_len)`` a slot and a tile's would have to be laid along lanes
    inside the kernel; no cell runs one — and for the row body
    (:func:`_paged_row_kernel`: ``group == 1`` on a float pool, not
    ``windowed``), whose arithmetic is along the row's lanes.
    """
    dtype = jnp.dtype(dtype)
    quant = dtype == jnp.int8
    if (_takes_rows(group, quant, windowed) or quant or block_len >= _TILE
            or _TILE % block_len or block_len % (32 // dtype.itemsize)):
        return 1
    return _TILE // block_len


def _walk_blocks(tbl_ref, nblk_ref, kv_hbm, kv_buf, sem, init, fold,
                 blocks=1):
    """This slot's resident blocks, in table order, ``blocks`` a loop step:
    ``fold(j, b)`` runs on step ``j`` once its blocks' rows sit in
    consecutive slices of ``kv_buf[b]``, while the rows of the steps after
    it are in flight into the other buffers.  The loop's trip count is the
    slot's own and so is every copy: a table entry past its block count
    costs neither a DMA nor an iteration, and the slices of a slot's last
    step that no block of it fills are zeroed (whatever an earlier step
    left there would reach the value product, and ``0 * NaN`` is ``NaN``).
    ``init()`` runs behind the first rows' DMAs."""
    s_idx = pl.program_id(0)
    n = nblk_ref[s_idx]
    n_buf = kv_buf.shape[0]
    block_len = kv_buf.shape[1] // blocks
    steps = n if blocks == 1 else (n + (blocks - 1)) // blocks

    def entry(j, c):
        """The table entry of step ``j``'s block ``c``."""
        return j if blocks == 1 else j * blocks + c

    def row(j, c):
        b = j % n_buf
        dst = kv_buf.at[b] if blocks == 1 else \
            kv_buf.at[b, pl.ds(c * block_len, block_len)]
        return pltpu.make_async_copy(
            kv_hbm.at[tbl_ref[s_idx, entry(j, c)]], dst, sem.at[b])

    def start(j):
        """Step ``j``'s blocks on their way, those the slot holds (``j``
        traced: no entry of the table is named at trace time)."""
        for c in range(blocks):
            pl.when(entry(j, c) < n)(lambda c=c: row(j, c).start())

    for j in range(n_buf - 1):
        if blocks == 1:
            pl.when(j < n)(row(j, 0).start)
        else:
            start(jnp.int32(j))
    init()

    def body(j, carry):
        ahead = j + (n_buf - 1)
        if blocks == 1:
            pl.when(ahead < n)(lambda: row(ahead, 0).start())
            row(j, 0).wait()
        else:
            b = j % n_buf
            pl.when(ahead < steps)(lambda: start(ahead))
            whole = entry(j, blocks - 1) < n

            @pl.when(whole)
            def _():
                for c in range(blocks):
                    row(j, c).wait()

            @pl.when(jnp.logical_not(whole))
            def _():  # the slot's last step: the blocks it has, zeros after
                row(j, 0).wait()
                for c in range(1, blocks):
                    pl.when(entry(j, c) < n)(lambda c=c: row(j, c).wait())

                    @pl.when(entry(j, c) >= n)
                    def _():
                        kv_buf[b, pl.ds(c * block_len, block_len)] = \
                            jnp.zeros((block_len, kv_buf.shape[2]),
                                      kv_buf.dtype)
        fold(j, j % n_buf)
        return carry

    jax.lax.fori_loop(0, steps, body, None)


def _paged_row_kernel(tbl_ref, len_ref, nblk_ref, q_ref, kv_hbm, o_ref,
                      kv_buf, sem, m_scr, l_scr, acc, *,
                      block_len, n_q, width, streams):
    """One slot at one query head a KV head (``G == 1``): every head of a
    block at once, as lane-dense arithmetic on the ``(block_len, KH * 2 *
    Dh)`` row.

    ``q_ref`` is ``(1, n_q, 1, L)``: query offset ``t``'s heads laid along
    the row's own lanes, scaled, zeros under the value lanes — so
    ``row * q`` holds every head's ``q . k`` products in that head's key
    lanes and :func:`_group_sums` leaves each head's score in all of its
    lanes, the value lanes among them, which is where the probabilities
    multiply the values.  Rows ``r, r + streams, ...`` of a block feed
    running statistics of their own (``(streams, L)`` a query offset: whole
    vregs, no reduction over sublanes inside the loop); the end merges the
    streams — the same online-softmax recurrence, its order of summation
    over positions changed.  The key lanes of ``acc`` and of the output
    carry nothing; the caller keeps the value lanes.
    """
    n_chunks = block_len // streams
    valid = len_ref[pl.program_id(0)]

    def init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    def over_queries(one):
        if n_q == 1:
            one(0, None)
        else:
            jax.lax.fori_loop(0, n_q, one, None)

    def fold(i, b):
        kv = kv_buf[b].astype(jnp.float32)            # (BL, L)
        rows = [kv[c * streams:(c + 1) * streams] for c in range(n_chunks)]
        pos = i * block_len + jax.lax.broadcasted_iota(
            jnp.int32, rows[0].shape, 0
        )

        def one(t, carry):
            q = q_ref[0, t]                           # (1, L)
            masks = [pos + c * streams < valid + t for c in range(n_chunks)]
            s = [jnp.where(mk, _group_sums(r * q, width), NEG_INF)
                 for r, mk in zip(rows, masks)]
            m_prev = m_scr[t]
            m_new = functools.reduce(jnp.maximum, s, m_prev)
            alpha = jnp.exp(m_prev - m_new)
            # Explicit p mask: see the per-head kernel below.
            p = [jnp.where(mk, jnp.exp(x - m_new), 0.0)
                 for x, mk in zip(s, masks)]
            l_scr[t] = alpha * l_scr[t] + sum(p)
            acc[t] = alpha * acc[t] + sum(x * r for x, r in zip(p, rows))
            m_scr[t] = m_new
            return carry

        over_queries(one)

    _walk_blocks(tbl_ref, nblk_ref, kv_hbm, kv_buf, sem, init, fold)

    def last(t, carry):
        m = m_scr[t]
        w = jnp.exp(m - jnp.max(m, axis=0, keepdims=True))
        l = jnp.sum(l_scr[t] * w, axis=0, keepdims=True)
        a = jnp.sum(acc[t] * w, axis=0, keepdims=True)
        o_ref[0, t] = (a / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return carry

    over_queries(last)


def _paged_head_kernel(tbl_ref, len_ref, nblk_ref, *rest,
                       scale, block_len, quant, n_q, group, blocks=1,
                       windowed=False):
    """One slot, a loop step a **tile** of ``blocks`` consecutive blocks
    (:func:`blocks_a_step`: 128 positions where a block holds fewer) and in
    it static loops over the KV heads: per head ONE ``(n_q * G, Dh) x (Dh,
    blocks * block_len)`` score and ONE ``(n_q * G, blocks * block_len) x
    (blocks * block_len, Dh)`` value matmul on the head's ``[k | v]`` lane
    group of the tile's rows — the heads' score products first, then their
    softmax steps, then their value products — accumulated through the
    online-softmax recurrence into that head's VMEM scratch; the end
    normalizes and writes the ``(KH, n_q * G, Dh)`` output.  A table lists a slot's blocks in
    logical order, so a tile's positions are consecutive in everything the
    fold computes.

    Both products are float32 by float32 — queries scaled once a slot,
    keys and values cast as they leave the buffer, the probabilities as
    they are — and the running max, normalizer and accumulator float32: a
    block a step (``blocks == 1``) is, a head, the arithmetic it was before
    a step held a tile, to the bit.  (The score product on the stored bfloat16
    with the scale on the scores read no faster on the chip, and 1.5%
    slower at ``block_len`` 128: my chip runs, PR 48.)

    ``n_q`` query positions ride as extra rows (row ``r`` is query offset
    ``r // group``): offset ``t`` attends positions ``< valid + t`` —
    per-position causality inside a speculative verify chunk, reducing to
    the classic decode bound at ``n_q == 1``.

    ``windowed``: two more prefetched scalars a slot — the lowest position
    it may attend (``lo_ref``: a window layer's ``q - window + 1``) and the
    position the table's first entry starts at (``first_ref``: a ring hands
    its resident blocks oldest first, not from position 0).
    """
    if windowed:
        lo_ref, first_ref, *rest = rest
    q_ref, kv_hbm, *rest = rest
    if quant:
        sc_ref, *rest = rest
    o_ref, kv_buf, sem, m_scr, l_scr, acc = rest
    KH, R, Dh = q_ref.shape[1:]
    width = blocks * block_len

    def init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    # Row r is query offset r // group; it may attend one position more
    # than the row before it (the verify chunk's causality).
    bound = len_ref[pl.program_id(0)]
    if n_q > 1:
        bound = bound + jax.lax.broadcasted_iota(
            jnp.int32, (R, width), 0) // group

    if windowed:
        lowest, first = (ref[pl.program_id(0)] for ref in (lo_ref, first_ref))

    # The slot's queries, cast and scaled once: (R, Dh) a KV head.
    qs = [q_ref[0, h].astype(jnp.float32) * scale for h in range(KH)]

    def fold(j, b):
        pos = j * width + jax.lax.broadcasted_iota(jnp.int32, (R, width), 1)
        if windowed:
            pos = pos + first
            mask = (pos < bound) & (pos >= lowest)
        else:
            mask = pos < bound
        # Three passes over the KV heads — every head's score product, then
        # every head's softmax step, then every head's value product — and
        # not one head after another.  A head's arithmetic is the same to
        # the bit; the compiler's MXU assigner then lays the products out
        # so that they do not wait on each other (on a v5e, my chip runs,
        # PR 48: 1.21 -> 0.71 us a 512 KB block at 8 KV heads of 16 rows,
        # the DMA's own time; 282 -> 219 us a launch at Falcon-H1's).
        def rows(h, half):  # head h's keys (0) or values (1): (width, Dh)
            lanes = (2 * h + half) * Dh
            return kv_buf[b, :, lanes:lanes + Dh].astype(jnp.float32)

        scores = [
            jax.lax.dot_general(
                qs[h], rows(h, 0), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (R, width)
            for h in range(KH)
        ]
        probs, alphas = [], []
        for h, s in enumerate(scores):
            if quant:
                # Per-position k scale commutes out of the Dh contraction;
                # v scale folds into the probability operand below.
                s = s * sc_ref[0, j, h, 0:1, :]
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h, :, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            # Explicit p mask: with the finite NEG_INF stand-in, a
            # fully-masked row would otherwise see exp(NEG_INF - NEG_INF)
            # = 1 per position.
            p = jnp.exp(s - m_new[:, None]) * mask.astype(jnp.float32)
            l_scr[h, :, 0] = alpha * l_scr[h, :, 0] + jnp.sum(p, axis=1)
            m_scr[h, :, 0] = m_new
            if quant:
                p = p * sc_ref[0, j, h, 1:2, :]
            probs.append(p)
            alphas.append(alpha)
        for h in range(KH):
            acc[h] = alphas[h][:, None] * acc[h] + jax.lax.dot_general(
                probs[h], rows(h, 1), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _walk_blocks(tbl_ref, nblk_ref, kv_hbm, kv_buf, sem, init, fold, blocks)
    o_ref[0] = (acc[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


@jax.jit  # a model's layers call it alike: traced once, not once a layer
def paged_decode_attention(
    q: jax.Array,
    kv_pool: jax.Array,
    block_tables: jax.Array,
    valid_len: jax.Array,
    kv_scale: Optional[jax.Array] = None,
    lowest: Optional[jax.Array] = None,
    first_pos: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-position attention against a block-pooled (paged) KV cache.

    The serving engine's hot op (``chainermn_tpu/serving/engine.py``): S
    decode slots each read their own logical sequence out of one shared
    physical pool through a per-slot block table.  Grid ``(S,)``, one step
    a slot, the tables and each slot's count of resident blocks
    scalar-prefetched: the pool stays in HBM and the step loops over the
    slot's own blocks (:func:`_walk_blocks`), DMAing the whole row
    ``pool[block_tables[s, i]]`` — every KV head of the block in one
    contiguous read — ``_ROWS_IN_VMEM - 1`` loop steps ahead of the one it
    folds through the online-softmax recurrence.  No contiguous per-slot
    cache copy is ever materialized, VMEM bounds no context length, and a
    table entry past the slot's last resident block costs neither a DMA
    nor a loop iteration — also where a loop step folds several blocks: a
    slot's last step copies the blocks it has and no other.

    How the heads of a row are handled follows from the static shapes
    alone: one query head a KV head (``G == 1``, float pool) as lane-dense
    arithmetic over the whole row (:func:`_paged_row_kernel`); grouped
    queries, and the int8 pool with its per-head scale panel, as a static
    loop of MXU matmuls over the KV heads (:func:`_paged_head_kernel`), a
    loop step :func:`blocks_a_step` consecutive blocks of the table.

    Args:
      q: ``(S, H, Dh)`` — each slot's current query position — or
        ``(S, T, H, Dh)`` for a T-position **speculative verify chunk**:
        query offset ``t`` of slot ``s`` attends positions
        ``< valid_len[s] + t`` (per-position causality inside the chunk;
        the chunk's K/V must already be written to the pool).  ``T`` is
        static and small (``<= MAX_VERIFY_T`` by :func:`paged_attend`).
      kv_pool: ``(num_blocks, block_len, KH * 2 * Dh)`` — the physical
        pool (float, or int8 with ``kv_scale``); lanes
        ``[h*2*Dh, h*2*Dh + Dh)`` of a row are head ``h``'s key, the next
        ``Dh`` its value (:mod:`chainermn_tpu.serving.kv_pool`).
      block_tables: ``(S, max_blocks)`` int32 — logical→physical block map
        per slot.  Entries past a slot's filled length may point anywhere
        (they are never read, conventionally 0 — the serving pool
        reserves physical block 0 as the parking block).
      valid_len: ``(S,)`` int32 — the FIRST query position's causal bound:
        positions ``< valid_len[s] + t`` attendable for query offset
        ``t`` (plain decode has ``T == 1``, ``t == 0`` — unchanged);
        ``0`` marks an idle slot (every row of query offset 0 is fully
        masked — zeros-over-guard, discarded by the engine; later
        offsets attend only the chunk's own parked writes, equally
        discarded).
      kv_scale: ``(num_blocks, KH, 2, block_len)`` fp32 — required iff the
        pool is int8: row 0 of a head's pair is the per-position key
        scale, row 1 the value scale (symmetric absmax: a stored value
        times its position's scale is the float it stood for).
      lowest, first_pos: ``(S,)`` int32, together or not at all — the
        **window** form (:func:`ring_attend`; ``T == 1``): slot ``s``
        attends positions ``lowest[s] <= j < valid_len[s]``, and entry
        ``i`` of its table holds positions ``first_pos[s] + i * block_len
        ...`` (a multiple of ``block_len``; 0 for an idle slot): the table
        lists the slot's resident blocks oldest first and the kernel walks
        those and no others.  The launch is then named
        ``paged_decode_window``.

    Returns ``(S, H, Dh)`` or ``(S, T, H, Dh)`` (matching ``q``) in
    ``q``'s dtype.
    """
    if q.ndim == 4:
        S, T, H, Dh = q.shape
    else:
        S, H, Dh = q.shape
        T = 1
    if kv_pool.ndim != 3 or kv_pool.shape[2] % (2 * Dh):
        raise ValueError(
            f"kv_pool must be (num_blocks, block_len, KH * 2 * Dh) with "
            f"Dh = {Dh}, got {kv_pool.shape}"
        )
    _, BL, L = kv_pool.shape
    KH = L // (2 * Dh)
    if H % KH:
        raise ValueError(f"H ({H}) must be a multiple of KH ({KH})")
    if block_tables.ndim != 2 or block_tables.shape[0] != S:
        raise ValueError(
            f"block_tables must be (S={S}, max_blocks), got "
            f"{block_tables.shape}"
        )
    G = H // KH
    MB = block_tables.shape[1]
    quant = kv_pool.dtype == jnp.int8
    if quant and kv_scale is None:
        raise ValueError("int8 pool needs kv_scale")
    scale = 1.0 / math.sqrt(Dh)
    tbl = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(valid_len, jnp.int32).reshape(S)
    windowed = lowest is not None
    if windowed != (first_pos is not None) or (windowed and T != 1):
        raise ValueError(
            "the window form takes lowest and first_pos together, for "
            "single-position queries"
        )
    # Blocks some query offset of the slot may attend: the kernel's trip
    # count.  A table entry past it is never read.
    if windowed:
        bounds = [jnp.asarray(x, jnp.int32).reshape(S)
                  for x in (lowest, first_pos)]
        nblk = jnp.minimum((lens - bounds[1] + (BL - 1)) // BL, MB)
    else:
        bounds = []
        nblk = jnp.minimum((lens + (T - 1) + (BL - 1)) // BL, MB)

    def slot(s, *prefetched):
        return (s, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    q4 = q.reshape(S, T, KH, G, Dh)
    by_row = _takes_rows(G, quant, windowed)
    C = blocks_a_step(BL, kv_pool.dtype, G, windowed)
    if by_row:
        # Query offset t's heads along the row's own lanes: scaled, zeros
        # under the value lanes.
        qrow = q4.reshape(S, T, KH, Dh).astype(jnp.float32) * scale
        qrow = jnp.concatenate([qrow, jnp.zeros_like(qrow)], axis=-1) \
            .reshape(S, T, 1, L)
        streams = math.gcd(BL, 8)
        kernel = functools.partial(
            _paged_row_kernel, block_len=BL, n_q=T, width=2 * Dh,
            streams=streams,
        )
        operands = [qrow, kv_pool]
        in_specs = [pl.BlockSpec((1, T, 1, L), slot), hbm]
        out_spec = pl.BlockSpec((1, T, 1, L), slot)
        out_shape = jax.ShapeDtypeStruct((S, T, 1, L), q.dtype)
        scratch = [pltpu.VMEM((T, streams, L), jnp.float32)] * 3
    else:
        # Query offsets ride as extra ROWS of each KV head: offset t of
        # group row g at row t*G + g (the kernel recovers t as row // G
        # for its per-offset causal bound).
        R = T * G
        qg = q4.transpose(0, 2, 1, 3, 4).reshape(S, KH, R, Dh)
        kernel = functools.partial(
            _paged_head_kernel, scale=scale, block_len=BL, quant=quant,
            n_q=T, group=G, blocks=C,
            **({"windowed": True} if windowed else {}),
        )
        operands = [qg, kv_pool]
        in_specs = [pl.BlockSpec((1, KH, R, Dh), slot), hbm]
        if quant:
            # The scale plane's 16-wide minor axis is no row a DMA issued
            # by hand can slice (Mosaic wants 128 lanes): the slot's scale
            # panels ride gathered, a per-slot block like the queries.
            operands.append(kv_scale[tbl])
            in_specs.append(pl.BlockSpec(
                (1, MB, KH, 2, BL), lambda s, *prefetched: slot(s) + (0,)))
        out_spec = pl.BlockSpec((1, KH, R, Dh), slot)
        out_shape = jax.ShapeDtypeStruct((S, KH, R, Dh), q.dtype)
        scratch = [
            pltpu.VMEM((KH, R, 1), jnp.float32),   # running max
            pltpu.VMEM((KH, R, 1), jnp.float32),   # normalizer
            pltpu.VMEM((KH, R, Dh), jnp.float32),  # output accumulator
        ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(bounds),
            grid=(S,),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((_ROWS_IN_VMEM, C * BL, L), kv_pool.dtype),
                pltpu.SemaphoreType.DMA((_ROWS_IN_VMEM,)),
            ] + scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=_use_interpret(),
        name="paged_decode_window" if windowed else "paged_decode",
    )(tbl, lens, nblk, *bounds, *operands)
    if by_row:
        out = out.reshape(S, T, KH, 2, Dh)[:, :, :, 1]
    else:
        out = out.reshape(S, KH, T, G, Dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(q.shape)


# ---------------------------------------------------------------------------
# Tensor-parallel (shard_map) entry point
# ---------------------------------------------------------------------------
#
# The kernel is embarrassingly parallel across KV heads: what it computes
# for kv head ``kh`` touches only that head's lane group of a pool row and
# query group ``kh`` of q.  A 1-D mesh cut on the KV-head axis therefore
# needs NO collective — each shard runs the unmodified kernel over its
# ``KH / n`` local heads and the per-shard outputs concatenate on the
# (query-)head axis, which is exactly the Megatron column cut the
# serving plane's attention projections already use
# (``serving/sharding.py — param_spec``).  The wrapper below only
# declares that cut to ``shard_map``; the kernel body is reused verbatim.


def _mesh_axis(mesh, axis: Optional[str]) -> str:
    if axis is None:
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"mesh has axes {mesh.axis_names}; pass axis= explicitly"
            )
        axis = mesh.axis_names[0]
    return axis


def sharded_paged_decode_attention(
    q: jax.Array,
    kv_pool: jax.Array,
    block_tables: jax.Array,
    valid_len: jax.Array,
    kv_scale: Optional[jax.Array] = None,
    *,
    mesh,
    axis: Optional[str] = None,
) -> jax.Array:
    """:func:`paged_decode_attention` under ``shard_map`` on a 1-D mesh.

    Queries shard on the query-head axis, the pool on its LAST axis (a
    head's ``[k | v]`` lanes are contiguous, so ``KH / n`` heads are one
    plain block of it) and the int8 scales on their KV-head axis 1 — the
    layout :func:`serving.sharding.pool_placement` already produces —
    block tables and lengths ride replicated.  Each shard runs the Pallas
    kernel over its ``KH / n`` local heads, so the output (sharded like
    ``q``) is bit-identical to the unsharded call: softmax never crosses
    KV heads.  Supports the 4-D multi-query verify form and the int8 pool
    exactly like the unsharded entry.

    ``mesh`` is the serving :class:`jax.sharding.Mesh`; ``axis`` defaults
    to the mesh's only axis name.  A mesh of size 1 falls through to the
    plain call.  ``KH % n != 0`` is a :class:`ValueError` naming the
    failing axes (mirrored ahead of engine construction by
    ``serving.sharding.validate_geometry``).
    """
    axis = _mesh_axis(mesh, axis)
    n = int(mesh.shape[axis])
    if n == 1:
        return paged_decode_attention(
            q, kv_pool, block_tables, valid_len, kv_scale
        )
    KH = kv_pool.shape[2] // (2 * q.shape[-1])
    if KH % n:
        raise ValueError(
            f"KV heads ({KH}, the pool's lane groups) are not divisible "
            f"by mesh axis '{axis}' ({n}); the per-shard paged kernel "
            f"needs a whole number of local KV heads"
        )
    P = jax.sharding.PartitionSpec
    q_spec = P(None, None, axis, None) if q.ndim == 4 else P(None, axis, None)
    operands = [q, kv_pool, block_tables, valid_len]
    in_specs = [q_spec, P(None, None, axis), P(None, None), P(None)]
    if kv_scale is not None:
        operands.append(kv_scale)
        in_specs.append(P(None, axis, None, None))
    sm = jax.shard_map(
        paged_decode_attention,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=q_spec,
        check_vma=False,
    )
    return sm(*operands)


# ---------------------------------------------------------------------------
# The gathered read, and the choice between it and the kernel
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def context_widths(max_blocks: int):
    """The widths, in blocks, :func:`pool_context_attend` may read of a
    table ``max_blocks`` wide: an eighth, a quarter, a half and the whole
    of it, rounded up (64 -> 8, 16, 32, 64)."""
    return tuple(sorted({-(-max_blocks // d) for d in (8, 4, 2, 1)}))


def _context_rung(last_pos, block_len: int, widths):
    """Index of the narrowest of ``widths`` that holds position
    ``last_pos`` — a Python int on the host, a traced scalar in the
    program; past the table's end the widest."""
    return sum(last_pos >= w * block_len for w in widths[:-1])


def context_blocks(last_pos: int, block_len: int, max_blocks: int) -> int:
    """How many blocks of each row's table :func:`pool_context_attend`
    reads for a chunk whose last query sits at ``last_pos`` — the host's
    side of the choice its program makes (the scheduler's
    ``ctx_blocks=``)."""
    widths = context_widths(max_blocks)
    return widths[_context_rung(int(last_pos), block_len, widths)]


def _attend_width(W, window, q, pool, scale, block_tables, q_pos):
    """:func:`pool_context_attend` over the first ``W`` blocks of each
    row's table (``W`` static: one branch of its switch).

    The gathered rows are contracted as stored, a head's whole lane group
    ``[k_h | v_h]`` at a time: the queries ride zero-padded over the value
    lanes (exact zeros into the scores) and the value product keeps its
    value half.  Slicing k and v out of the row first — its
    ``(L, KH, 2, Dh)`` view — costs the chip a padded relayout of the
    context for each (measured, ``PERF.md`` §6 PR 31); a lane group is
    whole ``(8, 128)`` tiles, one relayout serves both products, and the
    MXU has the room (``T`` query rows a head)."""
    B, T, H, Dh = q.shape
    BL = pool.shape[1]
    KH = pool.shape[2] // (2 * Dh)
    L = W * BL
    tbl = block_tables[:, :W]
    g = pool[tbl].reshape(B, L, KH, 2 * Dh)
    qg = q.reshape(B, T, KH, H // KH, Dh)
    s = jnp.einsum(
        "btkgd,blkd->bkgtl", jnp.concatenate([qg, jnp.zeros_like(qg)], -1),
        g, preferred_element_type=jnp.float32,
    ) / math.sqrt(Dh)
    if scale is not None:
        # (B, W, KH, 2, BL) -> (B, KH, 2, L)
        sg = jnp.transpose(scale[tbl], (0, 2, 3, 1, 4)).reshape(B, KH, 2, L)
        s = s * sg[:, :, 0][:, :, None, None, :]
    t_idx = jnp.arange(L)
    visible = (
        t_idx[None, None, None, None, :] <= q_pos[:, None, None, :, None]
    )
    if window:
        visible &= (
            t_idx[None, None, None, None, :]
            > q_pos[:, None, None, :, None] - window
        )
    s = jnp.where(visible, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if scale is not None:
        p = p * sg[:, :, 1][:, :, None, None, :]
    a = jnp.einsum(
        "bkgtl,blkd->btkgd", p, g, preferred_element_type=jnp.float32
    )[..., Dh:]
    return a.reshape(B, T, H, Dh).astype(q.dtype)


#: float32 scores :func:`pool_context_attend` may hold for all heads at
#: once; a chunk whose scores are more goes a KV head at a time
#: (:func:`_attend_by_head`).  32 rows of 25 heads over 1,024 positions
#: are 3 MB; 256 rows of 128 heads over 14,336 positions are 1.9 GB.
_SCORES_AT_ONCE = 64 * 2**20


def _attend_by_head(W, window, q, pool, scale, block_tables, q_pos,
                    first_pos=None):
    """:func:`_attend_width`'s result with the scores of ONE KV head's
    group alive at a time (``lax.map`` over the KV heads, in order): the
    float32 ``(G, T, L)`` scores of a head instead of ``(KH, G, T, L)`` —
    what a 128-head chunk of 256 rows over a long context has the room
    for.  A head's ``[k | v]`` lane group is sliced into its halves (whole
    vregs at a head width that is a multiple of 128) rather than contracted
    as stored.  Float pools only.

    ``first_pos`` ``(B,)``: the position row ``b``'s first table entry
    starts at (a ring's table lists its resident blocks oldest first);
    ``None``: 0, the table's own order."""
    assert scale is None, "an int8 pool's chunks go all heads at once"
    B, T, H, Dh = q.shape
    BL = pool.shape[1]
    KH = pool.shape[2] // (2 * Dh)
    G = H // KH
    L = W * BL
    g = jnp.moveaxis(
        pool[block_tables[:, :W]].reshape(B, L, KH, 2 * Dh), 2, 0)
    qg = jnp.moveaxis(q.reshape(B, T, KH, G, Dh), 2, 0)
    k_pos = jnp.arange(L)[None]
    if first_pos is not None:
        k_pos = k_pos + first_pos[:, None]
    visible = k_pos[:, None, :] <= q_pos[:, :, None]          # (B, T, L)
    if window:
        visible &= k_pos[:, None, :] > q_pos[:, :, None] - window

    def head(operands):
        qh, gh = operands                     # (B, T, G, Dh), (B, L, 2 Dh)
        s = jnp.einsum("btgd,bld->bgtl", qh, gh[..., :Dh],
                       preferred_element_type=jnp.float32) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(visible[:, None], s, -1e30), axis=-1)
        return jnp.einsum("bgtl,bld->btgd", p, gh[..., Dh:],
                          preferred_element_type=jnp.float32).astype(q.dtype)

    a = jax.lax.map(head, (qg, g))                      # (KH, B, T, G, Dh)
    return jnp.moveaxis(a, 0, 2).reshape(B, T, H, Dh)


def _attend_at(W, window, q, cache):
    """The body :func:`pool_context_attend` runs at width ``W``: all heads
    at once where their scores fit (:data:`_SCORES_AT_ONCE`), a KV head at
    a time where they do not."""
    B, T, H, _ = q.shape
    whole = 4 * B * H * T * W * cache["kv"].shape[1] <= _SCORES_AT_ONCE
    return functools.partial(
        _attend_width if whole or "kv_scale" in cache else _attend_by_head,
        W, window)


# a model's layers call it alike: its branches traced once, not once a layer
@functools.partial(jax.jit, static_argnames="window")
def pool_context_attend(q, cache, block_tables, q_pos, window=0):
    """Grouped-query attention of a ``(B, T, H, Dh)`` query chunk over the
    context it has, gathered: the blocks of each row's table up to the
    chunk's last position ``max(q_pos)`` — the narrowest of
    :func:`context_widths` that holds it, chosen by a ``lax.switch`` inside
    the program, so one program serves a chunk size wherever the chunk
    sits — contracted as stored (the pool's dtype, no kv-head-major copy)
    into float32 scores.  Query ``t`` of row ``b`` attends positions
    ``<= q_pos[b, t]`` (the last ``window`` of them, if given): the mask of
    the model's contiguous ``(B, L, KH, Dh)`` einsum path exactly, and the
    blocks left out are those it gives probability 0.0.  An int8 pool's
    per-(kv-head, position) scales fold into the scores (k) and the
    probabilities (v).  ``cache`` is the entry :func:`pool_write`
    returned: the chunk's own tokens are in it."""
    widths = context_widths(block_tables.shape[1])
    with jax.named_scope("attn.gathered"):
        rung = _context_rung(jnp.max(q_pos), cache["kv"].shape[1], widths)
        return jax.lax.switch(
            rung,
            [_attend_at(w, window, q, cache) for w in widths],
            q, cache["kv"], cache.get("kv_scale"), block_tables, q_pos,
        )


def paged_attend(q, cache, block_tables, decode_pos, q_pos, slot_mask=None,
                 *, kernel, window=0, mesh=None, chunk_rows=0):
    """A query chunk's attention over the pool — the one place that
    chooses between the Pallas kernel (scope ``attn.paged``) and the
    gathered read (``attn.gathered``), from what it can observe.

    The kernel takes single-token steps (``T == 1``) and verify chunks —
    per-row positions, ``1 < T <= MAX_VERIFY_T``: the speculative path —
    of a full-attention model whose head width it can slice
    (:func:`paged_kernel_takes`), where the caller allows it
    (``kernel``).  Prefill chunks (one scalar position for every row,
    large ``T``), window models and every ``kernel=False`` caller — the
    reference path — take :func:`pool_context_attend`.

    ``q`` is ``(B, T, H, Dh)``; ``cache``, ``block_tables``, ``q_pos`` and
    ``slot_mask`` are :func:`pool_write`'s (its result, for ``cache``).
    ``decode_pos`` is the chunk's first position as the model was given
    it, a scalar or ``(B,)`` per row — only its rank is read, ``q_pos`` is
    it spread over the chunk.  ``mesh``: a 1-D serving mesh, the kernel
    then runs per shard (:func:`sharded_paged_decode_attention`).

    ``chunk_rows`` (static): the LAST ``chunk_rows`` of the ``B``
    single-token rows are one slot's prefill chunk riding a decode step
    (the engine's ``mixed_step``) — consecutive positions of one sequence,
    each row carrying that slot's table.  They part from the rows before
    them here and nowhere else: those are read as they would be alone, the
    chunk's rows regrouped ``(1, chunk_rows, H, Dh)`` for the gathered
    read, whose switch picks its width from the chunk's last position.
    """
    T, Dh = q.shape[1], q.shape[3]
    if chunk_rows:
        if T != 1 or jnp.ndim(decode_pos) != 1:
            raise ValueError(
                "chunk_rows rides single-token rows with per-row positions "
                f"(T == 1, decode_pos (B,)), got T = {T}, decode_pos rank "
                f"{jnp.ndim(decode_pos)}"
            )
        S = q.shape[0] - chunk_rows
        c = pool_context_attend(
            jnp.swapaxes(q[S:], 0, 1), cache, block_tables[S:S + 1],
            q_pos[S:].T, window,
        )
        # The chunk's read FIRST, and the decode rows' held behind it: its
        # switch is a conditional, and on the chip a conditional is a fence
        # that no prefetch of a later operand crosses.  With the kernel
        # after it, the layer's remaining operands stream in under the
        # kernel, as they do in a plain decode step; with the kernel before
        # it they wait where the fence leaves them (PERF.md section 6,
        # PR 42).
        rows, c = jax.lax.optimization_barrier((q[:S], c))
        a = paged_attend(
            rows, cache, block_tables[:S], decode_pos[:S], q_pos[:S],
            None if slot_mask is None else slot_mask[:S],
            kernel=kernel, window=window, mesh=mesh,
        )
        return jnp.concatenate([a, jnp.swapaxes(c, 0, 1)], axis=0)
    # The kernel's causal bound is the FIRST query position's (offset t
    # adds t in-kernel); T == 1 reduces to the classic decode bound.  Idle
    # slots mask to 0.
    valid = q_pos[:, 0] + 1
    if slot_mask is not None:
        valid = jnp.where(slot_mask.astype(bool), valid, 0)
    verify = jnp.ndim(decode_pos) == 1 and 1 < T <= MAX_VERIFY_T
    if not (kernel and not window and (T == 1 or verify)
            and paged_kernel_takes(Dh)):
        return pool_context_attend(q, cache, block_tables, q_pos, window)
    with jax.named_scope("attn.paged"):
        args = (q[:, 0] if T == 1 else q, cache["kv"], block_tables, valid,
                cache.get("kv_scale"))
        if mesh is not None:
            # Tensor-parallel engines: the kernel runs per shard under
            # shard_map (q cut on heads, pool on kv heads — the placement
            # the serving plane already installs); bit-identical to the
            # unsharded call, no collective added here.
            a = sharded_paged_decode_attention(*args, mesh=mesh)
        else:
            a = paged_decode_attention(*args)
        return a[:, None] if T == 1 else a


# ---------------------------------------------------------------------------
# A window layer's ring by slot
# ---------------------------------------------------------------------------
#
# A layer that attends the last ``window`` positions only need not page its
# whole context: it keeps, for every slot, a RING of ``R`` blocks in the pool
# row's own format — ``{"ring": (slots, R, block_len, KH * 2 * Dh)}`` —
# where logical block ``b`` of the slot's sequence lives at ``b mod R``.  No
# allocator, no table on the host, nothing to free: the table is worked out
# inside the program from the positions, and masking is by absolute position
# (``q - window < j <= q``), so whatever an older turn of the ring — or the
# slot's previous request — left behind is never attended and a sequence
# that starts again at position 0 needs no zeroing.


def ring_blocks(window: int, chunk: int, block_len: int) -> int:
    """Blocks a slot's ring holds, ``R``: the window and one chunk of
    ``chunk`` positions written ahead of its own oldest query, so that a
    chunk's newest write never lands on a key its oldest query still sees —
    ``ceil((window + chunk) / block_len)``, and one more where chunks need
    not start on a block (``chunk`` no multiple of ``block_len``)."""
    if window < 1:
        raise ValueError(f"a ring holds a window of >= 1 positions: {window}")
    return -(-(window + chunk) // block_len) + bool(chunk % block_len)


def _ring_table(slots, first_block, R: int):
    """``(B, R)`` physical blocks of the flattened ring ``(slots * R, ...)``:
    row ``b``'s logical blocks ``first_block[b] ...``, oldest first."""
    return slots[:, None] * R + (first_block[:, None] + jnp.arange(R)) % R


def ring_write(cache, k, v, slots, q_pos, slot_mask=None):
    """:func:`pool_write` for a ring: row ``b``'s ``(T, KH, Dh)`` keys and
    values go to slot ``slots[b]``'s ring at ``(q_pos // block_len) mod R``.
    Rows ``slot_mask`` leaves out write nothing (their index lies outside
    the array, and a scatter drops such an update)."""
    ring = cache["ring"]
    n_slots, R, BL, L = ring.shape
    B, T = q_pos.shape
    with jax.named_scope("kv_write"):
        row = jnp.concatenate([k, v], axis=-1).reshape(B, T, L) \
            .astype(ring.dtype)
        pb = slots[:, None] * R + (q_pos // BL) % R
        if slot_mask is not None:
            pb = jnp.where(slot_mask.astype(bool)[:, None], pb, n_slots * R)
        new = ring.reshape(n_slots * R, BL, L).at[pb, q_pos % BL].set(
            row, mode="drop")
    return {"ring": new.reshape(ring.shape)}


def ring_attend(q, cache, slots, q_pos, slot_mask=None, *, window: int,
                kernel: bool, chunk_rows: int = 0):
    """A query chunk's attention over its slots' rings (:func:`ring_write`'s
    result), query ``q_pos`` seeing ``q_pos - window < j <= q_pos`` — scope
    ``attn.window``.  ``q`` ``(B, T, H, Dh)``, ``slots`` ``(B,)`` whose
    sequence each row is.

    Single-token rows (``T == 1``) run the Pallas kernel over the slot's
    resident blocks and no others, oldest first, where ``kernel`` allows and
    the head width tiles (:func:`paged_kernel_takes`;
    ``paged_decode_window``), and the gathered read of the ``R`` blocks
    otherwise.  A prefill chunk (``T > 1``, one row) and the last
    ``chunk_rows`` single-token rows (one slot's chunk riding a decode
    step) are one sequence: the gathered read of that slot's ``R`` blocks, a
    KV head at a time — one width wherever the chunk sits, so no
    conditional."""
    ring = cache["ring"]
    n_slots, R, BL, L = ring.shape
    pool = ring.reshape(n_slots * R, BL, L)
    B, T, _, Dh = q.shape

    def gathered(q, slots, q_pos):
        first = jnp.maximum(jnp.min(q_pos, axis=1) - window + 1, 0) // BL
        return _attend_by_head(R, window, q, pool, None,
                               _ring_table(slots, first, R), q_pos,
                               first_pos=first * BL)

    with jax.named_scope("attn.window"):
        if T > 1:
            return gathered(q, slots, q_pos)
        S = B - chunk_rows
        if kernel and paged_kernel_takes(Dh):
            pos = q_pos[:S, 0]
            live = (jnp.ones((S,), bool) if slot_mask is None
                    else slot_mask[:S].astype(bool))
            lowest = jnp.where(live, jnp.maximum(pos - window + 1, 0), 0)
            a = paged_decode_attention(
                q[:S, 0], pool, _ring_table(slots[:S], lowest // BL, R),
                jnp.where(live, pos + 1, 0), None,
                lowest=lowest, first_pos=lowest // BL * BL,
            )[:, None]
        else:
            a = gathered(q[:S], slots[:S], q_pos[:S])
        if chunk_rows:
            c = gathered(jnp.swapaxes(q[S:], 0, 1), slots[S:S + 1],
                         q_pos[S:].T)
            a = jnp.concatenate([a, jnp.swapaxes(c, 0, 1)], axis=0)
        return a

"""Block-granular KV allocator over a fixed device-resident pool.

The static-batch decode path (:func:`~chainermn_tpu.models.lm_generate`)
sizes one contiguous ``(B, L, ...)`` cache to the LONGEST request and holds
it for the whole batch — memory proportional to ``B · max_len`` even when
most rows finished long ago.  The serving engine instead draws from one
physical **block pool** per layer, token-major and lane-dense (the
shapes, the write and both reads are
:mod:`chainermn_tpu.ops.decode_attention`'s — ``pool_shapes`` there is
what this module allocates):

    ``{"kv"}``:  ``(num_blocks, block_len, KH * 2 * Dh)``
    ``{"kv_scale"}`` (int8 pools): ``(num_blocks, KH, 2, block_len)`` fp32

A token's row holds every KV head's key and value side by side,
``[k_0 | v_0 | k_1 | v_1 | ...]``: lanes ``[h*2*Dh, h*2*Dh + Dh)`` are head
``h``'s key, the next ``Dh`` its value.  (The int8 scale plane pairs a
head's key and value scale the same way, positions minor-most: the
``(2, block_len)`` panel the kernel multiplies into its scores.  Its
16-wide minor axis is no row a hand-issued DMA can slice, so the kernel's
caller gathers a slot's panels through its table — ``kv_scale[table]`` —
and hands them over as a per-slot block.)

**Why this layout** (chip compiler, PR 25; ``tests/ops_tests/
test_tpu_compile.py`` keeps the proof).  Three parties touch a pool — the
program's argument and result (what the runtime keeps at rest), XLA's
scatter that writes the step's tokens, and the Mosaic paged-attention
kernel that reads it — and each picks a physical layout for the shape it
is handed.  For the kv-head-major pool (KV heads outermost, ``Dh`` minor)
this engine had before, at ``Dh = 64`` they picked three different ones:

    ===========================  ==========================================
    argument / result            ``{1,3,2,0}``: ``num_blocks`` minor-most,
                                 padded 2049 → 2176
    scatter (and a DUS loop)     ``{3,0,2,1}``
    Mosaic kernel                ``{3,2,1,0}``, ``Dh`` 64 padded to 128 lanes
    ===========================  ==========================================

so every decode tick and every prefill call converted each pool of each
layer three times — whole-pool copies, a third of the device's time (ledger,
PR 24) — to write 0.2 MB.  With ``(num_blocks, block_len, KH * 2 * Dh)``
the minor axis is a multiple of 128 lanes whenever ``2 * Dh`` is, all three
agree on plain row-major ``{2,1,0}``, the scatter updates the donated
argument in place, and nothing is padded.  Fusing ``k`` and ``v`` is what
makes ``Dh = 64`` lane-dense, and a block's row — every KV head's key and
value for ``block_len`` tokens — is one contiguous read: the kernel
(:func:`~chainermn_tpu.ops.paged_decode_attention`) leaves the pool in HBM
and DMAs ``pool[table[s, i]]`` whole, 102 KB at 25 heads of 64 in bf16, for
each block a slot holds and for no other table entry.  A cut on KV heads is
still a plain block cut — of the last axis
(:mod:`~chainermn_tpu.serving.sharding`) — and the local row is then the
shard's DMA.

A decode slot owns an ordered list of physical blocks (its *block table*);
logical position ``p`` lives at ``(table[p // block_len], p % block_len)``.
Blocks are recycled through a host-side free list the moment a request
retires or is evicted — the next admission reuses them without touching the
device (vLLM's PagedAttention memory model, Kwon et al. 2023).

Accounting is **pure host state**: :class:`BlockAllocator` is a Python free
list + per-block refcount map, so allocation/share/free decisions in the
steady decode loop never read device memory and never force a sync.  The
only device work is the engine's jitted step itself.  Refcounts are what
make prefix sharing safe: one physical block can back the same prompt
prefix in many block tables (and stay pinned by the prefix trie after its
requests retire), and it returns to the free list only when the last
holder lets go.

Physical block 0 is reserved as the **parking block**: the pool's write
redirects idle slots' scatter there (with their own current value, so
duplicate indices carry duplicate values and the scatter stays
deterministic — ``ops/decode_attention.py`` ``pool_write``).  The
allocator never hands it out.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


class PoolExhausted(RuntimeError):
    """A request needs more blocks than the pool can ever provide."""


class BlockAllocator:
    """Host-side REFCOUNTED free-list accounting for the physical pool.

    No device syncs, ever: this is plain Python state.  ``alloc`` hands a
    block out at refcount 1; :meth:`share` lends it to another holder
    (prefix sharing — the same physical KV block mapped into several block
    tables, or pinned by the prefix trie); ``free`` drops one reference
    and reclaims the block only when the count hits zero.  Freeing a block
    nobody holds raises — silent accounting drift would surface later as
    two slots scribbling over the same physical block.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (block 0 is reserved), got {num_blocks}"
            )
        self.num_blocks = num_blocks
        # LIFO free list: recently-freed blocks are re-issued first (their
        # pool pages are the most likely to still be warm).
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._ref)

    def refcount(self, block: int) -> int:
        """Current holder count (0 = free or reserved)."""
        return self._ref.get(block, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` physical block ids at refcount 1 each, or ``None`` when
        the pool is exhausted (the scheduler's backpressure/eviction
        signal — never raises)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def share(self, blocks: Sequence[int]) -> None:
        """Add one reference per block (the block must be live — sharing
        a free block would resurrect reclaimed memory)."""
        for b in blocks:
            if b not in self._ref:
                raise ValueError(
                    f"sharing block {b} that is not allocated — a borrowed "
                    "reference must come from a live holder"
                )
        for b in blocks:
            self._ref[b] += 1

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; a block is reclaimed to the free
        list when its count reaches zero.  Freeing an unallocated block
        (over-free or foreign id) raises."""
        for b in blocks:
            n = self._ref.get(b, 0)
            if n == 0:
                raise ValueError(
                    f"freeing block {b} that was never allocated (over-"
                    "free or foreign id) — allocator state is corrupt"
                )
            if n == 1:
                del self._ref[b]
                self._free.append(b)
            else:
                self._ref[b] = n - 1


def blocks_for(tokens: int, block_len: int) -> int:
    """Blocks needed to hold ``tokens`` positions."""
    return max(1, math.ceil(tokens / block_len))


class PagedKVPool:
    """The device-resident pools (one ``{"kv"[, "kv_scale"]}`` dict per
    layer, laid out as the module docstring draws it) plus their
    :class:`BlockAllocator`.

    Built from the model's own geometry so the pool entries are exactly
    what :meth:`TransformerLM.__call__`'s paged decode branch hands
    ``pool_write``: ``n_kv_heads`` rows of the model's ``head_dim`` where
    it states one, of ``d_model // n_heads`` where it does not.

    A model that keeps **state by slot** beside its keys and values — a
    recurrence's, which no block holds: it is one array a slot however long
    the context — says so through ``state_shapes()`` (``{name: (shape,
    dtype)}`` a layer, :meth:`~chainermn_tpu.models.HybridLM.state_shapes`),
    and each layer's entry then carries ``{name: (slots, *shape)}`` beside
    its ``"kv"``: one tree, donated and returned by the engine's programs
    together.  ``state_bytes`` is what that costs, apart from the blocks'
    budget; a model without ``state_shapes`` gets the entries it always
    had.  A layer that attends a window only keeps no blocks of the pool
    at all: the model's ``ring_shapes(slots, block_len, prefill_chunk)``
    names, a layer, ``None`` or the ``(shape, dtype)`` of its **ring by
    slot** (:func:`~chainermn_tpu.ops.decode_attention.ring_attend`), and
    that layer's entry is ``{"ring": ...}`` in place of ``{"kv": ...}`` —
    ``ring_bytes``, O(window) a slot whatever the contexts; the allocator's
    blocks (``bytes_per_block``) are the other layers' alone.
    ``kv_dtype=jnp.int8`` models get int8 pools with fp32 scale planes —
    the same symmetric-absmax convention as the contiguous cache, at half
    the bf16 pool bytes.

    ``placement`` makes device placement EXPLICIT and injected (it used
    to be whatever ``jnp.zeros`` landed on — implicitly
    ``jax.devices()[0]``): a callable applied to every freshly-built
    pool array.  Pass
    :func:`~chainermn_tpu.serving.sharding.pool_placement` for a
    mesh shard on KV heads, ``lambda a: jax.device_put(a, dev)`` to
    pin a specific device, or ``None`` (the default-constructed
    single-device fast path — no extra transfer, unchanged behavior).
    """

    def __init__(self, model, num_blocks: int, block_len: int,
                 placement=None, slots: int = 0, prefill_chunk: int = 0):
        import jax.numpy as jnp

        from chainermn_tpu.ops.decode_attention import (
            blocks_a_step,
            pool_shapes,
        )

        if block_len < 1:
            raise ValueError(f"block_len must be >= 1, got {block_len}")
        kvh = model.n_kv_heads or model.n_heads
        dh = getattr(model, "head_dim", None) or model.d_model // model.n_heads
        kvd = getattr(model, "kv_dtype", None)
        kvd = kvd if kvd is not None else model.dtype
        shape, scale_shape = pool_shapes(num_blocks, block_len, kvh, dh)
        self.block_len = block_len
        #: table entries the paged kernel folds a loop step at this pool's
        #: geometry (what the scheduler's ``kv_steps=`` counts with)
        self.blocks_a_step = blocks_a_step(block_len, kvd,
                                           model.n_heads // kvh)
        self.num_blocks = num_blocks
        self.allocator = BlockAllocator(num_blocks)
        #: HBM bytes of the window layers' rings (0: every layer pages).
        self.ring_bytes = 0
        ring_shapes = getattr(model, "ring_shapes", None)
        rings = [None] * model.n_layers
        if ring_shapes is not None:
            rings = ring_shapes(slots, block_len, prefill_chunk)
        if any(r is not None for r in rings) and (
                slots < 1 or jnp.dtype(kvd) == jnp.int8):
            raise ValueError(
                "a model with window layers keeps a float ring by slot: "
                f"got slots={slots}, kv_dtype={kvd}"
            )
        if jnp.dtype(kvd) == jnp.int8:
            self.pools: List[Dict] = [
                {"kv": jnp.zeros(shape, jnp.int8),
                 "kv_scale": jnp.zeros(scale_shape, jnp.float32)}
                for _ in range(model.n_layers)
            ]
            # a block's int8 rows and its fp32 scale panels
            per_layer = math.prod(shape[1:]) + 4 * math.prod(scale_shape[1:])
        else:
            if not jnp.issubdtype(jnp.dtype(kvd), jnp.floating):
                raise ValueError(
                    f"kv_dtype must be a float dtype or jnp.int8, got {kvd}"
                )
            # a ring layer never holds a block of the pool: its entry is
            # built alone (a pool built first and dropped would not fit
            # beside the weights)
            self.pools = [
                {"kv": jnp.zeros(shape, kvd)} if ring is None
                else {"ring": jnp.zeros(*ring)} for ring in rings
            ]
            per_layer = math.prod(shape[1:]) * jnp.dtype(kvd).itemsize
            self.ring_bytes = sum(
                math.prod(r[0]) * jnp.dtype(r[1]).itemsize
                for r in rings if r is not None)
        #: HBM bytes of the slots' state across all layers (0: the model
        #: keeps none).
        self.state_bytes = 0
        state_shapes = getattr(model, "state_shapes", None)
        if state_shapes is not None:
            if slots < 1:
                raise ValueError(
                    "a model with state by slot needs the pool told how "
                    f"many slots there are, got slots={slots}"
                )
            for layer, shapes in zip(self.pools, state_shapes()):
                for name, (shp, dt) in shapes.items():
                    layer[name] = jnp.zeros((slots,) + tuple(shp), dt)
                    self.state_bytes += (
                        slots * math.prod(shp) * jnp.dtype(dt).itemsize
                    )
        if placement is not None:
            self.pools = [
                {n: placement(arr) for n, arr in layer.items()}
                for layer in self.pools
            ]
        #: HBM bytes one physical block costs across all layers.  Computed
        #: from geometry, NOT the arrays: the engine donates the pool
        #: buffers to its jitted step, so these initial arrays are deleted
        #: after the first iteration.
        self.bytes_per_block = int(
            per_layer * sum(r is None for r in rings))

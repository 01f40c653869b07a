"""Block allocator + paged pool geometry: pure host-side semantics.

The allocator is the serving engine's only memory-accounting authority —
silent drift here means two slots scribbling the same physical block, so
the failure modes (double free, foreign id) must raise, not warn.
"""

import jax.numpy as jnp
import pytest

from chainermn_tpu.serving import (
    BlockAllocator,
    PagedKVPool,
    blocks_for,
)

pytestmark = [pytest.mark.tier1, pytest.mark.serving]


# ------------------------------------------------------------- allocator
def test_block_zero_reserved():
    a = BlockAllocator(8)
    got = a.alloc(7)
    assert got is not None and sorted(got) == list(range(1, 8))
    assert a.alloc(1) is None  # block 0 is never handed out


def test_alloc_exhaustion_returns_none_not_raises():
    a = BlockAllocator(4)
    assert a.alloc(4) is None       # only 3 allocatable
    got = a.alloc(3)
    assert got is not None
    assert a.free_blocks == 0 and a.used_blocks == 3


def test_free_recycles_lifo():
    a = BlockAllocator(6)
    first = a.alloc(3)
    a.free(first)
    # LIFO: the most recently freed block comes back first.
    assert a.alloc(1) == [first[-1]]


def test_over_free_and_foreign_free_raise():
    a = BlockAllocator(4)
    got = a.alloc(2)
    a.free([got[0]])
    # Refcount hit zero: another free is an over-free, not a decrement.
    with pytest.raises(ValueError, match="over-free or foreign"):
        a.free([got[0]])
    with pytest.raises(ValueError, match="never allocated"):
        a.free([0])  # the reserved block was never issued


def test_share_refcounts_and_decrement_free():
    """Prefix-sharing semantics: ``share`` lends references, ``free`` of
    a ref>1 block is a DECREMENT (the old double-free) and the block is
    reclaimed only at zero."""
    a = BlockAllocator(6)
    got = a.alloc(2)
    a.share([got[0]])
    assert a.refcount(got[0]) == 2 and a.refcount(got[1]) == 1
    free_before = a.free_blocks
    a.free([got[0]])  # decrement, NOT a reclaim
    assert a.refcount(got[0]) == 1
    assert a.free_blocks == free_before
    a.free([got[0]])  # last holder: reclaimed
    assert a.refcount(got[0]) == 0
    assert a.free_blocks == free_before + 1
    with pytest.raises(ValueError, match="over-free or foreign"):
        a.free([got[0]])


def test_share_requires_live_block():
    a = BlockAllocator(4)
    got = a.alloc(1)
    with pytest.raises(ValueError, match="not allocated"):
        a.share([got[0] + 1 if got[0] + 1 < 4 else got[0] - 1])
    a.free(got)
    with pytest.raises(ValueError, match="not allocated"):
        a.share(got)  # sharing a freed block would resurrect it


def test_too_small_pool_rejected():
    with pytest.raises(ValueError, match=">= 2"):
        BlockAllocator(1)


def test_blocks_for():
    assert blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2
    assert blocks_for(0, 8) == 1  # a slot always owns at least one block


# ------------------------------------------------------------------ pool
def test_pool_geometry_token_major_lane_dense(make_model, model_kw):
    """One array a layer: (num_blocks, block_len, KH * 2 * Dh) — a token's
    row holds every head's [k | v] side by side (kv_pool.py says why)."""
    pool = PagedKVPool(make_model(), num_blocks=6, block_len=8)
    kvh = model_kw["n_kv_heads"]
    dh = model_kw["d_model"] // model_kw["n_heads"]
    assert len(pool.pools) == model_kw["n_layers"]
    for entry in pool.pools:
        assert set(entry) == {"kv"}
        assert entry["kv"].shape == (6, 8, kvh * 2 * dh)
        assert entry["kv"].dtype == jnp.float32


def test_pool_int8_variant_has_scale_planes(make_model, model_kw):
    pool = PagedKVPool(
        make_model(kv_dtype=jnp.int8), num_blocks=6, block_len=8
    )
    entry = pool.pools[0]
    kvh = model_kw["n_kv_heads"]
    assert set(entry) == {"kv", "kv_scale"}
    assert entry["kv"].dtype == jnp.int8
    # a head's k and v scale paired, positions minor-most
    assert entry["kv_scale"].shape == (6, kvh, 2, 8)
    assert entry["kv_scale"].dtype == jnp.float32


def test_pool_bytes_per_block_accounting(make_model, model_kw):
    pool = PagedKVPool(make_model(), num_blocks=6, block_len=8)
    kvh = model_kw["n_kv_heads"]
    dh = model_kw["d_model"] // model_kw["n_heads"]
    per_layer = 2 * kvh * 8 * dh * 4  # k+v, fp32
    assert pool.bytes_per_block == per_layer * model_kw["n_layers"]


def test_pool_rejects_bad_geometry(make_model):
    with pytest.raises(ValueError, match="block_len"):
        PagedKVPool(make_model(), num_blocks=6, block_len=0)
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedKVPool(
            make_model(kv_dtype=jnp.int32), num_blocks=6, block_len=8
        )

"""The paged KV pool (``ops/decode_attention.py``): the Pallas kernel
against a float32 oracle (interpret mode on the CPU), and the pool row's
write, gathered read and kernel choice against plain ``numpy``.

The kernel's oracle gathers each slot's context through its block table
and runs score / mask / softmax / value in float32: MHA, GQA grouping,
ragged ``valid_len`` rows, verify chunks, the int8 pool with its scale
plane, and the argument-validation contract.  The write
(:func:`pool_write`), the gathered read (:func:`pool_context_attend`) and
the choice (:func:`paged_attend`) are what the model's block calls for a
paged cache; their tests need no model.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.tier1  # small shapes; interpret mode is fast here


# ---------------------------------------------------------------- paged
def _fuse(kp, vp):
    """Per-head keys and values ``(KH, NB, BL, Dh)`` -> the engine's ONE pool
    ``(NB, BL, KH * 2 * Dh)``: token-major, each head's ``[k | v]`` side by
    side (``serving/kv_pool.py``).  The oracles below keep reading the
    per-head arrays, so they also check the layout."""
    kv = np.concatenate([np.asarray(kp), np.asarray(vp)], axis=-1)
    KH, NB, BL, W = kv.shape
    return jnp.asarray(kv.transpose(1, 2, 0, 3).reshape(NB, BL, KH * W))


def _fuse_scale(ks, vs):
    """``(KH, NB, BL)`` k and v scales -> ``(NB, KH, 2, BL)``."""
    sc = np.stack([np.asarray(ks), np.asarray(vs)], axis=2)
    return jnp.asarray(sc.transpose(1, 0, 2, 3))


def _paged_oracle(q, kp, vp, tbl, valid):
    """fp32 reference for the paged kernel's multi-query (verify) mode:
    gather each slot's logical cache through its block table, mask per
    query offset ``t`` at ``valid + t`` (per-position causality inside a
    speculative verify chunk)."""
    S, T, H, Dh = q.shape
    KH, NB, BL, _ = kp.shape
    G = H // KH
    MB = tbl.shape[1]
    out = np.zeros((S, T, H, Dh), np.float32)
    for s in range(S):
        kg = np.asarray(kp, np.float32)[:, tbl[s]].reshape(KH, MB * BL, Dh)
        vg = np.asarray(vp, np.float32)[:, tbl[s]].reshape(KH, MB * BL, Dh)
        for t in range(T):
            bound = int(valid[s]) + t
            if int(valid[s]) <= 0 or bound <= 0:
                continue
            for h in range(H):
                sc = (np.asarray(q, np.float32)[s, t, h]
                      @ kg[h // G, :bound].T) / np.sqrt(Dh)
                p = np.exp(sc - sc.max())
                p /= p.sum()
                out[s, t, h] = p @ vg[h // G, :bound]
    return out


def test_paged_multi_query_verify_matches_oracle():
    """The speculative-verify mode: T query positions per slot, offset t
    attending positions < valid + t, blocks walked through the table."""
    rng = np.random.RandomState(0)
    S, T, H, KH, Dh, NB, BL, MB = 3, 4, 4, 2, 8, 12, 4, 6
    q = jnp.asarray(rng.randn(S, T, H, Dh), jnp.float32)
    kp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    tbl = rng.randint(1, NB, size=(S, MB)).astype(np.int32)
    valid = np.asarray([9, 1, 17], np.int32)
    from chainermn_tpu.ops import paged_decode_attention

    out = paged_decode_attention(q, _fuse(kp, vp), jnp.asarray(tbl),
                                 jnp.asarray(valid))
    assert out.shape == (S, T, H, Dh)
    ref = _paged_oracle(q, kp, vp, tbl, valid)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


def test_paged_single_query_is_multi_query_t1():
    """The classic decode call (3-D q) must be bit-identical to the
    multi-query mode at T == 1 — one code path, two entry shapes."""
    rng = np.random.RandomState(1)
    S, H, KH, Dh, NB, BL, MB = 2, 4, 2, 8, 8, 4, 4
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    kp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    tbl = jnp.asarray(rng.randint(1, NB, size=(S, MB)), jnp.int32)
    valid = jnp.asarray([6, 11], jnp.int32)
    from chainermn_tpu.ops import paged_decode_attention

    pool = _fuse(kp, vp)
    a = paged_decode_attention(q, pool, tbl, valid)
    b = paged_decode_attention(q[:, None], pool, tbl, valid)[:, 0]
    assert (np.asarray(a) == np.asarray(b)).all()


def test_paged_idle_slot_zero_valid_is_defined():
    """valid == 0 (idle slot): offset-0 rows are fully masked and come
    out as the zeros-over-guard convention; later offsets only see the
    chunk's own parked writes — everything finite, engine discards it."""
    rng = np.random.RandomState(2)
    S, T, H, KH, Dh, NB, BL, MB = 2, 3, 4, 2, 8, 8, 4, 4
    q = jnp.asarray(rng.randn(S, T, H, Dh), jnp.float32)
    kp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    tbl = jnp.zeros((S, MB), jnp.int32)
    valid = jnp.zeros((S,), jnp.int32)
    from chainermn_tpu.ops import paged_decode_attention

    out = np.asarray(paged_decode_attention(q, _fuse(kp, vp), tbl, valid))
    assert np.isfinite(out).all()
    assert (out[:, 0] == 0).all()  # offset 0: fully masked


@pytest.mark.parametrize("T", [1, 3], ids=["decode", "verify_t3"])
def test_paged_pool_matches_reference_attention_on_gathered(T):
    """The kernel on the engine's pool against the repo's ONE attention
    oracle (``reference_attention``) on each slot's gathered keys/values:
    ragged ``valid_len``, an idle slot, GQA, and (T = 3) a verify chunk —
    query offset t of a slot holding ``valid - 1 + T`` positions is the
    causal attention's row ``valid - 1 + t``."""
    from chainermn_tpu.ops import paged_decode_attention, reference_attention

    rng = np.random.RandomState(11)
    S, H, KH, Dh, NB, BL, MB = 4, 6, 2, 16, 16, 4, 5
    q = rng.randn(S, T, H, Dh).astype(np.float32)
    kp = rng.randn(KH, NB, BL, Dh).astype(np.float32)
    vp = rng.randn(KH, NB, BL, Dh).astype(np.float32)
    tbl = np.stack([rng.permutation(np.arange(1, NB))[:MB] for _ in range(S)])
    tbl[2] = 0  # the idle slot's table is parked
    valid = np.asarray([13, 1, 0, 18 - (T - 1)], np.int32)
    got = paged_decode_attention(
        jnp.asarray(q if T > 1 else q[:, 0]), _fuse(kp, vp),
        jnp.asarray(tbl, jnp.int32), jnp.asarray(valid),
    )
    got = np.asarray(got).reshape(S, T, H, Dh)
    assert (got[2, 0] == 0).all() and np.isfinite(got).all()  # idle slot
    for s in (0, 1, 3):
        L = int(valid[s]) - 1 + T
        # this slot's context, gathered: (1, L, KH, Dh)
        kg = kp[:, tbl[s]].reshape(KH, MB * BL, Dh)[:, :L].transpose(1, 0, 2)
        vg = vp[:, tbl[s]].reshape(KH, MB * BL, Dh)[:, :L].transpose(1, 0, 2)
        qf = np.zeros((1, L, H, Dh), np.float32)
        qf[0, L - T:] = q[s]
        want = reference_attention(
            jnp.asarray(qf), jnp.asarray(kg[None]), jnp.asarray(vg[None]),
            causal=True,
        )[0, L - T:]
        np.testing.assert_allclose(got[s], np.asarray(want), atol=2e-5)


def test_paged_int8_pool_matches_dequantized_oracle():
    """int8 pool + the (NB, KH, 2, BL) scale plane: the kernel folds the k
    scale into the scores and the v scale into the probabilities — the same
    numbers as the float oracle on the dequantized pools."""
    from chainermn_tpu.ops import paged_decode_attention

    rng = np.random.RandomState(12)
    S, T, H, KH, Dh, NB, BL, MB = 3, 2, 4, 2, 8, 10, 4, 4
    q = jnp.asarray(rng.randn(S, T, H, Dh), jnp.float32)
    k8 = rng.randint(-127, 128, size=(KH, NB, BL, Dh)).astype(np.int8)
    v8 = rng.randint(-127, 128, size=(KH, NB, BL, Dh)).astype(np.int8)
    ks = (rng.rand(KH, NB, BL) * 0.02 + 0.001).astype(np.float32)
    vs = (rng.rand(KH, NB, BL) * 0.02 + 0.001).astype(np.float32)
    tbl = rng.randint(1, NB, size=(S, MB)).astype(np.int32)
    valid = np.asarray([7, 1, 12], np.int32)
    out = paged_decode_attention(q, _fuse(k8, v8), jnp.asarray(tbl),
                                 jnp.asarray(valid), _fuse_scale(ks, vs))
    ref = _paged_oracle(q, k8 * ks[..., None], v8 * vs[..., None], tbl, valid)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_paged_pool_shape_is_checked():
    """A pool that is not (num_blocks, block_len, KH * 2 * Dh) for the
    query's head width is refused by name, as is an int8 pool without its
    scale plane — read or written."""
    from chainermn_tpu.ops import paged_decode_attention

    q = jnp.zeros((2, 4, 8), jnp.float32)
    tbl = jnp.zeros((2, 3), jnp.int32)
    valid = jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match=r"KH \* 2 \* Dh"):
        paged_decode_attention(q, jnp.zeros((2, 6, 4, 8)), tbl, valid)
    with pytest.raises(ValueError, match=r"KH \* 2 \* Dh"):
        paged_decode_attention(q, jnp.zeros((6, 4, 24)), tbl, valid)
    with pytest.raises(ValueError, match="multiple of KH"):
        paged_decode_attention(q, jnp.zeros((6, 4, 3 * 16)), tbl, valid)
    with pytest.raises(ValueError, match="int8 pool needs kv_scale"):
        paged_decode_attention(q, jnp.zeros((6, 4, 32), jnp.int8), tbl, valid)
    # the write holds an int8 pool and its scales together the same way
    from chainermn_tpu.ops.decode_attention import pool_write

    kv = jnp.zeros((2, 1, 2, 8), jnp.int8)
    pos = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="int8 pool"):
        pool_write({"kv": jnp.zeros((6, 4, 32), jnp.int8),
                    "kv_scale": jnp.zeros((6, 2, 2, 4))}, kv, kv, None, tbl,
                   pos)
    with pytest.raises(ValueError, match="int8 pool"):
        pool_write({"kv": jnp.zeros((6, 4, 32))}, kv, kv,
                   (jnp.ones((2, 1, 2)),) * 2, tbl, pos)


def _pool_case(rng, kind, KH, NB, BL, Dh):
    """A random pool whose LAST block is poison — NaN keys and values, in
    an int8 pool NaN scales: ``(kp, vp, scale, kf, vf)``, the stored
    per-head arrays, the fused scale plane (or ``None``) and the floats the
    stored values stand for."""
    if kind == "int8":
        kp = rng.randint(-127, 128, size=(KH, NB, BL, Dh)).astype(np.int8)
        vp = rng.randint(-127, 128, size=(KH, NB, BL, Dh)).astype(np.int8)
        ks = (rng.rand(KH, NB, BL) * 0.02 + 0.001).astype(np.float32)
        vs = (rng.rand(KH, NB, BL) * 0.02 + 0.001).astype(np.float32)
        ks[:, NB - 1] = vs[:, NB - 1] = np.nan
        return (kp, vp, _fuse_scale(ks, vs),
                kp * ks[..., None], vp * vs[..., None])
    kp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.bfloat16)
    vp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.bfloat16)
    kp = kp.at[:, NB - 1].set(jnp.nan)
    vp = vp.at[:, NB - 1].set(jnp.nan)
    return kp, vp, None, np.asarray(kp, np.float32), np.asarray(vp, np.float32)


def _assert_kernel_is_the_oracle(q, pool, tbl, valid):
    """The kernel on ``pool`` (:func:`_pool_case`) for queries ``(S, T, H,
    Dh)``: finite — no poisoned or stale row reached it — the float32
    oracle's for every live slot, and zeros at offset 0 of an idle one."""
    from chainermn_tpu.ops import paged_decode_attention

    kp, vp, scale, kf, vf = pool
    T = q.shape[1]
    out = paged_decode_attention(
        q if T > 1 else q[:, 0], _fuse(kp, vp), jnp.asarray(tbl),
        jnp.asarray(valid), scale)
    out = np.asarray(out).reshape(q.shape)
    assert np.isfinite(out).all()
    ref = _paged_oracle(q, kf, vf, tbl, valid)
    live = valid > 0
    tol = dict(atol=1e-4, rtol=1e-4) if scale is not None else dict(atol=2e-5)
    np.testing.assert_allclose(out[live], ref[live], **tol)
    assert (out[~live, 0] == 0).all()  # idle: offset 0 fully masked


#: slot lengths of one call (block_len 16, a table 4 wide), by name
_BL, _MB = 16, 4
_LENGTHS = {
    "idle": [0] * 6,
    "one": [1] * 6,
    "bl_minus_1": [_BL - 1] * 6,
    "bl": [_BL] * 6,
    "bl_plus_1": [_BL + 1] * 6,
    "full_table": [_BL * _MB] * 6,
    "ragged": [0, 1, _BL + 3, 3 * _BL - 1, _BL * _MB, 2 * _BL],
}


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("T", [1, 4], ids=["decode", "verify_t4"])
@pytest.mark.parametrize("lengths", sorted(_LENGTHS))
@pytest.mark.parametrize("H, KH, Dh", [(25, 25, 64), (24, 2, 128), (4, 2, 64),
                                       (20, 4, 128)],
                         ids=["xl_mha", "sc2_gqa", "gqa_small", "falcon_gqa"])
def test_paged_whole_row_matches_oracle(H, KH, Dh, lengths, T, kind):
    """One grid step serves every head of a block: the kernel against the
    float32 oracle at the serving geometries' head shapes (GPT-2 XL's 25
    heads of 64 handled along the row's lanes, StarCoder2's 24 / 2 of 128,
    a small GQA and Falcon-H1's 20 / 4 of 128 as a loop over KV heads), at
    the slot lengths where a block fills, for decode and a verify chunk,
    float and int8 pools.  (A table 4 wide at ``block_len`` 16: where the
    per-head body folds a tile of 8 blocks, every slot here is one partial
    tile; ``test_paged_tiles_match_oracle`` below has the whole ones.)

    Table entries past what a slot can attend point at a block of NaN (in
    an int8 pool, at NaN scales): a step past the slot's last resident
    block must neither compute on the row it names nor, by the clamped
    index map, name another than the last resident one — any read of it
    poisons the output.  (Six slots in every case: the lengths of a
    (heads, T, pool dtype) share one compiled kernel.)"""
    from chainermn_tpu.ops import paged_decode_attention

    valid = np.asarray(_LENGTHS[lengths], np.int32)
    # the last query of a verify chunk attends through valid + T - 2
    valid = np.minimum(valid, _BL * _MB - (T - 1))
    S = len(valid)
    rng = np.random.RandomState(len(lengths) * 100 + T)
    NB = S * _MB + 2
    q = jnp.asarray(rng.randn(S, T, H, Dh), jnp.float32)
    pool = _pool_case(rng, kind, KH, NB, _BL, Dh)
    tbl = np.full((S, _MB), NB - 1, np.int32)  # the poisoned block
    for s in range(S):
        n = -(-(int(valid[s]) + T - 1) // _BL)
        tbl[s, :n] = 1 + s * _MB + np.arange(n)
    _assert_kernel_is_the_oracle(q, pool, tbl, valid)


#: blocks a slot holds, in the table's order of slots (block_len 16, so a
#: tile is C = 8 blocks): the longest first, so that the slot after it —
#: one whole tile, then a tile of ONE block — finds the buffers full of the
#: longer context's finite rows
_C = 128 // _BL
_TILE_COUNTS = [3 * _C + 5, _C + 1, 0, 1, _C - 1, _C, 2 * _C]


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("T", [1, 4], ids=["decode", "verify_t4"])
def test_paged_tiles_match_oracle(T, kind):
    """Falcon-H1's head shape over a table 32 wide at ``block_len`` 16: the
    per-head body folds eight table entries a loop step
    (:func:`blocks_a_step`; one, for the int8 pool), and a slot's block
    count is any number — none, one, a tile less one, a tile, a tile and
    one, two tiles, three and five.  Every entry past a slot's count names
    the block of NaN, so a copy of one poisons the output; the slices of a
    slot's last tile that no block fills hold what an earlier step left
    (in the interpreter NaN at first, then the longest slot's rows), and a
    row of them in the value product would be ``0 * NaN``: the output is
    finite and the oracle's."""
    from chainermn_tpu.ops.decode_attention import blocks_a_step

    H, KH, Dh, MB = 20, 4, 128, 32
    assert blocks_a_step(_BL, jnp.bfloat16, H // KH) == _C
    assert blocks_a_step(_BL, jnp.int8, H // KH) == 1
    S = len(_TILE_COUNTS)
    # a slot's last block part full, and as many blocks as the list says
    valid = np.asarray([max(n * _BL - (T - 1) - s % 5, 0)
                        for s, n in enumerate(_TILE_COUNTS)], np.int32)
    rng = np.random.RandomState(7 + T)
    NB = sum(_TILE_COUNTS) + 3
    q = jnp.asarray(rng.randn(S, T, H, Dh), jnp.float32)
    pool = _pool_case(rng, kind, KH, NB, _BL, Dh)
    tbl = np.full((S, MB), NB - 1, np.int32)  # the poisoned block
    free = iter(rng.permutation(np.arange(1, NB - 1)))
    for s in range(S):
        n = -(-(int(valid[s]) + T - 1) // _BL)
        assert n == max(_TILE_COUNTS[s], int(T > 1))
        tbl[s, :n] = [next(free) for _ in range(n)]
    _assert_kernel_is_the_oracle(q, pool, tbl, valid)


@pytest.mark.parametrize("H, KH", [(20, 4), (4, 4)], ids=["group5", "mha"])
def test_paged_window_form_walks_tiles(H, KH):
    """The window form at ``block_len`` 16: a ring's table rotated oldest
    first, its first entry at a position that is a multiple of
    ``block_len`` and not of the tile's 128 — a tile's positions are
    ``first_pos + j * 128 ...`` — the lower bound inside the first block,
    entries past a slot's newest block poisoned."""
    from chainermn_tpu.ops import paged_decode_attention

    Dh, R, window = 128, 16, 200
    valid = np.asarray([500, 77, 0, 1000], np.int32)
    lowest = np.maximum(valid - window, 0).astype(np.int32)
    first = lowest // _BL * _BL
    assert {int(f) % 128 for f in first} == {0, 32}
    S = len(valid)
    NB = S * R + 2
    rng = np.random.RandomState(H)
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    kp, vp, _, kf, vf = _pool_case(rng, "bf16", KH, NB, _BL, Dh)
    tbl = np.full((S, R), NB - 1, np.int32)
    ref = np.zeros((S, H, Dh), np.float32)
    for s in range(S):
        n = -(-(int(valid[s]) - int(first[s])) // _BL)
        tbl[s, :n] = 1 + s * R + (np.arange(n) + 3 * s) % R  # a ring's turn
        k, v = (x[:, tbl[s, :n]].reshape(KH, n * _BL, Dh) for x in (kf, vf))
        seen = slice(int(lowest[s] - first[s]), int(valid[s] - first[s]))
        for h in range(H if valid[s] else 0):
            sc = np.asarray(q)[s, h] @ k[h * KH // H, seen].T / np.sqrt(Dh)
            p = np.exp(sc - sc.max())
            ref[s, h] = p / p.sum() @ v[h * KH // H, seen]
    out = np.asarray(paged_decode_attention(
        q, _fuse(kp, vp), jnp.asarray(tbl), jnp.asarray(valid), None,
        lowest=jnp.asarray(lowest), first_pos=jnp.asarray(first)))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=2e-5)
    assert (out[2] == 0).all()  # the idle slot


# ------------------------------------------------- sharded (shard_map)
def _mesh2():
    """A 2-way serving mesh over the forced CPU pod (the tests/conftest
    env hook); KH=2 in the shapes below puts one KV head per shard."""
    from chainermn_tpu.serving.sharding import serving_mesh

    if len(jax.devices()) < 2:
        pytest.skip("multi-device CPU rig missing")
    return serving_mesh(2)


def test_sharded_paged_bit_identical_to_unsharded():
    """The shard_map wrapper is a pure layout move: per-shard kernels
    over the KV-head cut produce EXACTLY the unsharded kernel's output
    (softmax never crosses KV heads) — 3-D, 4-D verify, and int8."""
    from chainermn_tpu.ops import (
        paged_decode_attention,
        sharded_paged_decode_attention,
    )

    mesh = _mesh2()
    rng = np.random.RandomState(7)
    S, T, H, KH, Dh, NB, BL, MB = 2, 3, 4, 2, 8, 8, 4, 4
    q3 = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    q4 = jnp.asarray(rng.randn(S, T, H, Dh), jnp.float32)
    kp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    tbl = jnp.asarray(rng.randint(1, NB, size=(S, MB)), jnp.int32)
    valid = jnp.asarray([5, 14], jnp.int32)
    pool = _fuse(kp, vp)
    for q in (q3, q4):
        ref = paged_decode_attention(q, pool, tbl, valid)
        out = sharded_paged_decode_attention(q, pool, tbl, valid, mesh=mesh)
        assert (np.asarray(out) == np.asarray(ref)).all()
    ks = jnp.asarray(np.abs(rng.rand(KH, NB, BL)) + 0.1, jnp.float32)
    vs = jnp.asarray(np.abs(rng.rand(KH, NB, BL)) + 0.1, jnp.float32)
    pool8 = _fuse((kp * 5).astype(jnp.int8), (vp * 5).astype(jnp.int8))
    sc = _fuse_scale(ks, vs)
    ref = paged_decode_attention(q3, pool8, tbl, valid, sc)
    out = sharded_paged_decode_attention(q3, pool8, tbl, valid, sc,
                                         mesh=mesh)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_sharded_paged_single_query_is_multi_query_t1():
    """The T == 1 == 3-D-call identity pin, THROUGH the shard-local
    entry: the wrapper's 4-D spec at T == 1 must hit the same kernel
    path as the 3-D spec, bit for bit."""
    from chainermn_tpu.ops import sharded_paged_decode_attention

    mesh = _mesh2()
    rng = np.random.RandomState(8)
    S, H, KH, Dh, NB, BL, MB = 2, 4, 2, 8, 8, 4, 4
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    kp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    tbl = jnp.asarray(rng.randint(1, NB, size=(S, MB)), jnp.int32)
    valid = jnp.asarray([6, 11], jnp.int32)
    pool = _fuse(kp, vp)
    a = sharded_paged_decode_attention(q, pool, tbl, valid, mesh=mesh)
    b = sharded_paged_decode_attention(q[:, None], pool, tbl, valid,
                                       mesh=mesh)[:, 0]
    assert (np.asarray(a) == np.asarray(b)).all()


def test_sharded_wrapper_validation():
    """Indivisible KV heads must fail up front, naming both axes; a
    size-1 mesh falls through to the plain kernel call."""
    from chainermn_tpu.serving.sharding import serving_mesh

    from chainermn_tpu.ops import (
        paged_decode_attention,
        sharded_paged_decode_attention,
    )

    if len(jax.devices()) < 4:
        pytest.skip("multi-device CPU rig missing")
    rng = np.random.RandomState(10)
    S, H, KH, Dh, NB, BL, MB = 2, 4, 2, 8, 8, 4, 4
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    kp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(KH, NB, BL, Dh), jnp.float32)
    tbl = jnp.asarray(rng.randint(1, NB, size=(S, MB)), jnp.int32)
    valid = jnp.asarray([6, 11], jnp.int32)
    pool = _fuse(kp, vp)
    with pytest.raises(ValueError, match=r"KV heads \(2.*'model' \(4\)"):
        sharded_paged_decode_attention(q, pool, tbl, valid,
                                       mesh=serving_mesh(4))
    ref = paged_decode_attention(q, pool, tbl, valid)
    out = sharded_paged_decode_attention(q, pool, tbl, valid,
                                         mesh=serving_mesh(1))
    assert (np.asarray(out) == np.asarray(ref)).all()


# ------------------------------------------- the pool row: write and reads
_POS = {  # name -> (B, T, the chunk's first position: per row, or a scalar)
    "decode": (3, 1, [0, 9, 21]),
    "verify_t4": (3, 4, [2, 7, 17]),
    "prefill_c32": (1, 32, 5),
}
_KH, _DH, _PBL, _PMB = 2, 16, 8, 6
#: chunks whose LAST position lands in each width the gathered read may
#: take of this geometry's 6-block table — ``context_widths(6)`` is 1, 2,
#: 3, 6 blocks of 8: name -> (B, T, first position(s), the width taken)
_LADDER = {
    "c4_first_block": (1, 4, 0, 1),
    "c4_on_w1_edge": (1, 4, 4, 1),      # last position 7: the width's last
    "c4_past_w1_edge": (1, 4, 5, 2),    # last position 8: one past it
    "c8_on_w2_edge": (1, 8, 8, 2),
    "c8_past_w2_edge": (1, 8, 9, 3),
    "c8_past_w3_edge": (1, 8, 17, 6),
    "c8_full_table": (1, 8, 40, 6),     # last position 47: the table's last
    "rows_w1": (3, 1, [0, 3, 7], 1),
    "rows_on_w3_edge": (3, 4, [0, 9, 20], 3),  # the widest row decides
    "rows_past_w3_edge": (3, 4, [21, 9, 0], 6),
    "rows_full_table": (3, 4, [1, 20, 44], 6),
}
_READS = {**{m: v + (None,) for m, v in _POS.items()}, **_LADDER}


def _positions(mode):
    """``q_pos`` ``(B, T)`` as the model's block forms it."""
    B, T, p0 = _READS[mode][:3]
    return np.broadcast_to(
        np.asarray(p0).reshape(-1, 1) + np.arange(T)[None], (B, T))


def _tables(B, rng):
    """Block tables over disjoint physical blocks (block 0 is parking)."""
    ids = 1 + rng.permutation(B * _PMB)
    return ids.reshape(B, _PMB).astype(np.int32), B * _PMB + 1


def _random_cache(NB, kind, rng):
    """A pool with something in every row, so an untouched row shows."""
    if kind == "int8":
        return {
            "kv": jnp.asarray(rng.randint(
                -127, 128, size=(NB, _PBL, _KH * 2 * _DH)).astype(np.int8)),
            "kv_scale": jnp.asarray(
                (rng.rand(NB, _KH, 2, _PBL) + 0.5).astype(np.float32)),
        }
    return {"kv": jnp.asarray(rng.randn(NB, _PBL, _KH * 2 * _DH),
                              jnp.bfloat16)}


def _chunk(B, T, kind, rng):
    """A chunk's k, v and scales as the block hands them to the write."""
    if kind == "int8":
        k = rng.randint(-127, 128, size=(B, T, _KH, _DH)).astype(np.int8)
        v = rng.randint(-127, 128, size=(B, T, _KH, _DH)).astype(np.int8)
        ks = (rng.rand(B, T, _KH) * 0.02 + 0.001).astype(np.float32)
        vs = (rng.rand(B, T, _KH) * 0.02 + 0.001).astype(np.float32)
        return k, v, (jnp.asarray(ks), jnp.asarray(vs))
    k = rng.randn(B, T, _KH, _DH).astype(np.float32)
    v = rng.randn(B, T, _KH, _DH).astype(np.float32)
    return k, v, None


def _check_written(old, new, k, v, scales, tbl, q_pos, live):
    """Live rows' tokens sit at ``(table[pos // BL], pos % BL)`` as
    ``[k_h | v_h]`` lane groups (scales at ``[block, h, :, off]``); every
    other row of the pool and of the scale plane is bit-identical."""
    old = {n: np.asarray(a) for n, a in old.items()}
    new = {n: np.asarray(a) for n, a in new.items()}
    assert sorted(old) == sorted(new)
    dt = old["kv"].dtype
    touched = np.zeros(old["kv"].shape[:2], bool)
    for b in np.flatnonzero(live):
        for t in range(q_pos.shape[1]):
            blk, off = tbl[b, q_pos[b, t] // _PBL], q_pos[b, t] % _PBL
            row = new["kv"][blk, off].reshape(_KH, 2, _DH)
            assert (row[:, 0] == np.asarray(jnp.asarray(k[b, t], dt))).all()
            assert (row[:, 1] == np.asarray(jnp.asarray(v[b, t], dt))).all()
            if scales is not None:
                got = new["kv_scale"][blk, :, :, off]
                assert (got[:, 0] == np.asarray(scales[0])[b, t]).all()
                assert (got[:, 1] == np.asarray(scales[1])[b, t]).all()
            touched[blk, off] = True
    assert touched.sum() == live.sum() * q_pos.shape[1]
    same = new["kv"].view(np.uint8) == old["kv"].view(np.uint8)
    assert same.reshape(*touched.shape, -1).all(axis=-1)[~touched].all()
    if scales is not None:
        keep = np.broadcast_to(~touched[:, None, None, :],
                               old["kv_scale"].shape)
        assert (new["kv_scale"][keep] == old["kv_scale"][keep]).all()


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("mode", sorted(_POS))
def test_pool_write_then_context_roundtrip(mode, kind):
    """What a chunk writes comes back at exactly the positions written —
    in the pool's rows, and in the logical order the gathered read takes a
    slot's table in — and no other row of the pool changes."""
    from chainermn_tpu.ops.decode_attention import pool_write

    B, T, _ = _POS[mode]
    rng = np.random.RandomState(len(mode) + (kind == "int8"))
    tbl, NB = _tables(B, rng)
    q_pos = _positions(mode)
    old = _random_cache(NB, kind, rng)
    k, v, scales = _chunk(B, T, kind, rng)
    new = pool_write(old, jnp.asarray(k), jnp.asarray(v), scales,
                     jnp.asarray(tbl), jnp.asarray(q_pos, jnp.int32))
    _check_written(old, new, k, v, scales, tbl, q_pos, np.ones(B, bool))
    # the gathered view of a slot: its table's blocks, logical order
    ctx = np.asarray(new["kv"])[tbl].reshape(B, _PMB * _PBL, _KH, 2, _DH)
    dt = ctx.dtype
    for b in range(B):
        assert (ctx[b, q_pos[b], :, 0]
                == np.asarray(jnp.asarray(k[b], dt))).all()
        assert (ctx[b, q_pos[b], :, 1]
                == np.asarray(jnp.asarray(v[b], dt))).all()


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_masked_slot_writes_nothing(kind):
    """A masked slot's scatter lands on the parking block with the value
    already there: the pool and the scale plane stay bit-identical but
    for the live slot's own rows, parking block included."""
    from chainermn_tpu.ops.decode_attention import pool_write

    B, T = 3, 4
    rng = np.random.RandomState(3 + (kind == "int8"))
    tbl, NB = _tables(B, rng)
    q_pos = np.asarray([2, 7, 17])[:, None] + np.arange(T)[None]
    live = np.asarray([False, True, False])
    old = _random_cache(NB, kind, rng)
    k, v, scales = _chunk(B, T, kind, rng)
    new = pool_write(old, jnp.asarray(k), jnp.asarray(v), scales,
                     jnp.asarray(tbl), jnp.asarray(q_pos, jnp.int32),
                     jnp.asarray(live))
    _check_written(old, new, k, v, scales, tbl, q_pos, live)
    for n in old:  # the parking block itself
        assert (np.asarray(new[n])[0] == np.asarray(old[n])[0]).all()
    none = pool_write(old, jnp.asarray(k), jnp.asarray(v), scales,
                      jnp.asarray(tbl), jnp.asarray(q_pos, jnp.int32),
                      jnp.zeros(B, bool))
    for n in old:
        assert (np.asarray(none[n]).view(np.uint8)
                == np.asarray(old[n]).view(np.uint8)).all()


def _context_case(mode, kind, window):
    """A context scattered through the tables, and a query chunk: the
    arguments of the gathered read, and each slot's contiguous float keys
    and values (an int8 pool's, dequantised) for the oracle."""
    B, T = _READS[mode][:2]
    H, L = 2 * _KH, _PMB * _PBL
    rng = np.random.RandomState(7 + len(mode) + window)
    tbl, NB = _tables(B, rng)
    q_pos = _positions(mode)
    q = rng.randn(B, T, H, _DH).astype(np.float32)
    cache = {}
    if kind == "int8":
        kv = rng.randint(-127, 128, size=(B, L, _KH, 2, _DH)).astype(np.int8)
        sc = (rng.rand(B, L, _KH, 2) * 0.02 + 0.001).astype(np.float32)
        k, v = (kv[:, :, :, i] * sc[:, :, :, i, None] for i in (0, 1))
        plane = rng.rand(NB, _KH, 2, _PBL).astype(np.float32)
        for b in range(B):  # (L, KH, 2) -> (MB, KH, 2, BL)
            plane[tbl[b]] = sc[b].reshape(_PMB, _PBL, _KH, 2).transpose(
                0, 2, 3, 1)
        cache["kv_scale"] = jnp.asarray(plane)
        pool = rng.randint(-127, 128, size=(NB, _PBL, _KH * 2 * _DH)).astype(
            np.int8)
    else:
        kv = rng.randn(B, L, _KH, 2, _DH).astype(np.float32)
        k, v = kv[:, :, :, 0], kv[:, :, :, 1]
        pool = rng.randn(NB, _PBL, _KH * 2 * _DH).astype(np.float32)
    for b in range(B):
        pool[tbl[b]] = kv[b].reshape(_PMB, _PBL, -1)
    cache["kv"] = jnp.asarray(pool)
    return (jnp.asarray(q), cache, jnp.asarray(tbl),
            jnp.asarray(q_pos, jnp.int32)), (q, k, v, q_pos)


@pytest.mark.parametrize("kind", ["float", "int8"])
@pytest.mark.parametrize("window", [0, 5], ids=["full", "window"])
@pytest.mark.parametrize("mode", sorted(_READS))
def test_pool_context_attend_matches_reference(mode, window, kind):
    """The gathered read against the repo's one attention oracle on each
    slot's CONTIGUOUS keys and values: the pool holds a context scattered
    through the tables; query ``t`` of a chunk starting at ``p`` is row
    ``p + t`` of the causal (windowed) attention over the slot — whichever
    width of the table the chunk's last position makes the read take
    (``_LADDER``: first block only, on a width's edge, one past it, the
    whole table; one position for the chunk or one a row), float pool or
    int8 with its scale plane."""
    from chainermn_tpu.ops import reference_attention
    from chainermn_tpu.ops.decode_attention import (
        context_blocks,
        pool_context_attend,
    )

    args, (q, k, v, q_pos) = _context_case(mode, kind, window)
    B, T, _, width = _READS[mode]
    H, L = 2 * _KH, _PMB * _PBL
    if width is not None:
        assert context_blocks(q_pos.max(), _PBL, _PMB) == width
    got = np.asarray(pool_context_attend(*args, window))
    assert got.shape == (B, T, H, _DH)
    for b in range(B):
        qf = np.zeros((1, L, H, _DH), np.float32)
        qf[0, q_pos[b]] = q[b]
        want = reference_attention(
            jnp.asarray(qf), jnp.asarray(k[b:b + 1]), jnp.asarray(v[b:b + 1]),
            causal=True, window=window or None)
        np.testing.assert_allclose(got[b], np.asarray(want)[0, q_pos[b]],
                                   atol=2e-5)


@pytest.mark.parametrize("kind", ["float", "int8"])
@pytest.mark.parametrize("window", [0, 5], ids=["full", "window"])
@pytest.mark.parametrize("mode", sorted(_READS))
def test_context_width_read_is_the_full_width_read(mode, window, kind):
    """Reading only the blocks up to the chunk's last position changes no
    bit of the result in float32: every column left out carried
    probability exactly 0.0 in the read over the table's whole width."""
    from chainermn_tpu.ops.decode_attention import (
        _attend_width,
        pool_context_attend,
    )

    (q, cache, tbl, q_pos), _ = _context_case(mode, kind, window)
    got = np.asarray(pool_context_attend(q, cache, tbl, q_pos, window))
    whole = np.asarray(_attend_width(
        _PMB, window, q, cache["kv"], cache.get("kv_scale"), tbl, q_pos))
    assert got.dtype == np.float32
    assert (got.view(np.uint32) == whole.view(np.uint32)).all()


def test_context_widths_follow_the_table():
    """The ladder is derived from the table's width — an eighth, a
    quarter, a half, the whole, rounded up — and the host's
    ``context_blocks`` makes the program's choice: the narrowest width
    holding the position, the widest past the table's end."""
    from chainermn_tpu.ops.decode_attention import (
        context_blocks,
        context_widths,
    )

    assert context_widths(64) == (8, 16, 32, 64)
    assert context_widths(16) == (2, 4, 8, 16)
    assert context_widths(20) == (3, 5, 10, 20)
    assert context_widths(_PMB) == (1, 2, 3, 6)  # what ``_LADDER`` walks
    assert context_widths(2) == (1, 2)
    assert context_widths(1) == (1,)
    got = [context_blocks(p, 16, 64) for p in (0, 127, 128, 255, 256, 511,
                                               512, 1023, 5000)]
    assert got == [8, 8, 16, 16, 32, 32, 64, 64, 64]
    assert context_blocks(0, 8, 1) == 1


#: the dispatch table as data: name -> (T, per-row positions?, window,
#: the caller's ``kernel`` flag, the scope taken)
_CHOICE = {
    "t1": (1, True, 0, True, "attn.paged"),
    "verify_t4": (4, True, 0, True, "attn.paged"),
    "prefill_chunk": (4, False, 0, True, "attn.gathered"),
    "window": (1, True, 8, True, "attn.gathered"),
    "kernel_off": (1, True, 0, False, "attn.gathered"),
}


@pytest.mark.parametrize("case", sorted(_CHOICE))
def test_paged_attend_chooses_by_shape(case):
    """``paged_attend`` is the one place that chooses a read, from the
    chunk's length, the rank of the position it was given, the window and
    the caller's flag: the lowered text holds the scope of the read taken
    and not the other's.  (A verify chunk and a prefill chunk of the SAME
    length differ only in that rank.)"""
    from chainermn_tpu.ops.decode_attention import paged_attend

    T, per_row, window, kernel, taken = _CHOICE[case]
    B, H = 2, 2 * _KH
    rng = np.random.RandomState(5)
    tbl, NB = _tables(B, rng)
    cache = _random_cache(NB, "bf16", rng)
    q = jnp.asarray(rng.randn(B, T, H, _DH), jnp.float32)
    decode_pos = jnp.asarray([3, 11], jnp.int32) if per_row else jnp.int32(3)

    def attend(q, cache, tbl, decode_pos):
        q_pos = (decode_pos[:, None] if per_row else decode_pos) \
            + jnp.arange(T)[None]
        q_pos = jnp.broadcast_to(q_pos, (B, T))
        return paged_attend(q, cache, tbl, decode_pos, q_pos,
                            jnp.ones(B, bool), kernel=kernel, window=window)

    fn = jax.jit(attend)
    args = (q, cache, jnp.asarray(tbl), decode_pos)
    text = fn.lower(*args).as_text(debug_info=True)
    other = ({"attn.paged", "attn.gathered"} - {taken}).pop()
    assert taken in text and other not in text
    assert fn(*args).shape == q.shape


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gathered"])
def test_chunk_rows_part_at_the_read_and_nowhere_else(kernel):
    """``chunk_rows``: the last rows of a batch of single-token rows are one
    slot's prefill chunk riding a decode step.  They read as that chunk
    does alone — regrouped ``(1, C, H, Dh)``, the gathered read over the
    slot's table — and the rows before them as they do alone (the kernel
    where the caller allows it: then one program holds both scopes)."""
    from chainermn_tpu.ops.decode_attention import (
        paged_attend,
        pool_context_attend,
    )

    S, C, H = 3, 5, 2 * _KH
    rng = np.random.RandomState(8)
    tbl, NB = _tables(S + 1, rng)
    cache = _random_cache(NB, "bf16", rng)
    q = jnp.asarray(rng.randn(S + C, 1, H, _DH), jnp.float32)
    # decode rows anywhere; the chunk at positions 6..10 of the last table
    pos = jnp.asarray([3, 17, 9] + list(range(6, 6 + C)), jnp.int32)
    tables = jnp.asarray(np.concatenate([tbl[:S], np.repeat(tbl[S:], C, 0)]))
    live = jnp.asarray([True, False, True] + [True] * C)

    def mixed(q, cache):
        return paged_attend(q, cache, tables, pos, pos[:, None], live,
                            kernel=kernel, chunk_rows=C)

    got = jax.jit(mixed)(q, cache)
    rows = paged_attend(q[:S], cache, tables[:S], pos[:S], pos[:S, None],
                        live[:S], kernel=kernel)
    chunk = pool_context_attend(jnp.swapaxes(q[S:], 0, 1), cache,
                                tables[S:S + 1], pos[None, S:])
    assert got.shape == q.shape
    np.testing.assert_array_equal(np.asarray(got[:S]), np.asarray(rows))
    np.testing.assert_array_equal(np.asarray(got[S:, 0]),
                                  np.asarray(chunk[0]))
    text = jax.jit(mixed).lower(q, cache).as_text(debug_info=True)
    assert "attn.gathered" in text and ("attn.paged" in text) == kernel
    with pytest.raises(ValueError, match="single-token rows"):
        paged_attend(q, cache, tables, jnp.int32(3), pos[:, None], live,
                     kernel=kernel, chunk_rows=C)

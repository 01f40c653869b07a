"""fused_decode_attention vs the einsum oracle (Pallas interpret on CPU).

The oracle is the math the TransformerLM decode branch runs — fp32
score/softmax/value einsums with the length-bound mask — written directly
over the kernel's kv-head-major (B, KH, L, Dh) layout.  Covers MHA, GQA
grouping, ragged ``valid_len`` rows, the int8 cache with per-(position,
kv-head) scales, and the argument-validation contract.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import fused_decode_attention

pytestmark = pytest.mark.tier1  # small shapes; interpret mode is fast here


def _oracle(q, kc, vc, valid_len, k_scale=None, v_scale=None):
    """fp32 einsum reference over the kv-head-major cache layout."""
    B, H, Dh = q.shape
    _, KH, L, _ = kc.shape
    G = H // KH
    qg = np.asarray(q, np.float32).reshape(B, KH, G, Dh) / np.sqrt(Dh)
    k = np.asarray(kc, np.float32)
    v = np.asarray(vc, np.float32)
    s = np.einsum("bhgd,bhld->bhgl", qg, k)
    if k_scale is not None:
        s = s * np.asarray(k_scale, np.float32)[:, :, None, :]
    pos = np.arange(L)[None, None, None, :]
    mask = pos < np.asarray(valid_len, np.int64)[:, None, None, None]
    s = np.where(mask, s, -1e30)
    m = s.max(axis=-1, keepdims=True)
    p = np.exp(s - m)
    l = p.sum(axis=-1)
    if v_scale is not None:
        p = p * np.asarray(v_scale, np.float32)[:, :, None, :]
    o = np.einsum("bhgl,bhld->bhgd", p, v) / np.maximum(l, 1e-30)[..., None]
    return o.reshape(B, H, Dh)


def _setup(B=2, H=4, KH=4, L=32, Dh=8, seed=0):
    rng = np.random.RandomState(seed)
    q = rng.randn(B, H, Dh).astype(np.float32)
    kc = rng.randn(B, KH, L, Dh).astype(np.float32)
    vc = rng.randn(B, KH, L, Dh).astype(np.float32)
    return q, kc, vc


def test_mha_full_length_matches_oracle():
    q, kc, vc = _setup()
    valid = np.array([32, 32], np.int32)
    got = fused_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(valid)
    )
    np.testing.assert_allclose(
        np.asarray(got), _oracle(q, kc, vc, valid), rtol=1e-5, atol=1e-5
    )


def test_gqa_grouping_matches_oracle():
    q, kc, vc = _setup(B=2, H=8, KH=2, L=16, Dh=8, seed=1)
    valid = np.array([16, 16], np.int32)
    got = fused_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(valid)
    )
    np.testing.assert_allclose(
        np.asarray(got), _oracle(q, kc, vc, valid), rtol=1e-5, atol=1e-5
    )


def test_ragged_valid_len_masks_tail():
    q, kc, vc = _setup(B=3, H=4, KH=4, L=24, Dh=8, seed=2)
    valid = np.array([24, 7, 1], np.int32)
    got = np.asarray(fused_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(valid)
    ))
    np.testing.assert_allclose(
        got, _oracle(q, kc, vc, valid), rtol=1e-5, atol=1e-5
    )
    # The masked tail must be INERT: corrupting positions >= valid_len
    # cannot change the output (the real ragged-row guarantee, not just
    # agreement-on-this-sample).
    kc2, vc2 = kc.copy(), vc.copy()
    kc2[1, :, 7:, :] = 1e3
    vc2[1, :, 7:, :] = -1e3
    got2 = np.asarray(fused_decode_attention(
        jnp.asarray(q), jnp.asarray(kc2), jnp.asarray(vc2),
        jnp.asarray(valid)
    ))
    np.testing.assert_allclose(got2[1], got[1], rtol=1e-6, atol=1e-6)


def test_int8_cache_matches_dequantized_oracle():
    q, kc, vc = _setup(B=2, H=4, KH=2, L=16, Dh=8, seed=3)
    q = q.astype(np.float32)
    # Symmetric absmax per (b, kh, l) row — the kv-quant cache contract.
    k_scale = (np.abs(kc).max(axis=-1) / 127.0 + 1e-8).astype(np.float32)
    v_scale = (np.abs(vc).max(axis=-1) / 127.0 + 1e-8).astype(np.float32)
    k8 = np.clip(np.round(kc / k_scale[..., None]), -127, 127)
    v8 = np.clip(np.round(vc / v_scale[..., None]), -127, 127)
    valid = np.array([16, 11], np.int32)
    got = fused_decode_attention(
        jnp.asarray(q), jnp.asarray(k8, np.int8), jnp.asarray(v8, np.int8),
        jnp.asarray(valid), k_scale=jnp.asarray(k_scale),
        v_scale=jnp.asarray(v_scale),
    )
    # Oracle over the int8 codes with the scales folded exactly where the
    # kernel folds them (k scale on scores, v scale on probabilities).
    want = _oracle(q, k8, v8, valid, k_scale=k_scale, v_scale=v_scale)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_output_dtype_follows_query():
    q, kc, vc = _setup(B=1, H=2, KH=2, L=8, Dh=8, seed=4)
    got = fused_decode_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(kc, jnp.bfloat16),
        jnp.asarray(vc, jnp.bfloat16), jnp.asarray([8], jnp.int32)
    )
    assert got.dtype == jnp.bfloat16
    assert got.shape == (1, 2, 8)


def test_validation_errors():
    q, kc, vc = _setup(B=1, H=3, KH=2, L=8, Dh=8, seed=5)
    with pytest.raises(ValueError, match="multiple of KH"):
        fused_decode_attention(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray([8], jnp.int32)
        )
    q, kc, vc = _setup(B=1, H=2, KH=2, L=8, Dh=8, seed=6)
    with pytest.raises(ValueError, match="int8 cache needs"):
        fused_decode_attention(
            jnp.asarray(q), jnp.asarray(kc, jnp.int8),
            jnp.asarray(vc, jnp.int8), jnp.asarray([8], jnp.int32)
        )


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
    scale plane."""
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
@pytest.mark.parametrize("H, KH, Dh", [(25, 25, 64), (24, 2, 128), (4, 2, 64)],
                         ids=["xl_mha", "sc2_gqa", "gqa_small"])
def test_paged_whole_row_matches_oracle(H, KH, Dh, lengths, T, kind):
    """One grid step serves every head of a block: the kernel against the
    float32 oracle at the serving geometries' head shapes (GPT-2 XL's 25
    heads of 64 handled along the row's lanes, StarCoder2's 24 / 2 of 128
    and a small GQA as a loop over KV heads), at the slot lengths where a
    block fills, for decode and a verify chunk, float and int8 pools.

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
    if kind == "int8":
        kp = rng.randint(-127, 128, size=(KH, NB, _BL, Dh)).astype(np.int8)
        vp = rng.randint(-127, 128, size=(KH, NB, _BL, Dh)).astype(np.int8)
        ks = (rng.rand(KH, NB, _BL) * 0.02 + 0.001).astype(np.float32)
        vs = (rng.rand(KH, NB, _BL) * 0.02 + 0.001).astype(np.float32)
        ks[:, NB - 1] = vs[:, NB - 1] = np.nan
        scale = _fuse_scale(ks, vs)
        kf, vf = kp * ks[..., None], vp * vs[..., None]
    else:
        kp = jnp.asarray(rng.randn(KH, NB, _BL, Dh), jnp.bfloat16)
        vp = jnp.asarray(rng.randn(KH, NB, _BL, Dh), jnp.bfloat16)
        kp = kp.at[:, NB - 1].set(jnp.nan)
        vp = vp.at[:, NB - 1].set(jnp.nan)
        scale = None
        kf, vf = np.asarray(kp, np.float32), np.asarray(vp, np.float32)
    tbl = np.full((S, _MB), NB - 1, np.int32)  # the poisoned block
    for s in range(S):
        n = -(-(int(valid[s]) + T - 1) // _BL)
        tbl[s, :n] = 1 + s * _MB + np.arange(n)
    out = paged_decode_attention(
        q if T > 1 else q[:, 0], _fuse(kp, vp), jnp.asarray(tbl),
        jnp.asarray(valid), scale)
    out = np.asarray(out).reshape(S, T, H, Dh)
    assert np.isfinite(out).all()
    ref = _paged_oracle(q, kf, vf, tbl, valid)
    live = valid > 0
    tol = dict(atol=1e-4, rtol=1e-4) if kind == "int8" else dict(atol=2e-5)
    np.testing.assert_allclose(out[live], ref[live], **tol)
    assert (out[~live, 0] == 0).all()  # idle: offset 0 fully masked


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


def test_sharded_fused_bit_identical_to_unsharded():
    from chainermn_tpu.ops import (
        fused_decode_attention,
        sharded_fused_decode_attention,
    )

    mesh = _mesh2()
    rng = np.random.RandomState(9)
    B, H, KH, L, Dh = 3, 4, 2, 8, 8
    q = jnp.asarray(rng.randn(B, H, Dh), jnp.float32)
    kc = jnp.asarray(rng.randn(B, KH, L, Dh), jnp.float32)
    vc = jnp.asarray(rng.randn(B, KH, L, Dh), jnp.float32)
    valid = jnp.asarray([3, 8, 5], jnp.int32)
    ref = fused_decode_attention(q, kc, vc, valid)
    out = sharded_fused_decode_attention(q, kc, vc, valid, mesh=mesh)
    assert (np.asarray(out) == np.asarray(ref)).all()


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

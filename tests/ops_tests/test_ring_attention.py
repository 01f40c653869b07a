"""A window layer's ring by slot (``ops/decode_attention.py``: ``ring_blocks``,
``ring_write``, ``ring_attend``, the kernel's window form) and the gathered
read a KV head at a time, with no model: against a dense ``numpy`` oracle
that keeps every key of every sequence."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import decode_attention as da

pytestmark = pytest.mark.tier1

KH, G, DH, BL = 2, 3, 8, 4
H = KH * G


def _oracle(q, keys, values, pos, window):
    """One query (H, Dh) at ``pos`` over ``keys`` / ``values`` (n, KH, Dh):
    positions ``pos - window < j <= pos``."""
    lo = max(pos - window + 1, 0)
    k, v = keys[lo:pos + 1], values[lo:pos + 1]
    out = np.zeros((H, DH), np.float32)
    for h in range(H):
        s = k[:, h // G] @ q[h] / math.sqrt(DH)
        p = np.exp(s - s.max())
        out[h] = (p / p.sum()) @ v[:, h // G]
    return out


@pytest.mark.parametrize("window,chunk,want", [
    (4096, 256, 34), (12, 8, 5), (12, 6, 6), (24, 16, 5), (1, 4, 2)])
def test_ring_blocks(window, chunk, want):
    bl = 128 if window == 4096 else (8 if window == 24 else 4)
    assert da.ring_blocks(window, chunk, bl) == want
    with pytest.raises(ValueError):
        da.ring_blocks(0, chunk, bl)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gathered"])
@pytest.mark.parametrize("window,chunk", [(12, 8), (7, 4)])
def test_decode_rows_and_chunks_through_a_ring_that_turns(kernel, window,
                                                          chunk):
    """Three slots, sequences of 61, 37 and 50 positions written chunk by
    chunk then token by token into rings of R blocks (the ring turns several
    times; slot 1 is then used again from position 0 over its old keys; one
    row is masked and writes nothing): every read — a chunk's rows, a decode
    row through the kernel or the gathered read, a chunk riding beside decode
    rows — is the oracle's over the last ``window`` positions."""
    rng = np.random.RandomState(3)
    R = da.ring_blocks(window, chunk, BL)
    slots = 3
    ring = {"ring": jnp.asarray(rng.randn(slots, R, BL, KH * 2 * DH),
                                jnp.float32)}  # stale rubbish everywhere
    lengths = [61, 37, 50]
    K = [rng.randn(n, KH, DH).astype(np.float32) for n in lengths]
    V = [rng.randn(n, KH, DH).astype(np.float32) for n in lengths]
    Q = [rng.randn(n, H, DH).astype(np.float32) for n in lengths]

    def check(got, s, positions):
        for row, p in zip(np.asarray(got), positions):
            want = _oracle(Q[s][p], K[s], V[s], p, window)
            assert np.abs(row - want).max() < 2e-5, (s, p)

    # prefill by chunks: each slot's first 3 * chunk positions
    for s in range(slots):
        for p0 in range(0, 3 * chunk, chunk):
            pos = jnp.arange(p0, p0 + chunk)[None]
            ring = da.ring_write(ring, jnp.asarray(K[s][None, p0:p0 + chunk]),
                                 jnp.asarray(V[s][None, p0:p0 + chunk]),
                                 jnp.asarray([s]), pos)
            got = da.ring_attend(jnp.asarray(Q[s][None, p0:p0 + chunk]), ring,
                                 jnp.asarray([s]), pos, window=window,
                                 kernel=kernel)
            check(got[0], s, range(p0, p0 + chunk))
    # decode steps, all three slots a step; slot 2 is masked at first
    at = [3 * chunk] * slots
    for step in range(9):
        live = np.array([True, True, step >= 3])
        pos = jnp.asarray(at)[:, None]
        k = jnp.stack([jnp.asarray(K[s][at[s]]) for s in range(slots)])[:, None]
        v = jnp.stack([jnp.asarray(V[s][at[s]]) for s in range(slots)])[:, None]
        q = jnp.stack([jnp.asarray(Q[s][at[s]]) for s in range(slots)])[:, None]
        before = np.asarray(ring["ring"][2])
        ring = da.ring_write(ring, k, v, jnp.arange(slots), pos,
                             jnp.asarray(live))
        got = da.ring_attend(q, ring, jnp.arange(slots), pos,
                             jnp.asarray(live), window=window, kernel=kernel)
        for s in range(slots):
            if live[s]:
                check(got[s], s, [at[s]])
                at[s] += 1
        if not live[2]:  # a masked row wrote nothing
            assert (np.asarray(ring["ring"][2]) == before).all()
    # a chunk of slot 1's NEXT sequence (from position 0, over its old
    # keys) riding beside the decode rows of slots 0 and 2
    K[1], V[1], Q[1] = (rng.randn(chunk, KH, DH).astype(np.float32)
                        for _ in range(3))
    Q[1] = rng.randn(chunk, H, DH).astype(np.float32)
    rows = slots + chunk
    pos = jnp.concatenate([jnp.asarray(at), jnp.arange(chunk)])[:, None]
    who = jnp.concatenate([jnp.arange(slots), jnp.full((chunk,), 1)])
    live = jnp.asarray([True, False, True] + [True] * (chunk - 2) + [False] * 2)
    k = jnp.concatenate([
        jnp.stack([jnp.asarray(K[s][min(at[s], len(K[s]) - 1)])
                   for s in range(slots)]), jnp.asarray(K[1])])[:, None]
    v = jnp.concatenate([
        jnp.stack([jnp.asarray(V[s][min(at[s], len(V[s]) - 1)])
                   for s in range(slots)]), jnp.asarray(V[1])])[:, None]
    q = jnp.concatenate([
        jnp.stack([jnp.asarray(Q[s][min(at[s], len(Q[s]) - 1)])
                   for s in range(slots)]), jnp.asarray(Q[1])])[:, None]
    assert q.shape == (rows, 1, H, DH)
    ring = da.ring_write(ring, k, v, who, pos, live)
    got = da.ring_attend(q, ring, who, pos, live, window=window,
                         kernel=kernel, chunk_rows=chunk)
    check(got[0], 0, [at[0]])
    check(got[2], 2, [at[2]])
    check(got[slots:slots + chunk - 2, 0], 1, range(chunk - 2))


def test_the_window_form_needs_both_bounds_and_single_positions():
    q = jnp.zeros((2, H, DH))
    pool = jnp.zeros((9, BL, KH * 2 * DH))
    tbl, valid = jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="together"):
        da.paged_decode_attention(q, pool, tbl, valid, None,
                                  lowest=jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="single-position"):
        da.paged_decode_attention(
            jnp.zeros((2, 2, H, DH)), pool, tbl, valid, None,
            lowest=jnp.zeros((2,), jnp.int32),
            first_pos=jnp.zeros((2,), jnp.int32))
    # with the bounds at their neutral values it is the plain kernel
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(9, BL, KH * 2 * DH), jnp.float32)
    q = jnp.asarray(rng.randn(2, H, DH), jnp.float32)
    tbl = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    valid = jnp.asarray([11, 6], jnp.int32)
    plain = da.paged_decode_attention(q, pool, tbl, valid)
    zero = jnp.zeros((2,), jnp.int32)
    assert np.allclose(plain, da.paged_decode_attention(
        q, pool, tbl, valid, None, lowest=zero, first_pos=zero), atol=1e-6)


@pytest.mark.parametrize("window", [0, 9])
def test_a_kv_head_at_a_time_is_all_heads_at_once(window, monkeypatch):
    """``pool_context_attend`` picks its body from the scores' size: the two
    agree, through the switch over the context's widths too."""
    rng = np.random.RandomState(1)
    pool = {"kv": jnp.asarray(rng.randn(33, BL, KH * 2 * DH), jnp.float32)}
    q = jnp.asarray(rng.randn(1, 6, H, DH), jnp.float32)
    tbl = jnp.asarray(rng.permutation(32)[None, :16] + 1, jnp.int32)
    for last in (5, 20, 40, 63):
        q_pos = jnp.arange(last - 5, last + 1)[None]
        whole = da.pool_context_attend(q, pool, tbl, q_pos, window)
        monkeypatch.setattr(da, "_SCORES_AT_ONCE", 0)
        da.pool_context_attend.clear_cache()
        by_head = da.pool_context_attend(q, pool, tbl, q_pos, window)
        monkeypatch.undo()
        da.pool_context_attend.clear_cache()
        assert np.abs(np.asarray(whole) - np.asarray(by_head)).max() < 2e-6
    assert da._attend_at(16, window, q, pool).func is da._attend_width
    big = jnp.zeros((1, 256, 128, 128))
    assert da._attend_at(
        112, 0, big, {"kv": jnp.zeros((2, 128, 2048))}).func \
        is da._attend_by_head

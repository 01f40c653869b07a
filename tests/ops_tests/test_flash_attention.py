"""Flash-attention kernel tests (interpret mode on CPU): forward and all
three gradients must match the XLA softmax-attention oracle."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import flash_attention, reference_attention

pytestmark = pytest.mark.slow  # full-CI tier: long-pole battery (see tests/test_repo_health.py marker hygiene)


def _oracle(q, k, v, causal):
    # Thin alias of the shared fp32 oracle (single source of truth for every
    # flash test/benchmark; see chainermn_tpu.ops.reference_attention).
    return reference_attention(q, k, v, causal)


def _qkv(rng, B=2, T=128, H=2, D=32):
    return tuple(
        (rng.normal(size=(B, T, H, D)) * 0.6).astype(np.float32)
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(32, 32), (64, 32), (128, 128)])
def test_flash_forward_matches_oracle(causal, blocks):
    bq, bk = blocks
    q, k, v = _qkv(np.random.RandomState(0))
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = _oracle(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_oracle(causal):
    q, k, v = _qkv(np.random.RandomState(1), B=1, T=64, H=2, D=16)
    probe = jnp.asarray(
        np.random.RandomState(2).normal(size=q.shape).astype(np.float32)
    )

    def loss_flash(qkv):
        out = flash_attention(*qkv, causal=causal, block_q=32, block_k=32)
        return jnp.sum(out * probe)

    def loss_oracle(qkv):
        return jnp.sum(_oracle(*qkv, causal) * probe)

    g = jax.grad(loss_flash)((q, k, v))
    og = jax.grad(loss_oracle)((q, k, v))
    for name, a, b in zip("qkv", g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


def test_flash_bf16_forward_close():
    q, k, v = _qkv(np.random.RandomState(3), T=64, D=64)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = _oracle(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(32, 32), (64, 32)])
def test_flash_segments_match_oracle(causal, blocks):
    """Packed sequences: attention must stay within segment boundaries,
    forward AND gradients (the masked pairs' grads are exactly zero)."""
    bq, bk = blocks
    rng = np.random.RandomState(6)
    q, k, v = _qkv(rng, B=2, T=128, H=2, D=32)
    # Three packed documents per row + a padding tail with its own id.
    seg = np.zeros((2, 128), np.int32)
    seg[:, 40:90] = 1
    seg[:, 90:112] = 2
    seg[:, 112:] = 3
    seg[1, 30:] += 1  # different packing per row
    seg = jnp.asarray(seg)

    out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                          block_q=bq, block_k=bk)
    ref = reference_attention(q, k, v, causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)

    probe = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    g = jax.grad(lambda qkv: jnp.sum(flash_attention(
        *qkv, causal=causal, segment_ids=seg, block_q=bq, block_k=bk
    ) * probe))((q, k, v))
    og = jax.grad(lambda qkv: jnp.sum(reference_attention(
        *qkv, causal, segment_ids=seg
    ) * probe))((q, k, v))
    for name, a, b in zip("qkv", g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


def test_flash_segments_isolate_documents():
    """A document's output must be identical whether the other documents
    share its buffer or not — the packed computation leaks nothing."""
    rng = np.random.RandomState(7)
    q, k, v = _qkv(rng, B=1, T=64, H=2, D=16)
    seg = jnp.asarray(
        np.concatenate([np.zeros(32, np.int32), np.ones(32, np.int32)])
    )[None]
    packed = flash_attention(q, k, v, causal=True, segment_ids=seg,
                             block_q=32, block_k=32)
    alone = flash_attention(q[:, :32], k[:, :32], v[:, :32], causal=True,
                            block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(packed[:, :32]),
                               np.asarray(alone), atol=2e-5, rtol=1e-4)


def test_flash_cross_attention_matches_oracle():
    """kv length != q length (encoder-decoder shape), fwd + grads."""
    rng = np.random.RandomState(9)
    B, Tq, S, H, D = 2, 64, 96, 2, 16
    q = jnp.asarray((rng.normal(size=(B, Tq, H, D)) * 0.6).astype(np.float32))
    k = jnp.asarray((rng.normal(size=(B, S, H, D)) * 0.6).astype(np.float32))
    v = jnp.asarray((rng.normal(size=(B, S, H, D)) * 0.6).astype(np.float32))

    out = flash_attention(q, k, v, block_q=32, block_k=32)
    ref = reference_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)

    probe = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    g = jax.grad(lambda qkv: jnp.sum(flash_attention(
        *qkv, block_q=32, block_k=32) * probe))((q, k, v))
    og = jax.grad(lambda qkv: jnp.sum(
        reference_attention(*qkv, False) * probe))((q, k, v))
    for name, a, b in zip("qkv", g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )

    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32)


def test_flash_kv_padding_mask():
    """kv_segment_ids as a key-padding mask: padded keys (id 1) must be
    invisible — output equals attention over only the real keys."""
    rng = np.random.RandomState(10)
    B, Tq, S, H, D = 1, 32, 64, 2, 16
    q = jnp.asarray(rng.normal(size=(B, Tq, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    real = 40
    kv_seg = jnp.asarray(
        np.concatenate([np.zeros(real, np.int32),
                        np.ones(S - real, np.int32)])
    )[None]

    out = flash_attention(q, k, v, kv_segment_ids=kv_seg, block_q=32,
                          block_k=32)
    # Oracle: attention over the unpadded prefix only.
    ref = reference_attention(q, k[:, :real], v[:, :real], False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)

    # Backward with DISTINCT q/kv segments (a seg_q/seg_kv swap in the
    # backward kernels' arg/spec wiring would be invisible to symmetric
    # tests): grads must match the oracle and be exactly zero on pad keys.
    probe = jnp.asarray(
        np.random.RandomState(11).normal(size=q.shape).astype(np.float32)
    )
    g = jax.grad(lambda qkv: jnp.sum(flash_attention(
        *qkv, kv_segment_ids=kv_seg, block_q=32, block_k=32
    ) * probe))((q, k, v))
    og = jax.grad(lambda qkv: jnp.sum(reference_attention(
        qkv[0], qkv[1][:, :real], qkv[2][:, :real], False
    ) * probe))((q, k, v))
    for name, a, b in zip("qkv", g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )
    assert np.all(np.asarray(g[1])[:, real:] == 0.0)  # pad-key dk
    assert np.all(np.asarray(g[2])[:, real:] == 0.0)  # pad-key dv


def test_flash_segments_shape_validation():
    q, k, v = _qkv(np.random.RandomState(8), B=2, T=64)
    with pytest.raises(ValueError, match="segment_ids"):
        flash_attention(q, k, v, segment_ids=jnp.zeros((2, 32), jnp.int32),
                        block_q=32, block_k=32)


def test_flash_rejects_ragged_seq():
    q, k, v = _qkv(np.random.RandomState(4), T=100)
    with pytest.raises(ValueError, match="multiples? of block"):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_flash_inside_ulysses(devices):
    """The kernel drops into the Ulysses all-to-all wrapper as the local
    attention, sequence-sharded over 8 devices."""
    import chainermn_tpu as cmn
    from chainermn_tpu.parallel import ulysses_attention
    from jax.sharding import PartitionSpec as P

    comm = cmn.XlaCommunicator(cmn.hybrid_mesh({"seq": 8}, devices=devices))
    q, k, v = _qkv(np.random.RandomState(5), B=1, T=128, H=8, D=16)

    def attn_fn(q, k, v, causal):
        return flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)

    f = jax.jit(
        comm.spmd(
            lambda q, k, v: ulysses_attention(
                q, k, v, comm.axis_name, causal=True, attn_fn=attn_fn
            ),
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
            check_vma=False,
        )
    )
    out = np.asarray(f(q, k, v))
    ref = np.asarray(_oracle(q, k, v, True))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_flash_fully_masked_rows_zero():
    """A query row whose segment matches no kv id (e.g. a pad query, or
    cross-attention against an all-pad source row) must yield EXACT zeros,
    lse = "no mass", and zero gradients for that row — not a uniform average
    of V (the finite-NEG_INF rescue failure mode)."""
    from chainermn_tpu.ops.flash_attention import (
        NEG_INF, flash_attention_lse, _reference_attention_lse,
    )

    rng = np.random.RandomState(3)
    B, T, H, D = 2, 64, 2, 16
    q, k, v = _qkv(rng, B=B, T=T, H=H, D=D)
    # Row 0 of the batch: queries in the back half get segment id 7, which
    # appears nowhere in the kv segments -> those rows are fully masked.
    seg_q = np.zeros((B, T), np.int32)
    seg_q[0, T // 2:] = 7
    seg_kv = np.zeros((B, T), np.int32)

    out, lse = flash_attention_lse(
        q, k, v, segment_ids=jnp.asarray(seg_q),
        kv_segment_ids=jnp.asarray(seg_kv), block_q=32, block_k=32,
    )
    dead = np.asarray(out)[0, T // 2:]
    np.testing.assert_array_equal(dead, np.zeros_like(dead))
    assert np.all(np.asarray(lse)[0, :, T // 2:] <= NEG_INF * 0.5)
    # Live rows still match the oracle.
    ref, ref_lse = _reference_attention_lse(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        False, jnp.asarray(seg_q), jnp.asarray(seg_kv),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(lse)[0, :, : T // 2], np.asarray(ref_lse)[0, :, : T // 2],
        atol=2e-5, rtol=1e-4,
    )

    # Gradients: dead q rows get zero grad; dK/dV receive nothing from them.
    probe = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    def loss(qkv, fn):
        o = fn(
            *qkv, segment_ids=jnp.asarray(seg_q),
            kv_segment_ids=jnp.asarray(seg_kv),
        )
        o = o[0] if isinstance(o, tuple) else o
        return jnp.sum(o * probe)

    def flash_fn(q, k, v, **kw):
        return flash_attention_lse(q, k, v, block_q=32, block_k=32, **kw)

    def oracle_fn(q, k, v, *, segment_ids, kv_segment_ids):
        return _reference_attention_lse(
            q, k, v, False, segment_ids, kv_segment_ids
        )

    g = jax.grad(loss)((jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)),
                       flash_fn)
    og = jax.grad(loss)((jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)),
                        oracle_fn)
    dq_dead = np.asarray(g[0])[0, T // 2:]
    np.testing.assert_array_equal(dq_dead, np.zeros_like(dq_dead))
    for name, a, b in zip("qkv", g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


# ------------------------------------------------------------------ GQA/MQA
@pytest.mark.parametrize("kv_heads", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_forward_matches_oracle(kv_heads, causal):
    """Grouped-query attention (kv heads < q heads, inferred from shapes):
    kernel streams shared kv blocks via its index maps; the oracle expands
    kv by repeat.  kv_heads=1 is multi-query attention."""
    rng = np.random.RandomState(7)
    B, T, H, D = 2, 128, 4, 32
    q = (rng.normal(size=(B, T, H, D)) * 0.6).astype(np.float32)
    k = (rng.normal(size=(B, T, kv_heads, D)) * 0.6).astype(np.float32)
    v = (rng.normal(size=(B, T, kv_heads, D)) * 0.6).astype(np.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_gradients_match_oracle(causal):
    """dK/dV must group-sum over the query heads sharing each kv head."""
    rng = np.random.RandomState(8)
    B, T, H, KH, D = 1, 64, 4, 2, 16
    q = (rng.normal(size=(B, T, H, D)) * 0.6).astype(np.float32)
    k = (rng.normal(size=(B, T, KH, D)) * 0.6).astype(np.float32)
    v = (rng.normal(size=(B, T, KH, D)) * 0.6).astype(np.float32)
    probe = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))

    def loss(qkv, fn):
        return jnp.sum(fn(*qkv, causal=causal) * probe)

    def flash_fn(q, k, v, *, causal):
        return flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)

    g = jax.grad(loss)((jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)),
                       flash_fn)
    og = jax.grad(loss)((jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)),
                        reference_attention)
    assert g[1].shape == (B, T, KH, D) and g[2].shape == (B, T, KH, D)
    for name, a, b in zip("qkv", g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


#: mask kind -> (kv length, flash/oracle keyword arguments).  B = 2 batch
#: rows of T = 64 queries in blocks of 16 / 32: every kind runs the dK/dV
#: kernel's q loop over several trips and its kv grid over several blocks.
def _group_case(kind):
    T = 64
    if kind == "cross":  # S != T
        return 96, {}
    if kind == "segmented":  # q and kv ids DIFFER: a swap would show
        seg = np.repeat(np.array([[0, 1, 2, 2], [0, 0, 1, 7]], np.int32),
                        T // 4, axis=1)
        kv_seg = np.where(seg == 7, 8, seg).astype(np.int32)
        return T, {"causal": True, "segment_ids": jnp.asarray(seg),
                   "kv_segment_ids": jnp.asarray(kv_seg)}
    return T, {"causal": {"causal": True, "window": None},
               "full": {"causal": False, "window": None},
               "window": {"causal": True, "window": 24}}[kind]


@pytest.mark.parametrize("with_dlse", [False, True], ids=["o", "o+lse"])
@pytest.mark.parametrize("kind",
                         ["causal", "full", "window", "segmented", "cross"])
@pytest.mark.parametrize("group", [1, 2, 12])
def test_flash_group_gradients_match_oracle(group, kind, with_dlse):
    """The dK/dV kernel owns a KV head's rows for its whole query group
    (the group an inner grid axis adding into one resident fp32 block):
    dQ / dK / dV equal the fp32 oracle's for a group of 1 (the same body,
    an axis of length one), 2 and 12 under every mask, with and without a
    cotangent on the logsumexp (``dlse`` folds into ``delta``)."""
    from chainermn_tpu.ops.flash_attention import (
        _reference_attention_lse, flash_attention_lse,
    )

    S, kw = _group_case(kind)
    rng = np.random.RandomState(31)
    B, T, H, D = 2, 64, 12, 16
    KH = H // group
    q = jnp.asarray((rng.normal(size=(B, T, H, D)) * 0.6).astype(np.float32))
    k = jnp.asarray((rng.normal(size=(B, S, KH, D)) * 0.6).astype(np.float32))
    v = jnp.asarray((rng.normal(size=(B, S, KH, D)) * 0.6).astype(np.float32))
    probe = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    lse_probe = jnp.asarray(rng.normal(size=(B, H, T)).astype(np.float32))

    def loss(qkv, fn):
        o, lse = fn(*qkv)
        out = jnp.sum(o * probe)
        if with_dlse:
            # A fully masked row's lse is the NEG_INF sentinel in both.
            out = out + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) * lse_probe)
        return out

    def flash_fn(q, k, v):
        return flash_attention_lse(q, k, v, block_q=16, block_k=32, **kw)

    def oracle_fn(q, k, v):
        return _reference_attention_lse(
            q, k, v, kw.get("causal", False), kw.get("segment_ids"),
            kw.get("kv_segment_ids"), kw.get("window"))

    g = jax.grad(loss)((q, k, v), flash_fn)
    og = jax.grad(loss)((q, k, v), oracle_fn)
    assert g[1].shape == (B, S, KH, D) and g[2].shape == (B, S, KH, D)
    for name, a, b in zip("qkv", g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


def test_flash_gradients_at_the_training_cell_head_shape():
    """StarCoder2's heads — 24 query / 2 KV of 128, bf16 — at a short T, at
    the default blocks: the gradients the training cell's step takes, against
    the fp32 oracle on the same bf16 inputs (bf16 tolerance: the kernel
    rounds dQ/dK/dV once, from fp32 sums)."""
    rng = np.random.RandomState(32)
    B, T, H, KH, D = 1, 512, 24, 2, 128
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, T, h, D)) * 0.5, jnp.bfloat16)
        for h in (H, KH, KH)
    )
    probe = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)

    def loss(qkv, fn):
        return jnp.sum(fn(*qkv, causal=True).astype(jnp.float32) * probe)

    def oracle(q, k, v, causal):
        return reference_attention(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=causal)

    g = jax.grad(loss)((q, k, v), flash_attention)
    og = jax.grad(loss)((q, k, v), oracle)
    assert g[1].dtype == jnp.bfloat16 and g[1].shape == (B, T, KH, D)
    for name, a, b in zip("qkv", g, og):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 1e-2 * np.abs(b).max(), name


def test_flash_gqa_segments_match_oracle():
    """GQA composes with packed-segment masking (shared (B, T) segment rows
    are head-count independent)."""
    rng = np.random.RandomState(9)
    B, T, H, KH, D = 2, 96, 4, 2, 16
    q = (rng.normal(size=(B, T, H, D)) * 0.6).astype(np.float32)
    k = (rng.normal(size=(B, T, KH, D)) * 0.6).astype(np.float32)
    v = (rng.normal(size=(B, T, KH, D)) * 0.6).astype(np.float32)
    seg = np.repeat(np.arange(3)[None], B, 0).repeat(T // 3, 1).astype(np.int32)
    out = flash_attention(q, k, v, causal=True, segment_ids=jnp.asarray(seg),
                          block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=True,
                              segment_ids=jnp.asarray(seg))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4
    )


def test_flash_gqa_head_count_validated():
    rng = np.random.RandomState(10)
    q = rng.normal(size=(1, 32, 4, 16)).astype(np.float32)
    kv = rng.normal(size=(1, 32, 3, 16)).astype(np.float32)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, kv, kv, block_q=32, block_k=32)


# ------------------------------------------------------------ sliding window
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("window", [1, 16, 100, 1000])
def test_flash_window_matches_oracle(causal, window):
    """Sliding-window (local) attention: |q - k| < window, block-skipping
    loop bounds in all three kernels.  window >= T degenerates to full."""
    q, k, v = _qkv(np.random.RandomState(11), T=128)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_window_gradients_match_oracle(causal):
    q, k, v = _qkv(np.random.RandomState(12), B=1, T=96, H=2, D=16)
    probe = jnp.asarray(
        np.random.RandomState(13).normal(size=q.shape).astype(np.float32)
    )

    def loss(qkv, fn):
        return jnp.sum(fn(*qkv) * probe)

    g = jax.grad(loss)(
        (q, k, v),
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=24, block_q=32, block_k=32),
    )
    og = jax.grad(loss)(
        (q, k, v),
        lambda q, k, v: reference_attention(q, k, v, causal=causal,
                                            window=24),
    )
    for name, a, b in zip("qkv", g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


def test_flash_window_composes_with_gqa_and_segments():
    rng = np.random.RandomState(14)
    B, T, H, KH, D = 2, 96, 4, 2, 16
    q = (rng.normal(size=(B, T, H, D)) * 0.6).astype(np.float32)
    k = (rng.normal(size=(B, T, KH, D)) * 0.6).astype(np.float32)
    v = (rng.normal(size=(B, T, KH, D)) * 0.6).astype(np.float32)
    seg = np.repeat(np.arange(3)[None], B, 0).repeat(T // 3, 1).astype(np.int32)
    out = flash_attention(q, k, v, causal=True, window=20,
                          segment_ids=jnp.asarray(seg),
                          block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=True, window=20,
                              segment_ids=jnp.asarray(seg))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4
    )


def test_flash_window_validation():
    q, k, v = _qkv(np.random.RandomState(15), T=64)
    with pytest.raises(ValueError, match="window must be >= 1"):
        flash_attention(q, k, v, window=0)
    with pytest.raises(ValueError, match="equal q/kv lengths"):
        flash_attention(q, k[:, :32], v[:, :32], window=8)


def test_default_block_respects_mosaic_sublane_rule():
    """The chooser must only emit blocks Mosaic accepts: a multiple of 8, or
    the full dimension (the real chip rejected block 4 for the ViT token
    grid T=196 — a (1, 4, 64) block violates the (8, 128) tiling rule)."""
    from chainermn_tpu.ops.flash_attention import _default_block

    assert _default_block(2048, 256) == 256
    assert _default_block(2048, 512) == 512
    assert _default_block(1000, 512) == 200    # largest 8k | 1000, not pow2
    assert _default_block(4104, 512) == 456    # 8*513: non-pow2 divisor
    assert _default_block(196, 256) == 196     # 196 = 4*49: full-dim block
    assert _default_block(196, 512) == 196
    assert _default_block(7, 256) == 7         # tiny odd: full-dim
    for length in (196, 1000, 7, 2048, 640):
        b = _default_block(length, 256)
        assert length % b == 0
        assert b % 8 == 0 or b == length
    # Long lengths with no multiple-of-8 divisor must error (a full-dim
    # block would blow VMEM), pointing at upstream padding.
    with pytest.raises(ValueError, match="pad the sequence"):
        _default_block(4100, 512)


def test_flash_vit_geometry_matches_oracle():
    """ViT-S/16 geometry (T=196 tokens, D=64) through the kernel with
    DEFAULT blocks — the config the chip rejected before the chooser fix;
    interpret mode checks numerics, test_flash_tpu.py compiles it."""
    rng = np.random.RandomState(5)
    q, k, v = _qkv(rng, B=2, T=196, H=3, D=64)
    out = flash_attention(q, k, v, causal=False)
    ref = _oracle(q, k, v, False)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-5
    )

    def loss(args):
        return jnp.sum(flash_attention(*args, causal=False) ** 2)

    def loss_ref(args):
        return jnp.sum(_oracle(*args, False) ** 2)

    g = jax.grad(loss)((q, k, v))
    og = jax.grad(loss_ref)((q, k, v))
    for a, b in zip(g, og):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3
        )

"""VMEM-chunked flash attention: long sequences whose full-row staged refs
exceed the kernel VMEM budget are split into offset chunks and merged
through their logsumexps (``_stage_chunk`` / ``_merge_partials``).

The real chip rejected the unchunked kernel at T=16384, D=128 (16.25 MB
scoped VMEM > 16 MB).  These tests force tiny stage budgets via the
``max_stage_rows`` hook so the chunked path (static position offsets in
masks and block-skip ranges, fp32 partial accumulation in the backward)
runs in interpret mode and must match both the XLA oracle and the
unchunked kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import flash_attention, reference_attention
from chainermn_tpu.ops.flash_attention import (
    NEG_INF,
    _RESIDENT_BUDGET_BYTES,
    _merge_partials,
    _row_bytes,
    _stage_chunk,
    flash_attention_lse,
)

pytestmark = pytest.mark.slow  # full-CI tier: long-pole battery (see tests/test_repo_health.py marker hygiene)


def _inputs(B=2, T=256, H=2, D=32, S=None, KH=None, seed=0):
    rng = np.random.RandomState(seed)
    S = T if S is None else S
    KH = H if KH is None else KH
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KH, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KH, D), jnp.float32)
    return q, k, v


def _grads(fn, *args):
    def loss(*a):
        return (fn(*a).astype(jnp.float32) ** 2).mean()

    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("stage_rows", [64, 128])
def test_chunked_matches_reference(causal, stage_rows):
    q, k, v = _inputs()
    want = reference_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True, max_stage_rows=stage_rows)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    gw = _grads(lambda *a: reference_attention(*a, causal=causal), q, k, v)
    gg = _grads(
        lambda *a: flash_attention(*a, causal=causal, block_q=32,
                                   block_k=32, interpret=True,
                                   max_stage_rows=stage_rows),
        q, k, v,
    )
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_chunked_matches_unchunked_exact_lse():
    q, k, v = _inputs(T=128)
    full_o, full_lse = flash_attention_lse(q, k, v, causal=True, block_q=32,
                                           block_k=32, interpret=True)
    ch_o, ch_lse = flash_attention_lse(q, k, v, causal=True, block_q=32,
                                       block_k=32, interpret=True,
                                       max_stage_rows=32)
    np.testing.assert_allclose(ch_o, full_o, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(ch_lse, full_lse, atol=2e-6, rtol=2e-6)


def test_chunked_window():
    q, k, v = _inputs(T=256)
    want = reference_attention(q, k, v, causal=True, window=48)
    got = flash_attention(q, k, v, causal=True, window=48, block_q=16,
                          block_k=16, interpret=True, max_stage_rows=64)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # Backward too: the window branches of the q_off/kv_off block-range
    # arithmetic only run here.
    gw = _grads(lambda *a: reference_attention(*a, causal=True, window=48),
                q, k, v)
    gg = _grads(
        lambda *a: flash_attention(*a, causal=True, window=48, block_q=16,
                                   block_k=16, interpret=True,
                                   max_stage_rows=64),
        q, k, v,
    )
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_chunked_segments_and_padding():
    # Two packed documents + a pad tail given its own segment id; the pad
    # queries are fully masked rows (every kv id differs) and must come out
    # exactly zero through the chunked merge too.
    q, k, v = _inputs(B=1, T=128)
    seg = jnp.concatenate([
        jnp.zeros((1, 48), jnp.int32),
        jnp.ones((1, 48), jnp.int32),
        jnp.full((1, 32), 7, jnp.int32),
    ], axis=1)
    kv_seg = seg.at[:, 96:].set(8)  # pad keys match no query segment
    want = reference_attention(q, k, v, causal=True, segment_ids=seg,
                               kv_segment_ids=kv_seg)
    got = flash_attention(q, k, v, causal=True, segment_ids=seg,
                          kv_segment_ids=kv_seg, block_q=16, block_k=16,
                          interpret=True, max_stage_rows=32)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(got)[:, 96:] == 0.0)
    gw = _grads(
        lambda *a: reference_attention(*a, causal=True, segment_ids=seg,
                                       kv_segment_ids=kv_seg), q, k, v)
    gg = _grads(
        lambda *a: flash_attention(*a, causal=True, segment_ids=seg,
                                   kv_segment_ids=kv_seg, block_q=16,
                                   block_k=16, interpret=True,
                                   max_stage_rows=32), q, k, v)
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("H, KH", [(4, 2), (12, 1)],
                         ids=["group2", "group12"])
def test_chunked_gqa_cross_attention(H, KH):
    # Grouped-query + cross-attention (q len ≠ kv len) through the chunked
    # path: the kv-row index map and the dK/dV summed over the group inside
    # the kernel must both survive chunk offsets — the q rows in two chunks
    # of 32 (fp32 partials added outside) and the resident dK/dV rows in
    # four kv chunks of 48 (a grid axis).
    q, k, v = _inputs(B=2, T=64, S=192, H=H, KH=KH)
    want = reference_attention(q, k, v)
    got = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True,
                          max_stage_rows=48)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    gw = _grads(reference_attention, q, k, v)
    gg = _grads(
        lambda *a: flash_attention(*a, block_q=16, block_k=16,
                                   interpret=True, max_stage_rows=48),
        q, k, v,
    )
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_stage_chunk_arithmetic():
    kv128 = _row_bytes(128, 2)  # k+v staging, D=128 bf16
    # Fits → full length (chunk-free fast path), regardless of divisors.
    assert _stage_chunk(2048, kv128, 512, None) == 2048
    # 16384·128·bf16 busts the 8 MB budget → 8192-row chunks (the config
    # the real chip rejected unchunked).
    assert _stage_chunk(16384, kv128, 512, None) == 8192
    # Narrow heads double the row budget.
    assert _stage_chunk(16384, _row_bytes(64, 2), 512, None) == 16384
    # The dK/dV kernel's lse+delta rows are lane-dense (a q block a sublane
    # row, counted at eight sublanes a block): 64 bytes a double-buffered
    # row each, not 1024 — the cell's T=4096 stages whole (it took two
    # chunks of 2048 when the rows were lane-padded), T=16384 in quarters.
    qdo128 = _row_bytes(128, 2, n_dense=2, block=256)
    assert qdo128 == 1024 + 128
    assert _stage_chunk(4096, qdo128, 256, None) == 4096
    assert _stage_chunk(16384, qdo128, 256, None) == 4096
    # A segmented call stages the query rows' ids the same way; a block
    # that is no multiple of 128 pays for the lanes it leaves empty.
    assert _row_bytes(128, 2, n_dense=3, block=256) == 1024 + 192
    assert _row_bytes(128, 2, n_dense=2, block=200) == 1024 + 164
    # The resident fp32 dK + dV rows: 4096 a kv chunk at D=128.
    assert _stage_chunk(16384, 2 * 128 * 4, 512, None,
                        budget=_RESIDENT_BUDGET_BYTES) == 4096
    # Explicit cap wins; result stays a block-multiple divisor.
    assert _stage_chunk(256, _row_bytes(32, 4), 32, 96) == 64
    with pytest.raises(ValueError, match="stage budget"):
        _stage_chunk(7 * 97, _row_bytes(32, 4), 8, 97)


def test_merge_partials_dead_rows():
    # Rows dead in BOTH partials stay zero with lse = NEG_INF; rows alive
    # in one partial pass through exactly.
    o1 = jnp.asarray([[1.0, 2.0], [0.0, 0.0]], jnp.float32)[None]
    o2 = jnp.zeros((1, 2, 2), jnp.float32)
    lse1 = jnp.asarray([[0.5, NEG_INF]], jnp.float32)
    lse2 = jnp.full((1, 2), NEG_INF, jnp.float32)
    o, lse = _merge_partials(o1, lse1, o2, lse2)
    np.testing.assert_allclose(o[0, 0], [1.0, 2.0], atol=1e-6)
    np.testing.assert_allclose(o[0, 1], [0.0, 0.0])
    assert lse[0, 0] == pytest.approx(0.5, abs=1e-6)
    assert lse[0, 1] <= NEG_INF * 0.5

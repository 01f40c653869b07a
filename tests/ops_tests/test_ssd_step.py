"""The state-space recurrence carried across calls (``ops/ssd_scan.py``): one
position a row (``ssd_step``) against the chunked scan position for position,
the scan chunk to chunk through ``initial_state=`` / ``return_state=``, rows
given ``dt = 0`` leaving the state to the bit, and the convolution's tail
across a chunk boundary."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.ssd_scan import (
    causal_depthwise_conv,
    conv_step,
    conv_tail,
    ssd_scan,
    ssd_step,
)

pytestmark = pytest.mark.tier1

B, T, H, P, G, N, Q = 3, 32, 4, 8, 2, 16, 8


def _inputs(seed=0, t=T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (B, t, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, t, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, t, G, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, t, G, N), jnp.float32)
    D = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    s0 = jax.random.normal(ks[6], (B, H, P, N), jnp.float32)
    return x, dt, A, Bm, Cm, D, s0


def test_step_equals_the_scan_position_for_position():
    x, dt, A, Bm, Cm, D, s0 = _inputs()
    want, last = ssd_scan(x, dt, A, Bm, Cm, chunk=Q, D=D, initial_state=s0,
                          return_state=True)
    s, ys = s0, []
    for t in range(T):
        y, s = ssd_step(s, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s, last, rtol=2e-5, atol=2e-5)
    assert ys[0].dtype == jnp.float32 and s.dtype == jnp.float32


@pytest.mark.parametrize("cut", [8, 16, 24])
def test_two_chunks_through_initial_state_equal_one_whole_scan(cut):
    x, dt, A, Bm, Cm, D, s0 = _inputs(1)
    want, last = ssd_scan(x, dt, A, Bm, Cm, chunk=Q, D=D, initial_state=s0,
                          return_state=True)
    y1, s1 = ssd_scan(x[:, :cut], dt[:, :cut], A, Bm[:, :cut], Cm[:, :cut],
                      chunk=Q, D=D, initial_state=s0, return_state=True)
    y2, s2 = ssd_scan(x[:, cut:], dt[:, cut:], A, Bm[:, cut:], Cm[:, cut:],
                      chunk=Q, D=D, initial_state=s1, return_state=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), want,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s2, last, rtol=2e-5, atol=2e-5)


def test_rows_with_dt_zero_leave_the_state_to_the_bit():
    x, dt, A, Bm, Cm, D, s0 = _inputs(2)
    # the step: rows 0 and 2 are passed over
    live = jnp.asarray([False, True, False])
    _, s = ssd_step(s0, x[:, 0], jnp.where(live[:, None], dt[:, 0], 0.0), A,
                    Bm[:, 0], Cm[:, 0], D)
    assert np.array_equal(np.asarray(s[0]), np.asarray(s0[0]))
    assert np.array_equal(np.asarray(s[2]), np.asarray(s0[2]))
    assert not np.array_equal(np.asarray(s[1]), np.asarray(s0[1]))
    # the scan: a chunk of which no row holds text hands the state back
    _, s = ssd_scan(x[:, :Q], jnp.zeros_like(dt[:, :Q]), A, Bm[:, :Q],
                    Cm[:, :Q], chunk=Q, D=D, initial_state=s0,
                    return_state=True)
    assert np.array_equal(np.asarray(s), np.asarray(s0))


@pytest.mark.parametrize("n", [1, 3, 7])
def test_a_short_tail_ends_where_its_text_ends(n):
    """``n`` real rows of a chunk of ``Q``, the rest given ``dt = 0``: the
    state is the scan's after ``n`` positions, the first ``n`` outputs are
    the scan's."""
    x, dt, A, Bm, Cm, D, s0 = _inputs(3, t=Q)
    masked = jnp.where((jnp.arange(Q) < n)[None, :, None], dt, 0.0)
    y, s = ssd_scan(x, masked, A, Bm, Cm, chunk=Q, D=D, initial_state=s0,
                    return_state=True)
    want_s, want_y = s0, []
    for t in range(n):
        yt, want_s = ssd_step(want_s, x[:, t], dt[:, t], A, Bm[:, t],
                              Cm[:, t], D)
        want_y.append(yt)
    np.testing.assert_allclose(s, want_s, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y[:, :n], jnp.stack(want_y, 1), rtol=2e-5,
                               atol=2e-5)


def test_the_convolutions_tail_across_a_chunk_boundary():
    K, C = 4, 10
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (B, T, C), jnp.float32)
    kernel = jax.random.normal(ks[1], (K, C), jnp.float32)
    bias = jax.random.normal(ks[2], (C,), jnp.float32)
    want = causal_depthwise_conv(x, kernel, bias)
    zeros = jnp.zeros((B, K - 1, C), jnp.float32)
    # chunks of 8: each starts from the tail the one before left
    tail, outs = zeros, []
    for a in range(0, T, 8):
        chunk = x[:, a:a + 8]
        outs.append(causal_depthwise_conv(chunk, kernel, bias, tail=tail))
        tail = conv_tail(chunk, tail, 8)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, rtol=1e-6,
                               atol=1e-6)
    # a chunk dropped between two others shows (the tail is what is carried)
    dropped = causal_depthwise_conv(x[:, 8:16], kernel, bias, tail=zeros)
    assert float(jnp.max(jnp.abs(dropped[:, :K - 1] - want[:, 8:8 + K - 1]))) > 0.1
    # a short tail: 5 real rows of 8, then the rest of the text
    t5 = conv_tail(x[:, :8], zeros, 5)
    np.testing.assert_array_equal(t5, x[:, 2:5])
    np.testing.assert_array_equal(conv_tail(x[:, :8], zeros, 0), zeros)
    rest = causal_depthwise_conv(x[:, 5:13], kernel, bias, tail=t5)
    np.testing.assert_allclose(rest, want[:, 5:13], rtol=1e-6, atol=1e-6)
    # one position a row
    tail, outs = zeros, []
    for t in range(T):
        out, tail = conv_step(tail, x[:, t], kernel, bias)
        outs.append(out)
    np.testing.assert_allclose(jnp.stack(outs, 1), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(tail, x[:, T - (K - 1):])

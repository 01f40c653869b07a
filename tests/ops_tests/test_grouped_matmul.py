"""``ops/grouped_matmul.py``: the kernels (Pallas's interpreter here) against
plain ``jax.numpy`` — a group at a time, and the XLA twin that stands in for
them under a ``shard_map`` off the TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import grouped_matmul as gm

pytestmark = pytest.mark.tier1


def _case(sizes, tm, n_tiles, K=256, N=128, seed=0):
    rng = np.random.RandomState(seed)
    sizes = jnp.asarray(sizes, jnp.int32)
    group, active, first = gm.aligned_groups(sizes, tm, n_tiles)
    x = np.zeros((tm * n_tiles, K), np.float32)
    for g, (lo, n) in enumerate(zip(np.asarray(first), np.asarray(sizes))):
        x[lo:lo + n] = rng.randn(n, K)
    w = jnp.asarray(rng.randn(len(sizes), K, N) * 0.1, jnp.float32)
    return jnp.asarray(x), w, sizes, group, active, first


def _dense(x, w, sizes, first):
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for g, (lo, n) in enumerate(zip(np.asarray(first), np.asarray(sizes))):
        out = out.at[lo:lo + n].set(
            jnp.matmul(x[lo:lo + n], w[g], precision="highest"))
    return out


def test_groups_start_on_tiles_and_an_empty_group_keeps_one():
    group, active, first = gm.aligned_groups(
        jnp.asarray([13, 0, 30, 7], jnp.int32), 8, 12)
    assert group.tolist() == [0, 0, 1, 2, 2, 2, 2, 3, 3, 3, 3, 3]
    assert active.tolist() == [8] and first.tolist() == [0, 16, 24, 56]


@pytest.mark.parametrize("sizes,tm,n_tiles", [
    ([13, 0, 30, 7], 8, 12), ([40, 1, 1, 1], 16, 8), ([0, 0, 0, 64], 8, 11),
    ([5], 8, 2)])
def test_forward_is_each_groups_own_product(sizes, tm, n_tiles):
    x, w, sizes, group, active, first = _case(sizes, tm, n_tiles)
    used = int(active[0]) * tm
    with jax.default_matmul_precision("highest"):
        got = gm.grouped_matmul(x, w, group, active, tm)
    np.testing.assert_allclose(got[:used], _dense(x, w, sizes, first)[:used],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sizes,tm,n_tiles", [
    ([13, 0, 30, 7], 8, 12), ([40, 1, 1, 1], 16, 8)])
def test_gradients_are_the_dense_ones(sizes, tm, n_tiles):
    x, w, sizes, group, active, first = _case(sizes, tm, n_tiles, seed=1)
    used = (jnp.arange(x.shape[0]) < int(active[0]) * tm)[:, None]
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda x, w: jnp.sum(jnp.where(used, gm.grouped_matmul(
            x, w, group, active, tm), 0) ** 2), argnums=(0, 1))(x, w)
        want = jax.grad(lambda x, w: jnp.sum(
            _dense(x, w, sizes, first) ** 2), argnums=(0, 1))(x, w)
    rows = int(active[0]) * tm
    np.testing.assert_allclose(got[0][:rows], want[0][:rows], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-3)
    assert float(jnp.max(jnp.abs(got[1][1]))) == 0.0 or sizes[1] > 0


def test_the_xla_twin_computes_what_the_kernels_do():
    x, w, sizes, group, active, first = _case([13, 0, 30, 7], 8, 12, seed=2)
    dy = jnp.asarray(np.random.RandomState(3).randn(x.shape[0], w.shape[2]),
                     jnp.float32)
    dy = jnp.where((jnp.arange(x.shape[0]) < int(active[0]) * 8)[:, None],
                   dy, 0)
    used = int(active[0]) * 8
    with jax.default_matmul_precision("highest"):
        for transposed, a, b in ((False, x, w), (True, dy, w)):
            np.testing.assert_allclose(
                gm._mm(a, b, group, active, 8, transposed)[:used],
                gm._mm_xla(a, b, group, active, 8, transposed)[:used],
                rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            gm._dw(x, dy, group, active, 8, 4),
            gm._dw_xla(x, dy, group, active, 8, 4), rtol=1e-5, atol=1e-4)


def test_rows_must_be_whole_tiles():
    x, w, _, group, active, _ = _case([5], 8, 2)
    with pytest.raises(ValueError, match="multiple of the tile"):
        gm.grouped_matmul(x[:12], w, group, active, 8)

"""Utility-layer tests: honest benchmarking sync, pvary compat, tracing."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.utils import benchmark, pvary, sync, trace


def test_sync_blocks_on_tree():
    x = {"a": jnp.ones((8, 8)), "b": [jnp.zeros((2,))]}
    sync(x)  # must not raise; values materialized


def test_init_compile_cache_env_wins_else_fixed_checkout_path(monkeypatch,
                                                             tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the helper
    names no other directory.  Unset: ``<checkout>/.jax_cache``, a path that
    is the same in every run (the directory is part of the cache key)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from chainermn_tpu.utils import init_compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert init_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(repo, ".jax_cache")
        assert init_compile_cache() == fixed
        assert init_compile_cache() == fixed  # no pid/timestamp in it
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:  # later tests compile with the cache as they found it
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()


def test_benchmark_returns_positive_seconds():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    res = benchmark(f, x, iters=3, warmup=1)
    assert res["mean_s"] > 0
    assert res["min_s"] <= res["mean_s"] <= res["max_s"]


def test_pvary_outside_shard_map_is_identity():
    x = jnp.arange(4.0)
    y = pvary(x, ())  # no axes: trivially fine everywhere
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_pvary_inside_checked_shard_map(devices):
    from jax.sharding import PartitionSpec as P
    import chainermn_tpu as cmn

    comm = cmn.create_communicator("xla", devices=devices)

    def body(b):
        z = pvary(jnp.zeros((4,)), comm.axes)  # invariant → varying
        return z + b.sum()

    out = jax.jit(
        comm.spmd(body, in_specs=P(comm.axes), out_specs=P(comm.axes),
                  check_vma=True)
    )(jnp.ones((8, 2)))
    assert out.shape == (32,)  # per-rank (4,) stacked over the 8 ranks


@pytest.mark.slow  # ~25s: profiler spin-up dominates (tier-1 budget)
def test_trace_writes_profile(tmp_path):
    with trace(str(tmp_path)):
        jax.block_until_ready(jnp.ones((16, 16)) @ jnp.ones((16, 16)))
    # jax profiler writes plugins/profile/<run>/*.xplane.pb
    xplanes = []
    for root, dirs, files in os.walk(tmp_path):
        xplanes += [f for f in files if f.endswith(".xplane.pb")]
    assert xplanes, "trace produced no xplane profile artifact"


def test_mfu_from_compiled_step():
    from chainermn_tpu.utils import compiled_flops, mfu

    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((256, 256), jnp.float32)
    compiled = f.lower(x, x).compile()
    flops = compiled_flops(compiled)
    assert flops is not None and flops >= 2 * 256**3 * 0.9  # ~2·n³ matmul
    # Known device kind + fabricated step time → deterministic percentage.
    got = mfu(compiled, step_time_s=flops / 197e12, n_devices=1,
              device_kind="TPU v5 lite")
    assert got is not None and abs(got - 100.0) < 1e-6
    assert mfu(compiled, 1.0, device_kind="made-up-chip") is None


def test_attention_core_flops():
    from chainermn_tpu.utils import attention_core_flops, mfu

    # Two matmuls forward (QK^T, AV) at 2 FLOPs/MAC: 4*B*H*Tq*Tkv*Dh.
    assert attention_core_flops(1, 1, 2, 1, n_backward=0) == 16.0
    # Backward = 2.5x forward (5 matmuls incl. in-kernel score recompute).
    assert attention_core_flops(1, 1, 2, 1) == 16.0 + 40.0
    # Causal halves the attended area; remat re-runs the forward once.
    assert attention_core_flops(1, 1, 2, 1, causal=True) == 28.0
    assert attention_core_flops(1, 1, 2, 1, n_forward=2) == 72.0
    # Cross-attention area is Tq*Tkv.
    assert attention_core_flops(2, 3, 4, 5, kv_len=8, n_backward=0) == (
        4.0 * 2 * 3 * 4 * 8 * 5
    )
    # Consistency with the measured flash-vs-XLA tflops_per_step gap at
    # the seq2seq T=512 geometry (result/seq2seq_tpu_packed.json:
    # 14.043 - 12.110 = 1.933 TF): analytic core count must land within
    # 15% below it (the XLA arm additionally counts softmax/mask work).
    dh = 512 // 8
    analytic = (
        6 * attention_core_flops(64, 8, 512, dh, causal=False)
        + 6 * attention_core_flops(64, 8, 512, dh, causal=True)
        + 6 * attention_core_flops(64, 8, 512, dh, kv_len=512, causal=False)
    )
    gap = (14.043 - 12.110) * 1e12
    assert analytic <= gap * 1.001
    assert analytic >= gap * 0.85

    # mfu(extra_flops=) adds the uncounted work to the numerator.
    import jax as _jax
    import jax.numpy as _jnp

    f = _jax.jit(lambda a, b: a @ b)
    x = _jnp.ones((256, 256), _jnp.float32)
    compiled = f.lower(x, x).compile()
    from chainermn_tpu.utils import compiled_flops

    flops = compiled_flops(compiled)
    base = mfu(compiled, step_time_s=flops / 197e12,
               device_kind="TPU v5 lite")
    incl = mfu(compiled, step_time_s=flops / 197e12,
               device_kind="TPU v5 lite", extra_flops=flops)
    assert abs(base - 100.0) < 1e-6 and abs(incl - 200.0) < 1e-6

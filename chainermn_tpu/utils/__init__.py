"""Profiling, timing, and scaling-measurement utilities.

SURVEY.md §5: the reference's observability was minimal — `DummyCommunicator`
for comm-cost ablation, Chainer's TimerHook, rank-0-gated `LogReport`.  Here:
`jax.profiler` traces (ICI collective timeline in xprof), a benchmark harness
with honest device syncing, and scaling-efficiency accounting against
`BASELINE.md`'s ≥90%-linear target.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a place a caller can
    find again, and return it.  Call before the first compile.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
    set here.  Unset: the fixed path ``<checkout>/.jax_cache`` (the
    directory is part of the cache key, so it must never move between
    runs — no temp name, pid or timestamp)."""
    import os

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def atomic_json_dump(obj: Any, path: str, indent: int = 1) -> None:
    """Publish a JSON artifact atomically (write ``path.tmp``, then rename).

    ``bench.py`` reads every ``result/*.json`` it finds: a SIGTERM or a
    full disk landing mid-write must not leave a truncated file there.
    ``os.replace`` is atomic on POSIX for same-filesystem renames.
    """
    import json
    import os

    tmp = f"{path}.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=indent)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        # Don't strand a partial .tmp on a failed dump (non-serializable
        # obj, disk full).  A SIGKILL can still strand one — .gitignore
        # lists result/*.tmp.
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def pvary(x: Any, axis_name) -> Any:
    """Mark ``x`` device-varying over ``axis_name`` (vma type system)."""
    from jax import lax

    return lax.pcast(x, axis_name, to="varying")


def pvary_to_match(x: Any, *refs, axes: tuple = ()) -> Any:
    """Pvary ``x`` over the axes the ``refs`` vary over (plus ``axes``)
    that ``x`` does not — the scan-carry initializer's friend: a fresh
    zeros accumulator must enter a ``lax.scan`` with the same vma type its
    carry leaves with (the union of whatever the loop body mixes in), or
    ``check_vma=True`` rejects the loop.  Matching the actual inputs
    instead of hardcoding one axis keeps the same code correct on a
    single-axis mesh AND nested inside a wider program (e.g. the ring
    ported into the 4-axis ParallelLM, where q/k/v arrive already varying
    over data/stage/model — the r3 reason dryrun ran check_vma=False)."""
    want = set(axes if isinstance(axes, (tuple, list, set)) else (axes,))
    for r in refs:
        for leaf in jax.tree_util.tree_leaves(r):
            want |= set(jax.typeof(leaf).vma)

    def one(v):
        missing = tuple(sorted(want - set(jax.typeof(v).vma)))
        return pvary(v, missing) if missing else v

    return jax.tree_util.tree_map(one, x)


def psum_over_varying(x: Any, axes) -> Any:
    """``lax.psum`` over the subset of ``axes`` that ``x`` actually varies
    over.  Summing over an axis the value is REPLICATED on multiplies it
    by the axis size — a silent correctness bug ``check_vma=True`` rejects
    (and exactly what the r3 dryrun did to its reported loss: the pipeline
    output is already stage-reduced, so the all-axes psum inflated the
    total by the stage extent).  Only meaningful under ``check_vma=True``
    (with the checker off every value types as invarying and nothing would
    be summed) — callers run with the checker ON."""
    from jax import lax

    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    vary = tuple(a for a in axes if a in set(jax.typeof(x).vma))
    return lax.psum(x, vary) if vary else x


def sync(tree: Any) -> None:
    """Wait until every array in ``tree`` has been computed on its device.
    ``chip_smoke.py``'s train phase checks on the chip that nothing is left
    to wait for once this returns (``sync_residual_ms``)."""
    jax.block_until_ready(tree)


def benchmark(
    step: Callable,
    *args,
    warmup: int = 3,
    iters: int = 10,
    sync_out: Optional[Callable] = None,
) -> Dict[str, float]:
    """Time ``step(*args)`` honestly: per-iteration transfer-based sync.

    ``sync_out`` picks what to sync from the step's return value (default:
    the whole thing).  Returns mean/min/max seconds per iteration.
    """
    pick = sync_out or (lambda out: out)
    for _ in range(warmup):
        sync(pick(step(*args)))
    times: List[float] = []
    for _ in range(iters):
        t0 = time.perf_counter()
        sync(pick(step(*args)))
        times.append(time.perf_counter() - t0)
    return {
        "mean_s": float(np.mean(times)),
        "min_s": float(np.min(times)),
        "max_s": float(np.max(times)),
        "iters": float(iters),
    }


@contextlib.contextmanager
def trace(logdir: str):
    """``jax.profiler`` trace scope — view the collective/compute timeline in
    tensorboard/xprof (the TPU analog of nvprof-on-NCCL the reference era
    used)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# The FLOP/MFU primitives moved to the observability device plane
# (PR 11): the compile watcher captures cost_analysis() per compiled
# program and the ``device.*`` gauges share the same peak table and
# utilization formula as the benches.  These names stay importable here
# — ``from chainermn_tpu.utils import PEAK_BF16_FLOPS`` keeps working —
# but new code should import from ``chainermn_tpu.observability.device``.
from chainermn_tpu.observability.device import (  # noqa: E402,F401
    PEAK_BF16_FLOPS,
    attention_core_flops,
    compiled_flops,
)
from chainermn_tpu.observability.device import (  # noqa: E402
    mfu_pct as _device_mfu_pct,
)


def _mfu_pct(flops: float, step_time_s: float, n_devices: int,
             device_kind: Optional[str]) -> Optional[float]:
    """The one utilization formula both public entry points share, so the
    convention can never drift between ``mfu_pct`` and
    ``mfu_pct_incl_flash`` in an artifact — now delegating to the device
    plane's single implementation."""
    return _device_mfu_pct(flops, step_time_s, n_devices,
                           device_kind=device_kind)


def flash_mfu_fields(base_flops: Optional[float], extra_flops: float,
                     step_time_s: float, n_devices: int = 1,
                     device_kind: Optional[str] = None) -> dict:
    """The two artifact fields for a flash-kernel MFU correction —
    ``tflops_flash_uncounted`` (the analytic attention-core work XLA's
    counter can't see, :func:`attention_core_flops`) and
    ``mfu_pct_incl_flash`` (the inclusive utilization).  One shared
    implementation so the accounting convention (e.g. the 2.5× backward
    factor) lives in exactly one place; empty dict when the device kind
    has no peak-FLOPs entry or there is nothing to add."""
    if not base_flops or not extra_flops:
        return {}
    pct = _mfu_pct(base_flops + extra_flops, step_time_s, n_devices,
                   device_kind)
    if pct is None:
        return {}
    return {
        "tflops_flash_uncounted": round(extra_flops / 1e12, 3),
        "mfu_pct_incl_flash": round(pct, 2),
    }


def mfu(compiled, step_time_s: float, n_devices: int = 1,
        device_kind: Optional[str] = None,
        extra_flops: float = 0.0) -> Optional[float]:
    """Model FLOPs utilization (%) of a compiled step: XLA-counted FLOPs per
    execution ÷ (step time · per-chip bf16 peak · n_devices).  ``None`` when
    the device kind has no table entry or XLA reports no flops.  The
    compiler's count is the honest numerator — it includes remat recompute —
    EXCEPT that Pallas custom calls are opaque to it: pass ``extra_flops``
    (see :func:`attention_core_flops`) to add the analytically-counted work
    of flash kernels, and label the result as the inclusive number."""
    flops = compiled_flops(compiled)
    if flops is None:
        return None
    return _mfu_pct(flops + extra_flops, step_time_s, n_devices,
                    device_kind)


def scaling_efficiency(
    throughputs: Sequence[float], sizes: Sequence[int]
) -> List[float]:
    """Linear-scaling efficiency per pod size vs the smallest measured size:
    ``eff[i] = (T_i / n_i) / (T_0 / n_0)`` (per-chip throughput retention —
    the metric of BASELINE.md's ≥90% target)."""
    base = throughputs[0] / sizes[0]
    return [float((t / n) / base) for t, n in zip(throughputs, sizes)]


class StepTimer:
    """Trainer extension: logs steps/sec over each interval (rank 0)."""

    def __init__(self, trigger=(1, "epoch")):
        from chainermn_tpu.training import Extension

        self._last_t = time.perf_counter()
        self._last_iter = 0

        def fire(trainer):
            now = time.perf_counter()
            d_iter = trainer.iteration - self._last_iter
            dt = now - self._last_t
            if d_iter and jax.process_index() == 0:
                print(f"[timer] {d_iter / dt:.2f} iters/sec", flush=True)
            self._last_t, self._last_iter = now, trainer.iteration

        self.extension = Extension(fire, trigger=trigger, name="StepTimer")

"""Double-buffering overlap evidence (VERDICT r1 item 9).

The reference's ``_DoubleBufferingOptimizer`` existed to overlap the gradient
allreduce with the next step's compute (SURVEY.md §2.6/§3.3, side CUDA
stream).  Our port reproduces the 1-step-stale *semantics* in the jitted step
(contract-tested); this harness quantifies the *overlap*: with
``double_buffering=True`` the applied update uses the PREVIOUS step's reduced
grads, so this step's allreduce result is not needed until the next step and
the scheduler is free to run it concurrently with the optimizer update and —
under async dispatch — the next step's forward.

Method: a deliberately comm-bound config (wide MLP → large gradient pytree,
small per-chip batch → little compute) on whatever mesh is present; measure
steady-state step time for sync vs double-buffered variants.  Optionally
writes a ``jax.profiler`` trace for timeline inspection.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/overlap.py --out result/overlap_cpu.json
"""

from __future__ import annotations

import argparse
import json


def measure(dim: int = 2048, batch_per_chip: int = 8, iters: int = 20,
            trace_dir: str | None = None):
    import numpy as np

    import jax
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import MLP, classification_loss
    from chainermn_tpu.utils import sync

    comm = cmn.create_communicator("xla")
    n = comm.size
    B = batch_per_chip * n
    rng = np.random.RandomState(0)
    x = rng.normal(size=(B, dim)).astype(np.float32)
    y = rng.randint(0, 10, size=(B,)).astype(np.int32)

    import time

    out = {"devices": n, "dim": dim, "global_batch": B, "iters": iters,
           "platform": jax.devices()[0].platform}
    for dbuf in (False, True):
        model = MLP([dim, dim], 10)
        params = model.init(jax.random.PRNGKey(0), x[:1])["params"]
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1, momentum=0.9), comm, double_buffering=dbuf
        )
        state = opt.init(params)
        step = opt.make_train_step(classification_loss(model), has_aux=True)
        batch = comm.shard_batch((x, y))
        # Warmup/compile, then time the chain with ONE final materialization
        # (sequential state dependency bounds all steps).
        for _ in range(3):
            state, m = step(state, batch)
        sync(m)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, batch)
        sync(m)
        dt = time.perf_counter() - t0
        key = "dbuf" if dbuf else "sync"
        out[f"{key}_step_ms"] = round(dt / iters * 1000, 3)
        if trace_dir and dbuf:
            import os

            os.makedirs(trace_dir, exist_ok=True)
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    state, m = step(state, batch)
                sync(m)
    out["overlap_gain_pct"] = round(
        100.0 * (1.0 - out["dbuf_step_ms"] / out["sync_step_ms"]), 1
    )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--batch-per-chip", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()
    # NB: async dispatch stays ON — overlap across steps is the thing being
    # measured.  Single repeated program; the conftest deadlock concerns
    # multiple interleaved compiled programs.

    res = measure(args.dim, args.batch_per_chip, args.iters, args.trace_dir)
    print(json.dumps(res), flush=True)
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(res, args.out)


if __name__ == "__main__":
    main()

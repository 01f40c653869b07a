"""PipelineChain vs compute-replicated MultiNodeChainList.

The reference's ``MultiNodeChainList`` chains sub-models sequentially with no
microbatch interleaving (SURVEY.md §2.3 "Pipeline parallel: PARTIAL").  Our
API-parity tier reproduces that (and, under SPMD, is compute-replicated —
every device computes every stage); :class:`PipelineChain` is the tier that
must actually be *faster*: stage-sharded params, GPipe microbatching, per
-device work ∝ (S+M-1)/M microbatches instead of S full batches.

This harness measures both on an identical homogeneous stage stack
(fwd+bwd+update-free step), prints one JSON line per config, and reports the
speedup.  Run on the forced-CPU mesh (shared cores make total work visible)
or real chips:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/pipeline.py
"""

from __future__ import annotations

import argparse
import json


def measure(d: int = 256, B: int = 128, M: int = 4, iters: int = 5):
    """Return ``{"replicated_s", "pipeline_s", "speedup", ...}`` for an
    S=n_devices-stage tanh-MLP stack (fwd+bwd per step)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as cmn
    from chainermn_tpu.links import MultiNodeChainList, PipelineChain
    from chainermn_tpu.utils import benchmark

    comm = cmn.create_communicator("xla")
    S = comm.size
    rng = np.random.RandomState(0)
    stages = rng.normal(size=(S, d, d)).astype(np.float32) * (0.5 / np.sqrt(d))
    x = rng.normal(size=(B, d)).astype(np.float32)

    # --- compute-replicated chain (API-parity tier) ----------------------
    chain = MultiNodeChainList(comm)
    for s in range(S):
        chain.add_link(
            (lambda w: lambda p, h: jnp.tanh(h @ p))(None),
            rank=s,
            rank_in=s - 1 if s > 0 else None,
            rank_out=s + 1 if s < S - 1 else None,
        )

    def chain_loss(params_list, x):
        def body(*args):
            *ps, xx = args
            y = chain(list(ps), xx)
            y = cmn.functions.bcast(comm, y, root=S - 1)
            return jnp.sum(y**2)

        return comm.spmd(
            body,
            in_specs=tuple([P()] * S) + (P(),),
            out_specs=P(),
            check_vma=False,
        )(*params_list, x)

    chain_step = jax.jit(jax.grad(chain_loss))
    params_list = [stages[s] for s in range(S)]

    rep = benchmark(lambda: chain_step(params_list, x), warmup=2, iters=iters)

    # --- pipeline tier ---------------------------------------------------
    pipe = PipelineChain(lambda p, h: jnp.tanh(h @ p[0]), comm, n_microbatches=M)

    def pipe_loss(stages, x):
        f = comm.spmd(
            lambda p, xx: jnp.sum(pipe(p, xx) ** 2),
            in_specs=(P(comm.axes), P()),
            out_specs=P(),
            check_vma=False,
        )
        return f(stages, x)

    pipe_step = jax.jit(jax.grad(pipe_loss))
    pip = benchmark(lambda: pipe_step(stages, x), warmup=2, iters=iters)

    return {
        "devices": S,
        "stages": S,
        "microbatches": M,
        "dim": d,
        "batch": B,
        "replicated_s": round(rep["mean_s"], 5),
        "pipeline_s": round(pip["mean_s"], 5),
        "speedup": round(rep["mean_s"] / pip["mean_s"], 3),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default=None, help="optional JSON output path")
    args = ap.parse_args()

    import jax

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()
    if jax.default_backend() == "cpu":
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    res = measure(args.dim, args.batch, args.microbatches, args.iters)
    print(json.dumps(res), flush=True)
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(res, args.out)


if __name__ == "__main__":
    main()

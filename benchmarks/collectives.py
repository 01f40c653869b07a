"""Collective microbenchmarks: the comm data plane in isolation.

The reference benchmarked its communicator zoo by timing allreduce on raw
buffers across sizes (the hierarchical/two_dimensional design space).  Here
the zoo is XLA's scheduler, but the numbers still matter: this harness
times each collective primitive the framework builds on (psum, all_gather,
psum_scatter, ppermute ring hop, all_to_all) across payload sizes, and
derives achieved bytes/sec (algorithm bandwidth).

    python benchmarks/collectives.py --out result/collectives_tpu.json

On the single real chip this measures single-device latency floors (the
collectives compile to copies); the interesting numbers come from a real
multi-chip slice, and on the CPU mesh the values are plumbing-only — the
JSON records the platform so nobody mistakes either for ICI bandwidth.
"""

from __future__ import annotations

import argparse
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16,64")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as cmn
    from chainermn_tpu.utils import sync

    comm = cmn.create_communicator("xla")
    n = comm.size
    platform = jax.devices()[0].platform
    if platform == "cpu":
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    out = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n,
        "results": [],
    }

    def build(op):
        def body(x):
            if op == "psum":
                return lax.psum(x, comm.axis_name)
            if op == "psum_scatter":
                return lax.psum_scatter(
                    x.reshape(n, -1), comm.axis_name, scatter_dimension=0,
                    tiled=False,
                )
            if op == "all_gather":
                return lax.all_gather(x, comm.axis_name, axis=0, tiled=True)
            if op == "ppermute":
                return lax.ppermute(
                    x, comm.axis_name,
                    perm=[(i, (i + 1) % n) for i in range(n)],
                )
            if op == "all_to_all":
                return lax.all_to_all(
                    x.reshape(n, -1), comm.axis_name, split_axis=0,
                    concat_axis=0, tiled=True,
                )
            raise ValueError(op)

        return jax.jit(
            comm.spmd(body, in_specs=P(comm.axes), out_specs=P(comm.axes))
        )

    for mb in (float(s) for s in args.sizes_mb.split(",")):
        per_dev = int(mb * 1e6 / 4)
        per_dev -= per_dev % (n * n)  # all_to_all/psum_scatter divisibility
        if per_dev <= 0:
            continue
        x = jnp.asarray(
            np.random.RandomState(0).normal(size=(n * per_dev,)).astype(
                np.float32
            )
        )
        for op in ("psum", "psum_scatter", "all_gather", "ppermute",
                   "all_to_all"):
            f = build(op)
            r = f(x)
            sync(r)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                r = f(x)
            sync(r)
            dt = (time.perf_counter() - t0) / args.iters
            payload_bytes = per_dev * 4  # per-device contribution
            rec = {
                "op": op,
                "payload_mb_per_device": round(payload_bytes / 1e6, 3),
                "time_ms": round(dt * 1e3, 4),
                "gbytes_per_sec_per_device": round(
                    payload_bytes / dt / 1e9, 3
                ),
            }
            out["results"].append(rec)
            print(json.dumps(rec), flush=True)

    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(out, args.out)


if __name__ == "__main__":
    main()

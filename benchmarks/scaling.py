"""Scaling-efficiency harness: per-chip throughput retention across pod sizes.

The reference's headline claim is near-linear ResNet-50 scaling (BASELINE.md);
this harness measures the same quantity for any model/step on whatever
devices are present — real chips on a pod, or the forced-CPU simulation:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/scaling.py

Also runs the ``DummyCommunicator`` ablation (upper-bound scaling with
communication removed — the reference's stated purpose for that class),
so the printed efficiency gap attributes directly to comm cost.
Prints one JSON line per (size, communicator) config.
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-per-chip", type=int, default=64)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default=None, help="optional JSON output path")
    args = ap.parse_args()

    import jax

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()
    if jax.default_backend() == "cpu":
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import MLP, classification_loss
    from chainermn_tpu.utils import benchmark, scaling_efficiency

    all_devices = jax.devices()
    on_cpu = all_devices[0].platform == "cpu"
    sizes = [n for n in (1, 2, 4, 8, 16, 32, 64) if n <= len(all_devices)]
    rng = np.random.RandomState(0)

    results = {
        "platform": all_devices[0].platform,
        "device_kind": all_devices[0].device_kind,
        "sizes": sizes,
        "batch_per_chip": args.batch_per_chip,
        "dim": args.dim,
    }
    if on_cpu:
        # Honest framing: the forced-CPU virtual devices SHARE one host's
        # cores, so per-chip retention measures nothing — total throughput
        # staying flat as N grows, and the xla-vs-dummy gap (communication
        # cost), are the meaningful CPU-mesh quantities.
        results["note"] = (
            "virtual CPU mesh: devices share one host's cores; read "
            "total_samples_per_sec flatness and comm_overhead_pct, not "
            "per-chip scaling"
        )
    for dummy in (False, True):
        throughputs = []
        for n in sizes:
            devs = all_devices[:n]
            comm = (
                cmn.DummyCommunicator(cmn.flat_mesh(devs))
                if dummy
                else cmn.XlaCommunicator(cmn.flat_mesh(devs))
            )
            model = MLP([args.dim, args.dim], 10)
            B = args.batch_per_chip * n
            x = rng.normal(size=(B, args.dim)).astype(np.float32)
            y = rng.randint(0, 10, size=(B,)).astype(np.int32)
            params = model.init(jax.random.PRNGKey(0), x[:1])["params"]
            opt = cmn.create_multi_node_optimizer(
                optax.sgd(0.1, momentum=0.9), comm
            )
            state = opt.init(params)
            step = opt.make_train_step(
                classification_loss(model), has_aux=True, donate=False
            )
            batch = comm.shard_batch((x, y))
            holder = {"state": state}

            def run():
                holder["state"], m = step(holder["state"], batch)
                return m

            t = benchmark(run, warmup=2, iters=args.iters)
            ips = B / t["mean_s"]
            throughputs.append(ips)
            print(json.dumps({
                "config": "dummy" if dummy else "xla",
                "devices": n,
                "samples_per_sec": round(ips, 1),
                "per_chip": round(ips / n, 1),
            }), flush=True)
        effs = scaling_efficiency(throughputs, sizes)
        key = "dummy" if dummy else "xla"
        results[key] = {
            "samples_per_sec": [round(t, 1) for t in throughputs],
            "scaling_efficiency": [round(e, 3) for e in effs],
        }
        print(json.dumps({
            "config": key,
            "scaling_efficiency": [round(e, 3) for e in effs],
            "sizes": sizes,
        }), flush=True)
    # Communication-cost attribution: 1 - xla/dummy at each size (the
    # DummyCommunicator ablation is the reference's stated tool for this).
    overhead = [
        round(100.0 * (1.0 - a / b), 1) if b else 0.0
        for a, b in zip(
            results["xla"]["samples_per_sec"], results["dummy"]["samples_per_sec"]
        )
    ]
    results["comm_overhead_pct"] = overhead
    print(json.dumps({"comm_overhead_pct": overhead, "sizes": sizes}), flush=True)
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(results, args.out)
    return results


if __name__ == "__main__":
    main()

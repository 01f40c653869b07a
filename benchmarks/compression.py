"""Gradient-wire ablation: fp32 vs bf16 wire dtype vs int8+error-feedback.

A deliberately comm-bound config (wide MLP → big gradient pytree, tiny
per-chip batch → little compute) so the gradient collective dominates the
step; the int8_ef tier moves 4× fewer bytes than fp32 (2× fewer than bf16)
at the cost of the quantize/dequantize elementwise work.  NOTE the expected
CPU-mesh outcome (committed in ``result/compression_cpu.json``): int8_ef is
SLOWER there (~0.45× of fp32) — the in-process "collective" is a memcpy
with no bandwidth to save, so only the added elementwise work registers.
The byte reduction pays on bandwidth-bound interconnects (ICI/DCN), which
this harness measures whenever a multi-chip mesh is present.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/compression.py --out result/compression_cpu.json
"""

from __future__ import annotations

import argparse
import json


def measure(dim: int = 2048, batch_per_chip: int = 8, iters: int = 20):
    import time

    import numpy as np

    import jax
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import MLP, classification_loss
    from chainermn_tpu.utils import sync

    comm_plain = cmn.create_communicator("xla")
    n = comm_plain.size
    B = batch_per_chip * n
    rng = np.random.RandomState(0)
    x = rng.normal(size=(B, dim)).astype(np.float32)
    y = rng.randint(0, 10, size=(B,)).astype(np.int32)

    out = {"devices": n, "dim": dim, "global_batch": B, "iters": iters,
           "platform": jax.devices()[0].platform}
    modes = {
        "fp32": dict(comm=comm_plain, compression=None),
        "bf16_wire": dict(
            comm=cmn.create_communicator(
                "xla", allreduce_grad_dtype="bfloat16"
            ),
            compression=None,
        ),
        "int8_ef": dict(comm=comm_plain, compression="int8_ef"),
    }
    final_losses = {}
    for name, cfg in modes.items():
        model = MLP([dim, dim], 10)
        params = model.init(jax.random.PRNGKey(0), x[:1])["params"]
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1, momentum=0.9), cfg["comm"],
            grad_compression=cfg["compression"],
        )
        state = opt.init(params)
        step = opt.make_train_step(classification_loss(model), has_aux=True)
        batch = cfg["comm"].shard_batch((x, y))
        for _ in range(3):
            state, m = step(state, batch)
        sync(m)
        # Numerics cross-check EARLY (step 3), before this overfit config
        # saturates every mode to 0.0: a mis-scaled wire (e.g. a stray
        # 1/size) visibly diverges here.
        final_losses[name] = float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, batch)
        sync(m)
        dt = time.perf_counter() - t0
        out[f"{name}_step_ms"] = round(dt / iters * 1000, 3)
    out["loss_at_step3"] = {k: round(v, 6) for k, v in final_losses.items()}
    out["int8_vs_fp32_speedup"] = round(
        out["fp32_step_ms"] / out["int8_ef_step_ms"], 3
    )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--batch-per-chip", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()
    res = measure(args.dim, args.batch_per_chip, args.iters)
    line = json.dumps(res)
    print(line)
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(res, args.out)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Channel-parallel convnet — the reference's parallel-convnet example:
every rank owns 1/M of each conv layer's filters; activations re-assemble
through differentiable collectives between layers (filter tensor
parallelism).  Here that is an ``all_gather`` on the channel axis inside one
jitted SPMD step (`chainermn_tpu.models.parallel_convnet`).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/parallel_convnet/train_parallel_convnet.py --force-cpu
"""

import argparse

import jax


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batchsize", type=int, default=64)
    p.add_argument("--epoch", type=int, default=3)
    p.add_argument("--widths", default="32,64,128,128")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--n-train", type=int, default=2048)
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        init_channel_parallel,
        make_channel_parallel_train_step,
    )

    comm = cmn.create_communicator("xla")
    rank0 = jax.process_index() == 0
    widths = tuple(int(w) for w in args.widths.split(","))
    assert all(w % comm.size == 0 for w in widths), (
        f"widths {widths} must divide by the model-axis size {comm.size}"
    )
    if rank0:
        print(f"model-axis size: {comm.size}  widths: {widths}")

    # Synthetic CIFAR-shaped classification task.
    rng = np.random.RandomState(5)
    n_cls = 10
    protos = rng.normal(size=(n_cls, 32, 32, 3)).astype(np.float32)
    y = rng.randint(0, n_cls, size=(args.n_train,)).astype(np.int32)
    x = protos[y] + 0.5 * rng.normal(size=(args.n_train, 32, 32, 3)).astype(
        np.float32
    )

    params = init_channel_parallel(jax.random.PRNGKey(0), widths, n_cls)
    tx = optax.sgd(args.lr, momentum=0.9)
    opt_state = tx.init(params)
    step = make_channel_parallel_train_step(comm, tx, params, opt_state)
    carry = jax.tree_util.tree_map(jax.numpy.array, (params, opt_state))

    steps_per_epoch = args.n_train // args.batchsize
    for epoch in range(args.epoch):
        losses = []
        for i in range(steps_per_epoch):
            sl = slice(i * args.batchsize, (i + 1) * args.batchsize)
            carry, loss = step(carry, (x[sl], y[sl]))
            jax.block_until_ready(carry)
            losses.append(float(loss))
        if rank0:
            print(f"epoch {epoch + 1}  loss {np.mean(losses):.4f}", flush=True)


if __name__ == "__main__":
    main()

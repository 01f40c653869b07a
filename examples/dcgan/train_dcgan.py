#!/usr/bin/env python
"""Data-parallel DCGAN — the reference's GAN example family
(``examples/dcgan/train_dcgan.py`` + ``net.py`` + ``updater.py``): generator
and discriminator each wrapped in their own multi-node optimizer, both
updated every iteration from one shared forward.

TPU-native shape: the custom Chainer updater's two eager allreduces become
one jitted SPMD step (:func:`chainermn_tpu.models.make_gan_train_step`) with
both gradient means in-graph.  Run an 8-chip pod simulation on CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/dcgan/train_dcgan.py --force-cpu
"""

import argparse

import jax


def main():
    p = argparse.ArgumentParser(description="chainermn_tpu DCGAN example")
    p.add_argument("--batchsize", type=int, default=64, help="global batch size")
    p.add_argument("--epoch", type=int, default=3)
    p.add_argument("--nz", type=int, default=64, help="latent dim")
    p.add_argument("--ch", type=int, default=16)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--n-train", type=int, default=2048)
    p.add_argument("--force-cpu", action="store_true")
    p.add_argument("--out", default="result/dcgan_log.json")
    args = p.parse_args()

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.datasets import ArrayDataset
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.models import (
        Discriminator,
        Generator,
        gan_init,
        make_gan_train_step,
    )
    from chainermn_tpu.training import LogReport

    comm = cmn.create_communicator("xla")
    rank0 = jax.process_index() == 0
    if rank0:
        print(f"devices: {comm.size}")

    # Synthetic 32×32 "image" corpus: smooth blobs the generator can imitate
    # (stands in for the reference's CIFAR/imagefolder input; zero egress).
    rng = np.random.RandomState(7)
    yy, xx = np.mgrid[0:32, 0:32] / 31.0
    centers = rng.uniform(0.2, 0.8, size=(args.n_train, 2))
    widths = rng.uniform(0.05, 0.2, size=(args.n_train, 1, 1))
    imgs = np.exp(
        -((yy[None] - centers[:, :1, None]) ** 2 + (xx[None] - centers[:, 1:, None]) ** 2)
        / widths
    )
    imgs = (imgs * 2.0 - 1.0).astype(np.float32)[..., None]  # tanh range
    train = cmn.scatter_dataset(ArrayDataset(imgs), comm, shuffle=True, seed=11)

    gen = Generator(ch=args.ch, out_ch=1)
    disc = Discriminator(ch=args.ch)
    g_tx = optax.adam(args.lr, b1=0.5)
    d_tx = optax.adam(args.lr, b1=0.5)
    state = gan_init(
        gen, disc, g_tx, d_tx, comm, jax.random.PRNGKey(0),
        image_shape=(32, 32, 1), nz=args.nz,
    )
    step = make_gan_train_step(gen, disc, g_tx, d_tx, comm)

    it = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    log = LogReport(trigger=(1, "epoch"), out=args.out)
    zrng = np.random.RandomState(13)

    history = []
    while it.epoch < args.epoch:
        (real,) = next(it)
        z = zrng.normal(size=(len(real), args.nz)).astype(np.float32)
        state, metrics = step(state, comm.shard_batch((real, z)))
        jax.block_until_ready(state)
        history.append({k: float(v) for k, v in metrics.items()})
        if it.is_new_epoch and rank0:
            window = history[-it.iteration // max(it.epoch, 1):] or history
            means = {
                k: float(np.mean([h[k] for h in window])) for k in window[0]
            }
            print(
                f"epoch {it.epoch}  "
                + "  ".join(f"{k} {v:.4f}" for k, v in means.items()),
                flush=True,
            )
    del log  # LogReport kept for API symmetry with the other examples

    if rank0:
        import json, os

        os.makedirs("result", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(history[-5:], f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()

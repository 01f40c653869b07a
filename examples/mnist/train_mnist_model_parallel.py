#!/usr/bin/env python
"""Model-parallel MNIST — the reference's
``examples/mnist/train_mnist_model_parallel.py``: an MLP split across two
model ranks with send/recv between them, here on a hybrid ``data × model``
mesh (4-way data parallel × 2-stage chain on 8 devices) — the reference
needed a separate 2-process launch; the hybrid grid is free on a mesh
(SURVEY.md §2.3 "Hybrid DP×MP").

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/mnist/train_mnist_model_parallel.py --force-cpu
"""

import argparse

import jax


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batchsize", type=int, default=256)
    p.add_argument("--epoch", type=int, default=3)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
        # avoid in-process CPU collective rendezvous deadlocks (see tests/conftest.py)
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu import functions as F
    from chainermn_tpu.datasets import make_synthetic_classification
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.links import MultiNodeChainList
    from chainermn_tpu.training import LogReport, Trainer

    n_dev = len(jax.devices())
    mesh = cmn.hybrid_mesh({"data": n_dev // 2, "model": 2})
    comm = cmn.XlaCommunicator(mesh)
    dcomm = comm.sub("data")  # gradient averaging plane
    mcomm = comm.sub("model")  # chain/stage plane

    class Stage0(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.relu(nn.Dense(256)(x.reshape((x.shape[0], -1))))

    class Stage1(nn.Module):
        @nn.compact
        def __call__(self, h):
            return nn.Dense(10)(nn.relu(nn.Dense(256)(h)))

    s0, s1 = Stage0(), Stage1()
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    p0 = s0.init(k0, np.zeros((1, 784), np.float32))["params"]
    p1 = s1.init(k1, np.zeros((1, 256), np.float32))["params"]
    params = {"stage0": p0, "stage1": p1}

    chain = MultiNodeChainList(mcomm)
    chain.add_link(lambda p, x: s0.apply({"params": p}, x), rank=0, rank_out=1)
    chain.add_link(lambda p, h: s1.apply({"params": p}, h), rank=1, rank_in=0)

    def loss_fn(params, batch):
        x, y = batch
        logits = chain([params["stage0"], params["stage1"]], x)
        logits = F.bcast(mcomm, logits, root=1)  # output lives on model rank 1
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return loss, {"accuracy": acc}

    from chainermn_tpu.optimizers import model_parallel_grad_reduce

    # Stage grads are owner-localized on the model axis; psum them over
    # 'model' so every shard holds the owner's update, then pmean over 'data'.
    opt = cmn.create_multi_node_optimizer(
        optax.sgd(args.lr, momentum=0.9),
        dcomm,
        grad_reduce=model_parallel_grad_reduce(dcomm, mcomm),
    )
    state = opt.init(params)

    train = cmn.scatter_dataset(
        make_synthetic_classification(8192, 784, 10, seed=1), comm, shuffle=True,
        seed=42,
    )
    it = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    trainer = Trainer(opt, state, loss_fn, it, stop=(args.epoch, "epoch"),
                      has_aux=True)
    trainer.extend(LogReport(trigger=(1, "epoch")))
    if jax.process_index() == 0:
        print(f"mesh: data={n_dev // 2} × model=2")
    trainer.run()


if __name__ == "__main__":
    main()

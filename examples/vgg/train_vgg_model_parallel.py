#!/usr/bin/env python
"""Model-parallel VGG — the reference's parallel-convnet example family
(SURVEY.md §2.9 "dcgan/parallel-convnet variants"; BASELINE.md tracks
"model-parallel VGG via MultiNodeChainList analog").

A VGG-11 is partitioned into 4 contiguous stages placed on the 4 ranks of
the ``model`` mesh axis (MultiNodeChainList, ``ppermute`` edges), hybridized
with 2-way data parallelism on 8 devices — the reference needed an 8-process
MPI launch for this grid; on a mesh it's one program.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/vgg/train_vgg_model_parallel.py --force-cpu
"""

import argparse

import jax


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batchsize", type=int, default=64)
    p.add_argument("--epoch", type=int, default=2)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--stages", type=int, default=4)
    p.add_argument("--width-mult", type=float, default=0.25)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
        # avoid in-process CPU collective rendezvous deadlocks (see tests/conftest.py)
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu import functions as F
    from chainermn_tpu.datasets import ArrayDataset
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.models.vgg import (
        build_chain,
        init_stage_params,
        vgg_stage_modules,
    )
    from chainermn_tpu.optimizers import model_parallel_grad_reduce
    from chainermn_tpu.training import LogReport, Trainer

    n_dev = len(jax.devices())
    S = args.stages
    mesh = cmn.hybrid_mesh({"data": n_dev // S, "model": S})
    comm = cmn.XlaCommunicator(mesh)
    dcomm = comm.sub("data")
    mcomm = comm.sub("model")

    modules = vgg_stage_modules(
        "vgg11", num_classes=args.classes, n_stages=S,
        width_mult=args.width_mult,
    )
    chain = build_chain(modules, mcomm)

    # Synthetic CIFAR-shaped task (deterministic, zero-egress): each class
    # is a distinct low-frequency spatial template mixed into the image —
    # CNN-learnable structure, unlike a per-pixel random projection which
    # global pooling would erase.
    rng = np.random.RandomState(0)
    n = 2048
    templates = rng.normal(size=(args.classes, 8, 8, 3)).astype(np.float32)
    templates = np.kron(templates, np.ones((1, 4, 4, 1), np.float32))  # 32x32
    y_all = rng.randint(0, args.classes, size=n).astype(np.int32)
    x_all = (
        0.6 * templates[y_all]
        + rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    )

    params = {
        f"stage{i}": p
        for i, p in enumerate(
            init_stage_params(modules, jax.random.PRNGKey(0), x_all[:1])
        )
    }

    def loss_fn(params, batch):
        x, y = batch
        logits = chain([params[f"stage{i}"] for i in range(S)], x)
        logits = F.bcast(mcomm, logits, root=S - 1)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return loss, {"accuracy": acc}

    opt = cmn.create_multi_node_optimizer(
        optax.sgd(args.lr, momentum=0.9),
        dcomm,
        grad_reduce=model_parallel_grad_reduce(dcomm, mcomm),
    )
    state = opt.init(params)

    train = cmn.scatter_dataset(
        ArrayDataset(x_all, y_all), comm, shuffle=True, seed=42
    )
    it = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    trainer = Trainer(opt, state, loss_fn, it, stop=(args.epoch, "epoch"),
                      has_aux=True)
    trainer.extend(LogReport(trigger=(1, "epoch")))
    if jax.process_index() == 0:
        print(f"mesh: data={n_dev // S} × model={S}  (VGG-11/{args.width_mult}x)")
    trainer.run()


if __name__ == "__main__":
    main()

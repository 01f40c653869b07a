#!/usr/bin/env python
"""Data-parallel MNIST-style training — the reference's flagship example
(``examples/mnist/train_mnist.py``): create a communicator, scatter the
dataset, wrap the optimizer, train with rank-0 reporting.

Runs on any platform; to simulate an 8-chip pod on CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/mnist/train_mnist.py --communicator naive

(``--force-cpu`` does the same from the command line.)
"""

import argparse

import jax


def main():
    p = argparse.ArgumentParser(description="chainermn_tpu MNIST example")
    p.add_argument("--communicator", default="hierarchical")
    p.add_argument("--batchsize", type=int, default=256, help="global batch size")
    p.add_argument("--epoch", type=int, default=5)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--unit", type=int, default=256)
    p.add_argument("--wire-dtype", default=None, help="e.g. bfloat16 (fp16-allreduce analog)")
    p.add_argument("--double-buffering", action="store_true")
    p.add_argument("--force-cpu", action="store_true")
    p.add_argument("--out", default="result/mnist_log.json")
    p.add_argument("--checkpoint", default=None,
                   help="checkpoint dir; resumes from the latest snapshot "
                        "(restart-based fault tolerance)")
    p.add_argument("--train-npz", default=None,
                   help="file-backed training data (.npz archive or .npy "
                        "dir: flattened float images + int labels); "
                        "replaces the synthetic task")
    p.add_argument("--val-npz", default=None,
                   help="file-backed validation data (same format)")
    args = p.parse_args()

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
        # avoid in-process CPU collective rendezvous deadlocks (see tests/conftest.py)
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.datasets import make_synthetic_classification
    from chainermn_tpu.extensions import Evaluator, create_multi_node_evaluator
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.models import MLP, classification_loss, classification_metrics
    from chainermn_tpu.training import Extension, LogReport, Trainer

    comm = cmn.create_communicator(
        args.communicator, allreduce_grad_dtype=args.wire_dtype
    )
    if jax.process_index() == 0:
        print(f"devices: {comm.size}  communicator: {args.communicator}")

    # Dataset: rank 0 "owns" it; scatter = per-host shard (SURVEY §2.7).
    # --train-npz/--val-npz swap in real on-disk data (the reference
    # downloaded MNIST; the zero-egress default is the synthetic task).
    from chainermn_tpu.datasets import NpzDataset

    train = cmn.scatter_dataset(
        NpzDataset(args.train_npz) if args.train_npz
        else make_synthetic_classification(8192, 784, 10, seed=1),
        comm, shuffle=True, seed=42,
    )
    val = cmn.scatter_dataset(
        NpzDataset(args.val_npz) if args.val_npz
        else make_synthetic_classification(1024, 784, 10, seed=2),
        comm,
    )

    model = MLP(hidden=(args.unit, args.unit), n_out=10)
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 784), np.float32))["params"]

    opt = cmn.create_multi_node_optimizer(
        optax.sgd(args.lr, momentum=0.9), comm,
        double_buffering=args.double_buffering,
    )
    state = opt.init(params)
    loss_fn = classification_loss(model)

    train_iter = SerialIterator(train, args.batchsize, shuffle=True, seed=0)
    evaluator = create_multi_node_evaluator(
        Evaluator(
            lambda: SerialIterator(val, args.batchsize, repeat=False, shuffle=False),
            classification_metrics(model),
            comm,
        ),
        comm,
    )

    trainer = Trainer(
        opt, state, loss_fn, train_iter,
        stop=(args.epoch, "epoch"), has_aux=True,
    )
    trainer.extend(LogReport(trigger=(1, "epoch"), out=args.out))

    if args.checkpoint:
        ckpt = cmn.create_multi_node_checkpointer(
            "mnist", comm, path=args.checkpoint, trigger=(1, "epoch")
        )
        trainer.extend(ckpt)
        _, resumed = ckpt.maybe_load(trainer.state, trainer)
        if resumed and jax.process_index() == 0:
            print(f"resumed from iteration {resumed}")

    def run_eval(tr):
        metrics = evaluator.evaluate(tr.state.params)
        if jax.process_index() == 0:
            print("  ".join(f"{k} {v:.4f}" for k, v in metrics.items()), flush=True)

    trainer.extend(Extension(run_eval, trigger=(1, "epoch"), name="validation"))
    trainer.run()


if __name__ == "__main__":
    main()

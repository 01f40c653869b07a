#!/usr/bin/env python
"""Data-parallel ImageNet ResNet-50 — the reference's benchmark config
(``examples/imagenet/train_imagenet.py`` + ``models/resnet50.py``;
BASELINE.md's headline numbers).  Exercises: hierarchical/pure_nccl-analog
communicators, bf16 compute, optional bf16 wire dtype (the fp16-allreduce
path), sync-BN, double buffering, checkpointing.

Zero-egress environment: ``--synthetic`` (default) generates deterministic
fake ImageNet-shaped data; point ``--train-npz`` at real data when available.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/imagenet/train_imagenet.py --force-cpu --smoke
"""

import argparse

import jax


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--communicator", default="pure_nccl")
    p.add_argument("--batchsize", type=int, default=256, help="global batch")
    p.add_argument("--epoch", type=int, default=1)
    p.add_argument("--iters-per-epoch", type=int, default=50)
    p.add_argument("--lr", type=float, default=0.1,
                   help="learning rate (used as-is unless --base-batch "
                        "turns on linear scaling)")
    p.add_argument("--optimizer", default="momentum",
                   choices=["momentum", "lars", "lamb"],
                   help="momentum = the reference example's SGD; lars/lamb "
                        "= the large-batch tier (layer-wise trust ratios)")
    p.add_argument("--base-batch", type=int, default=None,
                   help="opt-in linear LR scaling (Goyal et al.): --lr is "
                        "calibrated at this batch and scaled by "
                        "batchsize/base-batch; omit to use --lr verbatim")
    p.add_argument("--warmup-epochs", type=float, default=0.0,
                   help="gradual-warmup epochs before cosine decay "
                        "(recommended 5 for lars at 8k+ batch)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--wire-dtype", default=None)
    p.add_argument("--double-buffering", action="store_true")
    p.add_argument("--grad-compression", default=None,
                   choices=["int8_ef"],
                   help="int8_ef = 4x-compressed gradient wire with error "
                        "feedback (beyond the bf16 --wire-dtype tier)")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--stem", default="conv7", choices=("conv7", "s2d"),
                   help="ResNet input stem: s2d = space-to-depth spelling "
                        "(exact-equivalent, s2d_stem_kernel migrates "
                        "conv7 checkpoints)")
    p.add_argument("--maxpool", default="xla", choices=("xla", "fused"),
                   help="ResNet stem max-pool backward: fused = the "
                        "scatter-free ops.max_pool_fused form")
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet50", "resnet18", "vit"])
    p.add_argument("--train-npz", default=None,
                   help="file-backed training data: a .npz archive or a "
                        "directory of memory-mapped .npy files (members: "
                        "images NHWC float + integer labels); sharded "
                        "across host processes via scatter_dataset")
    p.add_argument("--val-npz", default=None,
                   help="file-backed validation data (same format); "
                        "default: a synthetic held-out split")
    p.add_argument("--val-size", type=int, default=512,
                   help="synthetic validation-set size (no --val-npz)")
    p.add_argument("--augment", action="store_true",
                   help="device-side random crop+flip inside the jitted step")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes for CI (64px, 10 classes, resnet18)")
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
        # avoid in-process CPU collective rendezvous deadlocks (see tests/conftest.py)
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    if args.smoke:
        args.image_size, args.num_classes = 32, 10
        if args.arch == "resnet50":  # explicit --arch survives smoke mode
            args.arch = "resnet18"
        args.batchsize = min(args.batchsize, 64)
        args.iters_per_epoch = 4

    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        ResNet18,
        ResNet50,
        ViT,
        resnet_loss,
        vit_loss,
    )
    from chainermn_tpu.training import LogReport, Trainer

    comm = cmn.create_communicator(
        args.communicator, allreduce_grad_dtype=args.wire_dtype
    )
    if jax.process_index() == 0:
        print(f"devices: {comm.size}  arch: {args.arch}  "
              f"global batch: {args.batchsize}")

    x0 = np.zeros((8, args.image_size, args.image_size, 3), np.float32)
    if args.arch == "vit":
        if args.stem != "conv7" or args.maxpool != "xla":
            raise SystemExit(
                f"--stem/--maxpool are ResNet knobs; they have no meaning "
                f"for --arch {args.arch} — unset them"
            )
        # Stateless (no BN): ViT-S/16 geometry at full size, patch 4 in
        # --smoke so a 32px image still yields an 8x8 token grid.
        model = ViT(num_classes=args.num_classes,
                    patch=4 if args.smoke else 16)
        variables = model.init(jax.random.PRNGKey(0), x0, train=True)
        model_state = None
        loss_fn = vit_loss(model)
        stateful = False
    else:
        arch = ResNet50 if args.arch == "resnet50" else ResNet18
        model = arch(num_classes=args.num_classes, axis_name=comm.axis_name,
                     stem=args.stem, maxpool=args.maxpool)
        variables = model.init(jax.random.PRNGKey(0), x0, train=True)
        model_state = variables["batch_stats"]
        loss_fn = resnet_loss(model)
        stateful = True

    # Large-batch recipe (the reference's 32k-batch headline regime): opt-in
    # linear LR scaling from --base-batch, gradual warmup + cosine decay,
    # and optionally LARS/LAMB layer-wise trust ratios.  The defaults
    # (momentum, no --base-batch, no warmup) reproduce the reference
    # example's plain SGD at --lr exactly.
    from chainermn_tpu.optimizers import (
        lamb,
        lars,
        linear_scaled_lr,
        warmup_cosine_schedule,
    )

    peak_lr = (
        linear_scaled_lr(args.lr, args.batchsize, args.base_batch)
        if args.base_batch
        else args.lr
    )
    total_steps = args.epoch * args.iters_per_epoch
    if args.warmup_epochs > 0:
        # Clamp: a warmup longer than the run (e.g. the recommended 5
        # epochs under a short --epoch) just ramps for the whole run.
        lr = warmup_cosine_schedule(
            peak_lr,
            warmup_steps=min(
                int(args.warmup_epochs * args.iters_per_epoch), total_steps
            ),
            total_steps=total_steps,
        )
    else:
        lr = peak_lr
    tx = {
        "momentum": lambda: optax.sgd(lr, momentum=0.9, nesterov=True),
        "lars": lambda: lars(lr, weight_decay=1e-4, momentum=0.9),
        "lamb": lambda: lamb(lr, weight_decay=1e-2),
    }[args.optimizer]()
    opt = cmn.create_multi_node_optimizer(
        tx,
        comm,
        double_buffering=args.double_buffering,
        grad_compression=args.grad_compression,
    )
    state = opt.init(variables["params"], model_state=model_state)

    from chainermn_tpu.datasets import ArrayDataset, NpzDataset
    from chainermn_tpu.iterators import PrefetchIterator

    if args.train_npz:
        # File-backed path: on-disk numpy data (mmap'd when a .npy dir),
        # sharded across host processes exactly as the reference's
        # scatter_dataset split the corpus across MPI ranks; the per-chip
        # split happens at batch time (shard_batch), the two-level path.
        ds = cmn.scatter_dataset(
            NpzDataset(args.train_npz), comm, shuffle=True, seed=0
        )
        nproc = max(jax.process_count(), 1)
        if args.batchsize % nproc:
            raise SystemExit(
                f"--batchsize {args.batchsize} must be divisible by the "
                f"process count ({nproc}): a truncated per-host batch would "
                "silently change the effective global batch"
            )
        local_bs = args.batchsize // nproc
    else:
        # Synthetic epoch-resident image pool fed through the NATIVE
        # prefetch loader (the reference example's MultiprocessIterator
        # role): C++ worker threads assemble the next batches into a ring
        # of reusable buffers while the chip runs the current step.
        pool = args.iters_per_epoch * args.batchsize
        # Generate directly in float32 (rng.uniform would materialize a
        # float64 intermediate — 2x the pool, ~15 GB at default args).
        rng = np.random.default_rng(0)
        xs = rng.random(
            (pool, args.image_size, args.image_size, 3), dtype=np.float32
        )
        ys = (xs.mean(axis=(1, 2, 3)) * args.num_classes).astype(
            np.int32
        ).clip(0, args.num_classes - 1)
        ds = ArrayDataset(xs, ys)
        local_bs = args.batchsize
    it = PrefetchIterator(ds, local_bs, shuffle=True, seed=0)
    # Second pipeline stage: keep the next batches resident ON DEVICE so the
    # host→device transfer overlaps the previous step's compute (the
    # reference's pinned-buffer staging role, done with async dispatch).
    it = cmn.create_device_prefetch_iterator(it, comm, depth=2)
    step_kwargs = {}
    if args.augment:
        from chainermn_tpu.ops import random_crop_flip

        # Reference parity: the example's host-side random crop/flip
        # transforms, moved onto the chip (fused into the step's prologue).
        step_kwargs["augment"] = random_crop_flip(padding=4)
    trainer = Trainer(opt, state, loss_fn, it, stop=(args.epoch, "epoch"),
                      stateful=stateful, has_aux=not stateful,
                      step_kwargs=step_kwargs)
    trainer.extend(LogReport(trigger=(1, "epoch")))

    # Validation via the multi-node evaluator (reference parity: the example
    # attached a per-epoch evaluator) — top-1 accuracy on a held-out split,
    # aggregated mask-exactly across devices/processes.  BN models evaluate
    # with the live running stats threaded through the metric params.
    from chainermn_tpu.extensions import Evaluator, create_multi_node_evaluator
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.training import Extension

    if args.val_npz:
        val_ds = cmn.scatter_dataset(NpzDataset(args.val_npz), comm)
    else:
        vrng = np.random.default_rng(1)  # held-out seed ≠ training pool's
        vx = vrng.random(
            (args.val_size, args.image_size, args.image_size, 3),
            dtype=np.float32,
        )
        vy = (vx.mean(axis=(1, 2, 3)) * args.num_classes).astype(
            np.int32
        ).clip(0, args.num_classes - 1)
        val_ds = ArrayDataset(vx, vy)

    def val_metric(pm, batch):
        import jax.numpy as jnp

        vars_ = {"params": pm[0]}
        if stateful:
            vars_["batch_stats"] = pm[1]
        logits = model.apply(vars_, batch[0], train=False)
        acc = (jnp.argmax(logits, -1) == batch[1]).astype(jnp.float32)
        return {"val/accuracy": acc}

    evaluator = create_multi_node_evaluator(
        Evaluator(
            lambda: SerialIterator(val_ds, local_bs, repeat=False,
                                   shuffle=False),
            val_metric, comm,
        ),
        comm,
    )

    def run_eval(tr):
        metrics = evaluator.evaluate(
            (tr.state.params, tr.state.model_state)
        )
        if jax.process_index() == 0:
            print("  ".join(f"{k} {v:.4f}" for k, v in metrics.items()),
                  flush=True)

    trainer.extend(Extension(run_eval, trigger=(1, "epoch"),
                             name="validation"))
    if args.checkpoint:
        ckpt = cmn.create_multi_node_checkpointer(
            "imagenet", comm, path=args.checkpoint, trigger=(1, "epoch")
        )
        trainer.extend(ckpt)
        ckpt.maybe_load(trainer.state, trainer)
    trainer.run()


if __name__ == "__main__":
    main()

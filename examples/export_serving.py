#!/usr/bin/env python
"""Train → freeze → serve: the deployment path.

No reference analog (ChainerMN had no export story).  Trains a small
classifier data-parallel, freezes the trained forward into a portable
StableHLO artifact (``utils.export``, batch-polymorphic), then "serves" it
from a fresh callable that needs no model code — the shape a production
inference binary consumes.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/export_serving.py --force-cpu
"""

import argparse

import jax


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--out", default="result/served_model.hlo")
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.datasets import make_synthetic_classification
    from chainermn_tpu.models import MLP, classification_loss
    from chainermn_tpu.utils.export import load_forward_file, save_forward

    comm = cmn.create_communicator("xla")
    model = MLP(hidden=(64,), n_out=10)
    ds = make_synthetic_classification(4096, 32, seed=1)
    x, y = ds.arrays
    params = model.init(jax.random.PRNGKey(0), x[:1])["params"]
    opt = cmn.create_multi_node_optimizer(optax.adam(1e-3), comm)
    state = opt.init(params)
    loss_fn = classification_loss(model)
    bs = 256
    for i in range(args.steps):
        j = (i * bs) % (len(x) - bs)
        state, m = opt.update(state, (x[j:j + bs], y[j:j + bs]), loss_fn,
                              has_aux=True)
    if jax.process_index() == 0:
        print(f"trained: loss {float(m['loss']):.4f} "
              f"acc {float(m['accuracy']):.4f}")

    # Freeze: params baked in, batch dim symbolic — one artifact, any batch.
    trained = jax.device_get(state.params)

    def forward(inp):
        return model.apply({"params": trained}, inp)

    import os

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    path = save_forward(args.out, forward, x[:8], poly_batch=True)

    # Serve: reload WITHOUT the model/library state, run odd batch sizes.
    serve = load_forward_file(path)
    for b in (1, 7, 64):
        logits = np.asarray(serve(x[:b]))
        ref = np.asarray(forward(x[:b]))
        np.testing.assert_allclose(logits, ref, atol=1e-6)
    held_acc = float(
        (np.asarray(serve(x)).argmax(-1) == y).mean()
    )
    if jax.process_index() == 0:
        print(f"served artifact: {path} "
              f"({os.path.getsize(path)} bytes)  train-set acc "
              f"{held_acc:.4f}")


if __name__ == "__main__":
    main()

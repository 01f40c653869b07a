"""Observability overhead: the LM train step with the stack on vs off.

The observability subsystem is DEFAULT-ON, so its cost must be proven, not
assumed: this bench drives the identical jitted TransformerLM train step
through the :class:`~chainermn_tpu.training.Trainer` twice — once with the
full default-on stack (per-step registry publishers, step trace
annotations, a cadenced :class:`~chainermn_tpu.training.MetricsReport`
with rank-0 aggregation) and once with observability forced off
(``set_enabled(False)``: every publisher short-circuits, no extension
attached) — and reports the per-step delta.  The jitted step executable is
shared between arms (same optimizer, same loss callable → same step
cache), so the A/B isolates the host-side observability cost.

Contract (ISSUE 4 / docs/observability.md): overhead < 1% of step time at
real workload geometry.  The per-step cost is two instrument updates and
one TraceAnnotation; the cadenced cost is one float() sync + a small
object-plane gather per ``--report-every`` steps.

    python benchmarks/observability.py --out result/obs_overhead_tpu.json
    JAX_PLATFORMS=cpu python benchmarks/observability.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time


class _RepeatIterator:
    """Yields the same global batch forever (epoch never advances — the
    bench stops on iteration count)."""

    def __init__(self, batch):
        self._batch = batch
        self.epoch = 0

    def __next__(self):
        return self._batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--d-ff", type=int, default=3072)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--report-every", type=int, default=10,
                    help="MetricsReport cadence in the obs-on arm (the "
                         "float() metric sync + rank-0 gather interval)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu import observability as obs
    from chainermn_tpu.models import TransformerLM, lm_loss
    from chainermn_tpu.training import MetricsReport, Trainer

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.smoke:
        print(json.dumps({
            "error": f"observability bench needs a TPU (got {platform}); "
                     "pass --smoke for a CPU plumbing check"
        }))
        return
    if args.smoke:
        args.batch, args.seq, args.layers = 8, 128, 2
        args.d_model, args.heads, args.d_ff, args.vocab = 128, 4, 256, 1024
        # Warmup generous relative to iters: XLA:CPU's first executions
        # run well below steady state, and the smoke tier only checks
        # plumbing — the overhead NUMBER is meaningful on a real chip.
        args.iters, args.warmup = 8, 4
        args.report_every = 2
    if platform == "cpu":
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    comm = cmn.create_communicator("xla")
    model = TransformerLM(
        vocab=args.vocab, n_layers=args.layers, d_model=args.d_model,
        n_heads=args.heads, d_ff=args.d_ff, max_len=args.seq,
    )
    params = jax.jit(
        lambda r: model.init(r, np.zeros((1, args.seq), np.int32))
    )(jax.random.PRNGKey(0))["params"]
    loss_fn = lm_loss(model)
    rng = np.random.RandomState(0)
    toks = rng.randint(
        0, args.vocab, size=(args.batch, args.seq)
    ).astype(np.int32)
    batch = (toks, toks)
    _opt0 = cmn.create_multi_node_optimizer(optax.adamw(3e-4), comm)
    state0 = _opt0.init(params)

    obs_dir = tempfile.mkdtemp(prefix="cmn_obs_bench_")

    def run_arm(on: bool) -> float:
        """Per-step wall ms through the Trainer.  Each arm builds its
        OWN optimizer (→ its own jitted step, compiled in that arm's
        warmup): the compile-watch wrap latches at the step's birth
        (ISSUE 11), so a step born in the off arm would be a raw jit and
        the on arm would silently measure a stack with its fourth plane
        missing.  Identical programs compile identically; the compile
        lands in the warmup either way, never in the timed window."""
        obs.set_enabled(on)
        opt = cmn.create_multi_node_optimizer(optax.adamw(3e-4), comm)
        try:
            # device=True: the obs-on arm carries the FULL stack under
            # measurement, compile watcher + device roofline gauges
            # included (ISSUE 11 — the A/B proves the fourth plane also
            # fits the <1% contract; the one-time cost capture lands in
            # the arm's warmup, not the timed window).
            exts = (
                [MetricsReport(comm, trigger=(args.report_every,
                                              "iteration"),
                               out_dir=os.path.join(obs_dir, "on"),
                               device=True)]
                if on else []
            )
            # Fresh trainer + a fresh COPY of the state per arm: the step
            # donates its input, so handing both arms the same buffers
            # would leave arm B reading deleted arrays.
            import jax.numpy as jnp

            trainer = Trainer(
                opt, jax.tree_util.tree_map(jnp.array, state0),
                loss_fn, _RepeatIterator(comm.shard_batch(batch)),
                stop=(args.warmup, "iteration"), has_aux=True,
            )
            trainer.run()  # warmup (compile on first arm, cache after)
            if on:
                # Pre-warm the device plane's ONE-TIME cost capture (an
                # extra lowering of the step) outside the timed window —
                # the A/B measures the steady-state cost of the plane,
                # exactly as step compiles live in the warmup.
                from chainermn_tpu.observability import device as odev

                wf = odev.watch().find("train_step")
                if wf is not None:
                    wf.cost_analysis()
            trainer.stop_n = args.warmup + args.iters
            trainer.extensions = list(exts)
            t0 = time.perf_counter()
            trainer.run()
            _ = float(np.asarray(trainer.last_metrics["loss"]))
            return (time.perf_counter() - t0) / args.iters * 1000.0
        finally:
            obs.set_enabled(None)

    # Off first (pays the compile inside its warmup), then on; both timed
    # regions run the cached executable only.
    off_ms = run_arm(False)
    on_ms = run_arm(True)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0

    payload = {
        "metric": "observability_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "% of step time (obs default-on vs forced off)",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "step_ms_obs_off": round(off_ms, 3),
        "step_ms_obs_on": round(on_ms, 3),
        "report_every": args.report_every,
        "iters": args.iters,
        "config": {"batch": args.batch, "seq": args.seq,
                   "layers": args.layers, "d_model": args.d_model,
                   "heads": args.heads, "d_ff": args.d_ff,
                   "vocab": args.vocab},
        "contract": "overhead < 1% of step time (docs/observability.md)",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    print(json.dumps(payload))
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(payload, args.out)


if __name__ == "__main__":
    main()

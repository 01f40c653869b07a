"""Training goodput under chaos: peer fast-restore vs orbax-only, plus
the replication plane's steady-state overhead.

Three questions, one artifact (ISSUE 18):

1. **Goodput** — the same LM workload driven to a target step through a
   SEEDED crash schedule (:func:`chaos_schedule` /
   :class:`TrainingChaosHarness`), once with the replication plane as the
   restore tier and once with orbax-only checkpoints at the SAME cadence.
   Headline = useful-steps/wall-clock, reported as the peer/orbax ratio.
2. **Recovery latency** — ``recovery_ms`` p50 per arm: the replication
   restore is a local spill read + install; the orbax restore pays full
   checkpoint-manager I/O.  The acceptance bar is peer < orbax.
3. **Overhead** — the obs A/B discipline on the replication plane itself:
   identical train steps with the replicator attached vs absent, each arm
   with its own optimizer (compile lands in that arm's warmup, never the
   timed window).  Contract: < 1% of step time (docs/resilience.md).

Single-process honesty: in-process restores report
``restore_source=local`` (this process holds its own spill); the PEER
serve path is proven end-to-end across OS ranks by
``tests/multiprocess_tests/test_replicate_multiprocess.py``.  The
recovery-latency comparison is unaffected — both tiers restore the same
snapshot bytes.

    python benchmarks/resilience.py --out result/resilience_tpu.json
    JAX_PLATFORMS=cpu python benchmarks/resilience.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import tempfile
import time


class _RepeatIterator:
    """Yields the same global batch forever (the bench stops on
    iteration count)."""

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
    ap.add_argument("--target-step", type=int, default=40)
    ap.add_argument("--cadence", type=int, default=8)
    ap.add_argument("--failures", type=int, default=2)
    ap.add_argument("--seed", type=int, default=18)
    ap.add_argument("--overhead-iters", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.extensions import create_multi_node_checkpointer
    from chainermn_tpu.models import TransformerLM, lm_loss
    from chainermn_tpu.resilience.consistency import tree_digest
    from chainermn_tpu.resilience.replicate import (
        ShardReplicator,
        TrainingChaosHarness,
        chaos_schedule,
        negotiate_restore,
    )
    from chainermn_tpu.training import Trainer

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.smoke:
        print(json.dumps({
            "error": f"resilience bench needs a TPU (got {platform}); "
                     "pass --smoke for a CPU plumbing check"
        }))
        return
    if args.smoke:
        args.batch, args.seq, args.layers = 8, 128, 2
        args.d_model, args.heads, args.d_ff, args.vocab = 128, 4, 256, 1024
        args.target_step, args.cadence, args.failures = 16, 4, 2
        args.overhead_iters, args.warmup = 8, 4
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
    # ONE optimizer for every chaos attempt and the oracle: each attempt
    # replays the identical jitted step from the cache — a recompile
    # inside an attempt would masquerade as recovery cost.
    opt = cmn.create_multi_node_optimizer(optax.adamw(3e-4), comm)
    state0 = opt.init(params)
    import jax.numpy as jnp

    def fresh_trainer(stop):
        return Trainer(
            opt, jax.tree_util.tree_map(jnp.array, state0), loss_fn,
            _RepeatIterator(comm.shard_batch(batch)),
            stop=(stop, "iteration"), has_aux=True,
        )

    # ---- unfaulted oracle (also the compile warmup) --------------------
    t_oracle0 = time.perf_counter()
    oracle_tr = fresh_trainer(args.target_step)
    oracle_tr.run()
    oracle_wall = time.perf_counter() - t_oracle0
    oracle_digest = tree_digest(oracle_tr.state.params)

    work_dir = tempfile.mkdtemp(prefix="cmn_resilience_bench_")
    schedule = chaos_schedule(
        seed=args.seed, failures=args.failures,
        target_step=args.target_step, cadence=args.cadence,
        kinds=("crash",),
    )

    def run_mode(mode: str) -> dict:
        """One full chaos run to the target step; ``mode`` picks the
        restore tier: ``"rep"`` (ShardReplicator + negotiate_restore, no
        orbax anywhere) or ``"orbax"`` (MultiNodeCheckpointer at the SAME
        cadence, maybe_load on relaunch)."""
        tag_dir = os.path.join(work_dir, mode)

        def run_attempt(attempt, event):
            trainer = fresh_trainer(args.target_step)
            if mode == "rep":
                rep = ShardReplicator(
                    comm if comm.size > 1 else None,
                    every=args.cadence, spill_dir=tag_dir,
                    _use_process_injector=False,
                )
                trainer.extend(rep)
            else:
                ckpt = create_multi_node_checkpointer(
                    "bench", comm, path=tag_dir,
                    trigger=(args.cadence, "iteration"), async_save=False,
                )
                trainer.extend(ckpt)
            restored, source, recovery_ms = 0, None, None
            if attempt > 0:
                t0 = time.perf_counter()
                if mode == "rep":
                    new_state, it, rpt = negotiate_restore(
                        rep, trainer.state, trainer=trainer)
                    source, recovery_ms = rpt["source"], rpt["recovery_ms"]
                else:
                    new_state, it = ckpt.maybe_load(trainer.state, trainer)
                    source = "orbax"
                    recovery_ms = (time.perf_counter() - t0) * 1000.0
                trainer.state, trainer.iteration = new_state, it
                restored = int(it)
            # The "crash": the attempt ends at the event iteration (the
            # teardown/relaunch cost is the launcher's, identical for
            # both tiers — what differs, and what this measures, is the
            # restore path and the work replayed).
            if event is not None:
                trainer.stop_n = int(event["iter"])
            trainer.run()
            crashed = event is not None and \
                trainer.iteration < args.target_step
            if mode == "orbax":
                ckpt.finalize()
                ckpt.close()
            return {
                "rc": 1 if crashed else 0,
                "final_step": int(trainer.iteration),
                "restored_step": restored,
                "restore_source": source,
                "recovery_ms": recovery_ms,
                "digest": (
                    tree_digest(trainer.state.params)
                    if not crashed else None
                ),
            }

        result = TrainingChaosHarness(run_attempt, schedule).run()
        result["verdict"] = TrainingChaosHarness.verify(
            result, oracle_digest if mode == "rep" else None)
        return result

    rep = run_mode("rep")
    orbax = run_mode("orbax")

    def p50(xs):
        return round(statistics.median(xs), 3) if xs else None

    # ---- steady-state overhead A/B (replication on vs off) -------------
    def overhead_arm(on: bool) -> float:
        # Per-arm optimizer: the jitted step is born (and compiled)
        # inside this arm's warmup — the same compile-pinning discipline
        # as benchmarks/observability.py.
        arm_opt = cmn.create_multi_node_optimizer(optax.adamw(3e-4), comm)
        trainer = Trainer(
            arm_opt, jax.tree_util.tree_map(jnp.array, state0), loss_fn,
            _RepeatIterator(comm.shard_batch(batch)),
            stop=(args.warmup, "iteration"), has_aux=True,
        )
        trainer.run()  # warmup: compile out of the timed window
        if on:
            trainer.extend(ShardReplicator(
                None, every=args.cadence,
                spill_dir=os.path.join(work_dir, "overhead"),
                _use_process_injector=False,
            ))
        trainer.stop_n = args.warmup + args.overhead_iters
        t0 = time.perf_counter()
        trainer.run()
        _ = float(np.asarray(trainer.last_metrics["loss"]))
        return (time.perf_counter() - t0) / args.overhead_iters * 1000.0

    off_ms = overhead_arm(False)
    on_ms = overhead_arm(True)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0

    goodput_ratio = (
        rep["goodput_steps_per_s"] / orbax["goodput_steps_per_s"]
        if orbax["goodput_steps_per_s"] else None
    )
    payload = {
        "metric": "train_chaos_goodput",
        "value": round(goodput_ratio, 3) if goodput_ratio else None,
        "unit": "peer-restore goodput / orbax-only goodput (same seeded "
                "crash schedule)",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "seed": args.seed,
        "target_step": args.target_step,
        "cadence": args.cadence,
        "failures": len(schedule["events"]),
        "oracle_wall_s": round(oracle_wall, 3),
        "rep": {
            "goodput_steps_per_s": round(rep["goodput_steps_per_s"], 3),
            "wall_s": round(rep["wall_s"], 3),
            "recovery_ms_p50": p50(rep["recovery_ms"]),
            "lost_steps_per_failure": rep["lost_steps_per_failure"],
            "bit_exact_vs_oracle": rep["final_digest"] == oracle_digest,
            "invariant_holds": rep["verdict"]["holds"],
        },
        "orbax": {
            "goodput_steps_per_s": round(orbax["goodput_steps_per_s"], 3),
            "wall_s": round(orbax["wall_s"], 3),
            "recovery_ms_p50": p50(orbax["recovery_ms"]),
            "lost_steps_per_failure": orbax["lost_steps_per_failure"],
        },
        "recovery_ms_peer_p50": p50(rep["recovery_ms"]),
        "recovery_ms_orbax_p50": p50(orbax["recovery_ms"]),
        "rep_overhead_pct": round(overhead_pct, 3),
        "step_ms_rep_off": round(off_ms, 3),
        "step_ms_rep_on": round(on_ms, 3),
        "restore_note": "single-process restores report source=local; "
                        "the peer serve path is proven by "
                        "tests/multiprocess_tests/"
                        "test_replicate_multiprocess.py",
        "contract": "peer recovery_ms p50 < orbax p50; replication "
                    "overhead < 1% of step time (docs/resilience.md)",
        "config": {"batch": args.batch, "seq": args.seq,
                   "layers": args.layers, "d_model": args.d_model,
                   "heads": args.heads, "d_ff": args.d_ff,
                   "vocab": args.vocab},
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    print(json.dumps(payload))
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(payload, args.out)


if __name__ == "__main__":
    main()

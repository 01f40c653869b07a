"""MoE-LM training throughput: dense FFN vs mixture-of-experts at MATCHED
active FLOPs per token.

The EP subsystem's perf story (SURVEY.md §2.3 EP row — the reference shipped
only the ``alltoall`` building block; VERDICT r4 missing #2 asked for the
measured payoff).  One GPT-2-small trunk; the dense arm runs ``d_ff = k·F``,
the MoE arms run ``E`` experts of per-expert width ``F`` with top-``k``
routing, so every arm spends the same expert matmul FLOPs per token — the
measured delta IS the routing overhead (router + dispatch/combine einsums +
load-imbalance drops), i.e. the price of decoupling parameter count from
active compute.  Capacity-factor sweep records the drop-rate/overhead trade.

    python benchmarks/moe.py --out result/moe_tpu.json       # real chip
    JAX_PLATFORMS=cpu python benchmarks/moe.py --smoke ...    # plumbing
"""

from __future__ import annotations

import argparse
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--expert-ff", type=int, default=1536,
                    help="per-expert hidden width F; the dense arm runs "
                         "d_ff = k*F so active FLOPs match")
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--moe-k", type=int, default=2)
    ap.add_argument("--capacity-factors", default="1.0,1.25,2.0")
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import TransformerLM, lm_loss_chunked
    from chainermn_tpu.utils import compiled_flops, mfu

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    if platform != "tpu" and not args.smoke:
        print(json.dumps({
            "error": f"moe bench needs a TPU (got {platform}); "
                     "pass --smoke for a CPU plumbing check"
        }))
        return
    if args.smoke:
        args.batch, args.seq, args.layers = 8, 128, 2
        args.d_model, args.heads, args.expert_ff = 64, 2, 128
        args.experts, args.vocab, args.iters = 4, 512, 2
    if platform == "cpu":
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    cfs = [float(s) for s in args.capacity_factors.split(",")]
    out = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n_dev,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": vars(args),
        "note": "dense arm d_ff = k*expert_ff: identical active expert "
                "FLOPs/token; MoE deltas = routing overhead + drops",
    }

    comm = cmn.create_communicator("xla", allreduce_grad_dtype=jnp.bfloat16)
    tokens_per_step = args.batch * args.seq
    rng = np.random.RandomState(0)
    toks = rng.randint(
        0, args.vocab, size=(args.batch, args.seq)
    ).astype(np.int32)
    batch = comm.shard_batch((toks, toks))

    def run_arm(label, **model_kw):
        model = TransformerLM(
            vocab=args.vocab, n_layers=args.layers, d_model=args.d_model,
            n_heads=args.heads, max_len=args.seq, attention="auto",
            remat=True, **model_kw,
        )
        # adafactor both arms: the MoE arm's E/k-fold parameter surplus
        # with adamw fp32 moments would confound the throughput compare
        # with an optimizer-memory story.
        opt = cmn.create_multi_node_optimizer(optax.adafactor(3e-4), comm)
        params = jax.jit(
            lambda r: model.init(r, jnp.zeros((1, args.seq), jnp.int32))
        )(jax.random.PRNGKey(0))["params"]
        n_params = sum(x.size for x in jax.tree.leaves(params))
        state = jax.block_until_ready(jax.jit(opt.init)(params))
        step = opt.make_train_step(
            lm_loss_chunked(model, chunk_size=8192), has_aux=True
        )
        compiled = step.lower(state, batch).compile()
        flops = compiled_flops(compiled)
        for _ in range(2):
            state, metrics = step(state, batch)
            _ = float(metrics["loss"])  # device→host sync
        t0 = time.perf_counter()
        for _ in range(args.iters):
            state, metrics = step(state, batch)
        _ = float(metrics["loss"])
        dt = time.perf_counter() - t0
        rec = {
            "label": label,
            "n_params_m": round(n_params / 1e6, 1),
            "step_ms": round(dt / args.iters * 1000.0, 2),
            "tokens_per_sec_per_chip": round(
                tokens_per_step * args.iters / dt / n_dev, 1
            ),
        }
        if flops:
            rec["tflops_per_step"] = round(flops / 1e12, 3)
            m = mfu(compiled, dt / args.iters, n_dev, out["device_kind"])
            if m is not None:
                rec["mfu_pct"] = round(m, 2)
            from chainermn_tpu.ops import resolve_attention
            from chainermn_tpu.utils import (
                attention_core_flops,
                flash_mfu_fields,
            )

            if m is not None and resolve_attention(
                    "auto", args.seq, causal=True) == "flash":
                # The trunk's auto-attention resolves to the Pallas flash
                # kernel at this T, which XLA's FLOP counter can't see —
                # mfu_pct is a lower bound; emit the inclusive number too.
                extra = args.layers * attention_core_flops(
                    args.batch, args.heads, args.seq,
                    args.d_model // args.heads, causal=True,
                    n_forward=2,  # remat=True re-runs the forward kernel
                )
                rec.update(flash_mfu_fields(
                    flops, extra, dt / args.iters, n_dev,
                    out["device_kind"],
                ))
        for key in ("moe_aux", "moe_dropped"):
            if key in metrics:
                rec[key] = round(float(metrics[key]), 4)
        held = jax.tree.leaves((params, state))
        del params, state, step, compiled
        for a in held:
            try:
                a.delete()
            except Exception:
                pass
        jax.clear_caches()
        return rec

    arms = [("dense", dict(d_ff=args.moe_k * args.expert_ff))]
    for cf in cfs:
        arms.append((
            f"moe_cf{cf:g}",
            dict(d_ff=args.expert_ff, n_experts=args.experts,
                 moe_k=args.moe_k, moe_capacity_factor=cf),
        ))

    retryable = False
    results = []
    for label, kw in arms:
        try:
            rec = run_arm(label, **kw)
        except Exception as e:
            # Same artifact discipline as benchmarks/lm.py: OOM is a real
            # property of the geometry (recordable); anything else is
            # transient — withhold the artifact.
            rec = {"label": label,
                   "error": f"{type(e).__name__}: {str(e)[:200]}"}
            if "RESOURCE_EXHAUSTED" not in str(e):
                retryable = True
            jax.clear_caches()
        results.append(rec)
        print(json.dumps(rec), flush=True)
        if retryable:
            break
    out["arms"] = results

    dense = next((r for r in results if r["label"] == "dense"
                  and "step_ms" in r), None)
    for r in results:
        if dense and r is not dense and "step_ms" in r:
            r["vs_dense_tokens"] = round(
                r["tokens_per_sec_per_chip"]
                / dense["tokens_per_sec_per_chip"], 3
            )
    print(json.dumps({k: v for k, v in out.items() if k != "config"}))
    measured = [r for r in results if "step_ms" in r]
    complete = bool(measured) and not retryable
    if args.out and complete:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(out, args.out)
    elif args.out:
        print(json.dumps({"error": "incomplete run; artifact withheld"}))
    if not complete:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

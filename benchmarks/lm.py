"""Transformer-LM training throughput: tokens/sec/chip + flash-vs-XLA ablation.

The second headline workload (the reference's seq2seq/lm family at modern
scale): full DP training step of the decoder-only :class:`TransformerLM` —
bf16 compute, flash attention — measured in tokens/sec/chip with an MFU
estimate from XLA's compiled flop count, plus the same model with
materialized-scores XLA attention to quantify the Pallas kernel's
end-to-end contribution.

    python benchmarks/lm.py --out result/lm_tpu.json        # real chip
    JAX_PLATFORMS=cpu python benchmarks/lm.py --smoke ...   # plumbing check
"""

from __future__ import annotations

import argparse
import json
import time


def artifact_disposition(measured, oom_recorded, retryable, accept_oom):
    """Should this run's --out artifact land?  (Unit-tested in
    tests/examples_tests/test_benchmarks_smoke.py.)

    * any arm measured, no transient → land (the honest partial record);
    * all arms OOM'd deterministically → land ONLY under --accept-oom
      (fit probes, where the OOM is the answer);
    * any transient (non-OOM) failure → withhold, so a mis-wrapped
      transient never freezes in as a permanent error-only artifact.
    """
    return bool(measured or (oom_recorded and accept_oom)) and not retryable


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--d-ff", type=int, default=3072)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize decoder blocks (jax.checkpoint)")
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="stream the LM head in vocab chunks of this size "
                         "(chunked_softmax_cross_entropy) instead of "
                         "materializing (B,T,vocab) logits")
    ap.add_argument("--pos-enc", default="learned",
                    choices=("learned", "rope"),
                    help="positional scheme (rope = rotary q/k, no table)")
    ap.add_argument("--arms", default="flash,xla",
                    help="comma-joined subset of flash,xla to measure — "
                         "e.g. --arms flash for geometries where the "
                         "materialized-scores arm is a known OOM "
                         "(longcontext_tpu.json: XLA cannot run T>=8192; "
                         "the T=4096 1.5B tier is borderline) so a doomed "
                         "arm never costs the measured one its artifact")
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adafactor"),
                    help="adafactor = factored second moments, no fp32 "
                         "momentum tensors — the low-memory tier that fits "
                         "GPT-2-XL-scale (1.5B) training on one 16 GB chip "
                         "where adamw's moments alone need ~12 GB")
    ap.add_argument("--param-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="parameter STORAGE dtype. bfloat16 halves the "
                         "persistent params+grads bytes (adafactor stats "
                         "follow) — the storage lever for >2B configs, "
                         "where fp32 params OOM on the 15.75 GB chip")
    ap.add_argument("--lora", type=int, default=0, metavar="RANK",
                    help="LoRA fine-tuning step instead of full training: "
                         "frozen base params (in --param-dtype storage), "
                         "rank-RANK adapters on the attention projections, "
                         "optimizer state on the adapters only. Measures "
                         "the fine-tuning step time/MFU and records the "
                         "trainable-param fraction — the fits-where-full-"
                         "training-can't tier for >6B on one chip")
    ap.add_argument("--accept-oom", action="store_true",
                    help="an all-arms-OOM run still writes --out (the OOM "
                         "is the answer for a does-this-geometry-fit "
                         "stanza). Off by default so a mis-wrapped "
                         "transient at a known-good geometry can never "
                         "land a permanent error-only artifact")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CPU plumbing checks")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import TransformerLM, lm_loss, lm_loss_chunked

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    if platform != "tpu" and not args.smoke:
        # Same policy as flash_tpu.py / bench.py: never let a CPU-fallback
        # number land in the TPU artifact slot (--out is skipped too).
        print(json.dumps({
            "error": f"lm bench needs a TPU (got {platform}); "
                     "pass --smoke for a CPU plumbing check"
        }))
        return
    if args.smoke:
        # batch 8 divides any of the test meshes (1 device or the forced
        # 8-device CPU pool).
        args.batch, args.seq, args.layers = 8, 256, 2
        args.d_model, args.heads, args.d_ff, args.vocab = 128, 4, 256, 1024
        args.iters = 2
    if platform == "cpu":
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    out = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n_dev,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": {
            "batch": args.batch, "seq": args.seq, "layers": args.layers,
            "d_model": args.d_model, "heads": args.heads, "d_ff": args.d_ff,
            "vocab": args.vocab, "accum": args.accum, "remat": args.remat,
            "ce_chunk": args.ce_chunk, "optimizer": args.optimizer,
            "param_dtype": args.param_dtype,
            # Recorded so a deliberately single-arm artifact (--arms
            # flash at a known-XLA-OOM geometry) is distinguishable from
            # a full run whose other arm was lost.
            "arms": args.arms,
        },
    }

    comm = cmn.create_communicator("xla", allreduce_grad_dtype=jnp.bfloat16)
    tokens_per_step = args.batch * args.seq

    rng = np.random.RandomState(0)
    toks = rng.randint(0, args.vocab, size=(args.batch, args.seq)).astype(np.int32)
    batch = comm.shard_batch((toks, toks))

    def run_arm(impl):
        model = TransformerLM(
            vocab=args.vocab, n_layers=args.layers, d_model=args.d_model,
            n_heads=args.heads, d_ff=args.d_ff, max_len=args.seq,
            attention=impl, remat=args.remat, pos_enc=args.pos_enc,
            param_dtype=getattr(jnp, args.param_dtype),
        )
        base_opt = (
            optax.adafactor(3e-4)
            if args.optimizer == "adafactor"
            else optax.adamw(3e-4)
        )
        opt = cmn.create_multi_node_optimizer(base_opt, comm)
        # Jit both inits: one compiled program each instead of an eager
        # flax/optax init's hundreds of op-by-op compiles and dispatches.
        params = jax.jit(
            lambda r: model.init(r, jnp.zeros((1, args.seq), jnp.int32))
        )(jax.random.PRNGKey(0))["params"]
        base_params = None
        inner_loss = (
            lm_loss_chunked(model, chunk_size=args.ce_chunk)
            if args.ce_chunk
            else lm_loss(model)
        )
        if args.lora:
            # Fine-tuning tier: the optimizer's tree is the ADAPTER tree;
            # the frozen base stays alive as a closure constant of the
            # loss (so no donation / no drop — it must survive every
            # step).  Persistent memory: base params + rank-sized
            # adapters + adapter-sized opt state.
            from chainermn_tpu.models import (
                lora_init,
                lora_param_count,
                make_lora_loss,
            )

            base_params = params
            lora = jax.block_until_ready(jax.jit(
                lambda r: lora_init(r, base_params, rank=args.lora)
            )(jax.random.PRNGKey(1)))
            out["lora"] = {
                "rank": args.lora,
                "trainable_params": lora_param_count(lora),
                "total_params": sum(
                    int(x.size)
                    for x in jax.tree_util.tree_leaves(base_params)
                ),
            }
            # Same multi-host rule as the full-training path below:
            # opt.init goes through make_array_from_callback there, which
            # cannot run under a trace.
            state = (
                opt.init(lora)
                if jax.process_count() > 1
                else jax.block_until_ready(jax.jit(opt.init)(lora))
            )
            params = None
            loss_fn = make_lora_loss(inner_loss, base_params)
        elif jax.process_count() > 1:
            # Multi-host placement goes through make_array_from_callback,
            # which cannot run under a trace.
            state = opt.init(params)
        else:
            # DONATE the params into the jitted init: without donation the
            # init peak holds params TWICE (argument + the state's own copy
            # of them) plus the optimizer stats — params (fp32) + params +
            # stats ≈ 19.3 GB at 2.08B, an OOM before the first step even
            # though the steady-state step fits (the r5 fp32-2.08B attempt,
            # result/lm_2085m_stdout.log).  With donation XLA aliases the
            # argument buffers into the state and the peak is one params
            # copy + stats.  The params binding is dead afterwards either
            # way (donated; the state carries its own buffers) — dropping
            # it is the r4 dead-copy fix.  Not done on the multi-host path,
            # where opt.init may alias the caller's arrays into the state.
            state = jax.block_until_ready(
                jax.jit(opt.init, donate_argnums=0)(params)
            )
            params = None
        if not args.lora:
            loss_fn = inner_loss
        step = opt.make_train_step(loss_fn, has_aux=True,
                                   accum_steps=args.accum)

        # One shared flops/MFU implementation (utils.compiled_flops / mfu):
        # a local copy once drifted (stale `from bench import` silently
        # dropped mfu_pct from the artifact) — never again.
        from chainermn_tpu.utils import compiled_flops, mfu

        compiled = None
        try:
            compiled = step.lower(state, batch).compile()
            step = compiled
        except Exception as e:
            # A ResourceExhausted compile is a real property of the geometry
            # (note it, fall through to the per-call jit); anything else is
            # transient — re-raise so the outer handler withholds the
            # artifact.
            if not any(s in str(e) for s in (
                    "RESOURCE_EXHAUSTED", "Ran out of memory")):
                raise
            out[f"{impl}_compile_note"] = f"{type(e).__name__}: {str(e)[:150]}"
        flops = compiled_flops(compiled) if compiled is not None else None

        for _ in range(2):  # warmup
            state, metrics = step(state, batch)
            _ = float(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(args.iters):
            state, metrics = step(state, batch)
        _ = float(metrics["loss"])  # sequential dependency bounds the chain
        dt = time.perf_counter() - t0

        step_ms = dt / args.iters * 1000.0
        tps = tokens_per_step * args.iters / dt / n_dev
        rec = {"step_ms": round(step_ms, 2),
               "tokens_per_sec_per_chip": round(tps, 1)}
        if flops:
            rec["tflops_per_step"] = round(flops / 1e12, 3)
            m = mfu(compiled, dt / args.iters, n_dev, out["device_kind"])
            if m is not None:
                rec["mfu_pct"] = round(m, 2)
            if impl == "flash" and m is not None:
                # XLA's cost analysis cannot see inside Pallas custom
                # calls, so the flash arm's attention-core FLOPs are
                # missing from mfu_pct (a lower bound).  Add the analytic
                # core count (utils.attention_core_flops) and emit the
                # inclusive number alongside, clearly labeled.
                from chainermn_tpu.utils import (
                    attention_core_flops,
                    flash_mfu_fields,
                )

                extra = args.layers * attention_core_flops(
                    args.batch, args.heads, args.seq,
                    args.d_model // args.heads, causal=True,
                    n_forward=2 if args.remat else 1,
                )
                rec.update(flash_mfu_fields(
                    flops, extra, dt / args.iters, n_dev,
                    out["device_kind"],
                ))
        # Free this arm's HBM before the next arm compiles: at 774M the
        # fp32 params + adamw moments are ~9 GB — two arms alive at once
        # exceeded the 15.75 GB chip (RESOURCE_EXHAUSTED at the second
        # opt.init, 2026-08-01), killing the run after the flash number
        # had already been measured.
        held = jax.tree.leaves((params, state, base_params))
        del params, state, step, compiled, base_params
        for a in held:
            try:
                a.delete()
            except Exception:
                pass
        jax.clear_caches()
        return rec

    arms = tuple(a for a in args.arms.split(",") if a)
    if not arms or any(a not in ("flash", "xla") for a in arms):
        raise SystemExit(f"--arms {args.arms!r}: subset of flash,xla")
    retryable = False
    for impl in arms:
        try:
            out[impl] = run_arm(impl)
        except Exception as e:
            # An OOM'd ablation arm must not cost the measured arm(s): the
            # artifact lands with what succeeded plus an honest error record.
            # ONLY ResourceExhausted is a recordable outcome (a real property
            # of the geometry on this chip) — anything else (lost device,
            # coordination error) says nothing about the geometry and must
            # not be baked into an artifact that reads as a finished run.
            out[impl] = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            if not any(s in str(e) for s in (
                    "RESOURCE_EXHAUSTED", "Ran out of memory")):
                # "Ran out of memory": compile-time OOMs can say this
                # instead of RESOURCE_EXHAUSTED.
                retryable = True
            jax.clear_caches()
        print(json.dumps({impl: out[impl]}), flush=True)
        if retryable:
            # The run is already doomed to be withheld — don't burn chip
            # minutes compiling the remaining arm(s).
            break

    if "step_ms" in out.get("flash", {}) and "step_ms" in out.get("xla", {}):
        out["flash_speedup"] = round(
            out["xla"]["step_ms"] / out["flash"]["step_ms"], 3
        )
    print(json.dumps({k: v for k, v in out.items() if k != "config"}))
    measured = [k for k in ("flash", "xla") if "step_ms" in out.get(k, {})]
    oom_recorded = [
        k for k in ("flash", "xla") if "error" in out.get(k, {})
    ]
    # Only ResourceExhausted reaches oom_recorded without setting
    # `retryable`; see artifact_disposition for the landing contract.
    complete = artifact_disposition(
        measured, oom_recorded, retryable, args.accept_oom
    )
    if args.out:
        if complete:
            from chainermn_tpu.utils import atomic_json_dump

            atomic_json_dump(out, args.out)
        else:
            # Withheld: an arm died to a non-OOM error — leave --out
            # unwritten rather than land a degraded artifact.
            print(json.dumps({"error": "incomplete run; artifact withheld"}))
    if not complete:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

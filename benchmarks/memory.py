"""Memory-lever ablation: XLA's own buffer-assignment numbers per config.

For the Transformer-LM train step, compiles (never executes) each config and
records ``compiled.memory_analysis()`` — XLA's temp/argument/output buffer
sizes after fusion and scheduling.  This is the compiler's ground truth for
what the levers buy:

  * ``remat``    — decoder blocks rematerialized (``TransformerLM(remat=)``)
  * ``accum``    — gradient accumulation (``make_train_step(accum_steps=)``)
  * ``ce_chunk`` — chunked LM-head loss (``lm_loss_chunked``)

Lowering uses abstract ShapeDtypeStructs (``jax.eval_shape``), so no batch
or parameter arrays are materialized — the harness runs in seconds and needs
the device only as a compile target.  Numbers are per-platform (buffer
assignment differs between XLA:CPU and XLA:TPU); the TPU run is the honest
one (``result/memory_tpu.json``).

    python benchmarks/memory.py --out result/memory_tpu.json    # on TPU
    JAX_PLATFORMS=cpu python benchmarks/memory.py --smoke       # plumbing
"""

from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--d-ff", type=int, default=3072)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--ce-chunk", type=int, default=4096)
    ap.add_argument("--accum", type=int, default=4)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="explicitly permit a (clearly labeled) CPU run")
    ap.add_argument("--autopsy", action="store_true",
                    help="the 1.5B T=4096/B=1 OOM autopsy (VERDICT r4 "
                         "weak #4): compile the exact failing lm.py "
                         "geometry and its lever variants, and report "
                         "where the bytes go")
    ap.add_argument("--fitprobe", action="store_true",
                    help="the >2B storage-lever A/B: compile the 2.6B "
                         "(GPT-3-2.7B geometry) train step AND the donated "
                         "init program with fp32 vs bf16 param storage, "
                         "and report where the bytes go — compile-only "
                         "evidence for the param_dtype lever without "
                         "burning a full bench window")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.fitprobe:
        args.batch, args.seq = 1, 2048
        args.layers, args.d_model, args.heads = 32, 2560, 20
        args.d_ff, args.vocab = 10240, 32768
    if args.autopsy:
        # The config result/lm_1558m_t4096_stderr.log died on (both arms,
        # RESOURCE_EXHAUSTED on the 15.75 GB chip).
        args.batch, args.seq = 1, 4096
        args.layers, args.d_model, args.heads = 48, 1600, 25
        args.d_ff, args.vocab = 6400, 32768

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import jax.numpy as jnp
    import optax

    if (jax.devices()[0].platform != "tpu" and not args.smoke
            and not args.allow_cpu):
        # Same policy as the sibling benches: a CPU fallback must never
        # claim the TPU artifact slot (--out is skipped too).
        print(json.dumps({
            "error": f"memory ablation wants a TPU (got "
                     f"{jax.devices()[0].platform}); pass --smoke or "
                     "--allow-cpu for an explicitly labeled CPU run"
        }))
        return

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        TransformerLM,
        lm_loss,
        lm_loss_chunked,
    )

    if args.smoke:
        # batch 8 divides any of the test meshes (1 device or the forced
        # 8-device CPU pool) — same convention as lm.py's smoke config.
        args.batch, args.seq, args.layers = 8, 256, 2
        args.d_model, args.heads, args.d_ff = 128, 4, 256
        args.vocab, args.ce_chunk, args.accum = 1024, 256, 2

    comm = cmn.create_communicator("xla")
    out = {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "config": vars(args).copy(),
        "configs": {},
    }
    out["config"].pop("out", None)

    batch_abs = (
        jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
        jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
    )

    def analyze(name, remat=False, accum=1, ce_chunk=0, optimizer="adamw",
                param_dtype="float32", include_init=False):
        model = TransformerLM(
            vocab=args.vocab, n_layers=args.layers, d_model=args.d_model,
            n_heads=args.heads, d_ff=args.d_ff, max_len=args.seq,
            remat=remat, param_dtype=getattr(jnp, param_dtype),
        )
        loss_fn = (
            lm_loss_chunked(model, chunk_size=ce_chunk)
            if ce_chunk
            else lm_loss(model)
        )
        base_opt = (
            optax.adafactor(3e-4) if optimizer == "adafactor"
            else optax.adamw(3e-4)
        )
        opt = cmn.create_multi_node_optimizer(base_opt, comm)
        # Per-arm geometry recorded in the rec itself: the fitprobe's wall
        # arm re-points args at a different model size after the top-level
        # config snapshot, so the snapshot alone would misdescribe it.
        rec_geometry = {
            "layers": args.layers, "d_model": args.d_model,
            "heads": args.heads, "d_ff": args.d_ff,
            "batch": args.batch, "seq": args.seq,
            "param_dtype": param_dtype,
        }
        # Abstract all the way down: shapes of params/state via eval_shape,
        # so nothing is materialized on (or transferred to) the device.
        params_abs = jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, args.seq), jnp.int32)
            )["params"]
        )
        state_abs = jax.eval_shape(opt.init, params_abs)
        step = opt.make_train_step(loss_fn, has_aux=True, accum_steps=accum)
        rec = {"geometry": rec_geometry}
        if include_init:
            # The DONATED init program's own peak (benchmarks/lm.py runs
            # exactly this before the first step): with donation its
            # argument buffers alias into the state, so temp+output is the
            # honest init-time high-water mark — the live 2.08B fp32 OOM
            # happened here, not in the steady-state step.
            try:
                imem = (
                    jax.jit(opt.init, donate_argnums=0)
                    .lower(params_abs).compile().memory_analysis()
                )
                rec["init"] = {
                    k.replace("_in_bytes", "_mb"): round(
                        getattr(imem, k) / 2**20, 1
                    )
                    for k in (
                        "temp_size_in_bytes", "argument_size_in_bytes",
                        "output_size_in_bytes",
                    )
                    if getattr(imem, k, None) is not None
                }
            except Exception as e:
                # Same triage as the step path below: transients abort the
                # run (no artifact); only an OOM-ish verdict is a
                # recordable property of the geometry.
                msg = str(e)
                if not any(s in msg for s in (
                        "Ran out of memory", "RESOURCE_EXHAUSTED",
                        "hbm requirement", "tpu_compile_helper",
                )):
                    raise
                rec["init"] = {"compile_oom": True,
                               "compile_error": msg[:300]}
        try:
            mem = step.lower(state_abs, batch_abs).compile().memory_analysis()
        except Exception as e:
            # A config that doesn't fit fails AT COMPILE — and that failure
            # is the autopsy's subject, not a crash: record what the
            # compiler said and keep going so the lever variants that DO
            # fit report real memory_analysis numbers.  The compiler's
            # message format is not a contract, so the parse is
            # best-effort.
            import re

            msg = str(e)
            if any(t in msg for t in ("UNAVAILABLE", "DEADLINE_EXCEEDED")):
                # A lost device, not a memory verdict: abort with no
                # artifact — recording it would freeze an outage in as
                # compile_oom.
                raise
            oomish = any(s in msg for s in (
                "Ran out of memory", "RESOURCE_EXHAUSTED",
                "hbm requirement", "tpu_compile_helper",
            ))
            if not oomish:
                raise
            rec["compile_oom"] = True
            m = re.search(r"Used ([\d.]+)G of ([\d.]+)G hbm", msg)
            if m:
                rec["hbm_used_gb"], rec["hbm_capacity_gb"] = (
                    float(m.group(1)), float(m.group(2)))
            m = re.search(r"Program hbm requirement ([\d.]+)G", msg)
            if m:
                rec["program_hbm_requirement_gb"] = float(m.group(1))
            allocs = re.findall(
                r"Size: ([\d.]+[GMK])\s*\n\s*Operator: op_name=\"([^\"]+)\"",
                msg,
            )
            if allocs:
                rec["largest_allocations"] = [
                    {"size": s, "op": op} for s, op in allocs[:8]
                ]
            if len(rec) == 1:
                # Nothing parseable beyond the fact of failure — keep the
                # head of the message so the record stands alone.
                rec["compile_error"] = msg[:500]
            mem = None
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                rec[k.replace("_in_bytes", "_mb")] = round(v / 2**20, 1)
        # Where the persistent bytes go: params vs optimizer state, from
        # the abstract trees (exact — shapes and dtypes, no execution).
        rec["params_mb"] = round(sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(params_abs)
        ) / 2**20, 1)
        rec["opt_state_mb"] = round(sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(state_abs)
        ) / 2**20, 1) - rec["params_mb"]
        out["configs"][name] = rec
        print(json.dumps({name: rec}), flush=True)

    if args.autopsy:
        analyze("as_failed_adafactor_remat_ce8192", remat=True,
                ce_chunk=8192, optimizer="adafactor")
        analyze("ce2048", remat=True, ce_chunk=2048,
                optimizer="adafactor")
        analyze("ce512", remat=True, ce_chunk=512, optimizer="adafactor")
        analyze("adamw_for_scale", remat=True, ce_chunk=8192)
    elif args.fitprobe:
        analyze("fp32_params", remat=True, ce_chunk=8192,
                optimizer="adafactor", include_init=True)
        analyze("bf16_params", remat=True, ce_chunk=8192,
                optimizer="adafactor", param_dtype="bfloat16",
                include_init=True)
        if not args.smoke:
            # Where does the single-chip ladder END?  GPT-3-6.7B geometry
            # in the same bf16 layout: params alone are ~12.9 GiB — the
            # expected verdict is compile-OOM, recorded honestly as the
            # wall between 2.6B (fits) and 6.7B (cannot; needs ZeRO over
            # a real multi-chip mesh, optimizers/zero.py).
            args.layers, args.d_model, args.heads = 32, 4096, 32
            args.d_ff = 16384
            analyze("bf16_params_6700m_wall", remat=True, ce_chunk=8192,
                    optimizer="adafactor", param_dtype="bfloat16",
                    include_init=True)
    else:
        analyze("baseline")
        analyze("remat", remat=True)
        analyze(f"accum{args.accum}", accum=args.accum)
        analyze("ce_chunk", ce_chunk=args.ce_chunk)
        analyze("remat+accum+ce_chunk", remat=True, accum=args.accum,
                ce_chunk=args.ce_chunk)

    base = (out["configs"].get("baseline") or {}).get("temp_size_mb")
    if base:
        for name, rec in out["configs"].items():
            if "temp_size_mb" in rec:
                rec["temp_vs_baseline"] = round(rec["temp_size_mb"] / base, 3)
    print(json.dumps({k: v for k, v in out.items() if k != "config"}))
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(out, args.out)


if __name__ == "__main__":
    main()

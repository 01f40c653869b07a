"""Seq2seq (NMT family) training throughput: target tokens/sec/chip.

The third reference workload family (``examples/seq2seq`` — SURVEY §2.9)
measured at modern scale: full DP training step of the flash-kernel
:class:`TransformerSeq2Seq` on bucketed/padded variable-length batches
(the reference's ragged-batch story under XLA's static shapes), reported
in NON-PAD target tokens/sec/chip with the padding overhead stated, plus
the same model on materialized-scores XLA attention.

    python benchmarks/seq2seq.py --out result/seq2seq_tpu.json   # real chip
    JAX_PLATFORMS=cpu python benchmarks/seq2seq.py --smoke       # plumbing
"""

from __future__ import annotations

import argparse
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--src-len", type=int, default=512)
    ap.add_argument("--tgt-len", type=int, default=512)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--d-ff", type=int, default=2048)
    ap.add_argument("--enc", type=int, default=6)
    ap.add_argument("--dec", type=int, default=6)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--nonpad", type=float, default=0.87,
                    help="simulated non-pad fraction (the bucketing tier's "
                         "measured 0.87 at bucket_width=4)")
    ap.add_argument("--enc-attention", default=None,
                    choices=("flash", "xla", "auto"),
                    help="encoder-only attention override applied to BOTH "
                         "ablation arms (e.g. --enc-attention flash makes "
                         "the 'xla' arm the encoder-flash hybrid) — probes "
                         "the segment-masked non-causal encoder category "
                         "separately from the decoder's causal/cross rows")
    ap.add_argument("--packed", action="store_true",
                    help="train PACKED rows (datasets.pack_pairs: several "
                         "pairs per row, per-pair segment isolation) "
                         "instead of the bucketed/padded tier — non-pad "
                         "fraction rises from the bucketing 0.87 to the "
                         "measured packing efficiency (~0.95+)")
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
    from chainermn_tpu.models import TransformerSeq2Seq, seq2seq_loss
    from chainermn_tpu.models.seq2seq import PAD

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    if platform != "tpu" and not args.smoke:
        print(json.dumps({
            "error": f"seq2seq bench needs a TPU (got {platform}); "
                     "pass --smoke for a CPU plumbing check"
        }))
        return
    if args.smoke:
        args.batch, args.src_len, args.tgt_len = 8, 64, 64
        args.d_model, args.heads, args.d_ff = 64, 4, 128
        args.enc, args.dec, args.vocab, args.iters = 1, 1, 512, 2
    if platform == "cpu":
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    out = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n_dev,
        "config": {k: getattr(args, k.replace("-", "_")) for k in
                   ("batch", "src_len", "tgt_len", "d_model", "heads",
                    "d_ff", "enc", "dec", "vocab")},
        "enc_attention_override": args.enc_attention,
        "nonpad_fraction": None if args.packed else args.nonpad,
        "packed": args.packed,
    }

    comm = cmn.create_communicator("xla", allreduce_grad_dtype=jnp.bfloat16)

    rng = np.random.RandomState(0)
    if args.packed:
        # Packed rows: draw sentence pairs from a plausible NMT length
        # distribution and best-fit pack them (datasets.pack_pairs) until
        # `batch` rows exist.  Throughput is reported on non-pad target
        # tokens, so the packing efficiency directly becomes tokens/sec.
        from chainermn_tpu.datasets import pack_pairs, packing_efficiency

        def draw(mean, cap):
            L = int(np.clip(rng.normal(mean, 0.25 * mean), 4, cap))
            return rng.randint(3, args.vocab, size=L).astype(np.int32)

        pairs = []
        while True:
            pairs.extend(
                (draw(0.4 * args.src_len, args.src_len),
                 draw(0.4 * args.tgt_len, args.tgt_len))
                for _ in range(args.batch * 2)
            )
            src, tgt, sseg, tseg = pack_pairs(
                pairs, args.src_len, args.tgt_len
            )
            if src.shape[0] >= args.batch:
                break
        src, tgt = src[:args.batch], tgt[:args.batch]
        sseg, tseg = sseg[:args.batch], tseg[:args.batch]
        out["packing_efficiency"] = round(packing_efficiency(tseg), 4)
        batch = comm.shard_batch((src, tgt, sseg, tseg))
        real_tgt_tokens = int((tseg != 0).sum())
    else:
        # Bucketed/padded batch shape with the measured non-pad fraction:
        # the tail of each row is PAD (id 0), what bucket_batches emits.
        def make(lenq):
            toks = rng.randint(3, args.vocab,
                               size=(args.batch, lenq)).astype(np.int32)
            n_real = max(1, int(round(lenq * args.nonpad)))
            toks[:, n_real:] = PAD
            return toks
        batch = comm.shard_batch((make(args.src_len), make(args.tgt_len)))
        real_tgt_tokens = int(
            (np.asarray(jax.device_get(batch[1])) != PAD).sum()
        )

    def _transient(e):
        # A lost device (UNAVAILABLE / DEADLINE_EXCEEDED) is not an arm's
        # result: it must ABORT the run with no artifact — recording one
        # would freeze an outage in as a permanent "measurement".
        return any(t in str(e) for t in ("UNAVAILABLE", "DEADLINE_EXCEEDED"))

    for impl in ("flash", "xla"):
        if args.enc_attention == impl:
            # The override makes this arm identical to the uniform
            # configuration already captured elsewhere — don't spend chip
            # minutes re-measuring known data.
            continue
        # Resolved arm name, shared by success AND failure records — a bare
        # 'xla_error' under --enc-attention flash would misattribute the
        # hybrid arm's failure to the pure-XLA configuration.
        key = (
            f"enc_{args.enc_attention}_dec_{impl}"
            if args.enc_attention and args.enc_attention != impl
            else impl
        )
        model = TransformerSeq2Seq(
            vocab_src=args.vocab, vocab_tgt=args.vocab,
            d_model=args.d_model, n_heads=args.heads, d_ff=args.d_ff,
            n_enc=args.enc, n_dec=args.dec,
            max_len=max(args.src_len, args.tgt_len),
            dtype=jnp.bfloat16, attention=impl,
            enc_attention=args.enc_attention,
        )
        opt = cmn.create_multi_node_optimizer(optax.adamw(3e-4), comm)
        params = jax.jit(
            lambda r: model.init(
                r,
                jnp.zeros((1, args.src_len), jnp.int32),
                jnp.zeros((1, args.tgt_len), jnp.int32),
            )
        )(jax.random.PRNGKey(0))["params"]
        if jax.process_count() > 1:
            # Multi-host placement goes through make_array_from_callback,
            # which cannot run under a trace (same guard as lm.py).
            state = opt.init(params)
        else:
            state = jax.block_until_ready(jax.jit(opt.init)(params))
        step = opt.make_train_step(seq2seq_loss(model), has_aux=True)

        # Shared flops/MFU implementation (see lm.py's note on drift).
        from chainermn_tpu.utils import compiled_flops, mfu

        compiled = None
        try:
            compiled = step.lower(state, batch).compile()
            step = compiled
        except Exception as e:
            if _transient(e):
                raise
            out[f"{key}_compile_note"] = f"{type(e).__name__}: {str(e)[:150]}"
        flops = compiled_flops(compiled) if compiled is not None else None
        if compiled is None and any(
            s in out.get(f"{key}_compile_note", "")
            for s in ("Ran out of memory", "RESOURCE_EXHAUSTED")
        ):
            # Permanent compile OOM: the eager-jit fallback would recompile
            # for minutes and fail identically — the note IS this arm's
            # result.
            out[f"{key}_error"] = out[f"{key}_compile_note"]
            continue

        # A deterministic arm failure (e.g. the materialized-scores XLA arm
        # OOMs at T=2048 — 26.2G for B=16·H=8·T² decoder score tensors)
        # must not take the OTHER arm's finished measurement down with it:
        # record the failure as this arm's result and keep going.  Same
        # story the longcontext sweep tells — flash proceeding where XLA
        # cannot run at all IS the measurement.
        try:
            for _ in range(2):
                state, metrics = step(state, batch)
                _ = float(metrics["loss"])  # device->host sync
            t0 = time.perf_counter()
            for _ in range(args.iters):
                state, metrics = step(state, batch)
                _ = float(metrics["loss"])
            dt = time.perf_counter() - t0
        except Exception as e:
            if _transient(e):
                raise
            out[f"{key}_error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps({f"{key}_error": out[f"{key}_error"]}),
                  flush=True)
            continue

        rec = {
            "step_ms": round(dt / args.iters * 1000.0, 2),
            "nonpad_tgt_tokens_per_sec_per_chip": round(
                real_tgt_tokens * args.iters / dt / n_dev, 1
            ),
        }
        if flops:
            rec["tflops_per_step"] = round(flops / 1e12, 3)
            m = mfu(compiled, dt / args.iters, n_dev, out["device_kind"])
            if m is not None:
                rec["mfu_pct"] = round(m, 2)
                # Pallas flash kernels are invisible to XLA's FLOP
                # counter (mfu_pct is a lower bound for flash arms): add
                # the analytic attention-core count per component that
                # RESOLVES to flash.  'auto' components resolve the same
                # way the model does (segment-masked/causal rows use the
                # causal crossover — the packed tiers always carry
                # segment ids).
                from chainermn_tpu.ops import resolve_attention
                from chainermn_tpu.utils import (
                    attention_core_flops,
                    flash_mfu_fields,
                )

                dh = args.d_model // args.heads
                enc_impl = resolve_attention(
                    args.enc_attention or impl, args.src_len
                )
                dec_impl = resolve_attention(impl, args.tgt_len)
                cross_impl = resolve_attention(
                    impl, args.tgt_len, args.src_len
                )
                extra = 0.0
                if enc_impl == "flash":
                    extra += args.enc * attention_core_flops(
                        args.batch, args.heads, args.src_len, dh,
                        causal=False
                    )
                if dec_impl == "flash":
                    extra += args.dec * attention_core_flops(
                        args.batch, args.heads, args.tgt_len, dh,
                        causal=True
                    )
                if cross_impl == "flash":
                    extra += args.dec * attention_core_flops(
                        args.batch, args.heads, args.tgt_len, dh,
                        kv_len=args.src_len, causal=False
                    )
                rec.update(flash_mfu_fields(
                    flops, extra, dt / args.iters, n_dev,
                    out["device_kind"],
                ))
        out[key] = rec
        print(json.dumps({key: rec}), flush=True)

    if "flash" in out and "xla" in out:
        out["flash_speedup"] = round(
            out["xla"]["step_ms"] / out["flash"]["step_ms"], 3
        )
    print(json.dumps(out))
    if args.out and platform == "tpu":
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(out, args.out)


if __name__ == "__main__":
    main()

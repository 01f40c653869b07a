"""Long-context attention scaling on chip: flash vs sliding-window vs XLA.

SURVEY.md §5 makes long-context a first-class (beyond-parity) capability;
round 3 verified the Pallas kernels compile and win at T=2048.  This harness
measures how they SCALE: a sweep over sequence lengths at a constant total
token budget (B·T fixed, so HBM pressure and per-token cost stay
comparable), timing

  * full causal flash attention            — O(T²/2) work,
  * sliding-window flash (|q-k| < W)       — O(T·W) work,
  * XLA materialized-scores attention      — the baseline, skipped once the
    (B, H, T, T) score tensor would not fit (the point of flash),

fwd and fwd+bwd each, with achieved attention-FLOP/s so the O(T²) vs O(T·W)
curves are visible in one table.

    python benchmarks/longcontext.py --out result/longcontext_tpu.json
    JAX_PLATFORMS=cpu python benchmarks/longcontext.py --smoke ...
"""

from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=16384,
                    help="total tokens per config (batch = tokens // seq)")
    ap.add_argument("--seqs", default="2048,4096,8192,16384")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--xla-max-score-gb", type=float, default=2.0,
                    help="skip the XLA baseline when the bf16 (B,H,T,T) "
                         "score tensor alone would exceed this")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny interpret-mode config for CPU plumbing checks")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import time

    from chainermn_tpu.ops import flash_attention, reference_attention
    from chainermn_tpu.utils import sync

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.smoke:
        print(json.dumps({
            "error": f"longcontext sweep needs a TPU (got {platform}); "
                     "pass --smoke for a CPU plumbing check"
        }))
        return
    interpret = platform != "tpu"
    if args.smoke:
        args.tokens, args.seqs, args.window = 512, "256,512", 128
        args.heads, args.head_dim, args.iters = 2, 64, 2

    H, D, W = args.heads, args.head_dim, args.window
    seqs = [int(s) for s in args.seqs.split(",")]
    out = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "tokens_per_config": args.tokens,
        "heads": H, "head_dim": D, "window": W,
        "dtype": "bfloat16",
        "rows": [],
    }

    def flash_fn(window):
        def f(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   interpret=interpret)
        return f

    def xla_fn(q, k, v):
        return reference_attention(q, k, v, causal=True)

    def loss_of(fn):
        # Fixed cotangent so fwd+bwd exercises the real backward kernels.
        def loss(q, k, v):
            o = fn(q, k, v)
            return (o.astype(jnp.float32) ** 2).mean()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def bench(fn, *a):
        # Queue all iterations, then one data readback: the device runs
        # enqueued programs in order, so syncing the LAST output bounds all
        # of them — dispatch/readback latency is paid once, not per
        # iteration (flash_tpu.py's amortized pattern).
        sync(fn(*a))  # compile + warm
        sync(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            r = fn(*a)
        sync(r)
        return (time.perf_counter() - t0) / args.iters

    for T in seqs:
        B = max(1, args.tokens // T)
        rng = np.random.RandomState(0)
        shape = (B, T, H, D)
        q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)

        # Attention-only FLOPs (QKᵀ + PV, both 2·B·H·T_q·T_k·D multiply-adds
        # → factor 4), scaled by the visited fraction of the (T,T) plane.
        causal_frac = 0.5 * (1 + 1 / T)
        if W < T:
            # causal ∩ window: each query sees min(W, q+1) keys.
            win_frac = (min(W, T) * T - W * (W - 1) / 2) / (T * T)
        else:
            win_frac = causal_frac
        full_flops = 4.0 * B * H * T * T * D

        score_gb = B * H * T * T * 2 / 1e9
        variants = [
            ("flash_causal", flash_fn(None), causal_frac),
            ("flash_window", flash_fn(W), win_frac),
        ]
        if score_gb <= args.xla_max_score_gb:
            variants.append(("xla_causal", xla_fn, causal_frac))

        row = {"seq": T, "batch": B, "score_gb": round(score_gb, 2),
               "variants": {}}
        for name, raw_fn, frac in variants:
            fwd_s = bench(jax.jit(raw_fn), q, k, v)
            bwd_s = bench(loss_of(raw_fn), q, k, v)
            flops = full_flops * frac
            row["variants"][name] = {
                "fwd_ms": round(fwd_s * 1e3, 3),
                "fwd_bwd_ms": round(bwd_s * 1e3, 3),
                # bwd does ~2.5× the fwd attention work (dQ, dK, dV).
                "fwd_tflops_per_s": round(flops / fwd_s / 1e12, 2),
                "us_per_token_fwd_bwd": round(bwd_s * 1e6 / (B * T), 3),
            }
            print(f"# T={T} B={B} {name}: fwd {row['variants'][name]['fwd_ms']} ms, "
                  f"fwd+bwd {row['variants'][name]['fwd_bwd_ms']} ms", flush=True)
        if score_gb > args.xla_max_score_gb:
            row["variants"]["xla_causal"] = {
                "skipped": f"score tensor {score_gb:.1f} GB > "
                           f"{args.xla_max_score_gb} GB cap"
            }
        out["rows"].append(row)

    line = json.dumps(out)
    print(line)
    if args.out and not args.smoke:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(out, args.out)


if __name__ == "__main__":
    main()

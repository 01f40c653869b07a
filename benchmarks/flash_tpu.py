"""On-chip Pallas flash attention: Mosaic-compile smoke + block-size sweep.

VERDICT r1 item 3: the flagship kernel (`chainermn_tpu/ops/flash_attention.py`)
was verified for numerics in interpret mode but never compiled by Mosaic on
real hardware.  This harness, run on the TPU:

  1. compiles the kernel fwd+bwd NON-interpret and checks numerics against
     the XLA attention oracle (the compile itself is half the test),
  2. sweeps (block_q, block_k) at a realistic shape and times fwd / fwd+bwd,
  3. times XLA's own attention (jitted softmax(QKᵀ)V) as the baseline.

    python benchmarks/flash_tpu.py --out result/flash_tpu.json

Refuses to run on CPU unless ``--interpret-smoke`` (plumbing check only —
interpret-mode timings are meaningless).
"""

from __future__ import annotations

import argparse
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--blocks", default="128x128,256x256,128x512,512x128,256x512")
    ap.add_argument("--interpret-smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops import flash_attention, reference_attention
    from chainermn_tpu.utils import sync

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.interpret_smoke:
        print(json.dumps({
            "error": f"flash sweep needs a TPU (got {platform}); "
                     "pass --interpret-smoke for a plumbing check"
        }))
        return
    interpret = platform != "tpu"

    B, T, H, D = args.batch, args.seq, args.heads, args.head_dim
    if interpret:  # keep the smoke tiny
        B, T, H, D = 1, 256, 2, 64
    dtype = jnp.dtype(args.dtype)

    # Synthesize ON device: no host RNG, no host-to-device copy of tens of
    # MB per geometry.
    @jax.jit
    def _mk_qkv(key):
        ks = jax.random.split(key, 3)
        return tuple(
            jax.random.normal(kk, (B, T, H, D), jnp.float32).astype(dtype)
            for kk in ks
        )

    q, k, v = jax.block_until_ready(_mk_qkv(jax.random.PRNGKey(0)))

    out = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "shape": {"B": B, "T": T, "H": H, "D": D},
        "dtype": str(dtype),
        "causal": bool(args.causal),
        "compiled_non_interpret": not interpret,
        "configs": [],
    }

    # ---- numerics vs XLA oracle (fwd and grads), compiled ----------------
    def flash_loss(q, k, v, bq, bk):
        return jnp.sum(
            flash_attention(q, k, v, causal=args.causal, block_q=bq,
                            block_k=bk, interpret=interpret).astype(jnp.float32)
            ** 2
        )

    def xla_loss(q, k, v):
        return jnp.sum(reference_attention(q, k, v, args.causal).astype(jnp.float32) ** 2)

    gf = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)), static_argnums=(3, 4))
    gx = jax.jit(jax.grad(xla_loss, argnums=(0, 1, 2)))
    o_f = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, causal=args.causal, block_q=128, block_k=128,
            interpret=interpret,
        )
    )(q, k, v)
    o_x = jax.jit(lambda q, k, v: reference_attention(q, k, v, args.causal))(q, k, v)
    fwd_err = float(
        jnp.max(jnp.abs(o_f.astype(jnp.float32) - o_x.astype(jnp.float32)))
    )
    g_f = gf(q, k, v, 128, 128)
    g_x = gx(q, k, v)
    bwd_err = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(g_f, g_x)
    )
    out["fwd_max_abs_err_vs_xla"] = fwd_err
    out["bwd_max_abs_err_vs_xla"] = bwd_err
    # Gate on BOTH directions — a Mosaic-compiled backward with wrong
    # dq/dk/dv is exactly the failure this harness exists to catch.  The
    # grads of sum(o²) scale with values ~O(1)·T-ish accumulations, so the
    # bwd tolerance is relative to the oracle grad magnitude.
    g_scale = max(
        float(jnp.max(jnp.abs(g.astype(jnp.float32)))) for g in g_x
    )
    fwd_tol = 0.05 if dtype == jnp.bfloat16 else 2e-3
    bwd_tol = (0.05 if dtype == jnp.bfloat16 else 2e-3) * max(g_scale, 1.0)
    out["numerics_ok"] = bool(fwd_err < fwd_tol and bwd_err < bwd_tol)

    def bench(fn, *a):
        fn(*a)  # compile
        sync(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            r = fn(*a)
        sync(r)
        return (time.perf_counter() - t0) / args.iters * 1000.0

    # ---- XLA baseline ----------------------------------------------------
    xla_fwd_ms = bench(jax.jit(lambda q, k, v: reference_attention(q, k, v, args.causal)), q, k, v)
    xla_bwd_ms = bench(gx, q, k, v)
    out["xla_fwd_ms"] = round(xla_fwd_ms, 3)
    out["xla_fwdbwd_ms"] = round(xla_bwd_ms, 3)

    # ---- block sweep -----------------------------------------------------
    for spec in args.blocks.split(","):
        bq, bk = (int(x) for x in spec.split("x"))
        if T % bq or T % bk:
            continue
        try:
            f = jax.jit(
                lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, causal=args.causal, block_q=bq, block_k=bk,
                    interpret=interpret,
                )
            )
            fwd_ms = bench(f, q, k, v)
            bwd_ms = bench(
                jax.jit(
                    jax.grad(
                        lambda q, k, v, bq=bq, bk=bk: flash_loss(q, k, v, bq, bk),
                        argnums=(0, 1, 2),
                    )
                ),
                q, k, v,
            )
            out["configs"].append({
                "block_q": bq, "block_k": bk,
                "fwd_ms": round(fwd_ms, 3),
                "fwdbwd_ms": round(bwd_ms, 3),
                "fwd_vs_xla": round(xla_fwd_ms / fwd_ms, 2),
            })
        except Exception as e:  # Mosaic rejection IS a result worth recording
            out["configs"].append({
                "block_q": bq, "block_k": bk,
                "error": f"{type(e).__name__}: {str(e)[:200]}",
            })
        print(json.dumps(out["configs"][-1]), flush=True)

    print(json.dumps({k: v for k, v in out.items() if k != "configs"}),
          flush=True)
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(out, args.out)


if __name__ == "__main__":
    main()

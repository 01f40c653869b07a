#!/usr/bin/env python
"""First-contact proof that the system starts on the attached TPU.

One process drives the main path once through the entry points a user calls:
the LM trainer (communicator -> multi-node optimizer -> train step, fed by
the prefetch iterators) and the paged serving engine (``DecodeEngine`` under
``Scheduler``), at GPT-2-small width with random weights from ``--seed``.

    python chip_smoke.py              # one chip: device, train, serve
    python chip_smoke.py --multichip  # four chips: cross-chip paths only

Each phase prints one JSON line; the LAST line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  The exit code is
0 only when every phase passed on a TPU.  ``--size tiny`` is for rehearsing
the control flow on CPU (it still ends ``"ok": false`` there).  Compile and
step seconds are printed as information only — this is not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

#: GPT-2-small width (the defaults of ``benchmarks/lm.py``); ``tiny`` exists
#: only to rehearse the control flow off-chip.
SIZES = {
    "full": dict(
        model=dict(vocab=32768, n_layers=12, d_model=768, n_heads=12,
                   d_ff=3072),
        seq=2048, batch=8, steps=8, lr=3e-4,
        serve=dict(capacity=32, block_len=16, max_ctx=1024, prefill_chunk=32,
                   n_requests=36, n_family=8, family_prefix=96,
                   prompt=(16, 512), new=(8, 128), n_int8=8),
    ),
    "tiny": dict(
        model=dict(vocab=512, n_layers=2, d_model=64, n_heads=4, d_ff=128),
        seq=128, batch=8, steps=4, lr=3e-3,
        serve=dict(capacity=4, block_len=8, max_ctx=128, prefill_chunk=16,
                   n_requests=6, n_family=2, family_prefix=20,
                   prompt=(4, 40), new=(3, 10), n_int8=2),
    ),
}

#: Loss after N data-parallel steps on 4 chips vs the same steps on 1 chip
#: (same batches, same seed): bf16 matmuls on different per-device batch
#: shapes and a different gradient summation order, averaged over
#: batch*seq tokens.
DP_LOSS_RTOL = 1e-2


def emit(phase: str, ok: bool, **info) -> bool:
    print(json.dumps({"phase": phase, "ok": bool(ok), **info}), flush=True)
    return bool(ok)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def abstract(tree):
    """Shapes + shardings of a tree of arrays (what ``.lower`` needs; the
    arrays themselves may already be donated)."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree,
    )


def shard_devices(tree) -> list:
    """Sorted ids of the devices holding any shard of any leaf."""
    import jax

    return sorted({
        s.device.id
        for leaf in jax.tree_util.tree_leaves(tree)
        for s in leaf.addressable_shards
    })


def bytes_in_use(devices) -> list:
    """Per-device ``memory_stats()["bytes_in_use"]`` (None where the
    backend reports no stats, as the CPU does)."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("bytes_in_use"))
    return out


# ------------------------------------------------------------------ device
def phase_device(want: int):
    import importlib.metadata as md

    import jax
    import jaxlib

    from chainermn_tpu.observability.device import PEAK_BF16_FLOPS

    devs = jax.devices()
    d0 = devs[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    in_table = d0.device_kind in PEAK_BF16_FLOPS
    ok = d0.platform == "tpu" and len(devs) >= want and in_table
    emit("device", ok, **info, want=want, jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         kind_in_peak_table=in_table,
         memory_stats_keys=sorted(d0.memory_stats() or {}))
    return ok, info


# ------------------------------------------------------------------- train
def make_tokens(n_rows: int, seq: int, vocab: int, seed: int) -> np.ndarray:
    """``(n_rows, seq + 1)`` int32 rows of a sparse first-order Markov chain
    over the whole vocabulary: every token has 4 possible successors drawn
    from a Zipf-like marginal, taken with probabilities .55/.25/.12/.08.
    Learnable in a handful of steps (first the skewed marginal, then the
    successor table) where uniform tokens would pin the loss at ln(vocab).
    Vectorised over rows, so vocab 32768 costs the same as vocab 64."""
    rng = np.random.RandomState(seed)
    marginal = 1.0 / np.arange(1, vocab + 1) ** 1.1
    marginal /= marginal.sum()
    succ = rng.choice(vocab, size=(vocab, 4), p=marginal).astype(np.int32)
    branch = rng.choice(4, size=(n_rows, seq + 1), p=[.55, .25, .12, .08])
    out = np.empty((n_rows, seq + 1), np.int32)
    out[:, 0] = rng.choice(vocab, size=n_rows, p=marginal)
    for t in range(1, seq + 1):
        out[:, t] = succ[out[:, t - 1], branch[:, t]]
    return out


def train_run(devices, size: dict, seed: int) -> dict:
    """A handful of LM train steps over ``devices`` through the public
    calls, as ``examples/lm/train_lm.py`` makes them."""
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.datasets import ArrayDataset
    from chainermn_tpu.iterators import PrefetchIterator
    from chainermn_tpu.models import TransformerLM, lm_loss

    T, B, steps = size["seq"], size["batch"], size["steps"]
    vocab = size["model"]["vocab"]
    comm = cmn.create_communicator("xla", devices=devices)
    rows = make_tokens(B * steps, T, vocab, seed)
    host_it = PrefetchIterator(
        ArrayDataset(rows[:, :-1], rows[:, 1:]), B, shuffle=False,
    )
    it = cmn.create_device_prefetch_iterator(host_it, comm, depth=2)

    model = TransformerLM(
        max_len=T, dtype=jnp.bfloat16, attention="flash", **size["model"]
    )
    clock = [time.perf_counter()]
    params = jax.block_until_ready(jax.jit(
        lambda r: model.init(r, jnp.zeros((1, T), jnp.int32))["params"]
    )(jax.random.PRNGKey(seed)))
    clock.append(time.perf_counter())
    opt = cmn.create_multi_node_optimizer(optax.adamw(size["lr"]), comm)
    state = opt.init(params)  # eager, as the examples call it
    jax.block_until_ready(state)
    clock.append(time.perf_counter())
    step = opt.make_train_step(lm_loss(model), has_aux=True)

    losses, step_s, residual_ms = [], [], None
    state_abs = batch_abs = None
    for i in range(steps):
        batch = next(it)
        if state_abs is None:
            state_abs, batch_abs = abstract(state), abstract(batch)
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        if i == steps - 1:
            # Does block_until_ready wait for the whole step on this
            # backend?  Then nothing is left to wait for when the loss is
            # fetched (utils.sync relies on it).
            jax.block_until_ready((state, metrics))
            t1 = time.perf_counter()
            losses.append(float(metrics["loss"]))
            residual_ms = (time.perf_counter() - t1) * 1e3
        else:
            losses.append(float(metrics["loss"]))
        step_s.append(time.perf_counter() - t0)
    loader = "native" if host_it.native else "python"
    it.close()

    text = step.lower(state_abs, batch_abs).compile().as_text()
    want = {d.id for d in devices}
    placed = all(
        {d.id for d in leaf.devices()} == want
        for leaf in jax.tree_util.tree_leaves(state.params)
    )
    steady = sorted(step_s[1:])[len(step_s[1:]) // 2]
    return {
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": [round(x, 4) for x in losses],
        "step_compiles": int(step._cache_size()),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count("all-reduce("),
        "params_on_devices": placed,
        "param_shard_devices": shard_devices(state.params),
        "batch_shard_devices": shard_devices(batch),
        "loader": loader,
        "param_init_s": round(clock[1] - clock[0], 2),
        "opt_init_s": round(clock[2] - clock[1], 2),
        "first_step_s_incl_compile": round(step_s[0], 2),
        "step_s_median": round(steady, 4),
        "sync_residual_ms": round(residual_ms, 3),
        "bytes_in_use": bytes_in_use(devices),
    }


def phase_train(size: dict, seed: int) -> bool:
    import jax

    r = train_run(jax.devices()[:1], size, seed)
    ok = (
        all(math.isfinite(x) for x in r["losses"])
        and r["loss_last"] < r["loss_first"]
        and r["step_compiles"] == 1
        and r["tpu_custom_calls"] > 0
        and r["params_on_devices"]
        and jax.devices()[0].platform == "tpu"
    )
    return emit("train", ok, **r)


# ------------------------------------------------------------------- serve
def make_requests(sv: dict, vocab: int, seed: int):
    """Seeded request mix: independent prompts with log-uniform lengths,
    plus a family sharing one prefix (what the prefix cache exists for).
    Family members are spread through the list so some arrive while
    earlier ones are still running."""
    from chainermn_tpu.serving import Request

    rng = np.random.RandomState(seed)

    def length(lo_hi):
        lo, hi = lo_hi
        return int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))

    prefix = rng.randint(1, vocab, size=sv["family_prefix"]).tolist()
    n, every = sv["n_requests"], sv["n_requests"] // sv["n_family"]
    reqs = []
    for i in range(n):
        if i % every == every - 1:
            tail = max(1, length(sv["prompt"]) // 8)
            prompt = prefix + rng.randint(1, vocab, size=tail).tolist()
        else:
            prompt = rng.randint(1, vocab, size=length(sv["prompt"])).tolist()
        reqs.append(Request(id=i, prompt=prompt,
                            max_new_tokens=length(sv["new"])))
    return reqs


def serve_run(model, params, sv: dict, reqs, mesh=None) -> dict:
    """Drive ``reqs`` through a fresh engine + scheduler on the real clock:
    two thirds are queued at the start, the rest are submitted after 16
    scheduler iterations, into a running batch.  Scheduling is by
    iteration count, so both engines of a comparison see the same
    admission order whatever their speed."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.serving import DecodeEngine, Scheduler
    from chainermn_tpu.serving.sharding import replicated

    max_blocks = sv["max_ctx"] // sv["block_len"]
    t0 = time.perf_counter()
    eng = DecodeEngine(
        model, params, capacity=sv["capacity"],
        num_blocks=sv["capacity"] * max_blocks + 1,
        block_len=sv["block_len"], max_blocks_per_slot=max_blocks,
        prefill_chunk=sv["prefill_chunk"], mesh=mesh,
    )
    sched = Scheduler(eng)
    pools_abs = abstract(eng.pools)
    pool_devs = shard_devices(eng.pools)
    first = len(reqs) * 2 // 3
    for r in reqs[:first]:
        sched.submit(r)
    late = list(reqs[first:])
    ticks = 0
    while sched.pending or late:
        if late and ticks >= 16:
            for r in late:
                sched.submit(r)
            late = []
        if not sched.tick() and not late:
            raise RuntimeError("scheduler made no progress")
        ticks += 1
    sched.finish()
    wall = time.perf_counter() - t0

    S = sv["capacity"]
    # control vectors go up replicated on a mesh, uncommitted otherwise
    kw = {"sharding": replicated(mesh)} if mesh is not None else {}

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, **kw)

    text = eng.hot_program.lower(
        abstract(eng.params), pools_abs, arg((S,), jnp.int32), arg((S,), jnp.int32),
        arg((S, max_blocks), jnp.int32), arg((S,), jnp.bool_),
        arg((S, 2), jnp.uint32), arg((S,), jnp.float32),
    ).compile().as_text()
    by_id: dict = {}
    for c in sched.completions:
        by_id.setdefault(c.id, []).append(c)
    once = (
        sorted(by_id) == sorted(r.id for r in reqs)
        and all(len(v) == 1 for v in by_id.values())
        and all(v[0].status == "ok" and
                len(v[0].tokens) == reqs[i].max_new_tokens
                for i, v in sorted(by_id.items()))
    )
    return {
        "tokens": {i: v[0].tokens for i, v in by_id.items()},
        "terminated_once": once,
        "decode_compiles": eng.decode_compiles,
        "prefill_compiles": eng.prefill_compiles,
        "prefill_ladder": len(eng.prefill_ladder),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "pool_devices": pool_devs,
        "prefix_hit_tokens": sched.prefix_hit_tokens,
        "iterations": ticks,
        "generated": sum(len(v[0].tokens) for v in by_id.values()),
        "wall_s_incl_compile": round(wall, 2),
        "bytes_in_use": bytes_in_use(jax.devices()),
    }


def compare_tokens(ref_model, params, reqs, got: dict, want: dict,
                   n_layers: int, pad_to: int) -> dict:
    """Greedy agreement between two engines.  CPU tests pin identity; in
    bf16 on the chip an argmax near-tie may flip, after which the two
    sequences legitimately differ.  So only a request's FIRST divergence is
    judged, by the reference model's top-2 logit margin at that position
    (plain full-sequence forward, the repo's tie-flip triage): inside
    ``tol`` it is reported as a tie, beyond it it is a kernel fault.

    ``tol`` is fixed before any run from the dtype: bf16 keeps 8
    significant bits, each layer rounds its attention output and its
    residual once, the roundings add in quadrature over the layers, and 4
    sigma of that on the scale of the logits row (its standard deviation)
    bounds the margin a rounding difference can overturn."""
    import jax
    import jax.numpy as jnp

    tol_rel = 4.0 * math.sqrt(2.0 * n_layers) * 2.0 ** -8
    # params as an ARGUMENT: closed over, jit lowers them as literals
    fwd = jax.jit(lambda p, toks: ref_model.apply({"params": p}, toks))
    exact, ties, faults = 0, [], []
    for r in reqs:
        a, b = got[r.id], want[r.id]
        if a == b:
            exact += 1
            continue
        step = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        text = list(r.prompt) + list(b[:step])
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :len(text)] = text
        row = np.asarray(fwd(params, jnp.asarray(toks))[0, len(text) - 1],
                         np.float32)
        second, best = np.argsort(row)[-2:]
        margin, scale = float(row[best] - row[second]), float(row.std())
        rec = {"id": r.id, "step": step, "got": a[step], "want": b[step],
               "top2_margin": round(margin, 5),
               "tol": round(tol_rel * scale, 5),
               "ref_top2": [int(best), int(second)]}
        (ties if margin <= tol_rel * scale else faults).append(rec)
    return {"requests": len(reqs), "exact": exact,
            "agreement_rate": round(exact / len(reqs), 4),
            "tol_rel_to_logit_std": round(tol_rel, 5),
            "ties_within_tol": ties, "faults_beyond_tol": faults}


def serve_models(size: dict, seed: int):
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models import TransformerLM

    fused = TransformerLM(
        max_len=size["serve"]["max_ctx"], dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, decode_attention="fused", **size["model"]
    )
    params = jax.jit(
        lambda r: fused.init(r, jnp.zeros((1, 8), jnp.int32))["params"]
    )(jax.random.PRNGKey(seed + 1))
    return fused, params


def strip(run: dict) -> dict:
    return {k: v for k, v in run.items() if k != "tokens"}


def compiled_once(run: dict) -> bool:
    """The engine's compile contract: one decode program, at most one
    prefill program per ladder size, whatever the slot churn."""
    return (run["decode_compiles"] == 1
            and run["prefill_compiles"] <= run["prefill_ladder"])


def phase_serve(size: dict, seed: int) -> bool:
    import jax
    import jax.numpy as jnp

    sv, n_layers = size["serve"], size["model"]["n_layers"]
    fused, params = serve_models(size, seed)
    einsum = fused.clone(decode_attention="einsum")
    ref = einsum.clone(attention="xla")
    reqs = make_requests(sv, size["model"]["vocab"], seed)

    run_f = serve_run(fused, params, sv, reqs)
    run_e = serve_run(einsum, params, sv, reqs)
    cmp_bf16 = compare_tokens(ref, params, reqs, run_f["tokens"],
                              run_e["tokens"], n_layers, sv["max_ctx"])

    # int8 KV pool: reported, not deciding (no hardware history at all).
    few = reqs[:sv["n_int8"]]
    run_f8 = serve_run(fused.clone(kv_dtype=jnp.int8), params, sv, few)
    run_e8 = serve_run(einsum.clone(kv_dtype=jnp.int8), params, sv, few)
    cmp_int8 = compare_tokens(ref, params, few, run_f8["tokens"],
                              run_e8["tokens"], n_layers, sv["max_ctx"])

    tpu = [d.id for d in jax.devices()[:1]]
    on_tpu = jax.devices()[0].platform == "tpu"
    ok = (
        on_tpu
        and all(r["terminated_once"] for r in (run_f, run_e, run_f8, run_e8))
        and not cmp_bf16["faults_beyond_tol"]
        and all(compiled_once(r) for r in (run_f, run_e, run_f8, run_e8))
        and run_f["tpu_custom_calls"] > 0 and run_f8["tpu_custom_calls"] > 0
        and run_f["pool_devices"] == tpu and run_f8["pool_devices"] == tpu
        and run_f["prefix_hit_tokens"] > 0
    )
    return emit("serve", ok, fused=strip(run_f), einsum=strip(run_e),
                bf16_vs_einsum=cmp_bf16, int8_fused=strip(run_f8),
                int8_einsum=strip(run_e8), int8_vs_einsum=cmp_int8)


# --------------------------------------------------------------- multichip
def phase_dryrun(n: int) -> bool:
    import jax

    import __graft_entry__ as graft

    devs = jax.devices()[:n]
    graft.dryrun_multichip(n)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    ok = all(p is None or p > 0 for p in peaks)
    return emit("multichip_dryrun", ok, n=n, peak_bytes_in_use=peaks)


def phase_dp_train(size: dict, seed: int, n: int) -> bool:
    import jax

    devs = jax.devices()
    many = train_run(devs[:n], size, seed)
    one = train_run(devs[:1], size, seed)
    rel = abs(many["loss_last"] - one["loss_last"]) / abs(one["loss_last"])
    used = [b for b in many["bytes_in_use"] if b is not None]
    ok = (
        rel <= DP_LOSS_RTOL
        and many["step_compiles"] == 1 and many["all_reduces"] > 0
        and many["params_on_devices"]
        and len(many["param_shard_devices"]) == n
        and len(many["batch_shard_devices"]) == n
        and all(b > 0 for b in used)
        and (devs[0].platform != "tpu" or many["tpu_custom_calls"] > 0)
    )
    return emit("multichip_train", ok, n=n, loss_rel_diff=round(rel, 6),
                rtol=DP_LOSS_RTOL, dp=many, one_chip=one)


def phase_sharded_serve(size: dict, seed: int, n: int) -> bool:
    import jax

    from chainermn_tpu.serving import serving_mesh

    sv, n_layers = size["serve"], size["model"]["n_layers"]
    fused, params = serve_models(size, seed)
    ref = fused.clone(decode_attention="einsum", attention="xla")
    reqs = make_requests(sv, size["model"]["vocab"], seed)
    run_s = serve_run(fused, params, sv, reqs, mesh=serving_mesh(n))
    run_1 = serve_run(fused, params, sv, reqs)
    cmp = compare_tokens(ref, params, reqs, run_s["tokens"], run_1["tokens"],
                         n_layers, sv["max_ctx"])
    used = [b for b in run_s["bytes_in_use"][:n] if b is not None]
    ok = (
        run_s["terminated_once"] and run_1["terminated_once"]
        and not cmp["faults_beyond_tol"]
        and compiled_once(run_s) and compiled_once(run_1)
        and len(run_s["pool_devices"]) == n
        and all(b > 0 for b in used)
        and (jax.devices()[0].platform != "tpu"
             or run_s["tpu_custom_calls"] > 0)
    )
    return emit("multichip_serve", ok, n=n, sharded=strip(run_s),
                unsharded=strip(run_1), sharded_vs_unsharded=cmp)


# -------------------------------------------------------------------- main
def run(args) -> tuple:
    # Everything of the repo the run needs is imported here, first: in a
    # directory that holds this script alone the run dies before any phase.
    import jax

    import chainermn_tpu  # noqa: F401
    from chainermn_tpu.utils import init_compile_cache

    if jax.default_backend() == "cpu":
        # in-process CPU collectives deadlock under async dispatch
        # (tests/conftest.py); only rehearsals come this way
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    size = SIZES[args.size]
    n = 4 if args.multichip else 1
    cache_dir = init_compile_cache()
    before = cache_entries(cache_dir)
    ok, device = phase_device(n)
    if not ok and args.size == "full":
        return False, device  # no chip: nothing at full size is worth running
    if args.multichip:
        ok = phase_dryrun(n) and ok
        ok = phase_dp_train(size, args.seed, n) and ok
        ok = phase_sharded_serve(size, args.seed, n) and ok
    else:
        ok = phase_train(size, args.seed) and ok
        ok = phase_serve(size, args.seed) and ok
    emit("compile_cache", True, dir=cache_dir, entries_before=before,
         entries_after=cache_entries(cache_dir))
    return ok, device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip paths and their references")
    ap.add_argument("--size", choices=sorted(SIZES), default="full",
                    help="'tiny' rehearses the control flow on CPU")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    ok, device = False, None
    try:
        ok, device = run(args)
    finally:
        # Also on an exception (which still ends the run non-zero, with its
        # traceback): the last line says the run did not pass.
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

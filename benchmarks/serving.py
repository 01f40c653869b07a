"""Serving throughput: static batching vs continuous batching.

Mixed-length traffic is where static batching loses: ``lm_generate`` pads
every member of a batch to the longest prompt and decodes until the
longest ``max_new_tokens``, so short requests burn chip time generating
tokens nobody asked for, and the whole batch holds its slots until the
straggler finishes.  The continuous-batching arm streams the same
requests through the fixed-shape paged-KV engine
(``chainermn_tpu/serving``): a slot is recycled the moment its request
completes, so the device only ever decodes requested tokens.

Traffic model: open-loop Poisson arrivals; prompt lengths and
``max_new_tokens`` drawn per request from ranges wide enough that a
static batch's padded work is a multi-x of the useful work.  Both arms
see the identical request list and arrival times.  Reported tokens/sec
counts USEFUL tokens only (each request's own ``max_new_tokens``) over
the arm's makespan; per-token latency is a request's
(completion - arrival) / generated tokens, reported at p50/p95.

The static arm's wall clock is assembled from real measured batch service
times on a simulated arrival clock (batch i starts when its last member
has arrived and batch i-1 is done) — the same idle-skipping semantics the
scheduler's clock gives the continuous arm, so neither arm pays
real-world sleeps.

An observability A/B (short drain-mode passes, alternating the
serve.*/SLO/timeline stack off and on, median of per-pair ratios)
proves the ISSUE 6 overhead contract (< 1% tokens/s), and
``--trace-out`` exports the obs-on traffic pass's request timeline as
Perfetto-loadable Chrome trace JSON.

Two further arms ride the same alternating-pair methodology (ISSUE 7):

* ``--prefix-reuse N`` — Zipf-distributed shared-prefix traffic (N
  prompt templates, popularity ~ 1/rank^a: the system-prompt /
  few-shot-template regime) through a sharing engine vs an identical
  engine with ``prefix_cache=False``; reports ``prefix_hit_rate`` and
  the useful-tokens/s ratio (prefill for a hot prefix is mapped, not
  recomputed).
* ``--spec-k K`` — speculative decoding A/B: the zero-tail distilled
  draft (same construction as ``benchmarks/decode.py --draft-mode
  distilled`` — realistic draft cost, near-ideal acceptance) lifted
  into the engine vs the plain engine on the same zero-tail target;
  reports per-slot acceptance and the tokens/s ratio.

``--disagg`` (ISSUE 14) runs the disaggregated prefill/decode arm: a
prefill-role + decode-role engine pair over the in-process KV-migration
plane vs a colocated engine under identical traffic — p95 clean-decode
latency, the ``serve.mixed_ms`` mass shifted off the decode role (it
must be zero there), and the migration cost envelope.

``--chaos`` (ISSUE 15) drives the failure plane: a 3-replica router
under a seeded randomized fault schedule (``crash@serve_step`` replica
deaths mid-stream, ``skew@serve_step`` fail-slow, ``drop@migrate``
recovery-frame loss) with dead replicas revived behind the probation
circuit breaker — reports the terminal-invariant verdict (every
submitted request terminates exactly once) and the ``serve.health.*``
counters (replica_dead / recovered / poisoned / shed).

``--tenants N`` (ISSUE 16) runs the multi-tenant metering arm: the same
traffic labeled across N tenants with Zipf-distributed popularity
through a router whose usage ledger is on — per-tenant tokens/s and
block-second shares, the top-consumer share, and the exact-conservation
verdict.

``--elastic`` (ISSUE 17) runs the elastic-fleet arm: diurnal traffic
(sinusoid-modulated Poisson with a mid-run burst window) through a
closed-loop autoscaled fleet (min 1 replica, scale-up behind probation,
scale-down via the zero-loss drain) vs the same traffic through a fleet
statically provisioned for the peak — reports p95 request latency both
ways, replica-seconds both ways (``replica_seconds_saved_pct`` is the
headline: capacity held only while needed), the flap count (must be 0),
and a mid-traffic rolling-deploy sub-arm whose ``rollout_zero_loss``
verdict pins zero lost / duplicated requests across a full fleet
replacement.

``--multitenant`` (ISSUE 19) runs the SLO-policy arm: a bursty
adversarial tenant dumps a 2x-capacity burst at t=0 with a
latency-sensitive tenant queued behind it, served twice over the same
warmed engine — plain FIFO, then through a ``PolicyPlane`` giving the
SLO tenant a 4:1 weighted-fair (VTC) share — reporting the SLO tenant's
p95 both ways, ``slo_tenant_p95_held`` (policy p95 within 1.1x of
FIFO's), and ``fairness_throughput_pct`` (policy aggregate tokens/s as
a percent of FIFO's; contract: >= 95 — fairness reorders work, it must
not destroy it).

    python benchmarks/serving.py --out result/serving_tpu.json  # real chip
    JAX_PLATFORMS=cpu python benchmarks/serving.py --smoke      # plumbing
"""

from __future__ import annotations

import argparse
import json
import time


def _pct(xs, q):
    xs = sorted(xs)
    if not xs:
        return None
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[i]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8,
                    help="static arm's batch size AND the engine's slot "
                         "capacity — same concurrency budget both arms")
    ap.add_argument("--prompt-min", type=int, default=16)
    ap.add_argument("--prompt-max", type=int, default=128)
    ap.add_argument("--new-min", type=int, default=8)
    ap.add_argument("--new-max", type=int, default=192)
    ap.add_argument("--len-sigma", type=float, default=1.4,
                    help="lognormal sigma for prompt/new lengths (0 = "
                         "uniform in [min, max]).  Serving traces are "
                         "heavy-tailed: most requests are short, a few "
                         "are long — exactly the regime where a static "
                         "batch pads everything to its straggler.  The "
                         "default matches trace studies (ShareGPT-style "
                         "output lengths are lognormal with sigma ~1-1.5 "
                         "in log space); sweep it to see the speedup "
                         "collapse toward 1x as traffic turns uniform")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, requests/sec (0 = derive "
                         "one that keeps the system busy: requests "
                         "arrive ~4x faster than the static arm serves)")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=3072)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="physical KV blocks (0 = sized so the pool "
                         "covers ~batch x mean request length: real "
                         "contention, occasional eviction)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--decode-attention", default=None,
                    choices=("einsum", "fused"),
                    help="engine decode path: the paged Pallas kernel or "
                         "the gathered einsum fallback.  Default resolves "
                         "by platform — fused on TPU, einsum elsewhere "
                         "(off-TPU the Pallas kernels run in interpret "
                         "mode, never a perf win: the same policy as "
                         "ops.resolve_attention)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV pool + cache (both arms)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="measure each arm this many times and keep the "
                         "least-contended (fastest) pass — both arms' "
                         "phases are seconds-long, so a background blip "
                         "on the host otherwise decides the comparison")
    ap.add_argument("--obs-pairs", type=int, default=0,
                    help="alternating obs-on/obs-off pass pairs for the "
                         "observability-overhead estimate (0 = same as "
                         "--repeats).  The stack's cost (~0.4% profiled) "
                         "sits below per-pass host noise (±2% even on "
                         "an idle shared host), so the median needs "
                         "several pairs to resolve the <1% contract")
    ap.add_argument("--prefix-reuse", type=int, default=0, metavar="N",
                    help="also run the Zipf shared-prefix arm: N prompt "
                         "templates drawn Zipf(--zipf-a), each request = "
                         "template + a short unique suffix; sharing "
                         "engine vs prefix_cache=False engine on "
                         "identical traffic, alternating drain pairs "
                         "(0 = skip)")
    ap.add_argument("--zipf-a", type=float, default=1.2,
                    help="Zipf exponent for template popularity")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="also run the speculative A/B: K draft "
                         "proposals per round from the zero-tail "
                         "distilled draft (decode.py's construction) vs "
                         "the plain engine on the same zero-tail "
                         "target, alternating drain pairs (0 = skip)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="distilled draft depth (default layers // 4, "
                         "min 1)")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="also run the multi-replica ROUTER arm: N "
                         "engines behind least-loaded dispatch under "
                         "the same Poisson traffic; reports aggregate "
                         "tokens/s, per-replica occupancy spread and "
                         "router dispatch latency (0 = skip)")
    ap.add_argument("--mesh-model", type=int, default=1, metavar="M",
                    help="shard EACH router-arm engine tensor-parallel "
                         "over M devices (a GSPMD mesh per replica — "
                         "N x M devices total, disjoint groups; 1 = "
                         "unsharded replicas).  Both decode paths work "
                         "sharded: --decode-attention fused runs the "
                         "Pallas kernels per shard under shard_map, "
                         "einsum the gathered GSPMD fallback.  M > 1 "
                         "also runs the SHARDED-DECODE A/B arm: one "
                         "M-way engine per decode path on identical "
                         "steady-state full-capacity clean decode "
                         "steps, reporting per-step kernel-vs-einsum "
                         "time and the greedy-identity verdict")
    ap.add_argument("--disagg", action="store_true",
                    help="also run the DISAGGREGATED prefill/decode arm "
                         "(ISSUE 14): a prefill-role engine + a "
                         "decode-role engine over the in-process "
                         "migration plane vs a colocated engine under "
                         "identical Poisson traffic; reports p95 "
                         "clean-decode latency and the serve.mixed_ms "
                         "mass shifted off the decode role")
    ap.add_argument("--chaos", action="store_true",
                    help="also run the CHAOS arm (ISSUE 15): a "
                         "3-replica router under a seeded fault "
                         "schedule (crash/skew@serve_step + "
                         "drop@migrate) with probation revivals; "
                         "reports the terminal-invariant verdict and "
                         "the serve.health.* counters")
    ap.add_argument("--elastic", action="store_true",
                    help="also run the ELASTIC-FLEET arm (ISSUE 17): "
                         "diurnal sinusoid+burst traffic through a "
                         "closed-loop autoscaled fleet vs a peak-"
                         "provisioned static fleet (p95 both ways, "
                         "replica-seconds saved, flap count) plus a "
                         "mid-traffic rolling-deploy sub-arm "
                         "(rollout_zero_loss verdict)")
    ap.add_argument("--tenants", type=int, default=0, metavar="N",
                    help="also run the MULTI-TENANT metering arm "
                         "(ISSUE 16): the same traffic shape with "
                         "requests labeled across N tenants "
                         "(Zipf(--zipf-a) popularity — a few tenants "
                         "dominate, the realistic skew) through a "
                         "router with the usage ledger on; reports "
                         "per-tenant tokens/s and block-second shares, "
                         "the top-consumer share, and the conservation "
                         "verdict (0 = skip)")
    ap.add_argument("--multitenant", action="store_true",
                    help="also run the SLO-POLICY arm (ISSUE 19): a "
                         "bursty adversarial tenant's 2x-capacity "
                         "burst with a latency-sensitive tenant queued "
                         "behind it, served FIFO then through the "
                         "PolicyPlane (4:1 VTC weights) — reports "
                         "slo_tenant_p95_held and "
                         "fairness_throughput_pct (contract: >= 95)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--trace-out", default=None,
                    help="also export the obs-on arm's request timeline "
                         "as Chrome trace-event JSON (Perfetto-loadable)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chainermn_tpu.utils import init_compile_cache

    init_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import TransformerLM, lm_generate
    from chainermn_tpu.serving import DecodeEngine, Request, Scheduler

    platform = jax.devices()[0].platform
    if args.decode_attention is None:
        args.decode_attention = "fused" if platform == "tpu" else "einsum"
    if platform != "tpu" and not args.smoke:
        print(json.dumps({
            "error": f"serving bench needs a TPU (got {platform}); "
                     "pass --smoke for a CPU plumbing check"
        }))
        return
    if args.smoke:
        # Small enough to finish in a couple of minutes on CPU, big
        # enough that a decode step's compute amortizes the engine's
        # per-step host dispatch (a 128-wide toy model measures dispatch,
        # not serving) and that the drain tail — the last long request
        # finishing alone — doesn't dominate the makespan.  Explicitly
        # passed flags win over these smoke defaults.
        # repeats=4: on a small shared-CPU host both arms' phases sit
        # inside the noise floor of background load — min-of-4 passes is
        # the cheapest way to recover the uncontended service times the
        # comparison is about (on-chip runs keep the default).
        smoke_over = dict(
            requests=48, batch=8, prompt_min=8, prompt_max=48,
            new_min=4, new_max=64, layers=4, d_model=512, heads=8,
            d_ff=1024, vocab=4096, block_len=8, prefill_chunk=16,
            repeats=4, obs_pairs=12, prefix_reuse=4, spec_k=3,
            draft_layers=1, replicas=2, disagg=True, chaos=True,
            tenants=3, elastic=True, multitenant=True,
        )
        for k, v in smoke_over.items():
            if getattr(args, k) == ap.get_default(k):
                setattr(args, k, v)
    # NOTE: async CPU dispatch stays ON (the jax default).  Both arms'
    # timings sync on actual value readbacks — the static arm
    # materializes its scan output, the engine reads every step's sampled
    # tokens — so async cannot inflate either number.  Disabling it (as
    # the training benches do for step-time stability) would serialize
    # the engine's ~4 small control-vector uploads per decode step behind
    # each other, a pure dispatch-latency tax on the continuous arm that
    # the static arm's single-dispatch lax.scan never pays.

    rng = np.random.RandomState(args.seed)

    def draw_lens(lo, hi, n):
        if not args.len_sigma:
            return rng.randint(lo, hi + 1, size=n)
        # Clipped lognormal with the median at the low quartile of the
        # range: a realistic length mix (mostly short, occasional long).
        med = max(lo, (lo + hi) // 8)
        return np.clip(
            np.round(np.exp(rng.normal(np.log(med), args.len_sigma,
                                       size=n))),
            lo, hi,
        ).astype(int)

    plens = draw_lens(args.prompt_min, args.prompt_max, args.requests)
    prompts = [
        rng.randint(1, args.vocab, size=int(n)).astype(np.int32)
        for n in plens
    ]
    new_counts = draw_lens(args.new_min, args.new_max, args.requests)
    max_total = args.prompt_max + int(new_counts.max()) + args.prefill_chunk

    model = TransformerLM(
        vocab=args.vocab, n_layers=args.layers, d_model=args.d_model,
        n_heads=args.heads, d_ff=args.d_ff, max_len=max_total,
        pos_enc="rope", n_kv_heads=args.kv_heads,
        kv_dtype=jnp.int8 if args.kv_int8 else None,
        decode_attention=args.decode_attention,
    )
    params = jax.jit(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))
    )(jax.random.PRNGKey(0))["params"]

    useful_tokens = int(new_counts.sum())

    # ------------------------------------------------------- static arm
    # Batches in arrival order; every batch padded to its longest prompt
    # (right-padding + prompt_lengths gives lm_generate ragged semantics)
    # and decoded to its longest max_new.  One compiled program per
    # (prompt_pad, n_new) geometry — rounding the pad up to the prefill
    # chunk bounds the variant count, exactly as real static servers
    # bucket shapes.
    def pad_to(n, q):
        return int(-(-n // q) * q)

    order = list(range(args.requests))
    batches = [order[i:i + args.batch]
               for i in range(0, args.requests, args.batch)]
    gen = jax.jit(
        lambda p, pr, lens, n_new: lm_generate(
            model, p, pr, n_new, prompt_lengths=lens
        ),
        static_argnums=(3,),
    )
    # Warm every geometry first so the timed loop measures steady-state
    # service, not compiles (a long-lived server's regime).
    geoms = set()
    for b in batches:
        pp = pad_to(max(len(prompts[i]) for i in b), args.prefill_chunk)
        nn = int(max(new_counts[i] for i in b))
        geoms.add((pp, nn))
    for pp, nn in sorted(geoms):
        pr = jnp.zeros((args.batch, pp), jnp.int32)
        lens = jnp.ones((args.batch,), jnp.int32)
        np.asarray(gen(params, pr, lens, nn)[:1, -1:])

    repeats = max(1, args.repeats)
    service = [float("inf")] * len(batches)
    static_tokens = {}
    for _ in range(repeats):
        for bi, b in enumerate(batches):
            pp = pad_to(
                max(len(prompts[i]) for i in b), args.prefill_chunk
            )
            nn = int(max(new_counts[i] for i in b))
            pr = np.zeros((args.batch, pp), np.int32)
            lens = np.zeros((args.batch,), np.int32)
            for row, i in enumerate(b):
                pr[row, :len(prompts[i])] = prompts[i]
                lens[row] = len(prompts[i])
            lens = np.maximum(lens, 1)  # tail batch's empty rows
            t0 = time.perf_counter()
            out = gen(params, jnp.asarray(pr), jnp.asarray(lens), nn)
            out = np.asarray(out)
            service[bi] = min(
                service[bi], time.perf_counter() - t0
            )
            for row, i in enumerate(b):
                static_tokens[i] = out[row, :new_counts[i]].tolist()

    # Arrival schedule shared by both arms.  Default rate: fast enough
    # that the queue never starves (throughput measures the server, not
    # the arrival process).
    static_service = sum(service)
    rate = args.rate or (4.0 * args.requests / max(static_service, 1e-9))
    gaps = rng.exponential(1.0 / rate, size=args.requests)
    arrivals = np.cumsum(gaps)

    # Simulated static makespan on the shared arrival clock.
    t = 0.0
    static_lat = []
    done_at = {}
    for b, dt in zip(batches, service):
        t = max(t, float(arrivals[b[-1]])) + dt
        for i in b:
            done_at[i] = t
    static_makespan = max(done_at.values()) - float(arrivals.min())
    for i in range(args.requests):
        static_lat.append(
            (done_at[i] - float(arrivals[i])) / int(new_counts[i])
        )
    static_tps = useful_tokens / static_makespan

    # --------------------------------------------------- continuous arm
    # Pool sized to the DRAWN traffic (p85 of total request length), not
    # the range midpoint — lognormal draws sit far below the midpoint, and
    # a pool sized to the midpoint is several x the working set, silently
    # skipping the eviction/backpressure path this benchmark claims to
    # exercise.  p85 is the provisioning a real server would pick: tail
    # draws above it still force occasional evictions (reported in the
    # payload), while a mean-sized pool thrashes — every above-mean slot
    # evicts and recomputes, and the benchmark measures recompute waste
    # instead of steady-state serving.
    p85 = float(np.percentile(plens + new_counts, 85))
    num_blocks = args.num_blocks or (
        1 + args.batch * (1 + int(p85) // args.block_len + 1)
    )
    # Block tables sized to the drawn traffic's LONGEST request (padded to
    # the prefill chunk), not the model's max_len: the einsum fallback
    # gathers a fraction of the table's width (an eighth ... the whole:
    # the narrowest that holds the longest live context), so table slack
    # is masked compute in the hot loop.  A real deployment knows its
    # length cap the same way.
    from chainermn_tpu.serving.kv_pool import blocks_for

    longest = int((plens + new_counts).max())
    padded_longest = pad_to(longest, args.prefill_chunk)
    eng = DecodeEngine(
        model, params, capacity=args.batch, num_blocks=num_blocks,
        block_len=args.block_len, prefill_chunk=args.prefill_chunk,
        max_blocks_per_slot=blocks_for(padded_longest, args.block_len),
    )
    reqs = [
        Request(id=i, prompt=prompts[i].tolist(),
                max_new_tokens=int(new_counts[i]),
                arrival=float(arrivals[i]))
        for i in range(args.requests)
    ]
    # Warm the engine programs off the clock (same steady-state policy
    # as the static arm) — one request per prefill-ladder geometry plus
    # the decode step — then run the measured traffic, keeping the
    # least-contended of `repeats` passes, mirroring the static arm.
    warm_eng = Scheduler(eng)
    warm_eng.run([
        Request(id=-(i + 1), prompt=[1] * c, max_new_tokens=2)
        for i, c in enumerate(eng.prefill_ladder)
    ])

    # Headline continuous arm — observability ON, the shipped default
    # (serve.* metrics, SLO monitor, request timeline, flight provider).
    from chainermn_tpu import observability as obs

    obs.set_enabled(True)
    try:
        comps, sched_on, cont_makespan = None, None, float("inf")
        for _ in range(repeats):
            # Cold prefix cache every pass: this arm's headline is
            # continuous-vs-static batching, and this traffic draws
            # unique prompts anyway — a pass re-serving the previous
            # pass's cached prefills would measure the cache, not the
            # scheduler (the --prefix-reuse arm measures the cache).
            eng.drop_prefix_cache()
            sched = Scheduler(eng)
            cs = sched.run(reqs)
            span = (
                max(c.finished_at for c in cs)
                - min(c.arrival for c in cs)
            )
            if span < cont_makespan:
                comps, sched_on, cont_makespan = cs, sched, span
    finally:
        obs.set_enabled(None)
    cont_tps = useful_tokens / cont_makespan
    if args.trace_out:
        sched_on.export_trace(args.trace_out)
        print(f"# chrome trace -> {args.trace_out} "
              f"(load at ui.perfetto.dev)", flush=True)

    # Observability-overhead A/B (ISSUE 6 <1% contract).  Deliberately
    # NOT measured on the traffic simulation above: its seconds-long
    # passes are long enough that one background-contention burst on a
    # shared host lands a whole pass ±10% — far above the stack's
    # profiled self-time (<0.5%).  Instead: short DRAIN-mode passes
    # (every request available at t=0, so the arrival process adds no
    # variance), alternating obs-off/obs-on within each pair (the
    # scheduler latches the switch at construction), overhead = median
    # of per-pair makespan ratios — a spike contaminates one short pair,
    # and the median stays in the clean bulk.  The compiled programs are
    # shared and identical across arms; only host-side instrumentation
    # differs.
    ab_n = min(16, args.requests)
    ab_reqs = [
        Request(id=10_000 + i, prompt=prompts[i].tolist(),
                max_new_tokens=min(int(new_counts[i]), 24))
        for i in range(ab_n)
    ]
    ab_useful = sum(r.max_new_tokens for r in ab_reqs)
    pair_ratios = []
    # decode_compiles is cumulative across arms; attribute any recompile
    # to the arm whose pass raised it (delta per pass), so a regression
    # indicts the right arm.  1 = the shared warm-time compile.
    recompiles = {False: 0, True: 0}
    ab_best = {False: float("inf"), True: float("inf")}
    for rep in range(args.obs_pairs or repeats):
        spans = {}
        # Swap pair order every repeat so neither arm systematically
        # runs into a fresher (or staler) cache/contention state.
        for on in ((False, True) if rep % 2 == 0 else (True, False)):
            obs.set_enabled(on)
            before = eng.decode_compiles
            # Cold cache per pass: within a pair, the second arm would
            # otherwise re-serve the first's cached prefills — a
            # systematic bias toward whichever runs second.
            eng.drop_prefix_cache()
            try:
                cs = Scheduler(eng).run(ab_reqs)
            finally:
                obs.set_enabled(None)
            recompiles[on] += eng.decode_compiles - before
            spans[on] = max(c.finished_at for c in cs)
            ab_best[on] = min(ab_best[on], spans[on])
        pair_ratios.append(spans[True] / spans[False] - 1.0)
    compiles = {arm: 1 + recompiles[arm] for arm in (False, True)}
    rs = sorted(pair_ratios)
    mid = len(rs) // 2
    obs_overhead_pct = 100.0 * (
        rs[mid] if len(rs) % 2 else (rs[mid - 1] + rs[mid]) / 2.0
    )
    cont_lat = [
        (c.finished_at - c.arrival) / len(c.tokens) for c in comps
    ]
    evictions = sum(c.evictions for c in comps)

    # Greedy equivalence spot-check: the continuous arm must produce the
    # static arm's tokens request for request, or the speedup compares
    # different functions.  Exact in fp32 (pinned by the serving oracle
    # tests); under bf16 the gathered/paged attention and the contiguous
    # einsum are different XLA kernels whose logits differ in the last
    # bits, so a near-argmax-tie can flip and everything after diverges —
    # report the divergence structure (a logic bug diverges at step ~0 on
    # every request) exactly as benchmarks/decode.py does for its arms.
    per_req = []
    for c in comps:
        want = static_tokens[c.id]
        mm = [i for i, (a, b) in enumerate(zip(c.tokens, want)) if a != b]
        per_req.append((c.id, mm[0] if mm else None))
    diverged = [(i, f) for i, f in per_req if f is not None]
    agreement = {
        "requests_exact": len(per_req) - len(diverged),
        "requests": len(per_req),
        "min_first_divergence": min(
            (f for _, f in diverged), default=None
        ),
        "diverged_request_ids": [i for i, _ in diverged][:8],
    }

    def median(xs):
        xs = sorted(xs)
        mid = len(xs) // 2
        return xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2.0

    def warm_engine(e):
        """Compile an engine's whole ladder + its decode/spec step off
        the clock, then drop whatever the warm prompts cached."""
        Scheduler(e).run([
            Request(id=-(i + 1), prompt=[1] * c, max_new_tokens=2)
            for i, c in enumerate(e.prefill_ladder)
        ])
        e.drop_prefix_cache()

    # ---------------------------------------------- prefix-sharing arm
    # Zipf-distributed shared-prefix traffic (ROADMAP item 2's ground
    # truth): N templates, popularity ~ 1/rank^a — the system-prompt /
    # few-shot regime real traffic is dominated by.  Sharing engine vs
    # an identical prefix_cache=False engine on IDENTICAL traffic,
    # alternating drain-mode pass pairs (the PR-6 methodology: short
    # passes, a contention burst contaminates one pair, the median
    # stays in the clean bulk).  The sharing engine keeps its trie warm
    # across passes — a long-lived server's steady state IS the
    # treatment being measured; only host noise is paired away.
    prefix_payload = None
    if args.prefix_reuse:
        n_tpl = args.prefix_reuse
        tpl_lens = rng.randint(
            max(args.prompt_min, (3 * args.prompt_max) // 4),
            args.prompt_max + 1, size=n_tpl,
        )
        templates = [
            rng.randint(1, args.vocab, size=int(n)).astype(np.int32)
            for n in tpl_lens
        ]
        ranks = np.arange(1, n_tpl + 1, dtype=np.float64)
        pz = ranks ** -args.zipf_a
        pz /= pz.sum()
        n_px = max(24, min(args.requests, 48))
        choice = rng.choice(n_tpl, size=n_px, p=pz)
        suffix = max(2, args.prompt_min // 2)
        px_new = max(4, args.new_min)
        px_prompts = [
            np.concatenate([
                templates[c],
                rng.randint(1, args.vocab, size=suffix).astype(np.int32),
            ]).tolist()
            for c in choice
        ]
        px_reqs = [
            Request(id=20_000 + i, prompt=p, max_new_tokens=px_new)
            for i, p in enumerate(px_prompts)
        ]
        px_useful = n_px * px_new
        longest_px = max(len(p) for p in px_prompts) + px_new
        px_mbs = blocks_for(
            pad_to(longest_px + args.spec_k, args.prefill_chunk),
            args.block_len,
        )
        # Pool: templates stay resident (the trie) + a full-capacity
        # working set — contention is not this arm's subject.
        px_blocks = 1 + int(sum(
            blocks_for(int(n), args.block_len) for n in tpl_lens
        )) + args.batch * (px_mbs + 1)
        px_eng = {}
        for share in (False, True):
            px_eng[share] = DecodeEngine(
                model, params, capacity=args.batch,
                num_blocks=px_blocks, block_len=args.block_len,
                prefill_chunk=args.prefill_chunk,
                max_blocks_per_slot=px_mbs, prefix_cache=share,
            )
            warm_engine(px_eng[share])
        px_ratios = []
        px_best = {False: float("inf"), True: float("inf")}
        px_sched = None
        for rep in range(args.obs_pairs or repeats):
            spans = {}
            for share in (
                (False, True) if rep % 2 == 0 else (True, False)
            ):
                sched = Scheduler(px_eng[share])  # fresh per pass
                cs = sched.run(px_reqs)
                spans[share] = max(c.finished_at for c in cs)
                px_best[share] = min(px_best[share], spans[share])
                if share:
                    px_sched = sched
            px_ratios.append(spans[False] / spans[True])
        hit_rate = (
            px_sched.prefix_hit_tokens
            / max(px_sched.prefix_lookup_tokens, 1)
        )
        prefix_payload = {
            "templates": n_tpl,
            "zipf_a": args.zipf_a,
            "requests": n_px,
            "template_len": [int(tpl_lens.min()), int(tpl_lens.max())],
            "suffix_len": suffix,
            "max_new": px_new,
            # Steady-state (warm-trie) hit rate of the last sharing
            # pass: matched prompt tokens / looked-up prompt tokens.
            "prefix_hit_rate": round(hit_rate, 4),
            "tokens_per_sec_sharing": round(px_useful / px_best[True], 1),
            "tokens_per_sec_no_sharing": round(
                px_useful / px_best[False], 1
            ),
            # Median of paired no-sharing/sharing makespan ratios
            # (> 1 = sharing wins).
            "speedup_vs_no_sharing": round(median(px_ratios), 3),
            "pair_ratios": [round(r, 3) for r in px_ratios],
            "cached_blocks": px_eng[True].prefix.cached_blocks,
            "cow_compiles": px_eng[True].cow_compiles,
            "decode_compiles_sharing": px_eng[True].decode_compiles,
        }
        del px_eng  # drop both engines' device pools

    # ------------------------------------------------ speculative arm
    # Zero-tail distilled draft (benchmarks/decode.py --draft-mode
    # distilled): the target's blocks past `dl` become exact identities
    # (proj/ff2 zeroed), so its function collapses to its first dl
    # blocks at full honest cost — and those blocks + head ARE the
    # draft.  Realistic draft cost, near-ideal acceptance: the measured
    # bound a perfectly distilled draft reaches.  Spec engine vs plain
    # engine on the SAME zero-tail target, alternating drain pairs.
    spec_payload = None
    if args.spec_k:
        from chainermn_tpu.models import TransformerLM as _LM

        dl = args.draft_layers or max(1, args.layers // 4)
        zparams = dict(params)
        for i in range(dl, args.layers):
            blk = dict(zparams[f"block_{i}"])
            for nm in ("proj", "ff2"):
                blk[nm] = jax.tree.map(jnp.zeros_like, blk[nm])
            zparams[f"block_{i}"] = blk
        draft = _LM(
            vocab=args.vocab, n_layers=dl, d_model=args.d_model,
            n_heads=args.heads, d_ff=args.d_ff, max_len=max_total,
            pos_enc="rope", n_kv_heads=args.kv_heads,
            kv_dtype=jnp.int8 if args.kv_int8 else None,
            decode_attention=args.decode_attention,
        )
        dparams = {
            f"block_{i}": zparams[f"block_{i}"] for i in range(dl)
        }
        for nm in ("embed", "ln_f", "lm_head"):
            dparams[nm] = zparams[nm]
        # Decode-dominated drain traffic: short prompts, generous
        # budgets — speculation's win is sequential-step count.
        n_sp = max(12, min(args.requests, 24))
        sp_new = max(12, min(args.new_max, 24))
        sp_prompts = [
            rng.randint(
                1, args.vocab,
                size=int(rng.randint(args.prompt_min,
                                     max(args.prompt_min + 1, 17))),
            ).astype(np.int32).tolist()
            for _ in range(n_sp)
        ]
        longest_sp = max(len(p) for p in sp_prompts) + sp_new
        sp_mbs = blocks_for(
            pad_to(longest_sp + args.spec_k, args.prefill_chunk),
            args.block_len,
        )
        sp_blocks = 1 + args.batch * (sp_mbs + 1)
        sp_eng = {}
        for spec in (False, True):
            kw = dict(
                capacity=args.batch, num_blocks=sp_blocks,
                block_len=args.block_len,
                prefill_chunk=args.prefill_chunk,
                max_blocks_per_slot=sp_mbs,
            )
            if spec:
                kw.update(draft_model=draft, draft_params=dparams,
                          spec_k=args.spec_k)
            sp_eng[spec] = DecodeEngine(model, zparams, **kw)
            warm_engine(sp_eng[spec])
        sp_reqs = [
            Request(id=30_000 + i, prompt=p, max_new_tokens=sp_new)
            for i, p in enumerate(sp_prompts)
        ]
        sp_useful = n_sp * sp_new
        sp_ratios = []
        sp_best = {False: float("inf"), True: float("inf")}
        sp_tokens = {}
        accept, per_req_min = None, None
        for rep in range(args.obs_pairs or repeats):
            spans = {}
            for spec in (
                (False, True) if rep % 2 == 0 else (True, False)
            ):
                sp_eng[spec].drop_prefix_cache()
                sched = Scheduler(sp_eng[spec])  # fresh per pass
                cs = sched.run(sp_reqs)
                spans[spec] = max(c.finished_at for c in cs)
                sp_best[spec] = min(sp_best[spec], spans[spec])
                sp_tokens[spec] = {c.id: c.tokens for c in cs}
                if spec:
                    accept = (
                        sched.spec_accepted / max(sched.spec_proposed, 1)
                    )
                    per_req_min = min(
                        c.spec_accepted / max(c.spec_proposed, 1)
                        for c in cs
                    )
            sp_ratios.append(spans[False] / spans[True])
        # Greedy identity across arms (same zero-tail target): exact in
        # fp32; bf16 near-argmax ties can flip between the 1-token step
        # and the (k+1)-position verify kernel — report structure.
        mism = []
        for rid in sp_tokens[True]:
            a, b = sp_tokens[True][rid], sp_tokens[False][rid]
            first = next(
                (i for i, (x, y) in enumerate(zip(a, b)) if x != y), None
            )
            if first is not None:
                mism.append(first)
        spec_payload = {
            "k": args.spec_k,
            "draft_layers": dl,
            "target_layers": args.layers,
            "draft": "zero-tail distillation (realistic draft cost, "
                     "near-ideal acceptance)",
            "requests": n_sp,
            "max_new": sp_new,
            # Aggregate and worst per-request greedy acceptance from the
            # last speculative pass.
            "accept_rate": round(accept, 4),
            "accept_rate_per_request_min": round(per_req_min, 4),
            "tokens_per_sec_spec": round(sp_useful / sp_best[True], 1),
            "tokens_per_sec_plain": round(sp_useful / sp_best[False], 1),
            "speedup_vs_plain": round(median(sp_ratios), 3),
            "pair_ratios": [round(r, 3) for r in sp_ratios],
            "decode_compiles_spec": sp_eng[True].decode_compiles,
            "verify_compiles": sp_eng[True].verify_compiles,
            "greedy_agreement_vs_plain": {
                "requests_exact": n_sp - len(mism),
                "requests": n_sp,
                "min_first_divergence": min(mism) if mism else None,
            },
        }
        del sp_eng

    # --------------------------------------------------- router arm
    # N engines x M chips behind least-loaded dispatch (ISSUE 13): the
    # same Poisson request stream through a Router over N fresh engines
    # (each optionally sharded tensor-parallel over its own M-device
    # mesh group).  The single-engine continuous arm above is the
    # baseline: aggregate tokens/s should scale with N once the single
    # engine saturates, and the occupancy spread shows the dispatch
    # policy keeping the replicas even.  Router cost itself is
    # host-side only — dispatch latency is reported so its budget is
    # visible.
    router_payload = None
    if args.replicas:
        from chainermn_tpu.observability.metrics import MetricsRegistry
        from chainermn_tpu.serving import Router
        from chainermn_tpu.serving.sharding import serving_mesh

        N, M = args.replicas, max(1, args.mesh_model)
        devs = jax.devices()
        # Both decode paths run sharded since the shard_map port: the
        # engine wires the mesh into the fused kernels' dispatch, so
        # --decode-attention is honored as-is on the mesh path.
        rt_model = model
        if M > 1 and len(devs) < N * M:
            print(f"# router arm: {N}x{M} devices requested, "
                  f"{len(devs)} available — shrinking mesh to 1",
                  flush=True)
            M = 1
        meshes = [
            serving_mesh(M, devices=devs[i * M:(i + 1) * M])
            if M > 1 else None
            for i in range(N)
        ]
        rt_engines = []
        for i in range(N):
            e = DecodeEngine(
                rt_model, params, capacity=args.batch,
                num_blocks=num_blocks, block_len=args.block_len,
                prefill_chunk=args.prefill_chunk,
                max_blocks_per_slot=blocks_for(
                    padded_longest, args.block_len
                ),
                mesh=meshes[i],
                # Unsharded replicas still get their own chip when the
                # host has one to give — N engines piled on the default
                # device would measure single-chip contention, not
                # replica scaling.
                device=(
                    devs[i] if meshes[i] is None and len(devs) >= N
                    else None
                ),
            )
            warm_engine(e)
            rt_engines.append(e)
        rt_best, rt_router, rt_reg = float("inf"), None, None
        for _ in range(repeats):
            for e in rt_engines:
                e.drop_prefix_cache()
            # Fresh registry per pass: the dispatched/migrated/
            # backpressure counters below must describe the BEST run,
            # not accumulate across every repeat.
            reg = MetricsRegistry()
            router = Router(rt_engines, registry=reg)
            rcs = router.run([
                Request(id=40_000 + i, prompt=prompts[i].tolist(),
                        max_new_tokens=int(new_counts[i]),
                        arrival=float(arrivals[i]))
                for i in range(args.requests)
            ])
            span = (
                max(c.finished_at for c in rcs)
                - min(c.arrival for c in rcs)
            )
            if span < rt_best:
                rt_best, rt_router, rt_reg = span, router, reg
        rstats = rt_router.replica_stats()
        occs = [s["occupancy_mean"] for s in rstats]
        dms = sorted(rt_router.dispatch_ms)
        router_payload = {
            "replicas": N,
            "mesh_model": M,
            "decode_attention": rt_model.decode_attention,
            "aggregate_tokens_per_sec": round(useful_tokens / rt_best, 1),
            "makespan_s": round(rt_best, 3),
            "speedup_vs_single_engine": round(cont_makespan / rt_best, 3),
            "per_replica_occupancy_mean": [round(o, 4) for o in occs],
            "occupancy_spread": round(max(occs) - min(occs), 4),
            "dispatch_ms_p50": round(_pct(dms, 0.5), 4) if dms else None,
            "dispatch_ms_p95": round(_pct(dms, 0.95), 4) if dms else None,
            "dispatched": rt_reg.peek("serve.router.dispatched").value,
            "migrated": rt_reg.peek("serve.router.migrated").value,
            "backpressure_deferrals": rt_reg.peek(
                "serve.router.backpressure"
            ).value,
            "per_replica_served": [s["served"] for s in rstats],
            "decode_compiles": [
                s["engine"]["decode_compiles"] for s in rstats
            ],
        }
        del rt_engines, rt_router

    # ------------------------------------------- sharded-decode A/B arm
    # The shard_map kernel port's ground truth (ISSUE 20): one M-way
    # tensor-parallel engine per decode path — "fused" (Pallas paged
    # kernel per shard under shard_map) vs "einsum" (the gathered GSPMD
    # fallback) — on IDENTICAL steady-state full-capacity clean decode
    # steps.  The per-step comparison is the honest one: the einsum
    # path gathers and scores, for EVERY slot, the table width that holds
    # the longest live context each step, while the paged kernel streams
    # each pool byte once at storage width and walks only the blocks a
    # slot has actually filled (the block-skip recurrence) — the
    # PagedAttention claim, now held under sharding.  A small greedy
    # drain on both engines doubles as the token-identity verdict.
    #
    # CPU caveat (measured, not assumed): off-TPU the Pallas kernels
    # run in Pallas INTERPRET mode, whose per-grid-program emulation
    # overhead is orders of magnitude above the kernel's real cost —
    # the same reason the bench's --decode-attention default resolves
    # to einsum off-TPU ("never a perf win").  The CPU arm therefore
    # validates the comparison's PLUMBING (identical tokens, one
    # compile, both paths timed per step on a real multi-device mesh)
    # and flags itself ``interpret``; the speedup >= 1 claim is the
    # on-chip capture's, behind the standing TPU-probe note.
    sharded_payload = None
    if args.mesh_model > 1:
        from chainermn_tpu.serving.sharding import serving_mesh

        M = args.mesh_model
        devs = jax.devices()
        kvh = args.kv_heads or args.heads
        if len(devs) < M or kvh % M:
            print(f"# sharded-decode arm skipped: need {M} devices "
                  f"(have {len(devs)}) and kv heads ({kvh}) divisible "
                  f"by the mesh", flush=True)
        else:
            sd_mesh = serving_mesh(M, devices=devs[:M])
            S = args.batch
            MB = blocks_for(padded_longest, args.block_len)
            sd_blocks = max(num_blocks, 2 + S * MB)
            # Steady-state slot lengths: the drawn traffic's own mix
            # (prompt + generated so far), capped to the table width —
            # the regime a long-lived server decodes in.
            totals = (plens + new_counts)[:S]
            sd_pos = np.minimum(
                totals, MB * args.block_len - 1
            ).astype(np.int32)
            sd_tokens = np.random.RandomState(args.seed + 7).randint(
                1, args.vocab, size=S
            ).astype(np.int32)
            sd_tables = np.zeros((S, MB), np.int32)
            nxt = 1
            for s in range(S):
                need = 1 + int(sd_pos[s]) // args.block_len
                for m in range(need):
                    sd_tables[s, m] = nxt
                    nxt += 1
            sd_active = np.ones(S, bool)
            sd_steps = 12
            step_ms = {}
            sd_tok = {}
            sd_compiles = {}
            for attn in ("fused", "einsum"):
                e = DecodeEngine(
                    model.clone(decode_attention=attn), params,
                    capacity=S, num_blocks=sd_blocks,
                    block_len=args.block_len,
                    prefill_chunk=args.prefill_chunk,
                    max_blocks_per_slot=MB, mesh=sd_mesh,
                )
                # Greedy-identity drain (also compiles the ladder).
                cs = Scheduler(e).run([
                    Request(id=50_000 + i, prompt=prompts[i].tolist(),
                            max_new_tokens=8)
                    for i in range(min(6, args.requests))
                ])
                sd_tok[attn] = {c.id: list(c.tokens) for c in cs}
                # Clean steady-state steps: same control vectors both
                # paths, shapes fixed by construction (no recompiles).
                np.asarray(e.step(sd_tokens, sd_pos, sd_tables,
                                  sd_active))  # warm
                best = float("inf")
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    for _ in range(sd_steps):
                        out = e.step(sd_tokens, sd_pos, sd_tables,
                                     sd_active)
                    np.asarray(out)
                    best = min(best, time.perf_counter() - t0)
                step_ms[attn] = 1e3 * best / sd_steps
                sd_compiles[attn] = e.decode_compiles
                del e
            # Agreement structure, same shape as the headline arm's:
            # the kernel and einsum reductions are numerically
            # different programs, so at real model widths greedy argmax
            # ties may break differently mid-sequence — exact-request
            # counts tell that story honestly (the BIT-identity claim
            # is the ops-level sharded-vs-unsharded KERNEL pin, and the
            # tier-1 engine battery holds full fused-vs-einsum token
            # identity at its geometry).
            sd_exact = sum(
                sd_tok["fused"][i] == sd_tok["einsum"][i]
                for i in sd_tok["fused"]
            )
            sd_divs = [
                next((k for k, (a, b)
                      in enumerate(zip(sd_tok["fused"][i],
                                       sd_tok["einsum"][i]))
                      if a != b),
                     min(len(sd_tok["fused"][i]),
                         len(sd_tok["einsum"][i])))
                for i in sd_tok["fused"]
                if sd_tok["fused"][i] != sd_tok["einsum"][i]
            ]
            sharded_payload = {
                "mesh_model": M,
                "capacity": S,
                "max_blocks_per_slot": MB,
                "steps": sd_steps,
                "kernel_step_ms": round(step_ms["fused"], 3),
                "einsum_step_ms": round(step_ms["einsum"], 3),
                "kernel_speedup_vs_einsum": round(
                    step_ms["einsum"] / step_ms["fused"], 3
                ),
                "greedy_agreement_vs_einsum": {
                    "requests_exact": sd_exact,
                    "requests": len(sd_tok["fused"]),
                    "min_first_divergence": (min(sd_divs) if sd_divs
                                             else None),
                },
                "decode_compiles": sd_compiles,
                # Off-TPU the kernel arm times the Pallas INTERPRET
                # emulator, not the kernel (see the arm comment) — the
                # speedup is only a chip claim when this is False.
                "interpret": platform != "tpu",
            }

    # ------------------------------------------------ disaggregated arm
    # Prefill/decode role split over the in-process migration plane
    # (ISSUE 14) vs a colocated engine on IDENTICAL Poisson traffic.
    # The headline is latency attribution, not throughput: the colocated
    # engine's decode iterations that absorb queued prefill dispatches
    # book to serve.mixed_ms (the PR-6 tag); the decode ROLE runs clean
    # decode steps only, so its mixed mass must be ZERO and its
    # serve.slo token p95 is the clean-decode p95 the SLO monitor
    # already computes.  Same alternating best-of-N discipline as the
    # other arms (fewer passes — two full traffic simulations each).
    disagg_payload = None
    if args.disagg:
        from chainermn_tpu.observability.metrics import MetricsRegistry
        from chainermn_tpu.serving import (
            DecodeRole,
            LocalComm,
            MigrationTransport,
            PrefillRole,
            serve_disaggregated,
        )
        from chainermn_tpu.serving.scheduler import _Clock

        def mk_engine():
            e = DecodeEngine(
                model, params, capacity=args.batch,
                num_blocks=num_blocks, block_len=args.block_len,
                prefill_chunk=args.prefill_chunk,
                max_blocks_per_slot=blocks_for(
                    padded_longest, args.block_len
                ),
            )
            warm_engine(e)
            return e

        co_eng, pf_eng, de_eng = mk_engine(), mk_engine(), mk_engine()
        dz_reqs = [
            Request(id=50_000 + i, prompt=prompts[i].tolist(),
                    max_new_tokens=int(new_counts[i]),
                    arrival=float(arrivals[i]))
            for i in range(args.requests)
        ]

        def hist(reg, name):
            inst = reg.peek(name)
            if inst is None:
                return {"count": 0, "sum": 0.0}
            d = inst.to_dict()
            return {"count": d["count"], "sum": round(d["sum"], 3)}

        dz_repeats = max(1, min(2, repeats))
        co_best = (float("inf"), None, None, None)
        dz_best = (float("inf"), None, None, None, None)
        for _ in range(dz_repeats):
            co_eng.drop_prefix_cache()
            reg_co = MetricsRegistry()
            sched = Scheduler(co_eng, registry=reg_co)
            cs = sched.run(dz_reqs)
            span = (
                max(c.finished_at for c in cs)
                - min(c.arrival for c in cs)
            )
            if span < co_best[0]:
                co_best = (span, reg_co, sched, cs)
            pf_eng.drop_prefix_cache()
            de_eng.drop_prefix_cache()
            clock = _Clock()
            comm = LocalComm(2)
            reg_p, reg_d = MetricsRegistry(), MetricsRegistry()
            pr = PrefillRole(
                Scheduler(pf_eng, registry=reg_p, clock=clock),
                MigrationTransport(comm.endpoint(0), registry=reg_p),
                decode_ranks=[1],
            )
            dr = DecodeRole(
                Scheduler(de_eng, registry=reg_d, clock=clock),
                MigrationTransport(comm.endpoint(1), registry=reg_d),
                prefill_ranks=[0],
            )
            cs2 = serve_disaggregated(pr, dr, dz_reqs)
            span2 = (
                max(c.finished_at for c in cs2)
                - min(c.arrival for c in cs2)
            )
            if span2 < dz_best[0]:
                dz_best = (span2, reg_p, reg_d, dr, cs2)
        co_span, reg_co, co_sched, co_cs = co_best
        dz_span, reg_p, reg_d, dr, dz_cs = dz_best

        def slo_token_p95(sched):
            rep = (sched.slo.last_report or {}).get("token", {})
            v = rep.get("p95_ms")
            return round(v, 3) if v is not None else None

        co_tokens = {c.id: c.tokens for c in co_cs}
        mism = []
        for c in dz_cs:
            want = co_tokens[c.id]
            first = next(
                (i for i, (a, b) in enumerate(zip(c.tokens, want))
                 if a != b), None,
            )
            if first is None and len(c.tokens) != len(want):
                # A truncated/overlong completion with an identical
                # common prefix is still a divergence (zip is
                # length-blind) — first difference is the shorter end.
                first = min(len(c.tokens), len(want))
            if first is not None:
                mism.append(first)
        mig_ms = reg_p.peek("serve.migration.migrate_ms").to_dict()
        disagg_payload = {
            "requests": args.requests,
            "tokens_per_sec_disagg": round(useful_tokens / dz_span, 1),
            "tokens_per_sec_colocated": round(useful_tokens / co_span, 1),
            "speedup_vs_colocated": round(co_span / dz_span, 3),
            # p95 of CLEAN decode iterations (the SLO monitor's token
            # stream) — the acceptance headline.
            "clean_decode_p95_ms": slo_token_p95(dr.sched),
            "colocated_clean_decode_p95_ms": slo_token_p95(co_sched),
            # The steal, measured: mixed-iteration mass per arm.  The
            # decode role's must be zero — prefill interference now
            # lives on the prefill rank.
            "mixed_colocated": hist(reg_co, "serve.mixed_ms"),
            "mixed_decode_role": hist(reg_d, "serve.mixed_ms"),
            "decode_iterations_decode_role": hist(
                reg_d, "serve.decode_ms"
            )["count"],
            "prefill_role_decode_iterations": hist(
                reg_p, "serve.decode_ms"
            )["count"],
            "migration": {
                "slots": reg_p.peek(
                    "serve.migration.slots_migrated"
                ).value,
                "blocks": reg_p.peek(
                    "serve.migration.blocks_moved"
                ).value,
                "bytes": reg_p.peek("serve.migration.bytes").value,
                "migrate_ms_mean": round(
                    mig_ms["sum"] / max(mig_ms["count"], 1), 4
                ),
                "failed": reg_p.peek("serve.migration.failed").value,
            },
            "decode_compiles_decode_role": de_eng.decode_compiles,
            "greedy_agreement_vs_colocated": {
                "requests_exact": len(dz_cs) - len(mism),
                "requests": len(dz_cs),
                "min_first_divergence": min(mism) if mism else None,
            },
        }
        del co_eng, pf_eng, de_eng

    # ------------------------------------------------------- chaos arm
    # The failure plane under fire (ISSUE 15): a 3-replica router
    # driven by the seeded ChaosHarness — replicas crash mid-stream and
    # run fail-slow per the schedule, recovery re-dispatch frames drop
    # on the wire, dead replicas revive behind the probation circuit
    # breaker, and load shedding is armed.  The headline is not
    # throughput (replica deaths + revival recomputes make the makespan
    # a function of the schedule): it is the terminal invariant —
    # every submitted request terminates exactly once with a definite
    # status — plus the serve.health.* counter envelope.
    chaos_payload = None
    if args.chaos:
        from chainermn_tpu.observability.metrics import MetricsRegistry
        from chainermn_tpu.serving import ChaosHarness

        def chaos_engine():
            e = DecodeEngine(
                model, params, capacity=args.batch,
                num_blocks=num_blocks, block_len=args.block_len,
                prefill_chunk=args.prefill_chunk,
                max_blocks_per_slot=blocks_for(
                    padded_longest, args.block_len
                ),
            )
            warm_engine(e)
            return e

        cz_reg = MetricsRegistry()
        harness = ChaosHarness(
            chaos_engine, replicas=3, seed=args.seed,
            registry=cz_reg, revive_after=4, max_revives=2,
            shed_depth=4 * args.batch,
        )
        cz_n = min(args.requests, 32)
        cz_reqs = [
            Request(id=60_000 + i, prompt=prompts[i].tolist(),
                    max_new_tokens=int(new_counts[i]),
                    arrival=float(arrivals[i]))
            for i in range(cz_n)
        ]
        t0 = time.perf_counter()
        report = harness.run(cz_reqs)
        cz_wall = time.perf_counter() - t0

        def cz_cnt(name):
            inst = cz_reg.peek(name)
            return inst.value if inst is not None else 0

        router = harness.router
        ok_tokens = sum(
            len(c.tokens) for c in router.completions
            if c.status == "ok"
        )
        chaos_payload = {
            "replicas": 3,
            "seed": args.seed,
            "requests": cz_n,
            "schedule": harness.schedule,
            "invariant_holds": report["holds"],
            "by_status": report["by_status"],
            "lost": report["lost"],
            "duplicated": report["duplicated"],
            "replica_dead": cz_cnt("serve.health.replica_dead"),
            "recovered": cz_cnt("serve.health.recovered"),
            "retries": cz_cnt("serve.health.retries"),
            "poisoned": cz_cnt("serve.health.poisoned"),
            "shed": cz_cnt("serve.health.shed"),
            "deadline_cancels": sum(
                int(reg.peek("serve.health.deadline_cancels").value)
                if reg.peek("serve.health.deadline_cancels") is not None
                else 0
                for reg in router.replica_registries
            ),
            "revived": report["revived"],
            "health": report["health"],
            "wall_s": round(cz_wall, 3),
            "ok_tokens": ok_tokens,
            # One-compile contract on every replica whose tick loop
            # still runs and that actually decoded.
            "decode_compiles_up_replicas": [
                s.engine.decode_compiles
                for i, s in enumerate(router.schedulers)
                if router.health.is_up(i) and s._iterations
            ],
        }
        del harness, router

    # ------------------------------------------------------ elastic arm
    # Closed-loop autoscaling (ISSUE 17): diurnal traffic — a sinusoid-
    # modulated Poisson process with a 3x burst window in the middle
    # third — served two ways: a fleet statically provisioned for the
    # peak, and a fleet that starts at one replica behind a closed-loop
    # Autoscaler (scale-up behind probation on backlog, scale-down via
    # the zero-loss drain on idleness, hysteresis + cooldown against
    # flapping).  The headline is replica-seconds saved at held p95 —
    # capacity paid for only while the burst needs it — plus the flap
    # count (must be 0) and a mid-traffic rolling-deploy sub-arm whose
    # zero-loss verdict covers a full fleet replacement.
    elastic_payload = None
    if args.elastic:
        from chainermn_tpu.observability.metrics import MetricsRegistry
        from chainermn_tpu.serving import (
            Autoscaler,
            RollingDeploy,
            Router,
            verify_terminal_invariant,
        )

        def elastic_engine():
            e = DecodeEngine(
                model, params, capacity=args.batch,
                num_blocks=num_blocks, block_len=args.block_len,
                prefill_chunk=args.prefill_chunk,
                max_blocks_per_slot=blocks_for(
                    padded_longest, args.block_len
                ),
            )
            warm_engine(e)
            return e

        ez_max = max(2, args.replicas)
        ez_n = min(args.requests, 32)
        # Sinusoid + burst arrivals: base rate modulated over one full
        # period across the run, tripled in the middle third.  The base
        # is calibrated to ONE replica's measured service rate (the
        # continuous arm's saturated makespan), not the bench's global
        # 4x-overload `rate`: off-peak demand sits at half a replica's
        # capacity — one replica keeps up, so the static fleet's extra
        # replicas are pure idle spend — while the burst window pushes
        # past one replica and forces the scale-up the arm is about.
        one_replica_rate = args.requests / max(cont_makespan, 1e-9)
        base_rate = max(0.5 * one_replica_rate, 1e-6)
        t_arr, ez_arrivals = 0.0, []
        for i in range(ez_n):
            lam = base_rate * (
                1.0 + 0.8 * np.sin(2.0 * np.pi * i / max(ez_n, 1))
            )
            if ez_n // 3 <= i < 2 * ez_n // 3:
                lam *= 3.0
            t_arr += float(rng.exponential(1.0 / max(lam, 1e-9)))
            ez_arrivals.append(t_arr)

        def ez_reqs(base_id):
            return [
                Request(id=base_id + i, prompt=prompts[i].tolist(),
                        max_new_tokens=min(int(new_counts[i]), 24),
                        arrival=float(ez_arrivals[i]))
                for i in range(ez_n)
            ]

        def ez_drive(router, scaler=None):
            """Drain the fleet, integrating up-replica count over the
            shared virtual clock (replica-seconds: what a capacity bill
            charges) and skipping idle gaps to the next arrival exactly
            as Router.run does."""
            area, last = 0.0, router.clock.now()
            ticks = 0
            while router.pending:
                progressed = router.tick()
                ticks += 1
                if scaler is not None:
                    scaler.tick()
                now = router.clock.now()
                area += (now - last) * sum(
                    1 for i, s in enumerate(router.schedulers)
                    if s is not None and router.health.is_up(i)
                )
                last = now
                if not progressed:
                    nxt = [
                        t for t in (
                            [r.arrival
                             for r in router.queued_requests()[:1]]
                            + [s.next_arrival()
                               for i, s in enumerate(router.schedulers)
                               if s is not None
                               and router.health.is_up(i)]
                        )
                        if t is not None and t > now
                    ]
                    if nxt:
                        router.clock.skip_to(min(nxt))
            router.finish()
            return ticks, area

        def ez_p95(comps):
            return _pct(
                [c.finished_at - c.arrival for c in comps], 0.95
            )

        # Peak-provisioned static fleet.
        st_router = Router(
            [elastic_engine() for _ in range(ez_max)],
            registry=MetricsRegistry(),
        )
        st_reqs = ez_reqs(80_000)
        for r in st_reqs:
            st_router.submit(r)
        st_ticks, st_area = ez_drive(st_router)
        st_comps = st_router.completions
        st_report = verify_terminal_invariant(st_reqs, st_comps)

        # Warm standby pool: a real fleet scales up onto a machine that
        # compiled its programs long before the burst.  Building +
        # warming an engine inside the driven loop would charge
        # multi-second XLA compiles to the fleet's shared wall clock —
        # every queued request ages across the compile and both
        # headlines measure the build, not the policy.
        ez_spares = [elastic_engine() for _ in range(ez_max + 1)]

        def ez_factory(params=None):
            del params  # same-version scale-up / rollout
            return ez_spares.pop() if ez_spares else elastic_engine()

        # Autoscaled fleet: starts at one replica.
        ez_reg = MetricsRegistry()
        ez_router = Router([elastic_engine()], registry=ez_reg)
        # Aggressive-up, damped-down: every tick a burst spends queued
        # is p95 damage, so the up-trigger fires on the first breaching
        # tick; the down watch needs a 3-tick idle streak (the tick
        # after a scale-up always samples a transient occupancy dip —
        # the newcomer is empty — which must not register as a flap).
        scaler = Autoscaler(
            ez_router, ez_factory, registry=ez_reg,
            min_replicas=1, max_replicas=ez_max,
            up_depth=1.5, down_occ=0.25, hysteresis=1,
            down_hysteresis=3, cooldown_ticks=8,
        )
        el_reqs = ez_reqs(81_000)
        for r in el_reqs:
            ez_router.submit(r)
        ez_ticks, ez_area = ez_drive(ez_router, scaler)
        ez_comps = ez_router.completions
        ez_report = verify_terminal_invariant(el_reqs, ez_comps)
        st_p95 = ez_p95(st_comps)
        el_p95 = ez_p95(ez_comps)

        # Rolling-deploy sub-arm: replace every replica mid-traffic.
        rl_reg = MetricsRegistry()
        rl_router = Router(
            [elastic_engine() for _ in range(2)],
            registry=rl_reg, probation_ticks=8,
        )
        rl_reqs = [
            Request(id=85_000 + i, prompt=prompts[i].tolist(),
                    max_new_tokens=min(int(new_counts[i]), 24))
            for i in range(min(ez_n, 16))
        ]
        for r in rl_reqs:
            rl_router.submit(r)
        for _ in range(3):
            rl_router.tick()
        rollout = RollingDeploy(
            rl_router, ez_factory, registry=rl_reg,
        )
        guard = 0
        while not rollout.done and not rollout.paused:
            rl_router.tick()
            rollout.tick()
            guard += 1
            if guard > 200 * max(1, len(rl_router.schedulers)):
                break
        rl_router.run()
        rl_report = verify_terminal_invariant(
            rl_reqs, rl_router.completions
        )
        rollout_zero_loss = bool(
            rl_report["holds"] and rollout.done and not rollout.paused
            and all(c.status == "ok" for c in rl_router.completions)
        )

        saved_pct = round(
            100.0 * (1.0 - ez_area / max(st_area, 1e-9)), 2
        )
        elastic_payload = {
            "replicas_max": ez_max,
            "requests": ez_n,
            "traffic": {
                "shape": "sinusoidal+burst",
                "base_rate_per_sec": round(base_rate, 3),
                "burst_multiplier": 3.0,
            },
            "invariant_holds": bool(
                st_report["holds"] and ez_report["holds"]
            ),
            "static": {
                "p95_latency_s": round(st_p95, 4),
                "replica_seconds": round(st_area, 4),
                "mean_replicas": float(ez_max),
                "ticks": st_ticks,
            },
            "elastic": {
                "p95_latency_s": round(el_p95, 4),
                "replica_seconds": round(ez_area, 4),
                "mean_replicas": round(
                    scaler.replica_ticks / max(ez_ticks, 1), 2
                ),
                "ticks": ez_ticks,
                "scale_ups": len([
                    d for d in scaler.decisions
                    if d["action"] == "scale_up"
                ]),
                "scale_downs": len([
                    d for d in scaler.decisions
                    if d["action"] == "scale_down"
                ]),
                "flaps": scaler.flaps,
                "decisions": scaler.decisions[:8],
            },
            # "Held" = within 1.5x of the peak-provisioned fleet.  The
            # in-process harness ticks replicas SERIALLY on the shared
            # wall clock, so an added replica buys slots but never
            # wall-parallel compute — the elastic fleet can absorb a
            # burst it queued through, not out-run static.  The margin
            # covers the scale-up response window (watch trigger +
            # probation admission) that is the policy's real price.
            "replica_seconds_saved_pct": saved_pct,
            "p95_held": bool(el_p95 <= 1.5 * st_p95),
            "rollout": {
                "requests": len(rl_reqs),
                "replaced": list(rollout.replaced),
                "paused": rollout.paused,
                "zero_loss": rollout_zero_loss,
                "decode_compiles_per_replica": [
                    s.engine.decode_compiles
                    for s in rl_router.schedulers if s is not None
                ],
            },
        }
        del st_router, ez_router, rl_router

    # ------------------------------------------------------ tenants arm
    # Multi-tenant metering (ISSUE 16): the same traffic labeled across
    # N tenants with Zipf-distributed popularity (a couple of tenants
    # dominate — the skew a quota system must survive) through a router
    # whose fleet-wide usage ledger is ON.  Reuses the warmed continuous
    # engine: the arm's subject is attribution, not throughput.  The
    # headline is ``tenant_top_share`` (the top consumer's fraction of
    # fleet block-seconds — the scarce resource) plus the conservation
    # verdict: per-tenant sums equal fleet totals EXACTLY, every request
    # finalized exactly once.
    tenant_payload = None
    if args.tenants:
        from chainermn_tpu.observability.metrics import MetricsRegistry
        from chainermn_tpu.serving import Router

        n_t = args.tenants
        t_ranks = np.arange(1, n_t + 1, dtype=np.float64)
        pt = t_ranks ** -args.zipf_a
        pt /= pt.sum()
        tn_n = min(args.requests, 32)
        assign = rng.choice(n_t, size=tn_n, p=pt)
        eng.drop_prefix_cache()
        tn_reg = MetricsRegistry()
        tn_router = Router([eng], registry=tn_reg)
        tn_reqs = [
            Request(id=70_000 + i, prompt=prompts[i].tolist(),
                    max_new_tokens=min(int(new_counts[i]), 24),
                    arrival=float(arrivals[i]),
                    tenant=f"tenant{int(assign[i])}")
            for i in range(tn_n)
        ]
        tn_cs = tn_router.run(tn_reqs)
        tn_span = (
            max(c.finished_at for c in tn_cs)
            - min(c.arrival for c in tn_cs)
        )
        led = tn_router.ledger
        cons = led.verify_conservation(requests=tn_reqs)
        t_agg = led.aggregate()
        fleet_block_us = max(led.totals["block_us"], 1)
        tenant_payload = {
            "tenants": n_t,
            "zipf_a": args.zipf_a,
            "requests": tn_n,
            "conservation_holds": cons["holds"],
            # Top consumer's share of fleet block-seconds — also
            # published live as the serve.tenant.top_share gauge.
            "tenant_top_share": round(
                max(t["block_us"] for t in t_agg.values())
                / fleet_block_us, 4,
            ),
            "top": led.top(3),
            "per_tenant": {
                name: {
                    "requests": t["requests"],
                    "tokens": t["tokens"],
                    "tokens_per_sec": round(t["tokens"] / tn_span, 1),
                    "block_seconds": round(t["block_us"] / 1e6, 4),
                    "block_second_share": round(
                        t["block_us"] / fleet_block_us, 4
                    ),
                }
                for name, t in sorted(t_agg.items())
            },
        }
        del tn_router

    # -------------------------------------------------- multitenant arm
    # SLO-aware policy (ISSUE 19): a bursty adversarial tenant dumps a
    # 2x-capacity burst at t=0 with a latency-sensitive tenant's
    # requests queued BEHIND it (submission order — FIFO's worst case),
    # served twice over the same warmed engine: plain FIFO, then
    # through a PolicyPlane giving the SLO tenant a 4:1 VTC weight.
    # Same priority class both ways — the comparison is about admission
    # ORDER, not preemption recompute — so aggregate work is identical
    # and the fairness contract (policy tokens/s >= 95% of FIFO's) has
    # no systematic reason to fail; FIFO drains the whole burst before
    # the SLO tenant sees a slot, while the policy hands every freed
    # slot to the cheapest virtual clock, collapsing the SLO tenant's
    # p95.  Reuses the warmed continuous engine: decode_compiles must
    # stay pinned with the policy ON.
    mt_payload = None
    if args.multitenant:
        from chainermn_tpu.observability.metrics import MetricsRegistry
        from chainermn_tpu.serving import (
            PolicyPlane,
            Router,
            TenantPolicy,
        )

        mt_adv = min(2 * args.batch, 16)
        mt_slo = max(4, args.batch // 2)

        def mt_reqs(base_id):
            def pick(j, tenant, i):
                return Request(
                    id=base_id + j,
                    prompt=prompts[i % len(prompts)].tolist(),
                    max_new_tokens=min(
                        int(new_counts[i % len(new_counts)]), 16
                    ),
                    arrival=0.0, tenant=tenant,
                )
            adv = [pick(i, "adv", i) for i in range(mt_adv)]
            slo = [pick(500 + i, "slo", mt_adv + i)
                   for i in range(mt_slo)]
            return adv + slo  # burst first, SLO trickle queued behind

        def mt_pass(base_id, policy):
            eng.drop_prefix_cache()
            mr = Router([eng], registry=MetricsRegistry(),
                        policy=policy)
            reqs = mt_reqs(base_id)
            comps = mr.run(reqs)
            assert all(c.status == "ok" for c in comps)
            span = max(
                max(c.finished_at for c in comps)
                - min(c.arrival for c in comps), 1e-9,
            )
            tps = sum(len(c.tokens) for c in comps) / span
            slo_lat = [c.finished_at - c.arrival for c in comps
                       if c.id >= base_id + 500]
            adv_lat = [c.finished_at - c.arrival for c in comps
                       if c.id < base_id + 500]
            return tps, _pct(slo_lat, 0.95), _pct(adv_lat, 0.95)

        # Alternating best-of-2 passes per arm (the bench's min-of-N
        # idiom): both arms run the SAME work in a different order, so
        # any tokens/s gap is host noise — a single pass on a shared
        # CPU can swing the fairness ratio by several percent either
        # way and flip the >=95% verdict on nothing.
        fifo_runs, pol_runs = [], []
        mt_plane = None
        for mp in range(2):
            fifo_runs.append(mt_pass(90_000 + 2_000 * mp, None))
            mt_plane = PolicyPlane(
                tenants={"slo": TenantPolicy("slo", weight=4.0),
                         "adv": TenantPolicy("adv", weight=1.0)},
                registry=MetricsRegistry(),
            )
            pol_runs.append(mt_pass(91_000 + 2_000 * mp, mt_plane))
        fifo_tps = max(r[0] for r in fifo_runs)
        fifo_slo_p95 = min(r[1] for r in fifo_runs)
        fifo_adv_p95 = min(r[2] for r in fifo_runs)
        pol_tps = max(r[0] for r in pol_runs)
        pol_slo_p95 = min(r[1] for r in pol_runs)
        pol_adv_p95 = min(r[2] for r in pol_runs)
        mt_payload = {
            "adv_requests": mt_adv,
            "slo_requests": mt_slo,
            "weights": {"slo": 4.0, "adv": 1.0},
            "fifo": {
                "tokens_per_sec": round(fifo_tps, 1),
                "slo_p95_latency_s": round(fifo_slo_p95, 4),
                "adv_p95_latency_s": round(fifo_adv_p95, 4),
            },
            "policy": {
                "tokens_per_sec": round(pol_tps, 1),
                "slo_p95_latency_s": round(pol_slo_p95, 4),
                "adv_p95_latency_s": round(pol_adv_p95, 4),
                # VTC audit trail: admitted tenant order (first wave)
                # and the final virtual clocks — the SLO tenant's must
                # run ~1/4 the adversary's per unit charged.
                "admission_order": [
                    t for _, t, _ in mt_plane.admission_log[:8]
                ],
                "virtual_clock": {
                    t: round(v, 2)
                    for t, v in sorted(mt_plane.virtual.items())
                },
            },
            "decode_compiles": eng.decode_compiles,
            # Held = the policy's SLO-tenant p95 within 1.1x FIFO's
            # (in practice far below it: the burst no longer queues
            # ahead); the margin absorbs host jitter on the shared-CPU
            # smoke path.
            "slo_tenant_p95_held": bool(
                pol_slo_p95 <= 1.1 * fifo_slo_p95
            ),
            "fairness_throughput_pct": round(
                100.0 * pol_tps / max(fifo_tps, 1e-9), 2
            ),
            "contract": "slo p95 held at >= 95% of FIFO tokens/s",
        }

    payload = {
        "metric": "serving_tokens_per_sec",
        "value": round(cont_tps, 1),
        "unit": "useful generated tokens/sec",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "requests": args.requests,
        "capacity": args.batch,
        "repeats": repeats,
        "traffic": {
            "prompt_len": [args.prompt_min, args.prompt_max],
            "max_new": [args.new_min, args.new_max],
            "len_sigma": args.len_sigma,
            "poisson_rate_per_sec": round(rate, 3),
            "useful_tokens": useful_tokens,
        },
        "config": {"layers": args.layers, "d_model": args.d_model,
                   "heads": args.heads, "d_ff": args.d_ff,
                   "vocab": args.vocab, "kv_heads": args.kv_heads,
                   "decode_attention": args.decode_attention,
                   "kv_int8": bool(args.kv_int8)},
        "pool": {"num_blocks": num_blocks, "block_len": args.block_len,
                 "bytes_per_block": eng.pool.bytes_per_block,
                 "prefill_ladder": list(eng.prefill_ladder),
                 "evictions": evictions},
        "continuous": {
            "tokens_per_sec": round(cont_tps, 1),
            "makespan_s": round(cont_makespan, 3),
            "token_latency_ms_p50": round(_pct(cont_lat, 0.5) * 1e3, 3),
            "token_latency_ms_p95": round(_pct(cont_lat, 0.95) * 1e3, 3),
            "decode_compiles": eng.decode_compiles,
            "prefill_compiles": eng.prefill_compiles,
        },
        # Serving-plane observability overhead (ISSUE 6 contract: the
        # default-on stack costs < 1% tokens/s).  Drain-mode A/B (see
        # the comment above); ``overhead_pct`` is the median of paired
        # alternating-pass ratios — host jitter can land it slightly
        # negative, the contract reads the magnitude.  The obs-on/off
        # tokens/s are each arm's best drain pass over the A/B workload
        # (not the traffic headline above).
        "observability": {
            "tokens_per_sec_obs_on": round(ab_useful / ab_best[True], 1),
            "tokens_per_sec_obs_off": round(
                ab_useful / ab_best[False], 1
            ),
            "overhead_pct": round(obs_overhead_pct, 3),
            "overhead_pct_min_ratio": round(
                100 * (ab_best[True] / ab_best[False] - 1.0), 3
            ),
            "overhead_pair_ratios_pct": [
                round(100 * r, 3) for r in pair_ratios
            ],
            "contract": "obs-on within 1% of obs-off tokens/s",
            "decode_compiles_obs_off": compiles[False],
            "decode_compiles_obs_on": compiles[True],
            "slo_p95_ms": {
                s: round(rep["p95_ms"], 3)
                for s, rep in (sched_on.slo.last_report or {}).items()
                if rep.get("p95_ms") is not None
            } if sched_on.slo is not None else None,
            "timeline_events": (
                len(sched_on.timeline)
                if sched_on.timeline is not None else 0
            ),
        },
        "static": {
            "tokens_per_sec": round(static_tps, 1),
            "makespan_s": round(static_makespan, 3),
            "token_latency_ms_p50": round(_pct(static_lat, 0.5) * 1e3, 3),
            "token_latency_ms_p95": round(_pct(static_lat, 0.95) * 1e3, 3),
            "batches": len(batches),
            "padded_token_overhead": round(
                args.batch * sum(
                    max(new_counts[i] for i in b) for b in batches
                ) / useful_tokens, 3,
            ),
        },
        "speedup_vs_static": round(cont_tps / static_tps, 3),
        "greedy_agreement_vs_static": agreement,
    }
    if prefix_payload is not None:
        payload["prefix_reuse"] = prefix_payload
    if spec_payload is not None:
        payload["speculative"] = spec_payload
    if router_payload is not None:
        payload["router"] = router_payload
    if sharded_payload is not None:
        payload["sharded_decode"] = sharded_payload
    if disagg_payload is not None:
        payload["disagg"] = disagg_payload
    if chaos_payload is not None:
        payload["chaos"] = chaos_payload
    if elastic_payload is not None:
        payload["elastic"] = elastic_payload
    if tenant_payload is not None:
        payload["tenants"] = tenant_payload
    if mt_payload is not None:
        payload["multitenant"] = mt_payload
    print(json.dumps(payload))
    if args.out:
        from chainermn_tpu.utils import atomic_json_dump

        atomic_json_dump(payload, args.out)


if __name__ == "__main__":
    main()

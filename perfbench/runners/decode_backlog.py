"""Offline generation above the knee: every slot full, a deep queue behind
them, tokens per second over whole ticks."""

from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

from perfbench import estimators, serving, traffic_gen, weights
from perfbench import device as pdevice
from perfbench.checks import serve as check


def run(ctx) -> Dict[str, Any]:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    if ctx.rehearse:
        tr = dict(tr, **tr.get("rehearse", {}))
    model, m, pdt = serving.build_model(cfg, ctx.rehearse)
    params = jax.block_until_ready(weights.make_params(m, ctx.seed, pdt))
    ctx.mark("init_s")
    eng, sv = serving.build_engine(cfg, model, params, ctx.rehearse)
    serving.warm_programs(eng, ctx.clock, m["vocab"], sv["prefill_chunk"])
    ctx.mark("compile_or_load_s")

    reqs = traffic_gen.decode_backlog(tr, m["vocab"], ctx.seed)
    sched, rec = serving.new_scheduler(eng, ctx.clock, reqs)
    for r in reqs:
        serving.submit(sched, r, 0.0)
    S = tr["slots"]
    while rec.prefills_done < S:  # set-up fills every slot's cache
        if not sched.tick():
            raise RuntimeError("pool fill made no progress")
    ctx.settle()
    ctx.mark("pool_fill_s")
    setup_s = ctx.setup_seconds()

    # ---------------------------------------------------------- window
    ticks: List[Tuple[float, float, float]] = []
    traced: List[int] = []
    steps: List[Tuple[int, int]] = []  # decode steps each tick ran
    first, n_traced = tr.get("trace_from_tick", 2), tr.get("trace_ticks", 16)
    t_open = ctx.clock.now()
    while True:
        a = ctx.clock.now()
        if a - t_open >= ctx.seconds or not sched.pending:
            break  # closes on the first tick boundary at or after --seconds
        i = len(ticks)
        if ctx.trace and i == first:
            ctx.start_trace()
            window = jax.profiler.TraceAnnotation("pb:window")
            window.__enter__()
        n0, d0 = rec.n_tokens, rec.decode_steps
        with ctx.spans("tick"):
            sched.tick()
        ticks.append((a, ctx.clock.now(), float(rec.n_tokens - n0)))
        steps.append((d0, rec.decode_steps))
        if ctx.trace and first <= i < first + n_traced:
            traced.append(i)
            if i == first + n_traced - 1:
                window.__exit__(None, None, None)
                ctx.stop_trace()
    if ctx.trace and traced and ctx.spans.annotate:
        window.__exit__(None, None, None)
        ctx.stop_trace()
    rate = estimators.whole_unit_rate(ticks)
    gc_seen = ctx.gc_report()
    peak = pdevice.memory_peak_bytes(ctx.chips)

    # ----------------------------------------------------------- check
    t_check = time.perf_counter()
    tokens = serving.served(sched)
    done = [c for c in sched.completions]
    failed = sum(1 for c in done if c.status != "ok")
    serving.free_engine(eng)
    sample = check.pick_sample(reqs, tokens, tr["check_requests"], ctx.seed)
    pad = tr["check_pad"]
    numbers = check.served_gaps(params, m.get("pos_enc") == "rope", sample,
                                tokens, pad)
    limits = (cfg["rehearse"] if ctx.rehearse else cfg)["check"]["serve"]
    ok, rows = check.judge(numbers, limits)
    control = {q: check.served_gaps(params, m.get("pos_enc") == "rope",
                                    sample, tokens, pad, quant=q)
               for q in ctx.control}
    serving.free_params(eng, params)
    check_s = time.perf_counter() - t_check

    tick_ms = [(b - a) * 1e3 for a, b, _ in ticks]
    k0, k1 = (traced[0], traced[-1] + 1) if traced else (0, len(ticks))
    values = {
        "serve_tokens_per_s": rate["rate"],
        "setup_s": setup_s,
        "occupancy": 100.0 * sum(rec.live_per_step[steps[0][0]:steps[-1][1]])
        / (S * max(1, steps[-1][1] - steps[0][0])),
        "tick_ms_median": estimators.median(tick_ms),
    }
    facts = {
        "units": "ticks",
        "traced_units": len(traced),
        "traced_wall_s": (ticks[k1 - 1][1] - ticks[k0][0]) if traced else 0.0,
        "traced_context_tokens": float(sum(
            sum(rec.context_per_step[steps[i][0]:steps[i][1]])
            for i in traced)),
        "memory_peak_bytes": peak,
    }
    return {
        "correct": ok and failed == 0, "attempted": len(rec.admit),
        "failed": failed, "values": values, "facts": facts,
        "compared": rows, "check_s": check_s, "memory_peak_bytes": peak,
        "sound": numbers, "control": control, "gc": gc_seen,
        "info": {"ticks": len(ticks), "tokens": rate["work"],
                 "window_s": rate["seconds"], "finished": len(done),
                 "prefill_calls": rec.prefill_calls,
                 "tick_ms_median": values["tick_ms_median"],
                 "tick_ms_max": max(tick_ms),
                 "slowest_tick": tick_ms.index(max(tick_ms))},
        "counts": {"ticks": len(ticks), "tokens": rate["work"],
                   "admitted": len(rec.admit), "finished": len(done)},
    }

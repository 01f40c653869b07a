"""Offline generation above the knee: every slot full, a deep queue behind
them, tokens per second over whole ticks.

The schedule is the mix's (``traffic_gen.backlog_lengths``), so every run of
a cell runs the same chunks in the same ticks; ``info.schedule`` holds each
run to the host's replay of it, tick by tick, from the program's unit
ledger.  The window has to close on the clock with requests still queued:
``info.closed_on`` / ``info.queue_left`` say how it closed, and a queue that
fell under the mix's ``queue_left_min`` is compared as ``queue_short`` — a
rate over slots that had nothing to refill from is not the cell's."""

from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

from perfbench import estimators, serving, traffic_gen, weights
from perfbench import device as pdevice
from perfbench.checks import compare
from perfbench.checks import serve as check
from perfbench.reducers import unit_ledger


def schedule_against_replay(lengths, slots: int, chunk: int, n_ticks: int):
    """The window's ticks in the program's unit ledger (its last
    ``n_ticks`` units) against the replay: per tick the prefill chunks
    started, how many of them rode and the rows of the decode step.  ``None``
    for a program that keeps no such ledger; else how many ticks were
    compared, how many differ (a prefix hit between two random prompts may
    move a chunk by a tick) and the first of those, counted from the
    window's first tick."""
    ledger = unit_ledger.live_ledger("serve_tick")
    units = ledger.units()[-n_ticks:] if ledger is not None else []
    if len(units) < n_ticks:
        return None
    fill, ticks = traffic_gen.replay_backlog(
        lengths, slots, chunk, max_ticks=units[-1].ordinal + 1)
    want = [(t.calls, t.rode, t.live) for t in ticks[units[0].ordinal:]]
    got = [(u.calls.get("cmn_serve_prefill", 0),
            u.counts.get("cmn_serve_prefill.rode", 0),
            u.counts.get("cmn_serve_decode.live", 0)) for u in units]
    off = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    off += list(range(len(want), len(got)))
    return {"ticks": len(got), "fill": units[0].ordinal, "fill_replay": fill,
            "off": len(off), "first_off": off[:8],
            "calls": sum(x[0] for x in got), "rode": sum(x[1] for x in got),
            "calls_replay": sum(x[0] for x in want),
            "rode_replay": sum(x[1] for x in want)}


def run(ctx) -> Dict[str, Any]:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    if ctx.rehearse:
        tr = dict(tr, **tr.get("rehearse", {}))
    man = ctx.manifest
    model, m, pdt, specs = serving.build_model(man, cfg, ctx.rehearse)
    params = jax.block_until_ready(weights.make_params(specs, ctx.seed, pdt))
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
        if a - t_open >= ctx.seconds:
            closed_on = "clock"  # the first tick boundary at or after it
            break
        if not sched.pending:
            closed_on = "empty"
            break
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
    queue_left = sched.queue_depth
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
    ref = man.reference(cfg)
    numbers = check.served_gaps(ref, m, params, sample, tokens, pad)
    limits = (cfg["rehearse"] if ctx.rehearse else cfg)["check"]["serve"]
    ok, rows = check.judge(numbers, limits)
    left_ok, left_rows = compare(
        {"queue_short": float(max(0, tr["queue_left_min"] - queue_left))},
        {"queue_short": 0.0})
    ok, rows = ok and left_ok, left_rows + rows
    control = {q: check.served_gaps(ref, m, params, sample, tokens, pad,
                                    quant=q)
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
        "says": {"window": {"closed_on": closed_on, "queue_left": queue_left}},
        "info": {"ticks": len(ticks), "tokens": rate["work"],
                 "window_s": rate["seconds"], "finished": len(done),
                 "closed_on": closed_on, "queue_left": queue_left,
                 "schedule": schedule_against_replay(
                     [(len(r.prompt), r.max_new) for r in reqs], S,
                     sv["prefill_chunk"], len(ticks)),
                 "prefill_calls": rec.prefill_calls,
                 "tick_ms_median": values["tick_ms_median"],
                 "tick_ms_max": max(tick_ms),
                 "slowest_tick": tick_ms.index(max(tick_ms))},
        "counts": {"ticks": len(ticks), "tokens": rate["work"],
                   "admitted": len(rec.admit), "finished": len(done)},
    }

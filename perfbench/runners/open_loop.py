"""Independent users: arrivals on a fixed schedule whether or not earlier
requests have finished.  Judged by the tails (time to first token from when
the request was *due*; gaps between output tokens), never by throughput.

One thread: between scheduler ticks the loop submits whatever has come due,
as a server polls its socket between iterations; how late that made each
submission is reported (``gen_late_p95_ms``) and is inside every TTFT,
because TTFT counts from the due time."""

from __future__ import annotations

import time
from typing import Any, Dict, List, Sequence, Tuple

from perfbench import estimators, serving, traffic_gen, weights
from perfbench import device as pdevice
from perfbench.checks import serve as check


def serve(ctx, eng, reqs: Sequence[traffic_gen.Req], tr: Dict[str, Any],
          seconds: float, on_open=None) -> Dict[str, Any]:
    """Drive ``reqs`` through a fresh scheduler on ``eng``; the window is
    ``[lead_in_s, lead_in_s + seconds)`` of the generator's clock."""
    import jax

    clock, spans = ctx.clock, ctx.spans
    sched, rec = serving.new_scheduler(eng, clock, reqs)
    lead, cap = tr["lead_in_s"], tr["drain_cap_s"]
    horizon = lead + seconds
    sampled = [r for r in reqs if lead <= r.due < horizon]
    trace_at = lead + tr.get("trace_after_s", 2.0)
    trace_for = tr.get("trace_s", 6.0)
    pending = sorted(reqs, key=lambda r: (r.due, r.id))
    late: List[float] = []
    ticks: List[Tuple[float, float]] = []
    nxt, tracing, traced_ticks, window = 0, 0, 0, None
    opened = False
    t0 = clock.now()
    while True:
        now = clock.now() - t0
        if not opened and now >= lead:
            opened = True
            if on_open is not None:
                on_open()
        while nxt < len(pending) and pending[nxt].due <= now:
            r = pending[nxt]
            serving.submit(sched, r, t0 + r.due)
            late.append((now - r.due) * 1e3)
            nxt += 1
        if now >= horizon:
            waiting = [r for r in sampled if r.id not in rec.token_times]
            if not waiting or now >= horizon + cap:
                break
        if ctx.trace and tracing == 0 and now >= trace_at:
            ctx.start_trace()
            window = jax.profiler.TraceAnnotation("pb:window")
            window.__enter__()
            tracing, trace_t0 = 1, now
        if sched.pending:
            a = clock.now()
            with spans("tick"):
                sched.tick()
            ticks.append((a - t0, clock.now() - t0))
            traced_ticks += tracing == 1
        elif nxt < len(pending):
            with spans("generator_sleep"):
                time.sleep(max(0.0, pending[nxt].due - (clock.now() - t0)))
        else:
            break
        if tracing == 1 and clock.now() - t0 >= trace_t0 + trace_for:
            window.__exit__(None, None, None)
            ctx.stop_trace()
            tracing = 2
    if tracing == 1:
        window.__exit__(None, None, None)
        ctx.stop_trace()
    end = clock.now() - t0

    def rel(t):  # scheduler-clock time -> generator time
        return t - t0

    ttft, wait, prefill, failed = [], [], [], 0
    for r in sampled:
        times = rec.token_times.get(r.id)
        if times:
            ttft.append((rel(times[0]) - r.due) * 1e3)
            wait.append((rel(rec.admit[r.id]) - r.due) * 1e3)
            prefill.append((times[0] - rec.admit[r.id]) * 1e3)
        else:  # never answered: counts as the worst
            failed += 1
            ttft.append((end - r.due) * 1e3)
    gaps = []
    for times in rec.token_times.values():
        ts = [rel(t) for t in times if lead <= rel(t) < horizon]
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    in_window = [(b - a) * 1e3 for a, b in ticks if lead <= a < horizon]
    tokens_in_window = sum(1 for ts in rec.token_times.values()
                           for t in ts if lead <= rel(t) < horizon)
    return {
        "sched": sched, "rec": rec, "sampled": sampled, "failed": failed,
        "ttft_ms": ttft, "gap_ms": gaps, "tick_ms": in_window,
        "queue_wait_ms": wait, "prefill_ms": prefill, "late_ms": late,
        "tokens_in_window": tokens_in_window, "traced_ticks": traced_ticks,
        "unanswered_at_close": sum(
            1 for r in sampled if r.id not in rec.token_times
            or rel(rec.token_times[r.id][0]) >= horizon),
        "end_s": end,
    }


def tails(run: Dict[str, Any]) -> Dict[str, float]:
    p = estimators.percentile
    out = {
        "ttft_p90_ms": p(run["ttft_ms"], 90), "ttft_p50_ms": p(run["ttft_ms"], 50),
        "gap_p95_ms": p(run["gap_ms"], 95), "gap_p50_ms": p(run["gap_ms"], 50),
        "tick_ms_median": p(run["tick_ms"], 50),
        "gen_late_p95_ms": p(run["late_ms"], 95),
    }
    if run["queue_wait_ms"]:
        out["queue_wait_p90_ms"] = p(run["queue_wait_ms"], 90)
        out["prefill_ms_per_req"] = (sum(run["prefill_ms"])
                                     / len(run["prefill_ms"]))
    return out


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
    reqs = traffic_gen.open_loop(tr, m["vocab"], ctx.seed, ctx.seconds)
    opened = {}

    ctx.settle()

    def on_open():
        ctx.mark("lead_in_s")
        opened["setup_s"] = ctx.setup_seconds()

    out = serve(ctx, eng, reqs, tr, ctx.seconds, on_open)
    peak = pdevice.memory_peak_bytes(ctx.chips)
    gc_seen = ctx.gc_report()

    t_check = time.perf_counter()
    sched = out["sched"]
    done = {c.id: list(c.tokens) for c in sched.completions
            if c.status == "ok"}
    bad = sum(1 for c in sched.completions if c.status != "ok")
    sched.harvest_entries()
    serving.free_engine(eng)
    sample = check.pick_sample(reqs, done, tr["check_requests"], ctx.seed)
    numbers = check.served_gaps(params, m.get("pos_enc") == "rope", sample,
                                done, tr["check_pad"])
    limits = (cfg["rehearse"] if ctx.rehearse else cfg)["check"]["serve"]
    ok, rows = check.judge(numbers, limits)
    control = {q: check.served_gaps(params, m.get("pos_enc") == "rope",
                                    sample, done, tr["check_pad"], quant=q)
               for q in ctx.control}
    serving.free_params(eng, params)
    check_s = time.perf_counter() - t_check

    values = dict(tails(out), setup_s=opened["setup_s"])
    failed = out["failed"] + bad
    return {
        "correct": ok, "attempted": len(out["sampled"]), "failed": failed,
        "values": values,
        "facts": {"units": "ticks", "traced_units": out["traced_ticks"],
                  "memory_peak_bytes": peak},
        "compared": rows, "check_s": check_s, "memory_peak_bytes": peak,
        "sound": numbers, "control": control, "gc": gc_seen,
        "info": {
            "requests": len(reqs), "sampled": len(out["sampled"]),
            "ttft_p50_ms": values["ttft_p50_ms"],
            "gap_p50_ms": values["gap_p50_ms"], "gaps": len(out["gap_ms"]),
            "tokens_per_s_in_window": out["tokens_in_window"] / ctx.seconds,
            "unanswered_at_close": out["unanswered_at_close"],
            "ran_past_window_s": out["end_s"] - tr["lead_in_s"] - ctx.seconds,
        },
        "counts": {"requests": len(reqs), "sampled": len(out["sampled"]),
                   "finished": len(done), "gaps": len(out["gap_ms"])},
    }

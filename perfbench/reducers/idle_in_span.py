"""Device-idle time per traced tick or step, in ms, inside one phase of the
program: the gaps of device 0 whose midpoint lies in a ``cmn_*`` span
called ``span`` and in no shorter one — ``perfbench.trace.idle_gaps``'s
rule, one level deeper (that one stops at the benchmark's ``pb:*`` spans)."""

from perfbench import program_trace as pt
from perfbench import trace as ptrace


def idle_by_span(t, n):
    w = t.window
    ops = t.devices[min(t.devices)]
    gaps = ptrace.subtract([w], ptrace.union(ptrace.clip(ops, w)))
    spans = [s for s in t.spans if s.name.startswith(pt.SPAN_PREFIX)]
    acc = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [s for s in spans if s.start <= mid <= s.end]
        name = min(cover, key=lambda s: s.dur).name if cover else "outside"
        acc[name] = acc.get(name, 0.0) + 1e3 * (b - a) / n
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def reduce(facts, args):
    t, n = pt.current(facts), facts.get("traced_units")
    if t is None or not n or t.window is None or not t.devices:
        return None
    if not t.named(args["span"]):
        return None
    table = idle_by_span(t, n)
    pt.say_once("idle_by_program_span", t, lambda: table)
    return table.get(args["span"], 0.0)

"""Host time of one phase of the program, per traced tick or step, in ms:
the duration of the ``cmn_*`` spans called ``span`` (``where`` keeps those
whose stats match, ``{"program": "train_step"}``) minus what their child
spans cover, inside the traced window."""

from perfbench import program_trace as pt


def phases(t, n):
    """Every ``cmn_*`` span name of the window: how many, total and self
    ms per unit, and each of its counts summed over the window (the sums
    of two runs of one seed are identical)."""
    out = {}
    for s in t.spans:
        if not s.name.startswith(pt.SPAN_PREFIX) or not t.inside(s):
            continue
        row = out.setdefault(s.name, {"n": 0, "ms": 0.0, "self_ms": 0.0})
        row["n"] += 1
        row["ms"] += 1e3 * s.dur / n
        row["self_ms"] += 1e3 * t.self_seconds(s) / n
        for k, v in s.stats.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                sums = row.setdefault("sum", {})
                sums[k] = sums.get(k, 0) + v
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["self_ms"]))


def reduce(facts, args):
    t, n = pt.current(facts), facts.get("traced_units")
    if t is None or not n:
        return None
    pt.say_once("tick_phases", t, lambda: phases(t, n))
    spans = t.named(args["span"], args.get("where"))
    if not spans:
        return None
    return 1e3 * sum(t.self_seconds(s) for s in spans) / n

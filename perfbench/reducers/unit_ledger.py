"""Numbers over the **whole window** from the program's unit ledger
(``chainermn_tpu.observability.UnitLedger``): one record per scheduler tick
(``serve_tick``) or per wait for an input batch (``input_wait``), with the
unit's seconds and, per ``cmn_*`` phase that closed inside it, calls,
inclusive seconds and a few summed counts.  The ledger runs with or without
a profiler; the traced run is what finds the window in it.

**The join is the unit's ordinal.**  The traced span of a unit
(``cmn_serve_tick(tick=i)``, ``cmn_input_wait(n=k)``) carries the ordinal of
its ledger record.  The window's first unit is the smallest traced ordinal
minus the traffic file's ``trace_from_tick`` / ``trace_from_step`` (``from``);
its last is the ledger's last unit (the check that follows the window runs
no tick and draws no batch).

``args``: ``ledger`` (the kind), ``span`` and ``ordinal`` (the traced span
and the stat that holds its ordinal), ``from`` (the traffic file's key),
``what`` (a key of :data:`WHAT`) and what that reading takes (``name``,
``count``, ``over_ms``); ``over`` (``"traced"``: the reading is taken over
the traced units alone, which says how far the traced stretch stands for the
window; the tables stay the whole window's); ``call`` (the phase a unit
may hold many calls of,
priced for ``stall_ms`` and the stalls table), ``flags`` (what the stalls
table says a slow unit held, ``{label: a count's flat name or a phase}``) and
``join`` (the phases the join table compares beside the unit itself).  The
tables are printed once a run, from whichever metric of a ledger is read
first, so every metric file of one ledger gives these the same
(``tests/perfbench_tests/test_unit_ledger.py`` holds them to it).

``None`` where there is no trace, or the trace's units carry ordinals and
there is no ledger to join them to (``CMN_OBS=0`` leaves neither) or its ring
evicted the window's first unit.  A trace whose units carry **no ordinal** — a
program older than the ledger, the fixtures recorded on the chip — has no
whole window to find: the units are then the traced spans themselves, rebuilt
from the trace's own tree (:func:`units_of_trace`), and the tables say so
(``"from": "trace"``).

Three tables go to the log once a run: ``window_phases`` (every phase: calls,
mean and max ms a unit, share of the window), ``window_stalls`` (the five
slowest units and where each one's time went) and ``window_join`` (ledger
against trace, unit by unit, over the traced ordinals: the two clocks agree).
"""

from perfbench import estimators
from perfbench import program_trace as pt

BETWEEN = "(between children)"


def live_ledger(kind):
    """The program's newest ledger of ``kind``, or ``None`` (also for a
    program that has no such thing)."""
    try:
        from chainermn_tpu import observability
    except ImportError:
        return None
    find = getattr(observability, "unit_ledger", None)
    return find(kind) if find is not None else None


class Unit:
    """A unit rebuilt from a trace: the fields of the program's
    ``UnitRecord`` that the readings take."""

    def __init__(self, ordinal, t_mono, seconds):
        self.ordinal, self.t_mono, self.seconds = ordinal, t_mono, seconds
        self.calls, self.secs, self.direct, self.counts = {}, {}, {}, {}


def units_of_trace(t, span):
    """One :class:`Unit` per span called ``span`` inside the traced window,
    in order: every span under it is a call of its name with its inclusive
    seconds, and every whole-number stat a count."""
    units = []
    for i, root in enumerate(sorted(t.named(span), key=lambda s: s.start)):
        u = Unit(i, root.start, root.dur)
        todo = [(k, True) for k in root.children]
        while todo:
            k, top = todo.pop()
            s = t.spans[k]
            u.calls[s.name] = u.calls.get(s.name, 0) + 1
            u.secs[s.name] = u.secs.get(s.name, 0.0) + s.dur
            if top:
                u.direct[s.name] = u.direct.get(s.name, 0.0) + s.dur
            for key, v in s.stats.items():
                if isinstance(v, int) and not isinstance(v, bool):
                    flat = f"{s.name}.{key}"
                    u.counts[flat] = u.counts.get(flat, 0) + v
            todo.extend((c, False) for c in s.children)
        units.append(u)
    return units


def window(facts, args):
    """``(units of the window, traced spans by ordinal)`` — the second
    ``None`` where the units are the trace's own — or ``None``.  A test
    hands its ledgers in ``facts["unit_ledgers"]`` (by kind)."""
    t = pt.current(facts)
    if t is None:
        return None
    spans = t.named(args["span"])
    traced = {int(s.stats[args["ordinal"]]): s for s in spans
              if args["ordinal"] in s.stats}
    if not traced:
        return (units_of_trace(t, args["span"]), None) if spans else None
    ledger = (facts.get("unit_ledgers") or {}).get(args["ledger"])
    if ledger is None:
        ledger = live_ledger(args["ledger"])
    if ledger is None:
        return None
    units = ledger.units()
    lead = int(facts["traffic"][args["from"]])  # no default: the runner's
    first = max(0, min(traced) - lead)
    if not units or units[0].ordinal > first:
        return None  # the ring evicted the window's first unit
    if units[-1].ordinal < max(traced):
        return None  # another owner's ledger: these are not its units
    return [u for u in units if u.ordinal >= first], traced


# ---------------------------------------------------------------- readings
def _total(units, field, name):
    return sum(getattr(u, field).get(name, 0) for u in units)


def unit_ms(units, args):
    return 1e3 * sum(u.seconds for u in units) / len(units)


def unit_max_ms(units, args):
    return 1e3 * max(u.seconds for u in units)


def share_pct(units, args):
    return (100.0 * _total(units, "secs", args["name"])
            / sum(u.seconds for u in units))


def ms_per_call(units, args):
    """0 where the window made no call (as a share of nothing reads 0)."""
    calls = _total(units, "calls", args["name"])
    return 1e3 * _total(units, "secs", args["name"]) / max(calls, 1)


def count_per_call(units, args):
    calls = _total(units, "calls", args["name"])
    return (_total(units, "counts", f"{args['name']}.{args['count']}")
            / max(calls, 1))


def ms_outside(units, args):
    """Mean of a unit's seconds minus its seconds inside ``name``."""
    return 1e3 * sum(u.seconds - u.secs.get(args["name"], 0.0)
                     for u in units) / len(units)


def price(units, call):
    """``(base, per call)`` in seconds: what the window's median unit costs
    with no call of ``call`` in it, and the median of what one call adds to
    its unit — ``(seconds - base) / calls`` over the units that made calls.
    A call is priced by what it costs its unit, not by its own span: the
    device time of a dispatch-only prefill chunk lands in the decode step's
    readback, outside the span.  Without a unit free of calls the base is
    the median unit's seconds outside the calls' own spans."""
    free = [u.seconds for u in units if not u.calls.get(call)]
    if free:
        base = estimators.median(free)
    else:
        base = estimators.median([u.seconds - u.secs.get(call, 0.0)
                                  for u in units])
    each = [(u.seconds - base) / u.calls[call]
            for u in units if u.calls.get(call)]
    return base, (max(0.0, estimators.median(each)) if each else 0.0)


def unexplained(units, call):
    """``(base, per call, lost)``: :func:`price`, and per unit the seconds
    those prices do not explain."""
    base, per_call = price(units, call)
    return base, per_call, [u.seconds - base - u.calls.get(call, 0) * per_call
                            for u in units]


def stall_ms(units, args):
    """The sum, over the units whose unexplained time exceeds ``over_ms``,
    of that time: many calls in one unit (a refill) are explained, a unit
    that loses time at no call is not."""
    over = args.get("over_ms", 50.0) / 1e3
    return 1e3 * sum(x for x in unexplained(units, args["call"])[2]
                     if x > over)


WHAT = {f.__name__: f for f in (unit_ms, unit_max_ms, share_pct, ms_per_call,
                                count_per_call, ms_outside, stall_ms)}


# ------------------------------------------------------------------ tables
def phases(units):
    """Every phase name of the window: calls, mean and max ms a unit, share
    of the window's seconds (inclusive: a phase nested in another counts in
    both); ``BETWEEN`` is what no immediate child of a unit covers."""
    n, whole = len(units), sum(u.seconds for u in units)
    names = {k for u in units for k in u.calls}
    rows = {}
    for name in names:
        sec = [u.secs.get(name, 0.0) for u in units]
        rows[name] = {"calls": _total(units, "calls", name),
                      "mean_ms": 1e3 * sum(sec) / n, "max_ms": 1e3 * max(sec),
                      "share_pct": 100.0 * sum(sec) / whole}
    gap = [u.seconds - sum(u.direct.values()) for u in units]
    rows[BETWEEN] = {"calls": n, "mean_ms": 1e3 * sum(gap) / n,
                     "max_ms": 1e3 * max(gap),
                     "share_pct": 100.0 * sum(gap) / whole}
    return {"units": n, "window_s": whole, "mean_unit_ms": 1e3 * whole / n,
            "rows": dict(sorted(rows.items(),
                                key=lambda kv: -kv[1]["share_pct"]))}


def _holder(u, medians, direct_medians):
    """``(child, its excess, excess by phase)``: the immediate child of the
    unit (or ``BETWEEN``) with the largest excess over its own window median,
    and the three phases of any depth with the largest inclusive excess — a
    stall in ``cmn_engine_readback`` shows there and in every span around it,
    one between a span's children in that span alone."""
    excess = {k: v - direct_medians.get(k, 0.0) for k, v in u.direct.items()}
    excess[BETWEEN] = (u.seconds - sum(u.direct.values())
                       - direct_medians[BETWEEN])
    child = max(excess, key=excess.get)
    deep = {k: v - medians.get(k, 0.0) for k, v in u.secs.items()}
    top = sorted(deep, key=deep.get, reverse=True)[:3]
    return child, excess[child], {k: 1e3 * deep[k] for k in top}


def stalls(units, call=None, flags=None, n=5):
    """The ``n`` slowest units: ordinal, ms, seconds since the window opened,
    the time the window's prices leave unexplained (:func:`unexplained`; with
    no ``call`` to price, the excess over the median unit), the child that
    holds the largest excess and the phases of any depth that hold the most
    (:func:`_holder`), and per label of ``flags`` what the unit held of it:
    the summed count of that flat name, else the calls of that phase."""
    names = {k for u in units for k in u.calls}
    medians = {k: estimators.median([u.secs.get(k, 0.0) for u in units])
               for k in names}
    direct = {k: estimators.median([u.direct.get(k, 0.0) for u in units])
              for k in names}
    direct[BETWEEN] = estimators.median(
        [u.seconds - sum(u.direct.values()) for u in units])
    if call is not None:
        base, per_call, lost = unexplained(units, call)
    else:
        base, per_call = estimators.median([u.seconds for u in units]), 0.0
        lost = [u.seconds - base for u in units]
    rows = []
    for i in sorted(range(len(units)), key=lambda i: -units[i].seconds)[:n]:
        u = units[i]
        child, excess, deep = _holder(u, medians, direct)
        rows.append({
            "ordinal": u.ordinal, "ms": 1e3 * u.seconds,
            "at_s": u.t_mono - units[0].t_mono,
            "unexplained_ms": 1e3 * lost[i],
            "calls": u.calls.get(call, 0) if call else None,
            "child": child, "child_excess_ms": 1e3 * excess, "excess_ms": deep,
            **{label: u.counts.get(key, u.calls.get(key, 0))
               for label, key in (flags or {}).items()}})
    return {"base_ms": 1e3 * base, "call_ms": 1e3 * per_call, "rows": rows}


def join(t, units, traced, names=()):
    """Ledger against trace over the traced ordinals: each unit's seconds and
    its rows of ``names`` beside the trace's spans of that unit (ms), and the
    largest disagreement of each, relative and in us."""
    by_ordinal = {u.ordinal: u for u in units}

    def under(span, name):
        out, todo = 0.0, list(span.children)
        while todo:
            s = t.spans[todo.pop()]
            if s.name == name:
                out += s.dur
            todo.extend(s.children)
        return out

    rows = []
    worst = {k: {"rel": 0.0, "us": 0.0} for k in ("unit",) + tuple(names)}

    def compare(key, a, b):
        w = worst[key]
        w["us"] = max(w["us"], 1e6 * abs(a - b))
        if b:
            w["rel"] = max(w["rel"], abs(a - b) / b)
        return [1e3 * a, 1e3 * b]

    for ordinal, span in sorted(traced.items()):
        u = by_ordinal.get(ordinal)
        if u is None:
            continue
        row = {"ordinal": ordinal, "unit": compare("unit", u.seconds, span.dur)}
        for k in names:
            row[k] = compare(k, u.secs.get(k, 0.0), under(span, k))
        rows.append(row)
    return {"units": len(rows), "worst": worst, "rows": rows}


def reduce(facts, args):
    found = window(facts, args)
    if found is None:
        return None
    units, traced = found
    t = pt.current(facts)
    source = {"from": "trace" if traced is None else "ledger"}
    pt.say_once("window_phases", t, lambda: dict(source, **phases(units)))
    pt.say_once("window_stalls", t,
                lambda: dict(source, **stalls(units, args.get("call"),
                                              args.get("flags"))))
    if traced is not None:
        pt.say_once("window_join", t,
                    lambda: join(t, units, traced, args.get("join", ())))
    if args.get("over") == "traced" and traced is not None:
        units = [u for u in units if u.ordinal in traced]
    return WHAT[args["what"]](units, args) if units else None

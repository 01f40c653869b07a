"""Arithmetic from the benchmark's own clock readings to its metrics."""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple


def whole_unit_rate(units: Sequence[Tuple[float, float, float]]) -> Dict[str, float]:
    """Work per second over whole ticks (or steps).

    ``units`` is the log of the window: one ``(start, end, work)`` per tick
    that *started* inside the window, in order; the window closes at the end
    of the tick that was running when the clock passed ``--seconds``, so no
    tick is cut.  The rate is all their work over the time they took, first
    start to last end — every tick of the window and all of its time, idle
    gaps between ticks included."""
    if not units:
        raise ValueError("no whole unit of work in the window")
    t0, t1 = units[0][0], units[-1][1]
    work = float(sum(u[2] for u in units))
    if t1 <= t0:
        raise ValueError("window of zero length")
    return {"rate": work / (t1 - t0), "work": work, "seconds": t1 - t0,
            "units": float(len(units))}


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of all ``values``: the
    smallest value with at least ``q`` percent of the sample at or below
    it.  No interpolation, so a tail is a latency some request really had."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[k - 1])


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median — the contract's spread (``statistics.quantiles(n=4)``)."""
    import statistics

    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None

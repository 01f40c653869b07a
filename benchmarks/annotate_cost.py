"""What a host phase costs: ``observability.annotate`` open + close, in ns a
span, outside a unit (the shared no-op), inside a unit of a ``UnitLedger``
(timed and booked to the unit's record), with kept counts, and a whole unit.

A serving tick is one unit of ~20 spans, so "inside a unit" x 20 + "a whole
unit" is the ledger's cost a tick.  Host code only: no device is touched,
the number is the machine's it runs on (``docs/observability.md`` quotes the
chip machine's host).

    PYTHONPATH=. python benchmarks/annotate_cost.py [--spans 200000]
"""

from __future__ import annotations

import argparse
import json
import time

from chainermn_tpu.observability import tracing


def _ns_per_span(n: int, open_span) -> float:
    """Best of three loops of ``n`` spans, less the loop's own cost."""
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with open_span():
                pass
        best = min(best, time.perf_counter() - t0)
    return best / n * 1e9


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spans", type=int, default=200_000)
    n = ap.parse_args().spans
    annotate = tracing.annotate
    ledger = tracing.UnitLedger(
        "annotate_cost", ordinal="i",
        keep={"cmn_kept": ("tokens", "final")})
    out = {"spans": n}
    out["bare_with_ns"] = _ns_per_span(n, lambda: tracing._NO_SPAN)
    out["outside_unit_ns"] = _ns_per_span(n, lambda: annotate("cmn_x"))
    out["timed_outside_unit_ns"] = _ns_per_span(
        n, lambda: annotate("cmn_x", timed=True))
    with annotate("cmn_unit", ledger=ledger, i=0):
        out["inside_unit_ns"] = _ns_per_span(n, lambda: annotate("cmn_x"))
        out["inside_unit_kept_counts_ns"] = _ns_per_span(
            n, lambda: annotate("cmn_kept", req=7, slot=3, tokens=32,
                                final=0, lazy=len))
    out["whole_unit_ns"] = _ns_per_span(
        n, lambda: annotate("cmn_unit", ledger=ledger, i=1, iter=2))
    # a tick as the backlog cell runs it: ~20 spans, four with kept counts
    out["per_tick_us"] = (
        16 * (out["inside_unit_ns"] - out["outside_unit_ns"])
        + 4 * (out["inside_unit_kept_counts_ns"] - out["outside_unit_ns"])
        + out["whole_unit_ns"] - out["outside_unit_ns"]) / 1e3
    print(json.dumps({"annotate_cost": out}))


if __name__ == "__main__":
    main()

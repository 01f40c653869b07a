"""The unit ledger's tables of a perfbench run that had **no profiler**.

``perfbench``'s ``window_*`` metrics find the window through a trace, so a
``--trace 0`` run prints none of them — and those are the runs whose spread
and stalled ticks the tables are for.  This runs one perfbench run in this
process, unchanged, and then reads the program's ledger from outside: the
window is the ledger's last ``info.ticks`` (serving) or ``info.steps``
(training) units, what the run's own ``info`` line counted.  It prints the
run's lines as they come and one more, ``window_ledger``: phases, the five
slowest units and the unexplained time, from the same functions and the same
metric files' ``args`` as the traced run's tables.

    PYTHONPATH=. python benchmarks/window_ledger.py \\
        --workload gpt2-xl_serve_backlog --seed 2147489701 --seconds 45

Stopgap until a ``benchmark`` PR has the runner hand a reducer ``info.ticks``
(``PERF.md`` section 7); on the chip through ``chiprun``, one process a chip.
"""

from __future__ import annotations

import io
import json
import sys

from perfbench import run as prun
from perfbench.manifest import Manifest
from perfbench.reducers import unit_ledger as ul


class _Tee(io.TextIOBase):
    def __init__(self, out):
        self.out, self.kept = out, []

    def write(self, s):
        self.kept.append(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def _ledger_args(man: Manifest, workload: str):
    """The ``args`` of the cell's first metric that reads a unit ledger."""
    for m in man.metrics_for(workload, "per_layer"):
        spec = man.metric_file(m["name"])
        if spec.get("reducer") == "unit_ledger":
            return spec["args"]
    return None


def main(argv) -> int:
    tee = _Tee(sys.stdout)
    sys.stdout = tee
    try:
        rc = prun.main(argv)
    finally:
        sys.stdout = tee.out
    lines = [json.loads(line) for line in "".join(tee.kept).splitlines()
             if line.startswith("{")]
    info = next((d["info"] for d in lines if d.get("info")), None)
    workload = argv[argv.index("--workload") + 1]
    args = _ledger_args(Manifest(), workload)
    ledger = ul.live_ledger(args["ledger"]) if args else None
    n = (info or {}).get("ticks") or (info or {}).get("steps")
    if ledger is None or not n:
        print(json.dumps({"window_ledger": None}))
        return rc
    units = ledger.units()[-n:]
    table = ul.phases(units)
    found = ul.stalls(units, args.get("call"), args.get("flags"))
    print(json.dumps({"window_ledger": {
        "kind": args["ledger"], "units": len(units),
        "evicted": ledger.evicted, "ledger_window_s": table["window_s"],
        "info_window_s": info.get("window_s"),
        "mean_unit_ms": table["mean_unit_ms"],
        "max_unit_ms": ul.unit_max_ms(units, args),
        "stall_ms": (ul.stall_ms(units, args) if args.get("call")
                     else None),
        "base_ms": found["base_ms"], "call_ms": found["call_ms"],
        "stalls": found["rows"], "phases": table["rows"]}}))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

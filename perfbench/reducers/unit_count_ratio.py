"""One summed count of the program's unit ledger over another, across the
traced units (``over: "traced"``) or the whole window: ``count`` and ``per``
are flat names (``"cmn_engine_readback.moe_experts_touched"``), the window is
found as ``unit_ledger`` finds it (``ledger``, ``span``, ``ordinal``,
``from``).  A program that books neither count, or a window in which ``per``
sums to nothing, reports nothing."""

from perfbench.reducers import unit_ledger


def reduce(facts, args):
    found = unit_ledger.window(facts, args)
    if found is None:
        return None
    units, traced = found
    if args.get("over") == "traced" and traced is not None:
        units = [u for u in units if u.ordinal in traced]
    per = sum(u.counts.get(args["per"], 0) for u in units)
    if not per:
        return None
    return sum(u.counts.get(args["count"], 0) for u in units) / per

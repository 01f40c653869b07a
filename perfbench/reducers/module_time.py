"""Device time of the programs (``XLA Modules`` events) whose name matches
``pattern``, inside the traced window, averaged over the devices.
``per: "unit"`` gives ms per traced tick or step, ``per: "busy"`` the share
(%) of the device's busy time."""

from perfbench import trace as ptrace


def reduce(facts, args):
    t = facts.get("trace")
    if t is None or not t.devices:
        return None
    w = t.window
    secs = [ptrace.total(ptrace.union(ptrace.clip(
        ptrace.matching(d.modules, args["pattern"]), w)))
        for d in t.devices.values()]
    sec = sum(secs) / len(secs)
    if args["per"] == "unit":
        n = facts.get("traced_units")
        return 1e3 * sec / n if n else None
    busy, _ = ptrace.busy_seconds(t)
    return 100.0 * sec / busy if busy else None

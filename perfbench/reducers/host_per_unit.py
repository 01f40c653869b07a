"""Host time per tick or step: wall time of the traced ticks minus the time
an operation ran on the device in them, over their number, in ms."""

from perfbench import trace as ptrace


def reduce(facts, args):
    t, n = facts.get("trace"), facts.get("traced_units")
    if t is None or not n:
        return None
    busy, window = ptrace.busy_seconds(t)
    return 1e3 * (window - busy) / n

"""Share of the traced window in which no operation ran on the device."""

from perfbench import trace as ptrace


def reduce(facts, args):
    t = facts.get("trace")
    if t is None:
        return None
    busy, window = ptrace.busy_seconds(t)
    return 100.0 * (1.0 - busy / window)

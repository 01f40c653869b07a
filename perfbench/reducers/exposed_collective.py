"""Time the collective operations (names matching ``pattern``) ran with no
other operation running on that device, per traced step, in ms, averaged
over the devices."""

import re

from perfbench import trace as ptrace


def reduce(facts, args):
    t, n = facts.get("trace"), facts.get("traced_units")
    if t is None or not n or not t.devices:
        return None
    rx = re.compile(args["pattern"])
    w = t.window
    out = []
    for d in t.devices.values():
        coll = ptrace.union(ptrace.clip(
            [e for e in d.ops if rx.search(e.name)], w))
        rest = ptrace.union(ptrace.clip(
            [e for e in d.ops if not rx.search(e.name)], w))
        out.append(ptrace.total(ptrace.subtract(coll, rest)))
    return 1e3 * sum(out) / len(out) / n

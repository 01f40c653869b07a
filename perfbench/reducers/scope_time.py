"""Device time of the operations whose *scope* matches, inside the traced
window, averaged over the devices (``perfbench.program_trace``: the HLO
``op_name`` the program's ``jax.named_scope`` s and Flax modules wrote).

``scope`` is a regular expression matched as a whole token of the scope
path (``attn\\.\\w+``, ``optimizer_update|apply_updates``); ``key`` instead
names one row of the by-scope table (``unscoped``), the innermost token of
the vocabulary.  ``phase`` keeps ``fwd``, ``remat`` or ``bwd`` operations
only (``any`` by default).  ``per: "unit"`` gives ms per traced tick or
step, ``per: "busy"`` the share (%) of the device's busy time.  Time is an
operation's *own* (``program_trace.own_seconds``): a loop's body is not
counted again for the loop.  A scope that no operation of the window
carries reports nothing."""

from perfbench import program_trace as pt


def reduce(facts, args):
    t, n = pt.current(facts), facts.get("traced_units")
    if t is None or not t.devices or not n:
        return None
    pt.say_once("by_scope", t, lambda: pt.by_scope(t, n))
    rx = pt.token_regex(args["scope"]) if "scope" in args else None
    phase = args.get("phase", "any")
    secs, found = [], False
    for dev, events in t.devices.items():
        mine = 0.0
        for e, own in zip(events, t.own(dev)):
            if ((rx.search(e.scope) if rx is not None
                 else pt.scope_key(e.scope) == args["key"])
                    and (phase == "any" or pt.phase_of(e.scope) == phase)):
                found = True
                mine += own
        secs.append(mine)
    if not found:
        return None
    sec = sum(secs) / len(secs)
    if args["per"] == "unit":
        return 1e3 * sec / n
    busy = pt.busy_seconds(t)
    return 100.0 * sec / busy if busy else None

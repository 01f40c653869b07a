"""A *scope's* share of its roofline: like ``kernel_roofline``, for work that
is no kernel of its own but XLA fusions under one ``jax.named_scope`` — the
least time the chip could take for the operations and bytes the scope *needs*
over the device time of the operations that carry it, inside the traced
window.

``scope`` is a regular expression matched as a whole token of the scope path
(as ``scope_time`` matches it); time is an operation's *own*
(``program_trace.own_seconds``).  ``need`` names ``flops/<need>.py`` whose
``need(facts, calls) -> {"flops", "bytes"}`` is handed the number of matched
device events.  A scope no operation of the window carries, or a need of
nothing, reports nothing."""

import json

from perfbench import program_trace as pt
from perfbench.flops import roofline


def reduce(facts, args):
    t = pt.current(facts)
    if t is None or not t.devices or not facts.get("traced_units"):
        return None
    rx = pt.token_regex(args["scope"])
    seconds, events = 0.0, 0
    for dev, evs in t.devices.items():
        for e, own in zip(evs, t.own(dev)):
            if own and rx.search(e.scope):
                seconds += own
                events += 1
    k = len(t.devices)
    if not events:
        return None
    seconds /= k
    need = facts["manifest"].need(args["need"])(facts, events / k)
    if not (need["flops"] or need["bytes"]) or not seconds:
        return None
    least, bound = roofline.least_seconds(need, facts["peaks"])
    print(json.dumps({"roofline": args.get("label", args["scope"]),
                      "bound": bound, "least_s": least, "scope_s": seconds,
                      "need": need, "events": events}), flush=True)
    return 100.0 * least / seconds

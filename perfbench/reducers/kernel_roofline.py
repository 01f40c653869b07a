"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes its calls *need* over the time its events took in
the traced window.

``kernels`` lists ``{"pattern", "need"}``: events whose name matches
``pattern`` are that kernel's calls, one event a call (a kernel launched a
varying number of times a call is found by the launch that comes once, and
its other launches by a pattern whose need counts nothing,
``flops/flash_head_dim_time_only.py``).  A pattern is the name the kernel's
``pallas_call`` gave it
(``^%?flash_fwd\\b``), never a result type, which two kernels can share.
``need`` names the file ``flops/<need>.py`` whose ``need(facts, calls) ->
{"flops", "bytes"}`` counts what ``calls`` calls on each device require,
from the shapes in ``facts``.
The bound (compute or bytes) is whichever is larger for the calls together;
it is printed on an earlier line."""

import json

from perfbench import trace as ptrace
from perfbench.flops import roofline


def reduce(facts, args):
    t = facts.get("trace")
    if t is None or not t.devices:
        return None
    w = t.window
    need = {"flops": 0.0, "bytes": 0.0}
    seconds = 0.0
    matched = {}
    k = len(t.devices)
    for spec in args["kernels"]:
        events = [e for d in t.devices.values()
                  for e in ptrace.matching(d.ops, spec["pattern"])
                  if w is None or (e.start >= w[0] and e.end <= w[1])]
        if not events:
            continue
        seconds += sum(e.dur for e in events) / k
        matched[spec["need"]] = matched.get(spec["need"], 0) + len(events)
        calls = len(events) / k
        needed = facts["manifest"].need(spec["need"])(facts, calls)
        for key in need:
            need[key] += needed[key]
    if not seconds:
        return None
    least, bound = roofline.least_seconds(need, facts["peaks"])
    print(json.dumps({"roofline": args.get("label", "kernel"), "bound": bound,
                      "least_s": least, "kernel_s": seconds,
                      "need": need, "events": matched}), flush=True)
    return 100.0 * least / seconds

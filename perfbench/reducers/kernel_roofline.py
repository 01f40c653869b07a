"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes its calls *need* (``perfbench/flops/<need>.py``,
from shapes) over the time its events took in the traced window.

``kernels`` lists ``{"pattern", "need"}``: events whose name matches
``pattern`` are that kernel's calls; ``need`` names the function that gives
operations and bytes.  The bound (compute or bytes) is whichever is larger
for the calls together; it is printed on an earlier line."""

import json

from perfbench import trace as ptrace
from perfbench.flops import flash_attention, paged_decode_attention, roofline


def _need(name, facts, calls_per_device):
    m = facts["config"]["model"]
    H = m["n_heads"]
    KH = m.get("n_kv_heads") or H
    Dh = m["d_model"] // H
    if name == "paged_decode":
        # one call per layer per tick; contexts summed over the traced ticks
        return paged_decode_attention.call(
            facts["traced_context_tokens"] * m["n_layers"],
            facts["traffic"]["slots"] * m["n_layers"] * facts["traced_units"],
            H, KH, Dh)
    seq = facts["config"]["train"]["seq_len"]
    rows = facts["config"]["train"]["rows_per_chip"]
    fn = {"flash_forward": flash_attention.forward,
          "flash_backward": flash_attention.backward}[name]
    one = fn(rows, H, KH, seq, Dh)
    return {k: v * calls_per_device for k, v in one.items()}


def reduce(facts, args):
    t = facts.get("trace")
    if t is None or not t.devices:
        return None
    w = t.window
    need = {"flops": 0.0, "bytes": 0.0}
    seconds = 0.0
    k = len(t.devices)
    for spec in args["kernels"]:
        events = [e for d in t.devices.values()
                  for e in ptrace.matching(d.ops, spec["pattern"])
                  if w is None or (e.start >= w[0] and e.end <= w[1])]
        if not events:
            continue
        seconds += sum(e.dur for e in events) / k
        calls = len(events) / k / spec.get("events_per_call", 1)
        for key, v in _need(spec["need"], facts, calls).items():
            need[key] += v
    if not seconds:
        return None
    least, bound = roofline.least_seconds(need, facts["peaks"])
    print(json.dumps({"roofline": args.get("label", "kernel"), "bound": bound,
                      "least_s": least, "kernel_s": seconds,
                      "need": need}), flush=True)
    return 100.0 * least / seconds

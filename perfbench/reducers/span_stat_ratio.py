"""A ratio of two counts the program attached to its ``cmn_*`` spans called
``span``: 100 x the sum of ``num`` over the sum of ``den``, over the spans
inside the traced window."""

from perfbench import program_trace as pt


def reduce(facts, args):
    t = pt.current(facts)
    if t is None:
        return None
    spans = [s for s in t.named(args["span"])
             if args["num"] in s.stats and args["den"] in s.stats]
    den = sum(float(s.stats[args["den"]]) for s in spans)
    if not den:
        return None
    return 100.0 * sum(float(s.stats[args["num"]]) for s in spans) / den

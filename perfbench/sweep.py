"""Finding the knee of an open-loop cell, once, when the cell is defined.

    python3 -m perfbench.sweep --workload gpt2-xl_serve_chat --rates 0.6,0.9,1.2,1.5 --seconds 30

One process, one engine, one set of weights; each rate runs the cell's own
loop (``runners/open_loop.serve``) for ``--seconds`` on traffic generated at
that rate.  The knee is the highest rate at which the backlog does not grow:
requests due in the window that had no first token when it closed stay a
handful, and the run does not go on long past the window.  The table goes
into PERF.md and four fifths of the knee into the traffic file.  Not part of
a benchmark run."""

from __future__ import annotations

import argparse
import json
import sys

from perfbench import device as pdevice
from perfbench import run as prun
from perfbench import serving, traffic_gen, weights
from perfbench.manifest import Manifest
from perfbench.runners import open_loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    ctx = prun.new_context(Manifest(), args.workload, args.seed,
                           args.seconds, args.rehearse)
    cfg, tr, clock = ctx.config, ctx.traffic, ctx.clock
    if tr["kind"] != "open_loop":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    if args.rehearse:
        tr = dict(tr, **tr.get("rehearse", {}))
    try:
        prun.open_device(ctx.chips, args.rehearse)
    except pdevice.NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return prun.EXIT_NO_ACCELERATOR
    import jax

    model, m, pdt = serving.build_model(cfg, args.rehearse)
    params = jax.block_until_ready(weights.make_params(m, args.seed, pdt))
    eng, sv = serving.build_engine(cfg, model, params, args.rehearse)
    serving.warm_programs(eng, clock, m["vocab"], sv["prefill_chunk"])
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(tr, rate_per_s=rate)
        reqs = traffic_gen.open_loop(mix, m["vocab"], args.seed, args.seconds)
        out = open_loop.serve(ctx, eng, reqs, mix, args.seconds)
        tails = open_loop.tails(out)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "sampled": len(out["sampled"]),
            "unanswered_at_close": out["unanswered_at_close"],
            "failed": out["failed"],
            "ran_past_window_s": out["end_s"] - mix["lead_in_s"] - args.seconds,
            "tokens_per_s": out["tokens_in_window"] / args.seconds,
            **{k: round(v, 2) for k, v in tails.items()},
        }), flush=True)
        out["sched"].harvest_entries()  # empty the slots and the queue
        eng.drop_prefix_cache()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Reading the limits: sound runs and the lower-precision control, several
seeds in one process (one set-up of the chip, not one per seed).

    python3 -m perfbench.control --workload <cell> --seeds 1,2,3 --seconds 20

For every seed the cell runs as the benchmark runs it (same runner, same
sizes, a window of ``--seconds``) and, after the usual comparison with the
float32 reference, the control is read: the reference put in the program's
place and computed in the precision below the one the configuration states
(``check.control``).  Prints each seed's numbers and, last, the largest sound
and the smallest control reading of every number.  Not part of a benchmark
run; PERF.md records what it printed and the limits set from it."""

from __future__ import annotations

import argparse
import json
import sys

from perfbench import device as pdevice
from perfbench import run as prun
from perfbench.manifest import Manifest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--precisions", default=None,
                    help="comma list; default: the configuration's "
                         "check.control; 'none' reads sound runs only")
    args = ap.parse_args(argv)
    man = Manifest()
    w = man.workload(args.workload)
    cfg = man.config(w["config"])
    runner = man.runner(man.traffic(w["traffic"])["kind"])
    try:
        prun.open_device(w["chips"], args.rehearse)
    except pdevice.NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return prun.EXIT_NO_ACCELERATOR
    precisions = tuple(
        p for p in (args.precisions or cfg["check"]["control"]).split(",")
        if p != "none")
    sound, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = prun.new_context(man, args.workload, seed, args.seconds,
                               args.rehearse, control=precisions)
        res = runner.run(ctx)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "sound": res["sound"], "control": res["control"],
                          "values": res["values"], "info": res["info"],
                          "check_s": res["check_s"],
                          "detail": res["compared"][-1]}), flush=True)
        for k, v in res["sound"].items():
            if isinstance(v, float) and "gap" in k:
                sound[k] = max(sound.get(k, 0.0), v)
        for prec, nums in (res["control"] or {}).items():
            for k, v in nums.items():
                if isinstance(v, float) and "gap" in k:
                    key = f"{prec}:{k}"
                    control[key] = min(control.get(key, float("inf")), v)
        del res, ctx
    print(json.dumps({"largest_sound": sound, "smallest_control": control}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

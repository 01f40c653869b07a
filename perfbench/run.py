"""``python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One process: loads the cell's files, makes weights and traffic from the
seed, warms every program the window will use (all of that is ``setup_s``),
measures for ``--seconds``, compares what the timed path produced with the
plain reference, and prints one JSON object as the last line of stdout (each
number compared beside its limit under its last key, and again as the last
lines of stderr).
Without a TPU holding the chips the cell asks for it exits non-zero and
prints no result; ``--rehearse`` is the labelled tiny-size path for CPU tests
and never prints a device metric.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

if __package__ in (None, ""):  # ``python3 perfbench/run.py``
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import device as pdevice  # noqa: E402
from perfbench.manifest import ROOT, Manifest  # noqa: E402
from perfbench.spans import Clock, Spans  # noqa: E402

EXIT_NO_ACCELERATOR = 3


@dataclass
class Context:
    """What a runner is handed."""

    manifest: Manifest
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    chips: int
    clock: Clock
    spans: Spans
    trace_dir: str
    t_process: float  # perf_counter at process start
    #: lower precisions whose control numbers are read too (perfbench.control)
    control: tuple = ()
    marks: Dict[str, float] = field(default_factory=dict)
    gc_pause_ms: List[float] = field(default_factory=list)
    _gc_watch: Any = None

    def settle(self) -> None:
        """Last thing of set-up.  Collect the garbage set-up left (tracing a
        30-layer model leaves millions of objects) and freeze what survives,
        so that no full collection walks that heap inside the window.
        Collections that still happen are timed and reported
        (``gc_in_window``), which is how PERF.md could rule the collector out
        as the cause of the serving cell's stalled ticks."""
        import gc

        gc.collect()
        gc.freeze()
        started = [0.0]

        def watch(phase, info):
            if phase == "start":
                started[0] = time.perf_counter()
            else:
                self.gc_pause_ms.append((time.perf_counter() - started[0]) * 1e3)

        self._gc_watch = watch
        gc.callbacks.append(watch)

    def gc_report(self) -> Dict[str, float]:
        """What was collected since :meth:`settle`; ends the watch."""
        import gc

        if self._gc_watch in gc.callbacks:
            gc.callbacks.remove(self._gc_watch)
        p = self.gc_pause_ms
        return {"collections": len(p), "pause_ms_total": sum(p),
                "pause_ms_max": max(p, default=0.0)}

    def mark(self, name: str) -> None:
        """Close the set-up phase ``name`` (seconds since the last mark)."""
        now = time.perf_counter()
        last = self.marks.get("_last", self.t_process)
        self.marks[name] = self.marks.get(name, 0.0) + (now - last)
        self.marks["_last"] = now

    def setup_seconds(self) -> float:
        return time.perf_counter() - self.t_process

    def say(self, **line) -> None:
        print(json.dumps(line), flush=True)

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.spans.annotate = True

    def stop_trace(self) -> None:
        import jax

        self.spans.annotate = False
        jax.profiler.stop_trace()


def init_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
    ``<checkout>/.jax_cache``; every program is kept, however quickly it
    compiled, so a second run in a checkout compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def open_device(chips: int, rehearse: bool) -> Dict[str, Any]:
    """Compile cache (real runs), CPU dispatch mode (rehearsals), and the
    device the cell needs — or :class:`perfbench.device.NoAccelerator`."""
    import jax

    dev = pdevice.describe(chips, rehearse)
    if not rehearse:
        # only once the chips are there: a refused run leaves JAX's
        # configuration as it found it (the tests call this in-process)
        init_compile_cache()
    elif jax.default_backend() == "cpu":
        # in-process CPU collectives deadlock under async dispatch
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    return dev


def new_context(man: Manifest, workload: str, seed: int, seconds: float,
                rehearse: bool, trace: bool = False, control: tuple = (),
                t_process: Optional[float] = None) -> Context:
    w = man.workload(workload)
    clock = Clock()
    return Context(
        manifest=man, workload=w, config=man.config(w["config"]),
        traffic=man.traffic(w["traffic"]), seed=seed, seconds=seconds,
        trace=trace, rehearse=rehearse, chips=w["chips"], clock=clock,
        spans=Spans(clock),
        trace_dir=os.path.join(man.root, ".perfbench_trace", w["name"]),
        t_process=time.perf_counter() if t_process is None else t_process,
        control=control,
    )


def reduce_metrics(ctx: Context, metrics: List[Dict[str, Any]],
                   facts: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each metric through its own reader (``metrics/<name>.json`` names
    the reducer and its arguments); one that finds nothing to read is left
    out of the line."""
    out = {}
    for m in metrics:
        spec = ctx.manifest.metric_file(m["name"])
        value = ctx.manifest.reducer(spec["reducer"]).reduce(
            facts, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def compared_numbers(rows: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Each number the check compared beside its limit, by its short name."""
    return {r["number"]: {"value": r["value"], "limit": r["limit"]}
            for r in rows if "number" in r}


def say_compared(numbers: Dict[str, Dict[str, Any]]) -> None:
    """The last lines of standard error: where a run is not correct, the end
    of that stream is what the driver's record keeps."""
    for name, c in numbers.items():
        print(f"perfbench: compared {name} = {c['value']!r} "
              f"(limit {c['limit']!r})", file=sys.stderr, flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever device is there; prints "
                         "counts only, never a device metric")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under .perfbench_trace/")
    ap.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    ap.add_argument("--data", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    man = Manifest(args.root, args.data)
    seconds = float(args.seconds if args.seconds is not None
                    else man.doc["run_seconds"])
    ctx = new_context(man, args.workload, args.seed, seconds, args.rehearse,
                      trace=bool(args.trace), t_process=_T0)
    w, cfg, tr = ctx.workload, ctx.config, ctx.traffic
    runner = man.runner(tr["kind"])
    try:
        dev = open_device(w["chips"], args.rehearse)
    except pdevice.NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    import chainermn_tpu  # noqa: F401  (absent: the run dies here, no result)

    ctx.mark("import_s")
    res = runner.run(ctx)

    for row in res["compared"]:
        ctx.say(compared=row)
    numbers = compared_numbers(res["compared"])
    ctx.say(setup_breakdown={k: v for k, v in ctx.marks.items()
                             if not k.startswith("_")},
            check_s=res.get("check_s"), info=res.get("info"),
            gc_in_window=res.get("gc"))
    if args.rehearse:
        ctx.say(rehearsal=True, correct=bool(res["correct"]),
                attempted=res["attempted"], failed=res["failed"],
                counts=res.get("counts", {}), **res.get("says", {}),
                device={"platform": dev["platform"]}, compared=numbers)
        say_compared(numbers)
        return 0 if res["correct"] else 1

    facts = dict(res["facts"], manifest=man, values=res["values"], device=dev,
                 peaks=pdevice.peaks(dev["kind"]), config=cfg, traffic=tr,
                 chips=w["chips"], trace=None)
    device = dict(dev, memory_peak_bytes=res["memory_peak_bytes"])
    line: Dict[str, Any] = {
        "correct": bool(res["correct"]), "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
        **res.get("says", {}),  # what a runner wants read beside its numbers
    }
    if args.trace:
        from perfbench import trace as ptrace

        t = ptrace.load(ptrace.find_xplane(ctx.trace_dir))
        facts["trace"] = t
        busy, window = ptrace.busy_seconds(t)
        device.update(busy_s=busy, window_s=window)
        line["metrics"] = reduce_metrics(
            ctx, man.metrics_for(w["name"], "per_layer"), facts)
        line["breakdown"] = {"device_ops": ptrace.top_ops(t),
                             "idle_gaps": ptrace.idle_gaps(t)}
        if not args.keep_trace:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        line["metrics"] = reduce_metrics(
            ctx, man.metrics_for(w["name"], "end_to_end"), facts)
    line["device"] = device
    line["compared"] = numbers  # last: the end of the line is what is kept
    print(json.dumps(line), flush=True)
    say_compared(numbers)
    return 0


if __name__ == "__main__":
    sys.exit(main())

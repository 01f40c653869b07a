"""Builder's tools, not part of a run: look at a trace by hand, cut a small
fixture out of one."""

from __future__ import annotations

import json
import sys

from perfbench import trace as ptrace


def cut_fixture(src: str, dst: str, units: int) -> int:
    """Keep of a recorded trace what the reducers read — device planes'
    ``XLA Ops`` and ``XLA Modules`` lines and the host's ``pb:*`` spans —
    for the first ``units`` ``pb:tick``/``pb:dispatch`` spans, with the
    window span shortened to match and long HLO texts cut to 240
    characters.  Needs TensorFlow's ``xplane_pb2`` (a builder's tool; the
    harness itself reads traces with JAX alone)."""
    import gzip

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    host = next(p for p in space.planes if p.name == "/host:CPU")
    spans = []
    for line in host.lines:
        for e in line.events:
            name = host.event_metadata[e.metadata_id].name
            if name.startswith("pb:"):
                spans.append((line.timestamp_ns * 1000 + e.offset_ps, e, name,
                              line))
    spans.sort(key=lambda x: x[0])
    unit_spans = [s for s in spans if s[2] in ("pb:tick", "pb:dispatch")]
    window = next(s for s in spans if s[2] == "pb:window")
    t_end = unit_spans[units - 1][0] + unit_spans[units - 1][1].duration_ps
    t_end += 2_000_000_000  # 2 ms: the device finishes a little after
    hp = out.planes.add(name=host.name, id=host.id)
    ids = {}
    for t0, e, name, line in spans:
        if t0 >= t_end - 2_000_000_000:
            continue
        ol = next((l for l in hp.lines if l.id == line.id), None)
        if ol is None:
            ol = hp.lines.add(id=line.id, name=line.name,
                              timestamp_ns=line.timestamp_ns)
        if name not in ids:
            ids[name] = len(ids) + 1
            hp.event_metadata[ids[name]].id = ids[name]
            hp.event_metadata[ids[name]].name = name
        dur = e.duration_ps if name != "pb:window" else t_end - t0
        ol.events.add(metadata_id=ids[name], offset_ps=e.offset_ps,
                      duration_ps=dur)
    for plane in space.planes:
        if not ptrace.DEVICE_PLANE.match(plane.name):
            continue
        dp = out.planes.add(name=plane.name, id=plane.id)
        for line in plane.lines:
            if line.name not in (ptrace.OPS_LINE, ptrace.MODULES_LINE):
                continue
            ol = dp.lines.add(id=line.id, name=line.name,
                              timestamp_ns=line.timestamp_ns)
            for e in line.events:
                if line.timestamp_ns * 1000 + e.offset_ps >= t_end:
                    continue
                ol.events.add(metadata_id=e.metadata_id,
                              offset_ps=e.offset_ps,
                              duration_ps=e.duration_ps)
                if e.metadata_id not in dp.event_metadata:
                    md = dp.event_metadata[e.metadata_id]
                    md.id = e.metadata_id
                    full = plane.event_metadata[e.metadata_id].name
                    mark = 'custom_call_target="tpu_custom_call"'
                    md.name = full[:240] + (
                        " " + mark if mark in full[240:] else "")
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(out.SerializeToString())
    print(dst, __import__("os").path.getsize(dst))
    return 0


def spread(prefix_a: str, prefix_b: str) -> int:
    """Medians and spreads of two sets of runs (``<prefix><seed>.out``, the
    last line of each a result): per metric each set's median and spread,
    the wider spread, the bound five times it, and whether the second
    median is within that bound of the first."""
    import glob
    import statistics

    from perfbench.estimators import spread as iqr_share

    sets = []
    for prefix in (prefix_a, prefix_b):
        rows = []
        for f in sorted(glob.glob(prefix + "*.out")):
            last = [x for x in open(f) if x.startswith('{"correct"')]
            if last:
                rows.append(json.loads(last[-1]))
        sets.append(rows)
    print(json.dumps({"runs": [len(s) for s in sets],
                      "correct": [all(r["correct"] for r in s) for s in sets],
                      "memory_peak_bytes": sets[0][0]["device"]["memory_peak_bytes"]}))
    for name in sets[0][0]["metrics"]:
        vals = [[r["metrics"][name]["value"] for r in s] for s in sets]
        if name == "setup_s":  # each side's first run compiles or loads cold
            vals = [v[1:] for v in vals]
        med = [statistics.median(v) for v in vals]
        spr = [iqr_share(v) for v in vals]
        wide = max(spr)
        print(json.dumps({
            "metric": name, "medians": med, "spreads": spr,
            "bound_5x": max(0.01, 5 * wide),
            "second_vs_first": (med[1] - med[0]) / med[0],
            "values": vals}))
    return 0


def main(argv) -> int:
    cmd = argv[0]
    if cmd == "describe":  # describe <trace_dir> <out.json>
        path = ptrace.find_xplane(argv[1])
        with open(argv[2], "w") as f:
            json.dump(ptrace.describe(path), f, indent=1)
        print(path)
        return 0
    if cmd == "spread":  # spread <prefix_A_> <prefix_B_> : the two sets' files
        return spread(argv[1], argv[2])
    if cmd == "cut-fixture":  # cut-fixture <in.xplane.pb> <out.pb.gz> <ticks>
        return cut_fixture(argv[1], argv[2], int(argv[3]))
    raise SystemExit(f"unknown command {cmd!r}")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

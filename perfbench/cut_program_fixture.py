"""Builder's tool, not part of a run: cut a small fixture out of a chip
trace *with* what the program wrote into it.

``perfbench.devtools.cut_fixture`` keeps the ``pb:*`` spans and the device
events' names and drops every stat; the reducers built on
``perfbench.program_trace`` read exactly what it drops.  This cutter keeps,
for the first ``units`` ticks or steps of the traced window:

* the host's ``pb:*`` and ``cmn_*`` events **with their stats** (the counts
  the program attached: ``kv_blocks_resident``, ``program``, ``req`` ...);
* each device plane's ``XLA Ops`` and ``XLA Modules`` events, names cut to
  240 characters, and of each event's metadata the one stat that carries
  its scope (``tf_op``, the HLO ``op_name``).

A unit on the host starts at a span called ``unit_span`` (``pb:tick``; for
training ``pb:next_batch``, the first span of a step's iteration) and the
host cut is where unit ``units + 1`` starts; the device cut is the end of
the ``units``-th run of the heaviest program, if that is later (a training
step finishes on the device long after its dispatch returned).

    python3 -m perfbench.cut_program_fixture IN.xplane.pb OUT.pb.gz UNITS UNIT_SPAN
"""

from __future__ import annotations

import gzip
import os
import sys

from perfbench import program_trace as pt
from perfbench import trace as ptrace

KEEP = ("pb:", pt.SPAN_PREFIX)
MARK = 'custom_call_target="tpu_custom_call"'


def _start_ps(line, e) -> int:
    return line.timestamp_ns * 1000 + e.offset_ps


def cut(src: str, dst: str, units: int, unit_span: str) -> int:
    space = pt.read_xspace(src)
    out = type(space)()
    host = next(p for p in space.planes if p.name == "/host:CPU")
    kept = []
    for line in host.lines:
        for e in line.events:
            name = host.event_metadata[e.metadata_id].name
            if name.startswith(KEEP):
                kept.append((_start_ps(line, e), e, name, line))
    kept.sort(key=lambda x: x[0])
    window = next(k for k in kept if k[2] == "pb:window")
    starts = [k[0] for k in kept if k[2] == unit_span and k[0] >= window[0]]
    if len(starts) <= units:
        raise SystemExit(f"{src}: only {len(starts)} {unit_span} spans")
    host_end = starts[units]
    device_end = host_end
    for plane in space.planes:
        if not ptrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != ptrace.MODULES_LINE:
                continue
            runs = {}
            for e in line.events:
                if _start_ps(line, e) >= window[0]:
                    runs.setdefault(e.metadata_id, []).append(
                        (_start_ps(line, e), e.duration_ps))
            heavy = max(runs.values(), key=lambda r: sum(d for _, d in r))
            if len(heavy) >= units:
                a, d = sorted(heavy)[units - 1]
                device_end = max(device_end, a + d + 1_000_000)  # + 1 us
    # ------------------------------------------------------------- host
    hp = out.planes.add(name=host.name, id=host.id)
    ids, stat_ids = {}, {}

    def stat_id(src_plane, dst_plane, table, old):
        name = src_plane.stat_metadata[old].name
        if name not in table:
            table[name] = len(table) + 1
            dst_plane.stat_metadata[table[name]].id = table[name]
            dst_plane.stat_metadata[table[name]].name = name
        return table[name]

    def copy_stat(src_plane, dst_plane, table, st, into):
        new = into.add()
        new.CopyFrom(st)
        new.metadata_id = stat_id(src_plane, dst_plane, table, st.metadata_id)
        if st.WhichOneof("value") == "ref_value":
            new.ref_value = stat_id(src_plane, dst_plane, table, st.ref_value)

    for t0, e, name, line in kept:
        whole = name == "pb:window"
        if not whole and (t0 < window[0] or t0 + e.duration_ps > host_end):
            continue
        ol = next((x for x in hp.lines if x.id == line.id), None)
        if ol is None:
            ol = hp.lines.add(id=line.id, name=line.name,
                              timestamp_ns=line.timestamp_ns)
        if name not in ids:
            ids[name] = len(ids) + 1
            hp.event_metadata[ids[name]].id = ids[name]
            hp.event_metadata[ids[name]].name = name
        ne = ol.events.add(
            metadata_id=ids[name], offset_ps=e.offset_ps,
            duration_ps=(device_end - t0) if whole else e.duration_ps)
        for st in e.stats:
            copy_stat(host, hp, stat_ids, st, ne.stats)
    # ----------------------------------------------------------- devices
    for plane in space.planes:
        if not ptrace.DEVICE_PLANE.match(plane.name):
            continue
        dp = out.planes.add(name=plane.name, id=plane.id)
        table = {}
        for line in plane.lines:
            if line.name not in (ptrace.OPS_LINE, ptrace.MODULES_LINE):
                continue
            ol = dp.lines.add(id=line.id, name=line.name,
                              timestamp_ns=line.timestamp_ns)
            for e in line.events:
                if _start_ps(line, e) >= device_end:
                    continue
                ol.events.add(metadata_id=e.metadata_id,
                              offset_ps=e.offset_ps,
                              duration_ps=e.duration_ps)
                if e.metadata_id in dp.event_metadata:
                    continue
                old = plane.event_metadata[e.metadata_id]
                md = dp.event_metadata[e.metadata_id]
                md.id = e.metadata_id
                md.name = old.name[:240] + (
                    " " + MARK if MARK in old.name[240:] else "")
                for st in old.stats:
                    if plane.stat_metadata[st.metadata_id].name == "tf_op":
                        copy_stat(plane, dp, table, st, md.stats)
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(out.SerializeToString())
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(cut(sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4]))

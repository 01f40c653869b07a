"""Reduction of a JAX profiler trace (``.xplane.pb``) to numbers.

One traced sub-window per ``--trace 1`` run.  The harness wraps it in a
``pb:window`` host annotation and its own calls into the program in ``pb:*``
annotations (``pb:tick``, ``pb:next_batch``, ...), so device operations and
host spans sit on the profiler's one clock.  Everything is read with
``jax.profiler.ProfileData`` and nothing else.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per executed HLO operation (a Mosaic kernel is one such event), ``XLA
Modules`` one per executed program.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]  # seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "pb:"


@dataclass
class Event:
    name: str
    start: float
    end: float
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class DeviceTrace:
    ops: List[Event]
    modules: List[Event]


@dataclass
class Trace:
    devices: Dict[int, DeviceTrace]
    spans: List[Event]  # host ``pb:*`` annotations

    @property
    def window(self) -> Optional[Interval]:
        for s in self.spans:
            if s.name == SPAN_PREFIX + "window":
                return (s.start, s.end)
        return None


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _events(line, with_stats: bool) -> List[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns) * 1e-9
        stats = {}
        if with_stats:
            try:
                stats = {k: v for k, v in e.stats}
            except Exception:
                stats = {}
        out.append(Event(e.name, start, start + float(e.duration_ns) * 1e-9,
                         stats))
    return out


def _profile_data(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):  # the recorded fixture is kept compressed
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(path: str, with_stats: bool = False) -> Trace:
    data = _profile_data(path)
    devices: Dict[int, DeviceTrace] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line, with_stats)
                elif line.name == MODULES_LINE:
                    modules = _events(line, with_stats)
            devices[int(m.group(1))] = DeviceTrace(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = float(e.start_ns) * 1e-9
                        spans.append(Event(
                            e.name, s, s + float(e.duration_ns) * 1e-9))
    spans.sort(key=lambda e: e.start)
    return Trace(devices, spans)


# ------------------------------------------------------------- intervals
def clip(events: Iterable[Event], window: Optional[Interval]) -> List[Interval]:
    out = []
    for e in events:
        a, b = e.start, e.end
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------- names
_TRAIL = re.compile(r"([.\-_]\d+)+$")
_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<type>\(?[a-z0-9]+\[[0-9,]*\])?"
                  r"[^=]*?\s(?P<op>[a-z][a-z0-9\-]*)\(")


def op_key(name: str) -> str:
    """A short, instance-free label for a device event.  On the TPU an
    event's name is the whole HLO instruction (``%fusion.12 = bf16[32,1600]
    {...} fusion(...), kind=kOutput...``): the label is ``<name without
    instance numbers>:<opcode>:<result type and shape>``, with ``mosaic`` for
    the opcode of a Pallas kernel, so that the 48 layers' instances of one
    operation add up.  Any other name just loses its instance number."""
    m = _HLO.match(name)
    if not m:
        return _TRAIL.sub("", name.lstrip("%")) or name
    op = "mosaic" if "tpu_custom_call" in name else m.group("op")
    base = _TRAIL.sub("", m.group("name")) or m.group("name")
    return f"{base}:{op}:{(m.group('type') or '').lstrip('(')}"


def matching(events: Iterable[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


# -------------------------------------------------------------- reductions
def busy_seconds(trace: Trace) -> Tuple[float, float]:
    """``(busy_s, window_s)``: seconds in which an operation ran on the
    device inside the traced window, averaged over the devices."""
    w = trace.window
    if w is None:
        raise ValueError("trace has no pb:window annotation")
    if not trace.devices:
        raise ValueError("trace has no device plane")
    busy = [total(union(clip(d.ops, w))) for d in trace.devices.values()]
    return sum(busy) / len(busy), w[1] - w[0]


def top_ops(trace: Trace, n: int = 10) -> List[List[object]]:
    w = trace.window
    acc: Dict[str, float] = {}
    for d in trace.devices.values():
        for e in d.ops:
            for a, b in clip([e], w):
                acc[op_key(e.name)] = acc.get(op_key(e.name), 0.0) + (b - a)
    k = max(1, len(trace.devices))
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in rows]


def idle_gaps(trace: Trace, n: int = 10) -> List[List[object]]:
    """Idle time of device 0 inside the window, by the innermost ``pb:*``
    host span that covers the middle of each gap."""
    w = trace.window
    if w is None or not trace.devices:
        return []
    dev = trace.devices[min(trace.devices)]
    gaps = subtract([w], union(clip(dev.ops, w)))
    spans = [s for s in trace.spans if s.name != SPAN_PREFIX + "window"]
    acc: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [s for s in spans if s.start <= mid <= s.end]
        name = (min(cover, key=lambda s: s.dur).name[len(SPAN_PREFIX):]
                if cover else "outside_spans")
        acc[name] = acc.get(name, 0.0) + (b - a)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in rows]


def describe(path: str, per_line: int = 25) -> Dict[str, object]:
    """What a trace holds — planes, lines, the heaviest event names with
    their stats' keys.  For looking at one trace by hand."""
    out = {}
    for plane in _profile_data(path).planes:
        lines = {}
        for line in plane.lines:
            acc: Dict[str, List[float]] = {}
            stats_of: Dict[str, object] = {}
            n = 0
            for e in line.events:
                n += 1
                k = op_key(e.name)
                a = acc.setdefault(k, [0.0, 0.0])
                a[0] += float(e.duration_ns) * 1e-9
                a[1] += 1
                if k not in stats_of:
                    try:
                        stats_of[k] = {s: str(v)[:160] for s, v in e.stats}
                    except Exception:
                        stats_of[k] = None
            top = sorted(acc.items(), key=lambda kv: -kv[1][0])[:per_line]
            lines[line.name] = {
                "events": n,
                "top": [[k, v[0], v[1], stats_of.get(k)] for k, v in top],
            }
        out[plane.name] = lines
    return out

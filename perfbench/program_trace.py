"""What the *program* wrote into the profiler's trace: its ``cmn_*`` host
spans with their counts, and the scope of every device operation.

``perfbench.trace`` reads a trace with ``jax.profiler.ProfileData`` and
keeps the benchmark's own ``pb:*`` spans and the device events' names.
``ProfileData`` yields an event's *own* stats only, and on the TPU the scope
of a device event (the HLO ``op_name``: ``jit(step_impl)/TransformerLM/
block_3/attn.paged/paged_decode/pallas_call``) is a stat of the event's
*metadata* (``tf_op``), so this reader parses the ``.xplane.pb`` itself, once
per process, with the ``xplane_pb2`` that TensorFlow ships — loaded from its
file, which needs ``google.protobuf`` and nothing else: TensorFlow is not
imported, and nothing is lowered again.  (Source (a) of ISSUE 24; my chip
run, PR 24, found ``tf_op`` on every ``XLA Ops`` event.)  Times are on the
clock of ``perfbench.trace`` (seconds; an event starts at its line's
``timestamp_ns`` plus its ``offset_ps``).

Without that file, or without a trace on disk, :func:`current` is ``None``
and every reducer built on it reports nothing.  Run against a program that
has no ``cmn_*`` span or no scope (the parent of the PR that added them),
the reducers find no match and report nothing either.

**Stale names.**  JAX's persistent compile cache leaves metadata out of its
key (``jax_compilation_cache_include_metadata_in_key`` is False): an
executable cached before a scope was added comes back with the old
``op_name`` s.  Start a run that reads scopes from an empty cache; a traced
run whose ``*_unscoped_pct`` is near 100 read a stale executable.
"""

from __future__ import annotations

import functools
import glob
import gzip
import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from perfbench import trace as ptrace
from perfbench.manifest import ROOT

SPAN_PREFIX = "cmn_"

#: The flat vocabulary of scopes the program names device work by: the
#: *innermost* of these tokens on an event's scope path decides its row of
#: the by-scope table.  ``jax.named_scope`` s of chainermn_tpu
#: (models/transformer.py, optimizers/, serving/engine.py) and the Flax
#: modules that are layers of their own; a row that stands for several
#: tokens says which.
VOCABULARY: Tuple[str, ...] = (
    "attn_qkv", "attn_out", "attn.flash", "attn.xla", "attn.paged",
    "attn.gathered", "attn.fused", "attn.kv_major_einsum", "attn.einsum",
    "kv_write", "moe.dispatch", "moe.experts", "moe.combine", "ffn", "norm",
    "embed", "head", "ce", "sample", "cmn_allreduce_grads",
    "optimizer_update", "apply_updates", "loss_and_grad", "cow_copy",
    "kv_gather", "kv_put",
)
_TOKENS_OF = {"norm": "ln1|ln2|ln_f", "head": "head|lm_head"}
#: rows that say "no scope of ours": a device event whose path holds no
#: token of the vocabulary, or no path at all
UNSCOPED = "unscoped"

_BOUND = (r"(?<![\w.])(?:", r")(?![\w.])")
_TOKENS = re.compile(_BOUND[0] + "|".join(
    f"(?P<k{i}>{_TOKENS_OF.get(key, re.escape(key))})"
    for i, key in enumerate(VOCABULARY)) + _BOUND[1])


def token_regex(pattern: str) -> "re.Pattern[str]":
    """``pattern`` as a whole token of a scope path: ``ce`` finds
    ``jvp(ce)/while`` and ``loss_and_grad/ce/mul``, not ``reduce``."""
    return re.compile(_BOUND[0] + pattern + _BOUND[1])


@functools.lru_cache(maxsize=None)  # thousands of paths, millions of events
def scope_key(path: str) -> str:
    """The by-scope table's row for a scope path: its innermost token of
    the vocabulary."""
    last = None
    for last in _TOKENS.finditer(path or ""):
        pass
    if last is None:
        return UNSCOPED
    return VOCABULARY[int(last.lastgroup[1:])]


def phase_of(path: str) -> str:
    """``remat`` (recomputed forward), ``bwd`` or ``fwd``, as JAX's own
    name stack says it: ``rematted_computation``, ``transpose(jvp(..))``."""
    if "rematted_computation" in path:
        return "remat"
    return "bwd" if "transpose(" in path else "fwd"


@dataclass
class Span:
    """One ``cmn_*`` (or ``pb:*``) host annotation."""

    name: str
    start: float
    end: float
    stats: Dict[str, object] = field(default_factory=dict)
    thread: int = 0
    #: index of the enclosing span on the same thread, or -1
    parent: int = -1
    children: List[int] = field(default_factory=list)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class DeviceEvent:
    name: str
    start: float
    end: float
    scope: str  # the HLO op_name ('' where the trace carries none)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class ProgramTrace:
    path: str
    spans: List[Span]
    devices: Dict[int, List[DeviceEvent]]  # the ``XLA Ops`` line
    _own: Dict[int, List[float]] = field(default_factory=dict, repr=False)

    @property
    def window(self) -> Optional[Tuple[float, float]]:
        """The traced window: the benchmark's ``pb:window`` span."""
        for s in self.spans:
            if s.name == ptrace.SPAN_PREFIX + "window":
                return (s.start, s.end)
        return None

    def inside(self, s: Span) -> bool:
        w = self.window
        return w is None or (s.start >= w[0] and s.end <= w[1])

    def named(self, name: str,
              where: Optional[Dict[str, object]] = None) -> List[Span]:
        """The spans called ``name`` inside the traced window (``where``
        keeps those whose stats match)."""
        out = []
        for s in self.spans:
            if s.name != name or not self.inside(s):
                continue
            if where and any(str(s.stats.get(k)) != str(v)
                             for k, v in where.items()):
                continue
            out.append(s)
        return out

    def own(self, device: int) -> List[float]:
        """:func:`own_seconds` of that device's events, worked out once."""
        if device not in self._own:
            self._own[device] = own_seconds(self.devices[device],
                                            self.window)
        return self._own[device]

    def self_seconds(self, span: Span) -> float:
        """The span's duration minus what its children cover."""
        kids = ptrace.union((self.spans[i].start, self.spans[i].end)
                            for i in span.children)
        return ptrace.total(ptrace.subtract([(span.start, span.end)], kids))


# ------------------------------------------------------------------ parsing
def _xplane_pb2():
    """TensorFlow's generated ``xplane_pb2``, executed from its file: the
    module holds a serialized descriptor and needs ``google.protobuf``
    only, so ``import tensorflow`` (11 s, and its threads) never runs."""
    try:
        spec = importlib.util.find_spec("tensorflow")
        if spec is None or not spec.submodule_search_locations:
            return None
        path = os.path.join(list(spec.submodule_search_locations)[0], "tsl",
                            "profiler", "protobuf", "xplane_pb2.py")
        mod_spec = importlib.util.spec_from_file_location(
            "perfbench_xplane_pb2", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod
    except Exception:
        return None


def read_xspace(path: str):
    """The parsed ``XSpace`` of ``path`` (``.gz`` or not), or ``None``."""
    pb = _xplane_pb2()
    if pb is None:
        return None
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = pb.XSpace()
        space.ParseFromString(f.read())
    return space


def _stat_value(stat, names: Dict[int, str]):
    which = stat.WhichOneof("value")
    if which is None:
        return None
    v = getattr(stat, which)
    return names.get(v, v) if which == "ref_value" else v


def _stats(stats, names: Dict[int, str]) -> Dict[str, object]:
    return {names.get(s.metadata_id, str(s.metadata_id)): _stat_value(s, names)
            for s in stats}


def _nest(spans: List[Span]) -> None:
    """Parents by containment, thread by thread."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].thread, spans[i].start,
                                  -spans[i].end))
    stack: List[int] = []
    for i in order:
        s = spans[i]
        while stack and (spans[stack[-1]].thread != s.thread
                         or spans[stack[-1]].end < s.end
                         or spans[stack[-1]].end <= s.start):
            stack.pop()
        if stack:
            s.parent = stack[-1]
            spans[stack[-1]].children.append(i)
        stack.append(i)


def parse(space, path: str = "") -> ProgramTrace:
    spans: List[Span] = []
    devices: Dict[int, List[DeviceEvent]] = {}
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        m = ptrace.DEVICE_PLANE.match(plane.name)
        if m:
            scopes: Dict[int, str] = {}
            for mid, md in plane.event_metadata.items():
                for st in md.stats:
                    if names.get(st.metadata_id) == "tf_op":
                        scopes[mid] = str(_stat_value(st, names) or "")
            events = []
            for line in plane.lines:
                if line.name != ptrace.OPS_LINE:
                    continue
                t0 = line.timestamp_ns * 1e-9
                for e in line.events:
                    a = t0 + e.offset_ps * 1e-12
                    events.append(DeviceEvent(
                        plane.event_metadata[e.metadata_id].name, a,
                        a + e.duration_ps * 1e-12,
                        scopes.get(e.metadata_id, "")))
            devices[int(m.group(1))] = events
        elif plane.name.startswith("/host:"):
            for tid, line in enumerate(plane.lines):
                t0 = line.timestamp_ns * 1e-9
                for e in line.events:
                    name = plane.event_metadata[e.metadata_id].name
                    if not name.startswith((SPAN_PREFIX, ptrace.SPAN_PREFIX)):
                        continue
                    a = t0 + e.offset_ps * 1e-12
                    spans.append(Span(name, a, a + e.duration_ps * 1e-12,
                                      _stats(e.stats, names), tid))
    _nest(spans)
    return ProgramTrace(path, spans, devices)


_CACHE: Dict[str, Optional[ProgramTrace]] = {}


def load(path: str) -> Optional[ProgramTrace]:
    """``path`` parsed, once per process."""
    if path not in _CACHE:
        space = read_xspace(path)
        _CACHE[path] = None if space is None else parse(space, path)
    return _CACHE[path]


def newest_xplane(root: str = ROOT) -> Optional[str]:
    hits = glob.glob(os.path.join(
        root, ".perfbench_trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


def current(facts) -> Optional[ProgramTrace]:
    """The program's side of the trace this run just took: ``facts`` carries
    neither the trace's path nor the cell's name, so it is the newest
    ``.xplane.pb`` under ``<checkout>/.perfbench_trace/`` (``run.py``
    removes the directory only after the reducers ran).  ``None`` in an
    untraced run.  A test hands its own in ``facts["program_trace"]``."""
    if facts.get("program_trace") is not None:
        return facts["program_trace"]
    if facts.get("trace") is None:
        return None
    path = newest_xplane()
    return load(path) if path else None


# ------------------------------------------------------------ the tables
_SAID = set()


def say_once(kind: str, trace: ProgramTrace, make) -> None:
    """One JSON line per table (``make()``) and trace, however many metrics
    read it: the driver's log carries the whole split, also the rows no
    metric is defined for."""
    if (kind, trace.path) in _SAID:
        return
    _SAID.add((kind, trace.path))
    table = make()
    if table:
        print(json.dumps({kind: table}), flush=True)


def own_seconds(events: List[DeviceEvent], window) -> List[float]:
    """For each event, the seconds inside the window that are its own: its
    interval minus what the events nested in it cover.  A ``while`` or a
    ``conditional`` is an event *around* the events of its body (the
    chunked loss's loop is 31 ms a step around its own fusions), so plain
    durations count that time twice; own seconds add up to the busy time."""
    own = [0.0] * len(events)
    lo, hi = window if window is not None else (float("-inf"), float("inf"))

    def credit(i: int, a: float, b: float) -> None:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            own[i] += b - a

    stack: List[List[float]] = []  # [index, end, cursor]
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i].start, -events[i].end)):
        e = events[i]
        while stack and stack[-1][1] <= e.start:
            j, end, cursor = stack.pop()
            credit(int(j), cursor, end)
        if stack:
            top = stack[-1]
            credit(int(top[0]), top[2], min(e.start, top[1]))
            top[2] = max(top[2], e.end)
        stack.append([i, e.end, e.start])
    while stack:
        j, end, cursor = stack.pop()
        credit(int(j), cursor, end)
    return own


def busy_seconds(trace: ProgramTrace) -> float:
    """Seconds in which an operation ran inside the window, averaged over
    the devices (``perfbench.trace.busy_seconds`` on this reader's events)."""
    busy = [ptrace.total(ptrace.union(ptrace.clip(ev, trace.window)))
            for ev in trace.devices.values()]
    return sum(busy) / max(1, len(busy))


def by_scope(trace: ProgramTrace, units: int) -> Dict[str, object]:
    """Device time by scope and phase, per unit (tick or step) in ms,
    averaged over the devices; the phases of the step; and, for the
    heaviest operations by name, the scopes that own them.

    Every row is in *own* seconds (:func:`own_seconds`), so the rows add
    up to the busy time; ``nested_ms`` is what plain durations would add
    up to beyond it (control-flow operations around their bodies)."""
    window = trace.window
    k = max(1, len(trace.devices))
    per = 1e3 / k / max(1, units)
    rows: Dict[str, Dict[str, float]] = {}
    phases: Dict[str, float] = {}
    ops: Dict[str, Dict[str, float]] = {}
    nameless: Dict[str, float] = {}
    total = busy = plain = 0.0
    for dev, events in trace.devices.items():
        busy += ptrace.total(ptrace.union(ptrace.clip(events, window)))
        plain += ptrace.total(ptrace.clip(events, window))
        for e, sec in zip(events, trace.own(dev)):
            if not sec:
                continue
            key, ph = scope_key(e.scope), phase_of(e.scope)
            row = rows.setdefault(key, {})
            row[ph] = row.get(ph, 0.0) + sec * per
            row["all"] = row.get("all", 0.0) + sec * per
            phases[step_phase(e.scope)] = phases.get(
                step_phase(e.scope), 0.0) + sec * per
            owner = ops.setdefault(ptrace.op_key(e.name), {})
            owner[f"{key}/{ph}"] = owner.get(f"{key}/{ph}", 0.0) + sec * per
            if key == UNSCOPED:
                what = _DIGITS.sub("N", e.scope)[:60] or "(no op_name)"
                nameless[what] = nameless.get(what, 0.0) + sec * per
            total += sec
    heavy = sorted(ops.items(), key=lambda kv: -sum(kv[1].values()))[:12]
    return {
        "units": units, "busy_ms": busy * per, "sum_ms": total * per,
        "nested_ms": (plain - busy) * per,
        "rows": dict(sorted(rows.items(), key=lambda kv: -kv[1]["all"])),
        "phases": phases,
        "ops": {name: dict(sorted(own.items(), key=lambda kv: -kv[1])[:4])
                for name, own in heavy},
        # what the operations outside every scope call themselves: an
        # argument's name (``pools[N]['k']:``: a copy XLA put at the
        # program's edge) or nothing at all
        "unscoped": dict(sorted(nameless.items(), key=lambda kv: -kv[1])[:6]),
    }


_DIGITS = re.compile(r"\d+")
_STEP = (("allreduce", token_regex("cmn_allreduce_grads")),
         ("opt", token_regex("optimizer_update|apply_updates")),
         ("loss", token_regex("loss_and_grad")))


@functools.lru_cache(maxsize=None)
def step_phase(path: str) -> str:
    """Where in a training step an operation belongs: ``fwd``, ``remat``,
    ``bwd`` (inside ``loss_and_grad``), ``allreduce``, ``opt``, or
    ``other`` (none of the step's scopes: a serving program, or the glue
    around the step)."""
    for name, rx in _STEP:
        if rx.search(path):
            return phase_of(path) if name == "loss" else name
    return "other"

"""Span tracing of host-plane ops + device-profile trace annotations.

Every host-plane operation that can block a rank — an object-plane
send/recv, a composed collective, a checkpoint commit, a consistency vote —
records a :class:`Span` into a bounded in-memory ring: *op*, *peer rank*,
*bytes*, *wall time*, and whether it raised.  The ring is what the flight
recorder dumps when a rank dies, so a post-mortem can say "rank 2 spent its
last 28 s inside ``bcast_obj`` from rank 0" instead of guessing from a
truncated stdout.

Two integration layers:

* **Host spans** — :meth:`Tracer.span` context manager, called from
  :class:`~chainermn_tpu.hostcomm.HostComm` (at the same hook points the
  fault injector uses), the checkpointer, and the health guard.  Each span
  also feeds the metrics registry (``host_op.<op>`` count/bytes/latency),
  so the aggregated feed carries op rates without reading the ring.
* **Host phases** — :func:`annotate` names a host phase (the serving
  tick's phases, every watched program's dispatch and compile, the input
  iterators).  Under a profiler session it is a
  ``jax.profiler.TraceAnnotation`` with its counts, beside the
  ``jax.named_scope`` s that name device work by layer; inside a *unit* of
  a :class:`UnitLedger` (a scheduler tick, a wait for an input batch) it
  also leaves its seconds in the unit's record, whether a profiler runs
  or not, and the unit's ordinal joins the two.

Overhead discipline: a span is one ``perf_counter`` pair, one small object,
one deque append, and three instrument updates — all gated on
:func:`chainermn_tpu.observability.enabled`.  Nothing here ever touches a
device buffer.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from chainermn_tpu.observability import enabled as _obs_enabled
from chainermn_tpu.observability import metrics as _metrics

#: Bucket edges for host-op latency histograms (ms) — the registry default.
_OP_EDGES = _metrics.DEFAULT_MS_EDGES

#: Per-process epoch anchor: ONE wall-clock reading paired with ONE
#: monotonic reading, captured together at import.  Every span timestamp
#: is recorded on the monotonic clock (``perf_counter`` — the same clock
#: that times durations) and converted to wall time only through this
#: pair, so a rank's exported timestamps can never skew against its own
#: durations the way mixing ``time.time()`` starts with ``perf_counter``
#: durations could (NTP stepping the wall clock mid-run, coarse wall
#: resolution).  Cross-rank alignment maps between ranks' monotonic
#: clocks directly (:mod:`~chainermn_tpu.observability.fleet` estimates
#: the pairwise offsets); the wall anchor exists only to label a merged
#: trace with human time.
EPOCH_WALL = time.time()
EPOCH_PERF = time.perf_counter()


def mono_to_wall(t_mono: float) -> float:
    """Map a ``perf_counter`` timestamp onto this process's wall clock
    via the import-time epoch anchor."""
    return EPOCH_WALL + (t_mono - EPOCH_PERF)


@dataclass
class Span:
    """One completed (or failed) host-plane operation."""

    op: str
    peer: Optional[int] = None
    nbytes: Optional[int] = None
    #: start on the MONOTONIC clock (``perf_counter`` — one clock base
    #: per rank for both timestamps and durations; wall time is derived
    #: through the epoch anchor at export).
    t_mono: Optional[float] = None
    #: per-op sequence number (assigned at span open by the tracer):
    #: the k-th ``barrier`` span on every rank describes the SAME
    #: collective, however much each rank's ring has evicted — the
    #: fleet merge pairs collectives across ranks by this.
    seq: Optional[int] = None
    ms: float = 0.0
    ok: bool = True
    error: Optional[str] = None
    #: free-form detail (e.g. ``step=120`` for checkpoint spans).
    detail: Optional[str] = None

    def to_dict(self) -> dict:
        t = self.t_mono if self.t_mono is not None else EPOCH_PERF
        d = {"op": self.op, "t_mono": round(t, 6),
             "wall_start": round(mono_to_wall(t), 6),
             "ms": round(self.ms, 3), "ok": self.ok}
        for k in ("peer", "nbytes", "error", "detail", "seq"):
            v = getattr(self, k)
            if v is not None:
                d[k] = v
        return d


class SpanRing:
    """Bounded ring of completed spans (oldest evicted first)."""

    def __init__(self, capacity: int = 512):
        if capacity < 1:
            raise ValueError(f"span ring capacity must be >= 1: {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        #: Total ever appended (evictions = total - len).
        self.total = 0

    def append(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)
            self.total += 1
            if len(self._spans) > self.capacity:
                del self._spans[: len(self._spans) - self.capacity]

    def snapshot(self) -> List[dict]:
        with self._lock:
            return [s.to_dict() for s in self._spans]

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


class _OpenSpan:
    __slots__ = ("span", "t0")

    def __init__(self, span: Span, t0: float):
        self.span = span
        self.t0 = t0


class Tracer:
    """Process-wide span recorder.

    Tracks per-thread stacks of *open* spans so the flight recorder can
    name what a rank is blocked in **right now** (``in_flight()``), and
    keeps the most recent errored span (``last_error()``) for post-mortems
    taken after the stack has already unwound — the crash path: by the
    time ``sys.excepthook`` runs, the failing span has closed.
    """

    def __init__(self, ring: Optional[SpanRing] = None,
                 publish_metrics: bool = True):
        # `is None`, not `or`: an EMPTY ring is falsy (__len__ == 0) and
        # `or` would silently replace the caller's ring with a fresh one.
        self.ring = ring if ring is not None else SpanRing(
            int(os.environ.get("CMN_OBS_SPAN_RING", "512"))
        )
        self._publish = publish_metrics
        self._lock = threading.Lock()
        #: thread ident -> stack of open spans (dict, not thread-local:
        #: the flight recorder reads OTHER threads' stacks).
        self._open: Dict[int, List[_OpenSpan]] = {}
        #: per-op open counters: source of each span's ``seq``.
        self._op_seq: Dict[str, int] = {}
        self._last_error: Optional[Span] = None

    # ----------------------------------------------------------------- spans
    def span(self, op: str, peer: Optional[int] = None,
             nbytes: Optional[int] = None, detail: Optional[str] = None):
        """Context manager recording one host-plane op.  The yielded
        :class:`Span` is mutable — callers that only learn the byte count
        mid-op (recv) set ``span.nbytes`` before exit."""
        return _SpanCtx(self, Span(op=op, peer=peer, nbytes=nbytes,
                                   detail=detail))

    def _push(self, open_span: _OpenSpan) -> None:
        tid = threading.get_ident()
        span = open_span.span
        with self._lock:
            # Stamp at OPEN, under the tracer lock: ``t_mono`` shares the
            # exact reading the duration pair uses, and ``seq`` counts
            # opens per op — collectives open in the same order on every
            # rank, so equal (op, seq) across ranks is the same event.
            span.t_mono = open_span.t0
            span.seq = self._op_seq.get(span.op, 0)
            self._op_seq[span.op] = span.seq + 1
            self._open.setdefault(tid, []).append(open_span)

    def _pop(self, open_span: _OpenSpan, error: Optional[BaseException]):
        span = open_span.span
        span.ms = (time.perf_counter() - open_span.t0) * 1000.0
        if error is not None:
            span.ok = False
            span.error = f"{type(error).__name__}: {error}"[:300]
        tid = threading.get_ident()
        with self._lock:
            stack = self._open.get(tid)
            if stack and stack[-1] is open_span:
                stack.pop()
            elif stack and open_span in stack:  # defensive: odd unwind order
                stack.remove(open_span)
            if error is not None:
                self._last_error = span
        self.ring.append(span)
        if self._publish:
            reg = _metrics.registry()
            reg.counter(f"host_op.{span.op}.total").inc()
            if not span.ok:
                reg.counter(f"host_op.{span.op}.errors").inc()
            if span.nbytes is not None:
                reg.counter(f"host_op.{span.op}.bytes").inc(span.nbytes)
            reg.histogram(f"host_op.{span.op}.ms", _OP_EDGES).observe(span.ms)

    # ------------------------------------------------------------ inspection
    def in_flight(self) -> List[dict]:
        """Currently open spans across ALL threads, innermost last per
        thread — what each thread of this rank is sitting in right now."""
        now = time.perf_counter()
        out = []
        with self._lock:
            for tid, stack in self._open.items():
                for os_ in stack:
                    d = os_.span.to_dict()
                    d["open_ms"] = round((now - os_.t0) * 1000.0, 3)
                    d["thread"] = tid
                    del d["ms"]  # not finished; open_ms is the honest number
                    out.append(d)
        return out

    def last_error(self) -> Optional[dict]:
        with self._lock:
            return self._last_error.to_dict() if self._last_error else None

    def current_span_name(self) -> Optional[str]:
        """The innermost in-flight op (any thread; main thread preferred),
        falling back to the last *errored* span — the flight recorder's
        "what was this rank doing" one-liner."""
        main_id = threading.main_thread().ident
        with self._lock:
            stack = self._open.get(main_id)
            if stack:
                return stack[-1].span.op
            for other in self._open.values():
                if other:
                    return other[-1].span.op
            if self._last_error is not None:
                return self._last_error.op
        return None


class _SpanCtx:
    __slots__ = ("_tracer", "_open")

    def __init__(self, tracer_: Tracer, span: Span):
        self._tracer = tracer_
        self._open = _OpenSpan(span, 0.0)

    def __enter__(self) -> Span:
        self._open.t0 = time.perf_counter()
        self._tracer._push(self._open)
        return self._open.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._tracer._pop(self._open, exc)
        return False  # never swallow


# ------------------------------------------------ request-lifecycle timeline
@dataclass
class LifecycleEvent:
    """One serving-plane lifecycle event on the *scheduler clock*.

    ``kind`` ∈ {submit, admit, prefill, decode, evict, retire}; ``t`` is
    the event's start in scheduler-clock seconds (the clock the arrival
    schedule lives on, so queue waits render true even when the scheduler
    skips idle gaps); duration events carry ``dur_ms``.
    """

    kind: str
    t: float
    req: Optional[int] = None
    slot: Optional[int] = None
    dur_ms: float = 0.0
    info: Optional[dict] = None


class RequestTimeline:
    """Bounded recorder of request-lifecycle events for one serving run.

    Two sinks per event:

    * the timeline's own ring (capacity ``CMN_OBS_TIMELINE``, default
      32768 — sized for whole-run Chrome/Perfetto export; oldest events
      drop first and ``dropped`` counts them, so a truncated export is
      visible, never silent), and
    * optionally the process span ring (``ring=``): each event is
      mirrored as a ``serve.<kind>`` :class:`Span`, so a flight record
      of a dying serving rank shows its recent scheduling activity next
      to the host-plane ops.  Mirrored spans bypass the metric publisher
      — the scheduler's ``serve.*`` histograms already carry the rates.
    """

    def __init__(self, capacity: Optional[int] = None,
                 ring: Optional[SpanRing] = None):
        cap = int(
            capacity if capacity is not None
            else os.environ.get("CMN_OBS_TIMELINE", "32768")
        )
        if cap < 1:
            raise ValueError(f"timeline capacity must be >= 1: {cap}")
        self.capacity = cap
        self._lock = threading.Lock()
        # deque(maxlen): O(1) eviction — a full timeline sits on the
        # scheduler's per-iteration path, where a list-trim memmove of
        # `capacity` pointers per event would not.
        self._events: deque = deque(maxlen=cap)
        self.ring = ring
        #: total ever recorded (dropped = total - len).
        self.total = 0

    def record(self, kind: str, t: float, req: Optional[int] = None,
               slot: Optional[int] = None, dur_ms: float = 0.0,
               info: Optional[dict] = None) -> None:
        ev = LifecycleEvent(kind=kind, t=t, req=req, slot=slot,
                            dur_ms=dur_ms, info=info)
        with self._lock:
            self._events.append(ev)
            self.total += 1
        if self.ring is not None:
            detail = f"req={req}" if req is not None else (
                f"slots={len(info['reqs'])}" if info and "reqs" in info
                else None
            )
            self.ring.append(Span(
                op=f"serve.{kind}", peer=slot,
                t_mono=time.perf_counter(), ms=dur_ms, detail=detail,
            ))

    def events(self) -> List[LifecycleEvent]:
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self.total - len(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


#: Chrome trace-event track ids: the admission queue gets its own track
#: above the slot tracks.
_QUEUE_TID = 0


def chrome_trace_events(events, rank: int = 0) -> List[dict]:
    """Convert :class:`LifecycleEvent` s into Chrome trace-event JSON
    objects (the ``traceEvents`` array — Perfetto/``chrome://tracing``
    loadable).

    Track layout: one *process* per rank; thread 0 is the admission
    queue, thread ``1 + slot`` is that decode slot.  A request renders
    as:

    * a ``queue req N`` slice on the queue track (submit→admit, and
      again evict→readmission),
    * a ``req N`` slice on its slot track for each residency
      (admit→retire/evict), with nested ``prefill`` / ``decode`` slices,
    * an ``evict`` *instant* event at each eviction.

    Events still open when the recording ends (an aborted run) are
    closed at the last observed timestamp, so the export always loads.
    """
    out: List[dict] = []
    pid = int(rank)
    used_tids = {_QUEUE_TID}
    t_max = max((e.t + e.dur_ms / 1e3 for e in events), default=0.0)

    def us(t: float) -> float:
        return round(t * 1e6, 3)

    def slice_(name, cat, tid, t0, t1, args=None):
        ev = {"name": name, "cat": cat, "ph": "X", "pid": pid,
              "tid": tid, "ts": us(t0), "dur": max(us(t1) - us(t0), 0.0)}
        if args:
            ev["args"] = args
        out.append(ev)

    queue_since: Dict[int, float] = {}
    residency: Dict[int, tuple] = {}  # req -> (t_admit, slot)
    for e in events:
        if e.kind == "submit":
            queue_since[e.req] = e.t
        elif e.kind == "admit":
            t0 = queue_since.pop(e.req, None)
            if t0 is not None:
                slice_(f"queue req {e.req}", "queue", _QUEUE_TID,
                       t0, e.t, {"req": e.req})
            residency[e.req] = (e.t, e.slot)
            used_tids.add(1 + e.slot)
        elif e.kind == "prefill":
            used_tids.add(1 + e.slot)
            slice_("prefill", "prefill", 1 + e.slot, e.t,
                   e.t + e.dur_ms / 1e3,
                   {"req": e.req, **(e.info or {})})
        elif e.kind == "decode":
            info = e.info or {}
            for slot, req in info.get("reqs", ()):
                used_tids.add(1 + slot)
                slice_("decode", "decode", 1 + slot, e.t,
                       e.t + e.dur_ms / 1e3,
                       {"req": req, "mixed": info.get("mixed", False)})
        elif e.kind in ("evict", "retire"):
            start = residency.pop(e.req, None)
            if start is not None:
                t0, slot = start
                args = {"req": e.req}
                if e.kind == "evict":
                    args["evicted"] = True
                elif e.info:
                    args.update(e.info)
                slice_(f"req {e.req}", "request", 1 + slot, t0, e.t, args)
            if e.kind == "evict":
                out.append({"name": "evict", "cat": "evict", "ph": "i",
                            "s": "t", "pid": pid, "tid": 1 + e.slot,
                            "ts": us(e.t), "args": {"req": e.req}})
                queue_since[e.req] = e.t
    # Close anything the recording ended inside of.
    for req, (t0, slot) in residency.items():
        slice_(f"req {req}", "request", 1 + slot, t0, t_max,
               {"req": req, "open": True})
    for req, t0 in queue_since.items():
        if t0 < t_max:
            slice_(f"queue req {req}", "queue", _QUEUE_TID, t0, t_max,
                   {"req": req, "open": True})
    meta = [{"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": f"cmn-serve rank {pid}"}}]
    for tid in sorted(used_tids):
        name = "queue" if tid == _QUEUE_TID else f"slot {tid - 1}"
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": name}})
        meta.append({"name": "thread_sort_index", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"sort_index": tid}})
    return meta + out


def write_chrome_trace(path: str, events, rank: int = 0) -> str:
    """Write a Perfetto-loadable Chrome trace JSON file
    (``{"traceEvents": [...], "displayTimeUnit": "ms"}``) and return
    ``path``.  Strict JSON via the same sanitizer as the metric feeds."""
    import json

    from chainermn_tpu.observability import aggregate as _oagg

    payload = {
        "traceEvents": _oagg.sanitize_json(
            chrome_trace_events(events, rank=rank)
        ),
        "displayTimeUnit": "ms",
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


# ------------------------------------------- host phases and the unit ledger
# Host phases are named in ONE way: ``annotate("cmn_...")``.  Under a
# profiler session a phase is a ``jax.profiler.TraceAnnotation`` on the
# profiler's clock, beside the device operations and the
# ``jax.named_scope`` s that name device work (models/, ops/, optimizers/,
# serving/engine.py).  With or without one, a phase that closes inside a
# *unit* — an outermost span opened through a :class:`UnitLedger`: one
# scheduler tick, one wait for an input batch — adds its seconds to that
# unit's record, so every unit of a run leaves its time by phase, not only
# the few a profiler covered.  ``docs/observability.md``, "Profiler-clock
# spans and scopes", lists the vocabulary.
_perf_counter = time.perf_counter
_tls = threading.local()


def _taken(counts: dict) -> dict:
    return {k: v() if callable(v) else v for k, v in counts.items()}


class UnitRecord:
    """One closed unit of a :class:`UnitLedger`, fixed in shape: the
    unit's ``ordinal``, its start ``t_mono`` (``perf_counter``, the
    module's one clock base: :data:`EPOCH_PERF`), its ``seconds``, per
    phase name the ``calls`` that closed inside it and their inclusive
    ``secs`` (a phase nested in another counts in both), ``direct`` — the
    seconds of the unit's immediate children, which do add up to at most
    ``seconds`` — and the summed ``counts`` the ledger keeps
    (``"cmn_serve_prefill.tokens"``)."""

    __slots__ = ("ordinal", "t_mono", "seconds", "calls", "secs", "direct",
                 "counts")

    def __init__(self, ordinal: int, t_mono: float):
        self.ordinal = ordinal
        self.t_mono = t_mono
        self.seconds = 0.0
        # str -> number dicts: the collector never tracks them
        self.calls: Dict[str, int] = {}
        self.secs: Dict[str, float] = {}
        self.direct: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    @property
    def rows(self) -> Dict[str, tuple]:
        """``{name: (calls, seconds)}``."""
        return {k: (n, self.secs[k]) for k, n in self.calls.items()}

    def to_dict(self) -> dict:
        return {
            "ordinal": self.ordinal, "t_mono": round(self.t_mono, 6),
            "ms": round(self.seconds * 1e3, 3),
            "rows": {k: [n, round(self.secs[k] * 1e3, 3)]
                     for k, n in self.calls.items()},
            "counts": dict(self.counts),
        }


#: Units a :class:`UnitLedger` holds unless told otherwise: a 45 s window
#: of the backlog cell is ~1,710 ticks, ~2,730 at a 16.5 ms tick.
UNIT_RING_CAPACITY = 4096

#: The newest ledger of each kind.  A ledger holds numbers and names and
#: nothing of its owner, so this pins no scheduler, engine or device
#: buffer — and a reader that comes after the owner is gone (a reducer
#: after the run, a post-mortem) still finds the run's units.
_ledgers: Dict[str, "UnitLedger"] = {}


def unit_ledger(kind: str) -> Optional["UnitLedger"]:
    """The newest :class:`UnitLedger` of ``kind`` (``"serve_tick"``,
    ``"input_wait"``), or ``None``."""
    return _ledgers.get(kind)


class UnitLedger:
    """A bounded ring of :class:`UnitRecord` s, one per unit of its owner.

    The owner opens a unit with ``annotate(name, ledger=self, <ordinal>=i)``;
    until that span closes, every ``annotate`` span that closes on the
    same thread adds one call and its inclusive seconds to the unit's row
    of that name (a would-be unit of another ledger too: inside a unit it
    is a child).  ``keep`` names, per phase, the counts worth summing —
    plain integers only; a callable count is never called for the ledger.

    The ordinal is the join to a profiler's trace: the traced span of the
    unit carries the same count, so ledger unit ``i`` and traced
    ``cmn_serve_tick(tick=i)`` are one tick, on any clock.

    Capacity :data:`UNIT_RING_CAPACITY` units unless ``capacity`` says
    otherwise; ``evicted`` counts what fell out.  Owners build one only while observability is
    on (``CMN_OBS``), so with the switch off no unit ever opens.  The
    newest ledger of a kind is found by :func:`unit_ledger` and rides
    every flight record as ``units.<kind>``."""

    def __init__(self, kind: str, ordinal: str,
                 keep: Optional[Dict[str, tuple]] = None,
                 capacity: int = UNIT_RING_CAPACITY):
        if capacity < 1:
            raise ValueError(f"unit ring capacity must be >= 1: {capacity}")
        self.kind = kind
        self.ordinal = ordinal
        self.capacity = capacity
        self._keep = {name: tuple((k, f"{name}.{k}") for k in keys)
                      for name, keys in (keep or {}).items()}
        self._ring: deque = deque(maxlen=capacity)
        #: units ever closed (evicted = total - len).
        self.total = 0
        _ledgers[kind] = self
        from chainermn_tpu.observability import flight as _flight

        _flight.register_provider(f"units.{kind}", self.flight_state)

    # No lock: one thread closes units (a deque append is atomic), and a
    # reader may be a signal handler on that very thread (SIGUSR1 flight
    # snapshot) — a lock held by the interrupted append would never be
    # released to it.  A torn read shows one unit ago.
    def _close(self, rec: UnitRecord) -> None:
        self._ring.append(rec)
        self.total += 1

    def units(self) -> List[UnitRecord]:
        """The units still in the ring, oldest first."""
        while True:
            try:
                return list(self._ring)
            except RuntimeError:  # another thread appended meanwhile
                continue

    @property
    def evicted(self) -> int:
        return max(0, self.total - len(self._ring))

    def __len__(self) -> int:
        return len(self._ring)

    def flight_state(self, last: int = 16) -> dict:
        """The flight record's ``units.<kind>`` section: totals and the
        last units by phase."""
        units = self.units()
        return {"kind": self.kind, "ordinal": self.ordinal,
                "units": self.total, "evicted": self.evicted,
                "capacity": self.capacity,
                "last": [u.to_dict() for u in units[-last:]]}


class _Timed:
    """A phase on ``perf_counter``: ``t0``, ``t1`` and ``seconds`` once
    closed.  Inside a unit, closing it books the unit's row of its name."""

    __slots__ = ("_name", "_unit", "_keep", "t0", "t1")

    def __init__(self, name: str, unit: Optional["_Unit"], counts):
        self._name = name
        self._unit = unit
        self.t0 = self.t1 = 0.0
        if unit is None:
            self._keep = None
        else:
            self._keep = keep = unit._keeps.get(name)
            if keep is not None:
                self._sum(counts)

    def _sum(self, counts: dict) -> None:
        kept = self._unit._rec.counts
        for key, flat in self._keep:
            v = counts.get(key)
            if type(v) is int:
                kept[flat] = kept.get(flat, 0) + v

    def set_metadata(self, **counts) -> None:
        if self._keep is not None:
            self._sum(counts)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self):
        if self._unit is not None:
            self._unit._depth += 1
        self.t0 = _perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        self.t1 = t1 = _perf_counter()
        unit = self._unit
        if unit is not None:
            rec, name, dt = unit._rec, self._name, t1 - self.t0
            calls, secs = rec.calls, rec.secs
            if name in calls:
                calls[name] += 1
                secs[name] += dt
            else:
                calls[name] = 1
                secs[name] = dt
            unit._depth -= 1
            if not unit._depth:
                rec.direct[name] = rec.direct.get(name, 0.0) + dt
        return False


class _Unit(_Timed):
    """The outermost span of a unit: the thread's open unit from enter to
    exit, where it hands its record to the ledger."""

    __slots__ = ("_ledger", "_rec", "_keeps", "_depth")

    def __init__(self, name: str, ledger: UnitLedger, counts):
        super().__init__(name, None, None)
        self._ledger = ledger
        self._keeps = ledger._keep
        self._depth = 0
        self._rec = UnitRecord(counts.get(ledger.ordinal, ledger.total), 0.0)

    def __enter__(self):
        _tls.unit = self
        self._rec.t_mono = self.t0 = _perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        self.t1 = _perf_counter()
        _tls.unit = None
        self._rec.seconds = self.t1 - self.t0
        self._ledger._close(self._rec)
        return False


class _NoSpan:
    """What :func:`annotate` hands back where nothing records a phase."""

    __slots__ = ()
    t0 = t1 = seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts) -> None:
        pass


_NO_SPAN = _NoSpan()


class _Span(_TraceAnnotation):
    """Under a profiler session: the ``TraceAnnotation`` (its counts may be
    callables, called now that the span is really recorded) around the
    timed phase (the shared no-op where nothing times it), so a traced
    unit exists twice, with one ordinal."""

    def __init__(self, name: str, timed, counts):
        super().__init__(name, **_taken(counts))
        self._timed = timed

    def set_metadata(self, **counts) -> None:
        super().set_metadata(**_taken(counts))
        self._timed.set_metadata(**counts)

    def __enter__(self):
        super().__enter__()
        self._timed.__enter__()
        return self

    def __exit__(self, *exc):
        self._timed.__exit__(*exc)
        return super().__exit__(*exc)

    @property
    def t0(self) -> float:
        return self._timed.t0

    @property
    def t1(self) -> float:
        return self._timed.t1

    @property
    def seconds(self) -> float:
        return self._timed.seconds


def annotate(name: str, *, ledger: Optional[UnitLedger] = None,
             timed: bool = False, **counts):
    """A host phase called ``name`` (prefix ``cmn_``) with its ``counts``.
    Spans nest: a span's parent is the span open around it on the same
    thread; spans of one request carry ``req=``.

    * Under a profiler session (and ``CMN_OBS`` on) it is a
      ``jax.profiler.TraceAnnotation`` whose counts arrive in the trace as
      the event's stats.
    * ``ledger=`` makes it a *unit* of that :class:`UnitLedger` (unless a
      unit is already open on the thread: then it is a child like any
      other), and inside a unit every span is timed and booked to the
      unit's record, profiler or not.
    * ``timed=True`` keeps the span's one clock pair (``span.seconds``,
      ``span.t0``, ``span.t1``) where nothing else would: a publisher that
      needs the phase's duration reads it off the span and takes no clock
      of its own.

    A count given as a callable is called only under a profiler session,
    so one that costs something to take (a sum over the live slots) costs
    nothing otherwise; the ledger sums plain integers alone.  Counts known
    only at the end of the phase go in through
    ``span.set_metadata(tokens=...)`` before the ``with`` block closes.

    Outside a unit, with no profiler session, no ``ledger`` and no
    ``timed``, this is two flag reads and returns a shared no-op span."""
    unit = getattr(_tls, "unit", None)
    if unit is not None:
        inner = _Timed(name, unit, counts)
    elif ledger is not None:
        inner = _Unit(name, ledger, counts)
    elif timed:
        inner = _Timed(name, None, None)
    else:
        inner = _NO_SPAN
    if _TraceAnnotation.is_enabled() and _obs_enabled():
        return _Span(name, inner, counts)
    return inner


#: Process-wide tracer (lazy singleton, like the metrics registry).
_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def tracer() -> Tracer:
    global _tracer
    if _tracer is None:
        with _tracer_lock:
            if _tracer is None:
                _tracer = Tracer()
    return _tracer

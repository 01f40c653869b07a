"""Observability — see a run, not just its rank-0 stdout.

The MPMD design makes every job N opaque host processes: the resilience
layer (PRs 1–2) can say a run is *alive* and *healthy*, but nothing could
say what a run is *doing* — which collective a rank sits in, how step time
distributes across ranks, what a dead rank was executing when it died.
(The seed once shipped an ``observability/`` package as pyc-only ghosts;
this is the real one — ``tests/test_repo_health.py`` guards the ghosts.)

Four cooperating pieces, all default-on and all bounded:

* :mod:`~chainermn_tpu.observability.metrics` — per-rank registry of
  counters / gauges / histograms (fixed bucket edges, so the cross-rank
  merge is *exact*).  The Trainer, HostComm, checkpointer, failure
  detector, and training-health guard publish into it.
* :mod:`~chainermn_tpu.observability.tracing` — span records of host-plane
  ops (send/recv/bcast_obj/…, checkpoint save/restore, guard votes) in a
  bounded in-memory ring; host phases named by ``annotate`` (``cmn_*``):
  ``jax.profiler`` trace annotations under a profiler session, and — in
  every run — a :class:`~chainermn_tpu.observability.tracing.UnitLedger`
  record per scheduler tick and per input wait with its time by phase.
* :mod:`~chainermn_tpu.observability.flight` — flight recorder: snapshots
  the span ring + last-K metric samples + resilience state to a per-rank
  JSONL file on :class:`~chainermn_tpu.resilience.PeerFailedError` /
  :class:`~chainermn_tpu.resilience.RankDivergedError` crashes, on the
  preemption (75) and health-escalation (76) exits, and on ``SIGUSR1`` —
  post-mortems of dead ranks.
* :mod:`~chainermn_tpu.observability.aggregate` — rank-0 aggregation over
  the *existing* host object plane (no new meshes): a merged per-step
  JSONL feed plus an optional Prometheus-style textfile.
* :mod:`~chainermn_tpu.observability.slo` — streaming SLO monitor for
  the serving plane: TTFT / queue-wait / per-token latency in fixed-edge
  histograms plus rolling-window p50/p95 and a p95 drift detector
  (``serve.slo.*``); the serving scheduler also records per-request
  lifecycle events (:class:`~chainermn_tpu.observability.tracing.
  RequestTimeline`) exportable as Chrome trace-event JSON
  (:func:`~chainermn_tpu.observability.tracing.write_chrome_trace`,
  Perfetto-loadable).
* :mod:`~chainermn_tpu.observability.fleet` — the fleet plane: NTP-style
  clock offsets over the host p2p plane, ONE rank-0 merged Perfetto
  trace (collectives aligned across ranks by per-op span ``seq``),
  collective-skew histograms and gated straggler attribution
  (``fleet.*``); :mod:`~chainermn_tpu.observability.analyze` is the
  offline per-step critical-path reporter over a merged trace.
* :mod:`~chainermn_tpu.observability.memory` — device-memory plane: HBM
  watermark gauges (host-RSS fallback), a KV-pool occupancy /
  fragmentation timeline fed by the serving scheduler, a drain-cycle
  leak detector, and the ``"memory"`` flight-record provider
  (``mem.*``).
* :mod:`~chainermn_tpu.observability.device` — device/compile plane:
  the :class:`~chainermn_tpu.observability.device.CompileWatch` records
  every compilation of a wrapped jitted program (signature, compile
  time, recompile **blame** diffs, declared budgets → ``compile.*``),
  captures XLA's per-program cost model, and publishes MFU/roofline
  gauges (``device.*``); the FLOP helpers (``PEAK_BF16_FLOPS``,
  ``compiled_flops``, ``attention_core_flops``) live here now.
* :mod:`~chainermn_tpu.observability.perf` — offline perf-regression
  sentinel over the ``result/*.json`` artifact history
  (``python -m chainermn_tpu.observability.perf``); ``bench.py`` folds
  its compact verdict into ``bench_summary.perf_sentinel``.
* :mod:`~chainermn_tpu.observability.incident` — the incident plane:
  declarative :class:`~chainermn_tpu.observability.incident.Watch`
  rules over the live registry (evaluated on the stack's existing
  cadences), hysteresis + cooldown + fingerprint dedupe + a hard
  per-run cap, cross-plane debug bundles captured at fire time
  (``incident.*``; ``CMN_OBS_INCIDENT_*``), and the offline postmortem
  analyzer ``python -m chainermn_tpu.observability.incident report``.
* :mod:`~chainermn_tpu.observability.ledger` — the usage ledger
  (ISSUE 16): per-request :class:`~chainermn_tpu.observability.ledger.
  UsageRecord` cost attribution + per-tenant metering with an exact
  conservation invariant (``serve.tenant.*``; ``CMN_OBS_LEDGER*``);
  :mod:`~chainermn_tpu.observability.usage` is its offline analyzer
  (``python -m chainermn_tpu.observability.usage report``).

Env knobs (see ``docs/observability.md`` for the full table):

* ``CMN_OBS=0`` — master off-switch: publishers skip the registry, span
  hooks vanish, per-step trace annotations are not emitted.
* ``CMN_OBS_SPAN_RING`` — span-ring capacity (default 512).
* ``CMN_OBS_SAMPLES`` — metric-sample ring capacity (default 64).
* ``CMN_OBS_TIMELINE`` — request-lifecycle timeline capacity (32768).
* ``CMN_OBS_FLIGHT_DIR`` — where flight records land (the launcher sets a
  per-attempt path); ``CMN_OBS_FLIGHT=0`` disables the recorder.
* ``CMN_SLO_*`` — SLO monitor window / baseline / envelope knobs.
"""

from __future__ import annotations

import os
from typing import Optional

#: Process-wide override (``set_enabled``); None = follow the env.
_enabled_override: Optional[bool] = None


def enabled() -> bool:
    """Default-on master switch: ``CMN_OBS=0`` turns every publisher into
    a no-op.

    Hot-path publishers LATCH this at construction (``HostComm``,
    ``Trainer``, the guard, the detector resolve their instruments once
    — re-checking per op would put an env read on the hot path), so flip
    it BEFORE building them; ``MetricsReport`` re-checks at each fire.
    The overhead bench honors this by rebuilding its Trainer per arm."""
    if _enabled_override is not None:
        return _enabled_override
    return os.environ.get("CMN_OBS", "1") != "0"


def set_enabled(value: Optional[bool]) -> None:
    """Force observability on/off in-process (``None`` = follow the env).
    The A/B lever for the overhead benchmark and tests."""
    global _enabled_override
    _enabled_override = value


from chainermn_tpu.observability.metrics import (  # noqa: E402
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    histogram_quantile,
    merge_snapshots,
    registry,
)
from chainermn_tpu.observability.tracing import (  # noqa: E402
    LifecycleEvent,
    RequestTimeline,
    Span,
    SpanRing,
    Tracer,
    UnitLedger,
    UnitRecord,
    annotate,
    chrome_trace_events,
    tracer,
    unit_ledger,
    write_chrome_trace,
)
from chainermn_tpu.observability.slo import (  # noqa: E402
    SLOMonitor,
    rolling_quantile,
)
from chainermn_tpu.observability.flight import (  # noqa: E402
    FLIGHT_SCHEMA,
    FlightRecorder,
    recorder,
    register_provider,
    snapshot_on_crash,
)
from chainermn_tpu.observability.aggregate import (  # noqa: E402
    MetricsAggregator,
    render_prometheus,
)
from chainermn_tpu.observability.fleet import (  # noqa: E402
    ClockOffset,
    FleetClock,
    attribute_straggler,
    collective_occurrences,
    export_fleet_trace,
    merge_fleet_trace,
)
from chainermn_tpu.observability.memory import (  # noqa: E402
    MemoryMonitor,
    device_memory_stats,
    kv_pool_sample,
)
from chainermn_tpu.observability.device import (  # noqa: E402
    PEAK_BF16_FLOPS,
    CompileWatch,
    WatchedFunction,
    attention_core_flops,
    compiled_flops,
    mfu_pct,
    roofline,
    signature_diff,
    watch,
)
from chainermn_tpu.observability.ledger import (  # noqa: E402
    USAGE_SCHEMA,
    CostLedger,
    UsageRecord,
    ledger_enabled,
)

__all__ = [
    "enabled",
    "set_enabled",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "histogram_quantile",
    "merge_snapshots",
    "registry",
    "LifecycleEvent",
    "RequestTimeline",
    "Span",
    "SpanRing",
    "Tracer",
    "tracer",
    "chrome_trace_events",
    "annotate",
    "UnitLedger",
    "UnitRecord",
    "unit_ledger",
    "write_chrome_trace",
    "SLOMonitor",
    "rolling_quantile",
    "FLIGHT_SCHEMA",
    "FlightRecorder",
    "recorder",
    "register_provider",
    "snapshot_on_crash",
    "MetricsAggregator",
    "render_prometheus",
    "ClockOffset",
    "FleetClock",
    "attribute_straggler",
    "collective_occurrences",
    "export_fleet_trace",
    "merge_fleet_trace",
    "MemoryMonitor",
    "device_memory_stats",
    "kv_pool_sample",
    "PEAK_BF16_FLOPS",
    "CompileWatch",
    "WatchedFunction",
    "attention_core_flops",
    "compiled_flops",
    "mfu_pct",
    "roofline",
    "signature_diff",
    "watch",
    "USAGE_SCHEMA",
    "CostLedger",
    "UsageRecord",
    "ledger_enabled",
]

"""Device-memory observability, engine-free: watermark source fallback,
KV-pool sample arithmetic, monitor gauges/timeline bounds, the
``"memory"`` flight-record provider, and the monotonic span clock-base
(the PR's satellite fix).  The serving-engine end of the same plane
(drain-cycle zero-leak baseline, scheduler sampling cadence) lives in
``tests/serving_tests/test_serve_obs.py`` where the engines already
exist.
"""

import json
import types

import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.observability import memory as omem
from chainermn_tpu.observability import metrics as omet
from chainermn_tpu.serving.kv_pool import BlockAllocator

pytestmark = pytest.mark.tier1


def _fake_engine(num_blocks=10, block_len=8, bpb=1000, prefix_blocks=0):
    """The attribute surface ``kv_pool_sample`` reads, minus the device
    pools — the accounting is host-only by design, so a stub proves it."""
    pool = types.SimpleNamespace(
        allocator=BlockAllocator(num_blocks), num_blocks=num_blocks,
        block_len=block_len, bytes_per_block=bpb,
    )
    prefix = (
        types.SimpleNamespace(cached_blocks=prefix_blocks)
        if prefix_blocks else None
    )
    return types.SimpleNamespace(pool=pool, prefix=prefix)


# -------------------------------------------------------- watermark source
def test_device_memory_stats_always_answers():
    stats = omem.device_memory_stats()
    assert stats["source"] in ("device", "host_rss")
    assert stats["in_use_bytes"] and stats["in_use_bytes"] > 0
    assert stats["peak_bytes"] is None or \
        stats["peak_bytes"] >= 0


def test_device_memory_stats_statsless_device_falls_back():
    class _Dev:
        platform = "stub"

        def memory_stats(self):
            return None  # CPU-backend shape

    stats = omem.device_memory_stats(_Dev())
    assert stats["source"] == "host_rss"
    assert stats["platform"] == "stub"
    assert stats["in_use_bytes"] > 0  # RSS of this very process


def test_device_memory_stats_device_numbers_win():
    class _Dev:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_in_use": 123, "peak_bytes_in_use": 456,
                    "bytes_limit": 789}

    stats = omem.device_memory_stats(_Dev())
    assert stats == {"source": "device", "platform": "tpu",
                     "in_use_bytes": 123, "peak_bytes": 456,
                     "limit_bytes": 789}


# ---------------------------------------------------------- kv accounting
def test_kv_pool_sample_occupancy_and_fragmentation():
    eng = _fake_engine(num_blocks=10, block_len=8, bpb=1000)
    blocks = eng.pool.allocator.alloc(4)
    assert blocks is not None
    # Two live slots: 13 written positions over 2 blocks (16 capacity),
    # 5 over 2 — fragmentation = 1 - 18/32.
    s = omem.kv_pool_sample(eng, [(13, 2), (5, 2)])
    assert s["used_blocks"] == 4 and s["free_blocks"] == 5
    assert s["occupancy"] == pytest.approx(4 / 9)
    assert s["bytes_in_use"] == 4000
    assert s["fragmentation"] == pytest.approx(1 - 18 / 32)
    assert s["live_slots"] == 2
    # No live slots -> no fragmentation to speak of.
    assert omem.kv_pool_sample(eng, [])["fragmentation"] == 0.0


def test_kv_pool_sample_counts_prefix_pins():
    eng = _fake_engine(prefix_blocks=3)
    eng.pool.allocator.alloc(3)
    s = omem.kv_pool_sample(eng, [])
    assert s["cached_blocks"] == 3 and s["used_blocks"] == 3


# ------------------------------------------------------- monitor + gauges
def test_monitor_publishes_gauges_and_bounds_timeline():
    reg = omet.MetricsRegistry()
    mon = omem.MemoryMonitor(registry=reg, capacity=4)
    eng = _fake_engine()
    eng.pool.allocator.alloc(2)
    for _ in range(6):
        mon.sample(kv=omem.kv_pool_sample(eng, [(3, 1)]))
    snap = reg.snapshot()
    assert snap["mem.in_use_bytes"]["value"] > 0
    assert snap["mem.kv.used_blocks"]["value"] == 2
    assert snap["mem.kv.bytes_in_use"]["value"] == 2000
    assert 0.0 <= snap["mem.kv.fragmentation"]["value"] <= 1.0
    # Bounded ring: 6 samples through capacity 4, drops counted.
    assert len(mon) == 4 and mon.dropped == 2
    assert mon.last_kv["used_blocks"] == 2


def test_monitor_respects_master_switch(monkeypatch):
    monkeypatch.setattr(omet, "_registry", omet.MetricsRegistry())
    obs.set_enabled(False)
    try:
        mon = omem.MemoryMonitor()  # registry=None + disabled -> noop
        mon.sample(kv=omem.kv_pool_sample(_fake_engine(), []))
        assert omet.registry().snapshot() == {}
    finally:
        obs.set_enabled(None)
    # The timeline still records (an explicitly built monitor is an
    # explicit ask), only publishing is gated.
    assert len(mon) == 1


def test_check_drained_measures_leaks():
    class _LeakyEngine:
        def __init__(self):
            self.pool = types.SimpleNamespace(
                allocator=BlockAllocator(10), num_blocks=10,
                block_len=8, bytes_per_block=1000,
            )
            self.prefix = None
            self.leak = self.pool.allocator.alloc(2)

        def drop_prefix_cache(self):
            return 0

    reg = omet.MetricsRegistry()
    mon = omem.MemoryMonitor(registry=reg)
    eng = _LeakyEngine()
    assert mon.check_drained(eng) == 2  # two refs never given back
    assert reg.snapshot()["mem.kv.leaked_blocks"]["value"] == 2
    eng.pool.allocator.free(eng.leak)
    assert mon.check_drained(eng) == 0
    assert reg.snapshot()["mem.kv.leaked_blocks"]["value"] == 0


# ------------------------------------------------------- flight provider
def test_flight_record_includes_memory_section(tmp_path):
    from chainermn_tpu.observability.flight import FlightRecorder

    reg = omet.MetricsRegistry()
    mon = omem.MemoryMonitor(registry=reg)
    eng = _fake_engine()
    eng.pool.allocator.alloc(3)
    mon.sample(kv=omem.kv_pool_sample(eng, [(7, 2)]))
    rec = FlightRecorder(str(tmp_path), rank=0)
    path = rec.record("sigusr1")
    entry = json.loads(open(path).read().splitlines()[-1])
    mem = entry["resilience"]["memory"]
    # Crash-time truth: a FRESH watermark read plus the newest KV sample.
    assert mem["device"]["in_use_bytes"] > 0
    assert mem["kv"]["used_blocks"] == 3
    assert mem["timeline_samples"] == 1 and mem["timeline_dropped"] == 0


def test_flight_provider_newest_monitor_wins_and_never_pins(tmp_path):
    import gc

    from chainermn_tpu.observability.flight import FlightRecorder

    m1 = omem.MemoryMonitor(registry=omet.MetricsRegistry())
    m1.sample(kv=omem.kv_pool_sample(_fake_engine(), []))
    m2 = omem.MemoryMonitor(registry=omet.MetricsRegistry())
    eng = _fake_engine()
    eng.pool.allocator.alloc(5)
    m2.sample(kv=omem.kv_pool_sample(eng, []))
    assert omem._flight_section()["kv"]["used_blocks"] == 5
    del m1, m2
    gc.collect()
    # Weakref: a dropped monitor leaves only the device watermarks.
    section = omem._flight_section()
    assert "kv" not in section and section["device"]["in_use_bytes"] > 0
    # ...and a record still lands (provider never raises).
    rec = FlightRecorder(str(tmp_path), rank=0)
    entry = json.loads(open(rec.record("crash")).read().splitlines()[-1])
    assert "memory" in entry["resilience"]


# ------------------------------------------------- span clock-base (fix)
def test_spans_share_one_monotonic_base():
    """The satellite fix: exported span timestamps and durations come
    from the SAME clock (perf_counter via the epoch anchor) — two
    back-to-back spans may not overlap or regress within a rank, and the
    derived wall_start tracks t_mono exactly."""
    import math
    import time

    from chainermn_tpu.observability import tracing as otrace

    tr = otrace.Tracer(ring=otrace.SpanRing(8), publish_metrics=False)
    with tr.span("barrier"):
        time.sleep(0.005)
    with tr.span("barrier"):
        pass
    a, b = tr.ring.snapshot()
    assert a["seq"] == 0 and b["seq"] == 1
    # Second span opens AFTER the first closes on the shared clock.
    assert b["t_mono"] >= a["t_mono"] + a["ms"] / 1e3 - 1e-6
    # Both fields are rounded to 1e-6 s (half a microsecond each way, each)
    # and the anchor's sum is taken at the wall clock's magnitude (~1.8e9 s,
    # where a double's spacing is 2.4e-7 s): that much the arithmetic can
    # differ by, and no more.  A wall clock read on its own (time.time() at
    # span open) would miss by the microseconds between the two reads.
    for rec in (a, b):
        slack = 1e-6 + 4 * math.ulp(rec["wall_start"])
        assert rec["wall_start"] == pytest.approx(
            otrace.mono_to_wall(rec["t_mono"]), abs=slack
        )
    # ... and through the one anchor the two clocks keep the same distance
    # between the spans.
    assert b["wall_start"] - a["wall_start"] == pytest.approx(
        b["t_mono"] - a["t_mono"], abs=2e-6 + 4 * math.ulp(b["wall_start"]))

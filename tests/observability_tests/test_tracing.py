"""Span tracing: ring bounds, span fields, in-flight tracking, publication.

The span ring is the flight recorder's raw material — its BOUNDS are a
correctness property (a ring that grows breaks the "dying rank writes a
small record fast" contract), and the in-flight/last-error bookkeeping is
what lets a post-mortem name what a rank was doing.
"""

import json
import threading

import pytest

from chainermn_tpu.observability import MetricsRegistry, SpanRing, Tracer
from chainermn_tpu.observability import tracing as otrace

pytestmark = pytest.mark.tier1


def test_span_ring_bounded_with_eviction_count():
    ring = SpanRing(capacity=4)
    t = Tracer(ring=ring, publish_metrics=False)
    for i in range(10):
        with t.span("op", peer=i):
            pass
    assert len(ring) == 4
    assert ring.total == 10
    # Oldest evicted: the survivors are the newest four.
    assert [s["peer"] for s in ring.snapshot()] == [6, 7, 8, 9]


def test_span_ring_rejects_zero_capacity():
    with pytest.raises(ValueError):
        SpanRing(capacity=0)


def test_span_records_fields_and_is_json():
    t = Tracer(ring=SpanRing(8), publish_metrics=False)
    with t.span("send_obj", peer=3, detail="bcast_obj") as sp:
        sp.nbytes = 123
    (rec,) = t.ring.snapshot()
    json.dumps(rec)
    assert rec["op"] == "send_obj"
    assert rec["peer"] == 3
    assert rec["nbytes"] == 123
    assert rec["detail"] == "bcast_obj"
    assert rec["ok"] is True
    assert rec["ms"] >= 0.0 and rec["wall_start"] > 0


def test_error_span_recorded_and_named_after_unwind():
    """The crash path: by excepthook time the failing span has closed —
    current_span_name() must still name it via the last-error fallback."""
    t = Tracer(ring=SpanRing(8), publish_metrics=False)
    with pytest.raises(RuntimeError):
        with t.span("recv_obj", peer=1):
            raise RuntimeError("peer died")
    (rec,) = t.ring.snapshot()
    assert rec["ok"] is False
    assert "RuntimeError" in rec["error"]
    assert t.in_flight() == []
    assert t.last_error()["op"] == "recv_obj"
    assert t.current_span_name() == "recv_obj"


def test_nested_spans_in_flight_innermost_last():
    t = Tracer(ring=SpanRing(8), publish_metrics=False)
    with t.span("allgather_obj"):
        with t.span("send_obj", peer=2):
            open_now = t.in_flight()
            assert [s["op"] for s in open_now] == \
                ["allgather_obj", "send_obj"]
            assert all("open_ms" in s and "ms" not in s for s in open_now)
            assert t.current_span_name() == "send_obj"
    assert t.in_flight() == []
    # Both closed into the ring, inner first (it exited first).
    assert [s["op"] for s in t.ring.snapshot()] == \
        ["send_obj", "allgather_obj"]


def test_in_flight_visible_across_threads():
    t = Tracer(ring=SpanRing(8), publish_metrics=False)
    entered = threading.Event()
    release = threading.Event()

    def worker():
        with t.span("barrier", peer=0):
            entered.set()
            release.wait(5)

    th = threading.Thread(target=worker, daemon=True)
    th.start()
    assert entered.wait(5)
    try:
        # The flight recorder runs on a DIFFERENT thread than the blocked
        # op; it must still see the worker's open span.
        assert "barrier" in [s["op"] for s in t.in_flight()]
        assert t.current_span_name() == "barrier"
    finally:
        release.set()
        th.join(5)


def test_span_publishes_op_metrics(monkeypatch):
    """Spans feed host_op.* instruments in the process registry."""
    from chainermn_tpu.observability import metrics as omet

    fresh = MetricsRegistry()
    monkeypatch.setattr(omet, "_registry", fresh)
    t = Tracer(ring=SpanRing(8))  # publish_metrics=True (default)
    with t.span("send_obj", peer=1) as sp:
        sp.nbytes = 100
    with pytest.raises(ValueError):
        with t.span("send_obj", peer=1):
            raise ValueError("boom")
    snap = fresh.snapshot()
    assert snap["host_op.send_obj.total"]["value"] == 2
    assert snap["host_op.send_obj.errors"]["value"] == 1
    assert snap["host_op.send_obj.bytes"]["value"] == 100
    assert snap["host_op.send_obj.ms"]["count"] == 2



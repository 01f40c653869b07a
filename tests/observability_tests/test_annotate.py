"""``observability.annotate``: host phases on the profiler's clock, and —
inside a unit of a ``UnitLedger`` — in the unit's record whether a profiler
runs or not; nothing at all outside a unit with no session open."""

import glob
import os

import jax
import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.observability import tracing as otrace

pytestmark = pytest.mark.tier1


def _boom():
    raise AssertionError("a count was evaluated with no profiler session")


def test_without_a_profiler_session_no_count_is_evaluated():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    span = obs.annotate("cmn_quiet", a=_boom, b=3)
    assert span is otrace._NO_SPAN  # one shared no-op, nothing allocated
    with span as s:
        s.set_metadata(c=_boom)
    with obs.annotate("cmn_quiet"):
        with obs.annotate("cmn_quiet_child", n=_boom):
            pass
    # inside a unit the span is a timed one: its seconds are booked to the
    # unit, plain integers the ledger keeps are summed, and a callable count
    # is still never called
    ledger = obs.UnitLedger("quiet", ordinal="i",
                            keep={"cmn_quiet_child": ("n", "m", "lazy")})
    with obs.annotate("cmn_quiet", ledger=ledger, i=5, a=_boom) as unit:
        with obs.annotate("cmn_quiet_child", n=2, lazy=_boom) as child:
            child.set_metadata(m=3, lazy=_boom)
        assert child is not otrace._NO_SPAN and child.seconds > 0
    [u] = ledger.units()
    assert u.ordinal == 5 and u.seconds == unit.seconds >= child.seconds
    assert u.rows == {"cmn_quiet_child": (1, child.seconds)}
    assert u.counts == {"cmn_quiet_child.n": 2, "cmn_quiet_child.m": 3}
    # CMN_OBS=0: owners build no ledger, and every span is the shared no-op
    obs.set_enabled(False)
    try:
        assert obs.annotate("cmn_quiet", ledger=None, i=6, a=_boom) \
            is otrace._NO_SPAN
        timed = obs.annotate("cmn_quiet", timed=True, a=_boom)
        with timed:  # a publisher's clock pair survives the switch
            pass
        assert timed.seconds > 0 and len(ledger) == 1
    finally:
        obs.set_enabled(None)


def _unit(ledger, i, children=()):
    with obs.annotate("cmn_unit", ledger=ledger, i=i):
        for name in children:
            with obs.annotate(name):
                pass


def test_a_unit_books_every_span_that_closes_inside_it():
    ledger = obs.UnitLedger("nest", ordinal="i")
    other = obs.UnitLedger("other", ordinal="k")
    with obs.annotate("cmn_unit", ledger=ledger, i=0):
        with obs.annotate("cmn_a"):
            with obs.annotate("cmn_b"):
                pass
            with obs.annotate("cmn_b"):
                pass
        # a would-be unit inside a unit is a child like any other
        with obs.annotate("cmn_other_unit", ledger=other, k=9):
            with obs.annotate("cmn_b"):
                pass
    assert obs.annotate("cmn_after") is otrace._NO_SPAN  # the unit is shut
    [u] = ledger.units()
    assert len(other) == 0 and obs.unit_ledger("nest") is ledger
    assert {k: n for k, (n, _) in u.rows.items()} == {
        "cmn_a": 1, "cmn_b": 3, "cmn_other_unit": 1}
    # inclusive seconds: a parent holds its children; the immediate
    # children of the unit add up to at most the unit
    assert u.secs["cmn_a"] >= u.direct["cmn_a"] > 0
    assert set(u.direct) == {"cmn_a", "cmn_other_unit"}
    assert sum(u.direct.values()) <= u.seconds
    assert u.t_mono >= otrace.EPOCH_PERF
    # a unit that raises is closed all the same, and the next one is clean
    with pytest.raises(RuntimeError):
        with obs.annotate("cmn_unit", ledger=ledger, i=1):
            with obs.annotate("cmn_a"):
                raise RuntimeError("x")
    _unit(ledger, 2, ["cmn_b"])
    assert [(v.ordinal, sorted(v.calls)) for v in ledger.units()] == [
        (0, ["cmn_a", "cmn_b", "cmn_other_unit"]), (1, ["cmn_a"]),
        (2, ["cmn_b"])]


def test_the_ring_counts_what_it_evicts():
    ledger = obs.UnitLedger("small", ordinal="i", capacity=3)
    for i in range(5):
        _unit(ledger, i)
    assert [u.ordinal for u in ledger.units()] == [2, 3, 4]
    assert (ledger.total, ledger.evicted, len(ledger)) == (5, 2, 3)
    assert obs.UnitLedger("small", ordinal="i").capacity == \
        otrace.UNIT_RING_CAPACITY == 4096
    with pytest.raises(ValueError):
        obs.UnitLedger("small", ordinal="i", capacity=0)


def test_a_reader_on_another_thread_sees_whole_units_while_they_close():
    """One thread closes units, others snapshot the ring: every snapshot is
    a run of whole, consecutive units (no lock: a flight snapshot may be a
    signal handler on the closing thread)."""
    import sys
    import threading

    ledger = obs.UnitLedger("stress", ordinal="i", capacity=64)
    stop, bad = threading.Event(), []

    def read():
        while not stop.is_set():
            got = [u.ordinal for u in ledger.units()]
            if got and got != list(range(got[0], got[0] + len(got))):
                bad.append(got)
            state = ledger.flight_state(4)
            if state["evicted"] < 0 or len(state["last"]) > 4:
                bad.append(state)

    readers = [threading.Thread(target=read) for _ in range(8)]
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for r in readers:
            r.start()
        for i in range(5000):
            _unit(ledger, i, ["cmn_a"])
    finally:
        stop.set()
        for r in readers:
            r.join(timeout=10)
        sys.setswitchinterval(before)
    assert not any(r.is_alive() for r in readers) and not bad, bad[:2]
    assert (ledger.total, ledger.evicted, len(ledger)) == (5000, 4936, 64)
    assert [u.ordinal for u in ledger.units()] == list(range(4936, 5000))


def test_the_flight_record_carries_the_last_units_by_phase(tmp_path):
    import json

    from chainermn_tpu.observability.flight import FlightRecorder

    ledger = obs.UnitLedger("flown", ordinal="i", capacity=40,
                            keep={"cmn_a": ("tokens",)})
    for i in range(50):
        with obs.annotate("cmn_unit", ledger=ledger, i=i):
            with obs.annotate("cmn_a", tokens=i):
                pass
    path = FlightRecorder(str(tmp_path), rank=0).record("sigusr1")
    with open(path) as f:
        got = json.loads(f.readlines()[-1])["resilience"]["units.flown"]
    assert (got["units"], got["evicted"], got["capacity"]) == (50, 10, 40)
    assert [u["ordinal"] for u in got["last"]] == list(range(34, 50))
    last = got["last"][-1]
    assert last["rows"]["cmn_a"][0] == 1 and last["ms"] >= \
        last["rows"]["cmn_a"][1] >= 0
    assert last["counts"] == {"cmn_a.tokens": 49}


def _cmn_events(trace_dir):
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("cmn_"):
                    out.append((e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)))
    return out


def test_in_a_session_spans_nest_and_carry_their_counts(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.annotate("cmn_outer", iter=7, lazy=lambda: 11) as outer:
            with obs.annotate("cmn_inner", req=3, program="decode_step"):
                pass
            outer.set_metadata(tokens=lambda: 5, retired=1)
        obs.set_enabled(False)  # CMN_OBS=0: nothing is recorded
        with obs.annotate("cmn_switched_off", n=_boom):
            pass
    finally:
        obs.set_enabled(None)
        jax.profiler.stop_trace()
    events = {name: (a, d, stats) for name, a, d, stats in
              _cmn_events(str(tmp_path))}
    assert set(events) == {"cmn_outer", "cmn_inner"}
    a0, d0, outer = events["cmn_outer"]
    a1, d1, inner = events["cmn_inner"]
    assert outer == {"iter": 7, "lazy": 11, "tokens": 5, "retired": 1}
    assert inner == {"req": 3, "program": "decode_step"}
    assert a0 <= a1 and a1 + d1 <= a0 + d0  # the child lies in its parent


def test_a_watched_program_names_its_dispatch_and_its_compile(tmp_path):
    """``cmn_dispatch(program=)`` around every call of a watched program,
    and a ``cmn_compile(program=, n=)`` child in the call that compiled."""
    import jax.numpy as jnp

    from chainermn_tpu.observability import device as odev
    from chainermn_tpu.observability.metrics import MetricsRegistry

    wf = odev.CompileWatch(registry=MetricsRegistry()).wrap(
        jax.jit(lambda x: x * 2), "doubler")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        wf(jnp.ones((4,)))
        wf(jnp.ones((4,)))
        wf(jnp.ones((5,)))  # a second variant
    finally:
        jax.profiler.stop_trace()
    events = _cmn_events(str(tmp_path))
    dispatches = [s for n, _, _, s in events if n == "cmn_dispatch"]
    compiles = [s for n, _, _, s in events if n == "cmn_compile"]
    assert [s["program"] for s in dispatches] == ["doubler"] * 3
    assert [(s["program"], s["n"]) for s in compiles] == [
        ("doubler", 1), ("doubler", 2)]
    assert all(s["backend_ms"] >= 0 for s in compiles)
    assert wf.compiles == 2

"""``observability.annotate``: host phases on the profiler's clock, free
while no profiler session is open."""

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

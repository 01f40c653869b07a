"""``window_prefill_rode_share``: the share of the window's prefill chunks
that rode the decode step (``cmn_serve_prefill(rode=)`` over its calls, from
the unit ledger), held to a count taken by hand at the engine's door on a
rehearsal of ``gpt2-xl_serve_backlog`` — and 0.0 for a program whose ledger
has no such count (the parent of the PR that added it)."""

import glob
import os

import jax
import pytest

from chainermn_tpu import observability as obs
from perfbench import program_trace as pt
from perfbench import serving, traffic_gen, weights
from perfbench.manifest import Manifest
from perfbench.reducers import unit_ledger as ul
from perfbench.spans import Clock

pytestmark = pytest.mark.tier1

NAME = "window_prefill_rode_share"


def _reduce(man, facts):
    spec = man.metric_file(NAME)
    return man.reducer(spec["reducer"]).reduce(facts, spec["args"])


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The backlog cell at its rehearsal size, driven as its runner drives
    it (pool fill, then a window whose ticks 2-4 are profiled), with every
    ``mixed_step`` and every ``prefill`` of the engine counted by tick."""
    man = Manifest()
    cfg = man.config("gpt2-xl")
    traffic = man.traffic("decode_backlog")
    tr = dict(traffic, **traffic["rehearse"])
    model, m, pdt, specs = serving.build_model(man, cfg, rehearse=True)
    eng, sv = serving.build_engine(
        cfg, model, weights.make_params(specs, 13, pdt), rehearse=True)
    clock = Clock()
    serving.warm_programs(eng, clock, m["vocab"], sv["prefill_chunk"])
    reqs = traffic_gen.decode_backlog(tr, m["vocab"], 2**31 + 42)
    sched, rec = serving.new_scheduler(eng, clock, reqs)
    for r in reqs:
        serving.submit(sched, r, 0.0)
    door = {"mixed_step": [], "prefill": []}

    def counted(name):
        call = getattr(eng, name)

        def wrapper(*a, **k):
            door[name].append(sched._ticks - 1)  # the tick now open
            return call(*a, **k)

        setattr(eng, name, wrapper)

    for name in door:
        counted(name)
    while rec.prefills_done < tr["slots"]:
        assert sched.tick()
    fill = sched._ticks
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    trace_dir = str(tmp_path_factory.mktemp("rode_trace"))
    first, n = tr["trace_from_tick"], tr["trace_ticks"]
    i = 0
    while sched.pending:
        if i == first:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("pb:tick"):
            assert sched.tick()
        if i == first + n - 1:
            jax.profiler.stop_trace()
        i += 1
    assert i > first + n, "the run was too short"
    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    t = pt.load(path)
    assert t is not None, "no xplane_pb2 to read the trace with"
    facts = {"program_trace": t, "traffic": traffic, "traced_units": n,
             "unit_ledgers": {"serve_tick": obs.unit_ledger("serve_tick")}}
    return {"man": man, "facts": facts, "door": door, "fill": fill}


def test_rode_share_is_the_hand_count_over_the_window(rehearsal):
    door, fill = rehearsal["door"], rehearsal["fill"]
    rode = sum(1 for tick in door["mixed_step"] if tick >= fill)
    own = sum(1 for tick in door["prefill"] if tick >= fill)
    assert rode and own, "the window held no riding chunk, or none else"
    got = _reduce(rehearsal["man"], rehearsal["facts"])
    assert got == pytest.approx(rode / (rode + own))
    # the pool fill's chunks (ticks before the window) are not the window's
    assert any(tick < fill for tick in door["mixed_step"])
    units, _ = ul.window(rehearsal["facts"],
                         rehearsal["man"].metric_file(NAME)["args"])
    assert sum(u.counts.get("cmn_serve_decode.chunk_rows", 0) > 0
               for u in units) == rode


def test_a_ledger_without_the_count_reads_zero():
    """The parent's program counts no ``rode``: its chunks are all calls of
    their own, and the share of them that rode is 0.0, not nothing."""
    spec = Manifest().metric_file(NAME)
    assert spec["args"]["what"] == "count_per_call"
    units = [ul.Unit(i, float(i), 0.02) for i in range(3)]
    for u in units:
        u.calls["cmn_serve_prefill"] = 2
        u.counts["cmn_serve_prefill.tokens"] = 64
    assert ul.WHAT[spec["args"]["what"]](units, spec["args"]) == 0.0
    units[1].counts["cmn_serve_prefill.rode"] = 1
    assert ul.WHAT[spec["args"]["what"]](units, spec["args"]) == 1 / 6

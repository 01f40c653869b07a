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
    warmed = {"cow": eng.cow_compiles, "mixed": eng.mixed_compiles,
              "prefill": eng.prefill_compiles,
              "cached_blocks": eng.prefix.cached_blocks,
              "free_blocks": eng.pool.allocator.free_blocks}
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
    facts = {"program_trace": t, "traffic": tr, "traced_units": n,
             "unit_ledgers": {"serve_tick": obs.unit_ledger("serve_tick")}}
    return {"man": man, "facts": facts, "door": door, "fill": fill,
            "tr": tr, "chunk": sv["prefill_chunk"], "reqs": reqs,
            "warmed": warmed, "eng": eng}


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


def test_the_replay_is_the_schedulers_schedule_tick_for_tick(rehearsal):
    """``traffic_gen.replay_backlog`` against the scheduler itself, over the
    pool fill and the whole drain: per tick the chunks started, the one
    that rode and the rows of the decode step, from the unit ledger — and
    the calls at the engine's door, tick by tick."""
    tr, door = rehearsal["tr"], rehearsal["door"]
    lengths = [(len(r.prompt), r.max_new) for r in rehearsal["reqs"]]
    assert lengths == traffic_gen.backlog_lengths(tr)
    fill, ticks = traffic_gen.replay_backlog(lengths, tr["slots"],
                                             rehearsal["chunk"])
    units = rehearsal["facts"]["unit_ledgers"]["serve_tick"].units()
    assert fill == rehearsal["fill"]
    assert [u.ordinal for u in units] == list(range(len(ticks)))
    assert [(u.calls.get("cmn_serve_prefill", 0),
             u.counts.get("cmn_serve_prefill.rode", 0),
             u.counts.get("cmn_serve_decode.live", 0),
             u.counts.get("cmn_serve_admit.admitted", 0),
             u.counts.get("cmn_serve_emit.retired", 0)) for u in units] == \
        [(t.calls, t.rode, t.live, t.admitted,
          # a request over at its first token is retired outside the emit
          t.finished if t.live else 0) for t in ticks]
    assert door["mixed_step"] == [i for i, t in enumerate(ticks) if t.rode]
    assert door["prefill"] == [i for i, t in enumerate(ticks)
                               for _ in range(t.calls - t.rode)]
    assert sum(t.tokens for t in ticks) == sum(o for _, o in lengths)


def test_warm_up_reaches_every_program_a_window_can_call(rehearsal):
    """``serving.warm_programs``: the decode step, the mixed step, the
    whole-chunk prefill and — through a request that shares half a block
    with an earlier one — the copy-on-write program, whose first call a
    prefix hit between two random prompts otherwise makes inside the window
    (PERF.md: 169 ms of ``cmn_compile`` in tick 1214 of seed ...713).  It
    leaves the trie empty and every block free, and the drive after it
    compiles nothing."""
    warmed, eng = rehearsal["warmed"], rehearsal["eng"]
    assert warmed["cow"] == 1 and warmed["mixed"] == 1
    assert warmed["prefill"] == 1 and warmed["cached_blocks"] == 0
    assert warmed["free_blocks"] == eng.pool.allocator.num_blocks - 1
    assert (eng.cow_compiles, eng.mixed_compiles, eng.prefill_compiles) == \
        (1, 1, 1)
    units = rehearsal["facts"]["unit_ledgers"]["serve_tick"].units()
    assert sum(u.calls.get("cmn_compile", 0) for u in units) == 0


def test_the_traced_share_is_taken_over_the_traced_ticks_alone(rehearsal):
    """``traced_prefill_rode_share``: the same count over the traced ticks
    only — how far the profiled stretch stands for the window."""
    man, tr = rehearsal["man"], rehearsal["tr"]
    spec = man.metric_file("traced_prefill_rode_share")
    whole = man.metric_file(NAME)
    assert spec["args"] == dict(whole["args"], over="traced")
    got = man.reducer(spec["reducer"]).reduce(rehearsal["facts"],
                                              spec["args"])
    _, ticks = traffic_gen.replay_backlog(
        [(len(r.prompt), r.max_new) for r in rehearsal["reqs"]],
        tr["slots"], rehearsal["chunk"])
    a = rehearsal["fill"] + tr["trace_from_tick"]
    traced = ticks[a:a + tr["trace_ticks"]]
    calls = sum(t.calls for t in traced)
    assert calls, "the traced ticks of the rehearsal hold no chunk"
    assert got == pytest.approx(sum(t.rode for t in traced) / calls)


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

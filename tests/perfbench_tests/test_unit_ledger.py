"""The unit ledger behind ``annotate`` and the ``window_*`` metrics that read
it: a rehearsal of ``gpt2-xl_serve_backlog`` with a profiler session over
three ticks (ledger and trace are joined by the tick's ordinal and agree;
the ledger's counts are the ``Recorder``'s), a planted stall that the finder
finds beside refill ticks that it does not mistake for one, and the reducers
over hand-made ledgers, the recorded fixture and both manifests."""

import glob
import os
import time

import jax
import pytest

from chainermn_tpu import observability as obs
from perfbench import program_trace as pt
from perfbench import serving, traffic_gen, weights
from perfbench.manifest import HERE, Manifest
from perfbench.reducers import unit_ledger as ul
from perfbench.spans import Clock

pytestmark = pytest.mark.tier1

SERVE = ("window_tick_ms", "window_prefill_share_pct",
         "window_prefill_ms_call", "window_prefill_ctx_blocks",
         "window_host_ms_tick", "window_stall_ms")
INPUT = ("window_input_wait_ms_step", "window_input_wait_max_ms")


def _reduce(man, name, facts):
    spec = man.metric_file(name)
    return man.reducer(spec["reducer"]).reduce(facts, spec["args"])


def _engine(man, capacity=None):
    cfg = man.config("gpt2-xl")
    model, m, pdt, specs = serving.build_model(man, cfg, rehearse=True)
    params = weights.make_params(specs, 11, pdt)
    if capacity is None:
        eng, sv = serving.build_engine(cfg, model, params, rehearse=True)
    else:
        from chainermn_tpu.serving import DecodeEngine

        sv = cfg["rehearse"]["serve"]
        eng = DecodeEngine(
            model, params, capacity=capacity,
            num_blocks=1 + capacity * sv["max_ctx"] // sv["block_len"],
            block_len=sv["block_len"],
            max_blocks_per_slot=sv["max_ctx"] // sv["block_len"],
            prefill_chunk=sv["prefill_chunk"])
    clock = Clock()
    serving.warm_programs(eng, clock, m["vocab"], sv["prefill_chunk"])
    return eng, clock, m, sv


def _window(sched, first, n_traced, trace_dir):
    """The runner's loop: ticks under ``pb:tick``, a profiler session and
    ``pb:window`` over ticks ``first .. first + n_traced - 1``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    i, window = 0, None
    while sched.pending:
        if i == first:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window = jax.profiler.TraceAnnotation("pb:window")
            window.__enter__()
        with jax.profiler.TraceAnnotation("pb:tick"):
            assert sched.tick()
        if i == first + n_traced - 1:
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            window = None
        i += 1
    assert window is None and i > first + n_traced, "the run was too short"
    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    t = pt.load(path)
    assert t is not None, "no xplane_pb2 to read the trace with"
    return t, i


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The backlog cell at its rehearsal size, driven as its runner drives
    it: pool fill, then a window whose ticks 2-4 are profiled."""
    man = Manifest()
    traffic = man.traffic("decode_backlog")
    tr = dict(traffic, **traffic["rehearse"])
    eng, clock, m, sv = _engine(man)
    reqs = traffic_gen.decode_backlog(tr, m["vocab"], 2**31 + 41)
    sched, rec = serving.new_scheduler(eng, clock, reqs)
    for r in reqs:
        serving.submit(sched, r, 0.0)
    while rec.prefills_done < tr["slots"]:
        assert sched.tick()
    fill = sched._ticks
    before = (rec.prefill_calls, rec.prefill_tokens, rec.prefills_done)
    t, ticks = _window(sched, tr["trace_from_tick"], tr["trace_ticks"],
                       str(tmp_path_factory.mktemp("ledger_trace")))
    ledger = obs.unit_ledger("serve_tick")
    assert ledger is sched._units
    facts = {"program_trace": t, "traffic": tr,
             "traced_units": tr["trace_ticks"],
             "unit_ledgers": {"serve_tick": ledger}}
    return {"man": man, "facts": facts, "ledger": ledger, "trace": t,
            "rec": rec, "before": before, "fill": fill, "ticks": ticks,
            "tr": tr, "sv": sv, "sched": sched}


def test_ledger_and_trace_are_one_tick_by_the_ordinal_and_agree(rehearsal):
    t, tr = rehearsal["trace"], rehearsal["tr"]
    spec = rehearsal["man"].metric_file("window_tick_ms")["args"]
    units, traced = ul.window(rehearsal["facts"], spec)
    # the window: from the first tick after the pool fill to the last tick
    assert [u.ordinal for u in units] == list(range(
        rehearsal["fill"], rehearsal["fill"] + rehearsal["ticks"]))
    first = rehearsal["fill"] + tr["trace_from_tick"]
    assert sorted(traced) == list(range(first, first + tr["trace_ticks"]))
    by = {u.ordinal: u for u in units}
    # two clocks, one stretch of work: call for call on every traced tick,
    # and second for second over the traced ticks together (a tick alone
    # may lose a preemption between the two recorders' enters)
    led, trc = {}, {}

    def both(name, a, b):
        led[name] = led.get(name, 0.0) + a
        trc[name] = trc.get(name, 0.0) + b

    for ordinal, span in traced.items():
        u = by[ordinal]
        both("unit", u.seconds, span.dur)
        kids = [t.spans[i] for i in span.children]
        for name in ("cmn_serve_prefill_round", "cmn_serve_decode"):
            both(name, u.direct.get(name, 0.0),
                 sum(s.dur for s in kids if s.name == name))
        under = [s for s in t.spans
                 if span.start <= s.start and s.end <= span.end]
        for name in ("cmn_serve_prefill", "cmn_engine_readback"):
            mine = [s for s in under if s.name == name]
            assert u.calls.get(name, 0) == len(mine)
            both(name, u.secs.get(name, 0.0), sum(s.dur for s in mine))
    table = ul.join(t, units, traced, spec["join"])
    assert table["units"] == tr["trace_ticks"]
    for name in ["unit"] + spec["join"]:
        both("join." + name, sum(r[name][0] for r in table["rows"]) / 1e3,
             sum(r[name][1] for r in table["rows"]) / 1e3)
    assert led["join.unit"] == pytest.approx(led["unit"])
    n = len(traced)
    for name, b in trc.items():
        assert abs(led[name] - b) <= max(0.05 * b, n * 50e-6), (name, led, trc)


def test_a_units_children_fit_in_it_and_its_counts_are_the_recorders(
        rehearsal):
    from chainermn_tpu.ops.decode_attention import context_widths

    rec, sv = rehearsal["rec"], rehearsal["sv"]
    all_units = rehearsal["ledger"].units()
    assert rehearsal["ledger"].evicted == 0
    for u in all_units:
        assert sum(u.direct.values()) <= u.seconds
        assert set(u.direct) <= {
            "cmn_serve_deadlines", "cmn_serve_admit",
            "cmn_serve_prefill_round", "cmn_serve_decode",
            "cmn_serve_publish"}
        for name, sec in u.secs.items():
            assert 0 <= sec <= u.seconds, name

    def total(units, key):
        return sum(u.counts.get(key, 0) for u in units)

    # over the scheduler's whole life, and over the window alone
    assert sum(u.calls.get("cmn_serve_prefill", 0) for u in all_units) \
        == rec.prefill_calls
    assert total(all_units, "cmn_serve_prefill.tokens") == rec.prefill_tokens
    assert total(all_units, "cmn_serve_prefill.final") == rec.prefills_done
    assert total(all_units, "cmn_serve_admit.admitted") == len(rec.admit)
    assert [u.counts["cmn_serve_decode.live"] for u in all_units
            if "cmn_serve_decode" in u.calls] == rec.live_per_step
    assert total(all_units, "cmn_serve_emit.tokens") == sum(rec.live_per_step)
    assert total(all_units, "cmn_serve_emit.retired") == len(rec.retired)
    units = [u for u in all_units if u.ordinal >= rehearsal["fill"]]
    calls0, tokens0, done0 = rehearsal["before"]
    assert sum(u.calls.get("cmn_serve_prefill", 0) for u in units) \
        == rec.prefill_calls - calls0
    assert total(units, "cmn_serve_prefill.tokens") \
        == rec.prefill_tokens - tokens0
    assert total(units, "cmn_serve_prefill.final") == rec.prefills_done - done0
    # every call reads one of the program's widths, and pads to a ladder size
    widths = context_widths(sv["max_ctx"] // sv["block_len"])
    for u in all_units:
        n = u.calls.get("cmn_serve_prefill", 0)
        if n:
            assert n * widths[0] <= u.counts["cmn_serve_prefill.ctx_blocks"] \
                <= n * widths[-1]
            assert u.counts["cmn_serve_prefill.padded"] >= \
                u.counts["cmn_serve_prefill.tokens"]
    # the spans of the traced ticks carry the same counts
    traced = {s.stats["tick"]: s for s in
              rehearsal["trace"].named("cmn_serve_tick")}
    by = {u.ordinal: u for u in all_units}
    for s in rehearsal["trace"].named("cmn_serve_prefill"):
        tick = rehearsal["trace"].spans[
            rehearsal["trace"].spans[s.parent].parent]
        assert tick.stats["tick"] in traced
    for ordinal, tick in traced.items():
        chunks = [s for s in rehearsal["trace"].named("cmn_serve_prefill")
                  if tick.start <= s.start and s.end <= tick.end]
        assert sum(s.stats["ctx_blocks"] for s in chunks) == \
            by[ordinal].counts.get("cmn_serve_prefill.ctx_blocks", 0)


def test_every_serving_metric_reads_the_rehearsals_ledger(rehearsal, capsys):
    man, facts = rehearsal["man"], rehearsal["facts"]
    units = [u for u in rehearsal["ledger"].units()
             if u.ordinal >= rehearsal["fill"]]
    got = {name: _reduce(man, name, facts) for name in SERVE}
    assert all(v is not None for v in got.values()), got
    whole = sum(u.seconds for u in units)
    assert got["window_tick_ms"] == pytest.approx(1e3 * whole / len(units))
    assert 0 < got["window_prefill_share_pct"] < 100
    assert 0 < got["window_prefill_ms_call"] < got["window_tick_ms"] * 50
    assert 2 <= got["window_prefill_ctx_blocks"] <= 16
    assert 0 < got["window_host_ms_tick"] < got["window_tick_ms"]
    assert got["window_stall_ms"] >= 0
    # the three tables, once
    import json

    said = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("{"):
            said.update(json.loads(line))
    assert {"window_phases", "window_stalls", "window_join"} <= set(said)
    rows = said["window_phases"]["rows"]
    assert said["window_phases"]["units"] == len(units)
    for name in ("cmn_serve_decode", "cmn_serve_prefill", "cmn_dispatch",
                 "cmn_engine_readback", ul.BETWEEN):
        assert rows[name]["calls"] > 0 and rows[name]["share_pct"] <= 100
    assert len(said["window_stalls"]["rows"]) == 5
    assert said["window_join"]["units"] == rehearsal["tr"]["trace_ticks"]


def test_an_evicted_window_reads_nothing(rehearsal):
    units = rehearsal["ledger"].units()

    class Late:
        def units(self):
            return units[rehearsal["fill"] + 1:]

    facts = dict(rehearsal["facts"], unit_ledgers={"serve_tick": Late()})
    for name in SERVE:
        assert _reduce(rehearsal["man"], name, facts) is None


def test_a_planted_stall_is_found_and_a_refill_is_not_mistaken_for_one(
        tmp_path):
    """``skew@serve_step:N`` (the hook inside ``cmn_serve_decode``, outside
    the engine's spans), once, in a run whose first ticks hold eight prefill
    calls each."""
    from chainermn_tpu.resilience.faults import FaultInjector, parse_fault_spec
    from chainermn_tpu.serving import Scheduler

    man = Manifest()
    eng, clock, m, sv = _engine(man, capacity=8)
    planted, step, slept = 0.15, 9, []

    def once(seconds):
        if not slept:
            slept.append(seconds)
            time.sleep(seconds)

    fault = FaultInjector(
        parse_fault_spec(f"skew@serve_step:{step}:{int(planted * 1e3)}ms"),
        sleep=once)
    reqs = [traffic_gen.Req(i, [1 + (3 * i + j) % 500 for j in range(40)], 24)
            for i in range(8)]
    rec = serving.Recorder(clock, {r.id: len(r.prompt) for r in reqs})
    sched = Scheduler(eng, clock=clock, timeline=rec, fault=fault)
    for r in reqs:
        serving.submit(sched, r, 0.0)
    t, _ = _window(sched, 4, 3, str(tmp_path))
    assert slept == [planted]
    ledger = obs.unit_ledger("serve_tick")
    facts = {"program_trace": t, "traffic": {"trace_from_tick": 4},
             "unit_ledgers": {"serve_tick": ledger}}
    args = man.metric_file("window_stall_ms")["args"]
    units, _ = ul.window(facts, args)
    assert units[0].ordinal == 0
    refills = [u for u in units if u.calls.get("cmn_serve_prefill") == 8]
    assert len(refills) == 3  # 40 tokens in chunks of 16
    # the stalled tick is the one that ran decode step `step`
    steps, hit = 0, None
    for u in units:
        steps += u.calls.get("cmn_serve_decode", 0)
        if steps >= step and hit is None:
            hit = u
    lost = dict(zip((u.ordinal for u in units),
                    ul.unexplained(units, args["call"])[2]))
    assert 0.8 * planted <= lost[hit.ordinal] <= planted + 1.0
    for u in refills:
        assert lost[u.ordinal] <= 0.05, (u.ordinal, lost[u.ordinal])
    assert _reduce(man, "window_stall_ms", facts) >= 0.8 * planted * 1e3
    top = ul.stalls(units, args["call"], args["flags"])["rows"][0]
    assert top["ordinal"] == hit.ordinal and top["calls"] == 0
    assert top["child"] == "cmn_serve_decode"
    assert top["child_excess_ms"] >= 0.8 * planted * 1e3
    # inside decode, and in none of the engine's spans under it
    deep = top["excess_ms"]
    assert list(deep)[0] == "cmn_serve_decode"
    assert all(v < 0.2 * planted * 1e3 for k, v in deep.items()
               if k != "cmn_serve_decode")
    assert 0 < top["at_s"] < 60
    assert (top["compile"], top["admitted"], top["final"]) == (0, 0, 0)
    # a refill tick is flagged by what it held: final chunks, no compile
    held = {r["ordinal"]: r for r in ul.stalls(
        units, args["call"], args["flags"], n=len(units))["rows"]}
    assert sum(held[u.ordinal]["final"] for u in refills) == 8
    assert sum(r["admitted"] for r in held.values()) == 8


# ------------------------------------------- hand-made ledgers, both manifests
class _Hand:
    def __init__(self, units):
        self._units = units

    def units(self):
        return list(self._units)


def _made_up_trace(span, key, ordinals):
    spans = [pt.Span("pb:window", 0.0, 100.0)]
    for k, i in enumerate(ordinals):
        spans.append(pt.Span(span, 1.0 + k, 1.5 + k, {key: i}))
    pt._nest(spans)
    return pt.ProgramTrace("made_up", spans, {})


def _tick(ordinal, seconds, calls=0, call_s=0.003, readback=0.010):
    u = obs.UnitRecord(ordinal, 100.0 + ordinal)
    u.seconds = seconds
    u.calls = {"cmn_serve_decode": 1, "cmn_engine_readback": 1 + calls}
    u.secs = {"cmn_serve_decode": seconds - calls * call_s - 0.001,
              "cmn_engine_readback": readback}
    u.direct = {"cmn_serve_decode": u.secs["cmn_serve_decode"]}
    if calls:
        u.calls.update(cmn_serve_prefill=calls, cmn_serve_prefill_round=1)
        u.secs.update(cmn_serve_prefill=calls * call_s,
                      cmn_serve_prefill_round=calls * call_s)
        u.direct["cmn_serve_prefill_round"] = calls * call_s
        u.counts = {"cmn_serve_prefill.ctx_blocks": 16 * calls}
    return u


def test_the_reducers_over_a_hand_made_ledger(man):
    """Ticks 10.. of a scheduler whose window opened at tick 12 (traced
    ticks 14-16, ``trace_from_tick`` 2): 16 ms with no prefill call, + 8 ms
    a call whose own span is 3 ms (the device time of a chunk lands in the
    decode step's readback); one refill of eight calls; one tick that lost
    400 ms at no call."""
    units = [_tick(i, 0.016) for i in range(10, 20)]
    units += [_tick(20, 0.024, calls=1), _tick(21, 0.032, calls=2),
              _tick(22, 0.080, calls=8), _tick(23, 0.416),
              _tick(24, 0.024, calls=1)]
    facts = {"program_trace": _made_up_trace("cmn_serve_tick", "tick",
                                             [14, 15, 16]),
             "traffic": dict(man.traffic("decode_backlog"),
                             trace_from_tick=2),
             "unit_ledgers": {"serve_tick": _Hand(units)}}
    window = units[2:]
    got = {name: _reduce(man, name, facts) for name in SERVE}
    whole = sum(u.seconds for u in window)
    assert got["window_tick_ms"] == pytest.approx(1e3 * whole / 13)
    assert got["window_prefill_share_pct"] == pytest.approx(
        100 * 12 * 0.003 / whole)
    assert got["window_prefill_ms_call"] == pytest.approx(3.0)
    assert got["window_prefill_ctx_blocks"] == pytest.approx(16.0)
    assert got["window_host_ms_tick"] == pytest.approx(
        1e3 * (whole - 13 * 0.010) / 13)
    assert got["window_stall_ms"] == pytest.approx(400.0)
    assert ul.price(window, "cmn_serve_prefill") == pytest.approx(
        (0.016, 0.008))
    rows = ul.stalls(window, "cmn_serve_prefill")["rows"]
    assert [r["ordinal"] for r in rows[:2]] == [23, 22]
    assert rows[0]["unexplained_ms"] == pytest.approx(400.0)
    assert rows[0]["child"] == "cmn_serve_decode"
    assert rows[1]["unexplained_ms"] == pytest.approx(0.0, abs=1e-6)
    assert rows[1]["calls"] == 8
    # no trace, no unit in the trace, an empty ledger, a ring that lost the
    # window's first unit, another owner's ledger: no number, and no error
    # the traffic file states where the trace starts: there is no default
    with pytest.raises(KeyError):
        _reduce(man, "window_tick_ms", dict(facts, traffic={}))
    for broken in (dict(facts, program_trace=None, trace=None),
                   dict(facts, program_trace=_made_up_trace(
                       "cmn_other", "tick", [14, 15, 16])),
                   dict(facts, unit_ledgers={"serve_tick": _Hand([])}),
                   dict(facts, unit_ledgers={"serve_tick": _Hand(units[3:])}),
                   dict(facts, unit_ledgers={"serve_tick": _Hand(units[:5])})):
        for name in SERVE:
            assert _reduce(man, name, broken) is None


def test_the_input_reducers_over_a_hand_made_ledger(man):
    units = []
    for n, sec in enumerate([0.0015] * 9 + [1.1] + [0.0017] * 4):
        u = obs.UnitRecord(n, 50.0 + n)
        u.seconds = sec
        u.calls = {"cmn_input_host_batch": 1, "cmn_input_device_put": 1}
        u.secs = {"cmn_input_host_batch": sec * 0.5,
                  "cmn_input_device_put": sec * 0.4}
        u.direct = dict(u.secs)
        units.append(u)
    facts = {"program_trace": _made_up_trace("cmn_input_wait", "n", [6, 7]),
             "traffic": man.traffic("train_steps"),
             "unit_ledgers": {"input_wait": _Hand(units)}}
    window = units[4:]  # the traced waits start at the window's third
    assert _reduce(man, "window_input_wait_ms_step", facts) == pytest.approx(
        1e3 * sum(u.seconds for u in window) / len(window))
    assert _reduce(man, "window_input_wait_max_ms", facts) == pytest.approx(
        1100.0)
    top = ul.stalls(window)["rows"][0]
    assert top["ordinal"] == 9 and top["child"] == "cmn_input_host_batch"
    assert top["unexplained_ms"] == pytest.approx(1100.0 - 1.5)


def test_a_trace_without_ordinals_is_its_own_window(man):
    """The backlog fixture was recorded before the ledger: its two ticks
    carry no ``tick=``, so they are the units, rebuilt from the span tree —
    whatever ledger the process holds."""
    t = pt.load(os.path.join(HERE, "fixtures",
                             "serve_backlog_2ticks_program.xplane.pb.gz"))
    ticks = t.named("cmn_serve_tick")
    assert len(ticks) == 2 and "tick" not in ticks[0].stats
    facts = {"program_trace": t, "traffic": man.traffic("decode_backlog"),
             "unit_ledgers": {"serve_tick": _Hand([_tick(0, 0.016)])}}
    units = ul.units_of_trace(t, "cmn_serve_tick")
    for u, s in zip(units, ticks):
        assert u.seconds == s.dur and sum(u.direct.values()) <= u.seconds
        assert u.calls["cmn_serve_decode"] == 1
        assert u.counts["cmn_serve_decode.live"] == 32
        assert 0 < u.secs["cmn_engine_readback"] <= u.secs["cmn_serve_decode"]
    got = {name: _reduce(man, name, facts) for name in SERVE}
    assert got["window_tick_ms"] == pytest.approx(
        1e3 * sum(s.dur for s in ticks) / 2)
    readback = sum(s.dur for s in t.named("cmn_engine_readback"))
    assert got["window_host_ms_tick"] == pytest.approx(
        got["window_tick_ms"] - 1e3 * readback / 2)
    # two decode ticks made no prefill call
    assert got["window_prefill_share_pct"] < 1
    assert got["window_prefill_ms_call"] == 0.0
    assert got["window_prefill_ctx_blocks"] == 0.0
    assert got["window_stall_ms"] == 0.0
    # no ledger was joined: there is nothing to compare the trace with
    found = ul.window(facts, man.metric_file("window_tick_ms")["args"])
    assert found[1] is None and len(found[0]) == 2


def test_the_metrics_of_one_ledger_name_the_same_tables(man):
    """The tables are printed once a run from whichever metric is read
    first: what they are made from is the same in every file of a ledger."""
    shared = ("span", "ordinal", "from", "call", "flags", "join")
    by_kind = {}
    for name in SERVE + INPUT:
        args = man.metric_file(name)["args"]
        by_kind.setdefault(args["ledger"], []).append(
            {k: args.get(k) for k in shared})
    assert {k: len(v) for k, v in by_kind.items()} == {
        "serve_tick": len(SERVE), "input_wait": len(INPUT)}
    for kind, specs in by_kind.items():
        assert all(s == specs[0] for s in specs), kind
        assert specs[0]["from"] in man.traffic(
            "decode_backlog" if kind == "serve_tick" else "train_steps")


def test_the_cells_list_their_window_metrics(man):
    per_layer = {m["name"]: m for m in man.doc["per_layer"]}
    for name in SERVE + INPUT:
        assert per_layer[name]["source"] == "program_span"
        spec = man.metric_file(name)
        assert spec["reducer"] == "unit_ledger"
        assert spec["args"]["what"] in ul.WHAT
        assert spec["layer"] == per_layer[name]["layer"]
        assert spec["moves"] == per_layer[name]["moves"]
    for w in man.doc["workloads"]:
        kind = man.traffic(w["traffic"])["kind"]
        mine = {m["name"] for m in man.metrics_for(w["name"], "per_layer")}
        if kind == "decode_backlog":
            assert set(SERVE) <= mine and not set(INPUT) & mine
        elif w["name"] != "standin_train":
            assert set(INPUT) <= mine and not set(SERVE) & mine

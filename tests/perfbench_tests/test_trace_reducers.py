"""The reduction from a profiler trace to numbers: interval arithmetic on
made-up events, then every serving reducer on a trace recorded on the chip
(two decode ticks of ``gpt2-xl_serve_backlog``, my chip run, PR 24, with the
program's spans, scopes and kernel names; cut to what the reducers read by
``perfbench.cut_program_fixture``)."""

import os

import pytest

from perfbench import device, program_trace
from perfbench import trace as pt
from perfbench.manifest import HERE, Manifest

pytestmark = pytest.mark.tier1

FIXTURE = os.path.join(HERE, "fixtures",
                       "serve_backlog_2ticks_program.xplane.pb.gz")


def ev(name, a, b):
    return pt.Event(name, a, b)


def test_union_clip_subtract():
    ivs = pt.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9.5)])
    assert ivs == [(0, 3), (5, 7), (9, 9.5)]
    assert pt.total(ivs) == 5.5
    assert pt.clip([ev("a", -1, 1), ev("b", 4, 12), ev("c", 20, 21)],
                   (0, 10)) == [(0, 1), (4, 10)]
    assert pt.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert pt.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == [(0, 0.5), (2.5, 3)]
    assert pt.subtract([(0, 1)], []) == [(0, 1)]


def test_op_key_folds_instances_and_marks_kernels():
    hlo = ('%block_33.1 = bf16[32,25,1,64]{3,2,1,0:T(2,128)(2,1)S(1)} '
           'custom-call(s32[32,64]{1,0} %copy-done.131), '
           'custom_call_target="tpu_custom_call"')
    assert pt.op_key(hlo) == "block:mosaic:bf16[32,25,1,64]"
    assert pt.op_key('%copy.17849 = bf16[25,2049,16,64]{3,2,1,0} '
                     'copy(bf16[25,2049,16,64]{3,2,1,0} %p.1)') == \
        "copy:copy:bf16[25,2049,16,64]"
    assert pt.op_key("fusion.123") == "fusion"
    assert pt.op_key("jit_step_impl(917427)") == "jit_step_impl(917427)"


def synthetic():
    ops = [ev("%all-reduce.1 = f32[8] all-reduce(f32[8] %x)", 1.0, 3.0),
           ev("%fusion.1 = f32[8] fusion(f32[8] %x)", 0.0, 2.0),
           ev("%fusion.2 = f32[8] fusion(f32[8] %x)", 4.0, 5.0)]
    mods = [ev("jit_train(1)", 0.0, 5.0)]
    spans = [ev("pb:window", 0.0, 6.0), ev("pb:dispatch", 0.0, 0.5),
             ev("pb:wait_step", 3.2, 6.0), ev("pb:next_batch", 5.2, 5.4)]
    return pt.Trace({0: pt.DeviceTrace(ops, mods)}, spans)


def test_busy_idle_and_gap_attribution_on_made_up_events():
    t = synthetic()
    busy, window = pt.busy_seconds(t)
    assert (busy, window) == (4.0, 6.0)
    gaps = dict((k, v) for k, v in pt.idle_gaps(t))
    # 3..4 and 5..6 are idle; both midpoints lie in wait_step, none in the
    # shorter next_batch span
    assert gaps == {"wait_step": 2.0}
    top = pt.top_ops(t)
    assert top[0] == ["fusion:fusion:f32[8]", 3.0]
    assert top[1] == ["all-reduce:all-reduce:f32[8]", 2.0]


def test_reducers_on_made_up_events():
    man = Manifest()
    facts = {"trace": synthetic(), "traced_units": 2, "values": {}}
    assert man.reducer("device_idle").reduce(facts, {}) == \
        pytest.approx(100 * 2 / 6)
    assert man.reducer("host_per_unit").reduce(facts, {}) == \
        pytest.approx(1e3 * 2.0 / 2)
    # the collective runs alone only from 2 to 3
    assert man.reducer("exposed_collective").reduce(
        facts, {"pattern": "all-reduce"}) == pytest.approx(1e3 * 1.0 / 2)
    assert man.reducer("module_time").reduce(
        facts, {"pattern": "jit_train", "per": "unit"}) == pytest.approx(2500)
    for name in ("device_idle", "host_per_unit", "module_time",
                 "exposed_collective", "kernel_roofline"):
        assert man.reducer(name).reduce(
            {"trace": None, "values": {}}, {"pattern": "x", "per": "unit",
                                            "kernels": []}) is None


@pytest.fixture(scope="module")
def recorded():
    return pt.load(FIXTURE)


def test_recorded_trace_has_what_the_reducers_read(recorded):
    assert sorted(recorded.devices) == [0]
    d = recorded.devices[0]
    assert len(d.modules) == 2 and len(d.ops) > 5000
    assert all("step_impl" in m.name for m in d.modules)
    names = [s.name for s in recorded.spans]
    assert names.count("pb:tick") == 2 and names.count("pb:window") == 1
    busy, window = pt.busy_seconds(recorded)
    assert 0 < busy < window < 1.5
    # the device was busy ~99% of a decode tick (ledger PR 22: 99.1%)
    assert 0.97 < busy / window < 1.0


def test_recorded_trace_breakdown_names_the_two_heavy_operations(recorded):
    top = pt.top_ops(recorded, 3)
    assert top[0][0] == "paged_decode:mosaic:bf16[32,25,1,64]"
    assert top[1][0] == "copy:copy:bf16[25,2049,16,64]"
    assert top[0][1] + top[1][1] > 0.98 * pt.busy_seconds(recorded)[0]
    gaps = pt.idle_gaps(recorded)
    assert gaps and gaps[0][0] == "tick"
    assert len(top) <= 10 and len(gaps) <= 10


def test_every_backlog_layer_metric_reads_the_recorded_trace(recorded):
    man = Manifest()
    facts = {"trace": recorded, "traced_units": 2, "manifest": man,
             "program_trace": program_trace.load(FIXTURE),
             "values": {"occupancy": 100.0},
             "peaks": device.peaks("TPU v5 lite"),
             "config": man.config("gpt2-xl"),
             "traffic": man.traffic("decode_backlog"),
             "traced_context_tokens": 2 * 32 * 300.0,
             "memory_peak_bytes": 14.0e9}
    got = {}
    for m in man.metrics_for("gpt2-xl_serve_backlog", "per_layer"):
        spec = man.metric_file(m["name"])
        got[m["name"]] = man.reducer(spec["reducer"]).reduce(
            facts, spec.get("args", {}))
    assert all(v is not None for v in got.values()), got
    assert got["paged_ms_tick"] == pytest.approx(651, rel=0.01)
    assert 0 < got["host_ms_per_tick"] < 20
    assert got["prefill_dev_share"] == 0.0
    assert 0 < got["serve_device_idle_pct"] < 3
    assert got["serve_peak_hbm_gb"] == 14.0
    # 48 layers x 2 ticks x 9600 resident positions x 25 heads x 64 x 2 (K, V)
    # x 2 bytes over 819 GB/s, against 0.86 s of kernel time: under 1%
    need = 48 * 2 * (2 * 9600 * 25 * 64 * 2 + 2 * 32 * 25 * 64 * 2)
    kernel_s = pt.top_ops(recorded, 1)[0][1]
    assert got["paged_roofline"] == pytest.approx(
        100 * need / 819e9 / kernel_s, rel=1e-6)
    assert got["paged_roofline"] < 100


MIXED = os.path.join(HERE, "fixtures",
                     "serve_backlog_2ticks_mixed_program.xplane.pb.gz")


@pytest.fixture(scope="module")
def mixed():
    """The first two ticks of the traced stretch (window ticks 1157 and
    1158; my chip run, PR 43, seed 2147490102): a chunk riding the decode
    step beside a call of its own, then a riding chunk alone — as
    ``traffic_gen.replay_backlog`` says they are."""
    return pt.load(MIXED), program_trace.load(MIXED)


def test_paged_ms_tick_is_the_ticks_main_program_whichever_it_is(
        recorded, mixed):
    man = Manifest()
    t, spans = mixed
    [d] = t.devices.values()
    runs = {}
    for m in d.modules:
        runs.setdefault(m.name.split("(")[0], []).append(m.dur)
    # no tick of this stretch ran the plain step: a pattern naming
    # step_impl alone (as until PR 43) reads nothing here
    assert {k: len(v) for k, v in runs.items()} == {
        "jit_mixed_impl": 2, "jit_prefill_impl": 1}

    def read(name, trace, **args):
        spec = man.metric_file(name)
        return man.reducer(spec["reducer"]).reduce(
            {"trace": trace, "traced_units": 2},
            dict(spec["args"], **args))

    assert read("paged_ms_tick", t, pattern="step_impl") == 0.0
    assert read("paged_ms_tick", t) == pytest.approx(
        1e3 * sum(runs["jit_mixed_impl"]) / 2)
    assert 16.5 < read("paged_ms_tick", t) < 18
    # ... and the older fixture's two plain steps still answer to it
    assert read("paged_ms_tick", recorded) == pytest.approx(651, rel=0.01)
    # prefill_dev_share stays the share of chunks that are calls of their own
    busy, _ = pt.busy_seconds(t)
    assert read("prefill_dev_share", t) == pytest.approx(
        100 * runs["jit_prefill_impl"][0] / busy)
    assert 10 < read("prefill_dev_share", t) < 20
    # the program's counts say the same of the two ticks
    chunks = [(s.stats["rode"], s.stats["tokens"], s.stats["final"])
              for s in spans.named("cmn_serve_prefill")]
    assert chunks == [(1, 10, 1), (0, 32, 0), (1, 32, 0)]
    assert [(s.stats["live"], s.stats["chunk_rows"])
            for s in spans.named("cmn_serve_decode")] == [(30, 10), (31, 32)]


def test_the_mixed_fixture_is_the_replays_ticks(mixed):
    from perfbench import traffic_gen as tg

    man = Manifest()
    tr = man.traffic("decode_backlog")
    chunk = man.config("gpt2-xl")["serve"]["prefill_chunk"]
    fill, ticks = tg.replay_backlog(tg.backlog_lengths(tr), tr["slots"],
                                    chunk, max_ticks=1300)
    spans = mixed[1]
    got = sorted(s.stats["tick"] for s in spans.named("cmn_serve_tick"))
    first = fill + tr["trace_from_tick"]
    assert got == [first, first + 1]
    want = ticks[first:first + 2]
    assert [(t.live, t.calls, t.rode) for t in want] == [
        (30, 2, 1), (31, 1, 1)]
    assert [s.stats["live"] for s in spans.named("cmn_serve_decode")] == \
        [t.live for t in want]
    assert len(spans.named("cmn_serve_prefill")) == sum(
        t.calls for t in want)

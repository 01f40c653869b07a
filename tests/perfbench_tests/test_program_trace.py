"""``perfbench.program_trace`` and the reducers built on it: made-up
traces with known answers first, then the two traces recorded on the chip
(``perfbench/fixtures/*_program.xplane.pb.gz``, cut by
``perfbench.cut_program_fixture`` from my chip runs, PR 24)."""

import os

import pytest

from perfbench import device
from perfbench import program_trace as pt
from perfbench import trace as ptrace
from perfbench.manifest import HERE, Manifest

pytestmark = pytest.mark.tier1

BWD = ("jit(train_step)/jit(main)/shard_map/loss_and_grad/"
       "transpose(jvp(TransformerLM))/loss_and_grad/jvp(TransformerLM)/"
       "checkpoint/")


@pytest.mark.parametrize("path,key,phase,step", [
    ("jit(step_impl)/TransformerLM/block_3/attn.paged/paged_decode/"
     "pallas_call", "attn.paged", "fwd", "other"),
    ("jit(step_impl)/TransformerLM/block_0/kv_write/scatter", "kv_write",
     "fwd", "other"),
    ("pools[0]['k']:", "unscoped", "fwd", "other"),
    ("", "unscoped", "fwd", "other"),
    ("jit(train_step)/jit(main)/shard_map/loss_and_grad/jvp(TransformerLM)/"
     "block_7/ffn/ff1/dot_general", "ffn", "fwd", "fwd"),
    (BWD + "block_7/ln2/reduce_sum", "norm", "bwd", "bwd"),
    (BWD + "rematted_computation/block_7/attn.flash/flash_fwd/pallas_call",
     "attn.flash", "remat", "remat"),
    ("jit(train_step)/jit(main)/shard_map/loss_and_grad/transpose(jvp(ce))/"
     "while/body/closed_call/mul", "ce", "bwd", "bwd"),
    ("jit(train_step)/jit(main)/shard_map/loss_and_grad/jvp(TransformerLM)/"
     "reduce_precision", "loss_and_grad", "fwd", "fwd"),
    ("jit(train_step)/jit(main)/shard_map/optimizer_update/mul",
     "optimizer_update", "fwd", "opt"),
    ("jit(train_step)/jit(main)/shard_map/cmn_allreduce_grads/psum",
     "cmn_allreduce_grads", "fwd", "allreduce"),
    ("jit(train_step)/jit(main)/shard_map/loss_and_grad/jvp(TransformerLM)/"
     "block_1/ffn/moe.experts/exd,edf->exf/dot_general", "moe.experts",
     "fwd", "fwd"),
    ("jit(f)/reduce_sum/force", "unscoped", "fwd", "other"),  # no 'ce' here
])
def test_a_scope_path_gives_its_row_its_phase_and_its_place_in_the_step(
        path, key, phase, step):
    assert pt.scope_key(path) == key
    assert pt.phase_of(path) == phase
    assert pt.step_phase(path) == step


SHIFT = ("jit(train_step)/jit(main)/shard_map/loss_and_grad/jvp(GatedLM)/"
         "block_1/mix.shift/")


@pytest.mark.parametrize("path,extra,key", [
    (SHIFT + "mul", (), "loss_and_grad"),  # a scope the vocabulary lacks
    (SHIFT + "mul", ("mix.shift",), "mix.shift"),
    (SHIFT + "g/dot_general", ("mix.shift",), "mix.shift"),
    # innermost still decides, and a shipped token keeps its row
    (SHIFT + "ffn/dot_general", ("mix.shift",), "ffn"),
    (BWD + "block_7/ln2/reduce_sum", ("mix.shift",), "norm"),
    ("jit(f)/block_1/mix.shift/mul", ("ffn", "mix.shift"), "mix.shift"),
    ("jit(f)/block_1/remix.shifted/mul", ("mix.shift",), "unscoped"),
    ("jit(f)/block_1/mix.shift/mul", (), "unscoped"),
])
def test_a_configurations_scopes_extend_the_vocabulary(path, extra, key):
    assert pt.scope_key(path, extra) == key


def test_a_token_is_a_whole_word_of_the_path():
    assert pt.token_regex("ce").search("loss_and_grad/jvp(ce)/while")
    assert not pt.token_regex("ce").search("jit(f)/reduce/slice")
    assert pt.token_regex(r"attn\.\w+").search("block_0/attn.flash/mul")
    assert not pt.token_regex("ffn").search("block_0/my_ffn2/mul")


def span(name, a, b, thread=0, **stats):
    return pt.Span(name, a, b, dict(stats), thread)


def made_up():
    """Two ticks of one second.  Device: busy 0.1-0.5 (kv_write 0.1-0.2,
    attention 0.2-0.45, an operation with no scope 0.45-0.5) and
    1.1-1.6 (attention).  Host: build, dispatch, readback, emit per tick;
    the first tick's emit has two children that overlap each other."""
    spans = [
        span("pb:window", 0.0, 2.0),
        span("cmn_serve_tick", 0.0, 0.9, iter=0),
        span("cmn_serve_decode", 0.05, 0.85, live=2, kv_blocks_resident=6,
             kv_blocks_grid=16, table_width=8),
        span("cmn_serve_build", 0.05, 0.1),
        span("cmn_engine_readback", 0.12, 0.52),
        span("cmn_serve_emit", 0.55, 0.85, tokens=2, retired=0),
        span("cmn_child_a", 0.6, 0.7),
        span("cmn_child_b", 0.65, 0.75),  # overlaps child a
        span("cmn_serve_tick", 1.0, 1.95, iter=1),
        span("cmn_serve_decode", 1.02, 1.9, live=2, kv_blocks_resident=8,
             kv_blocks_grid=16, table_width=8),
        span("cmn_serve_build", 1.02, 1.1),
        span("cmn_engine_readback", 1.12, 1.62),
        span("cmn_serve_emit", 1.65, 1.9, tokens=2, retired=1),
        span("cmn_other_thread", 0.0, 2.0, thread=1),
    ]
    pt._nest(spans)
    S = "jit(step_impl)/TransformerLM/block_0/"
    ops = [pt.DeviceEvent("%fusion.1 = bf16[8] fusion()", 0.1, 0.2,
                          S + "kv_write/scatter"),
           pt.DeviceEvent("%paged_decode.1 = bf16[8] custom-call()", 0.2,
                          0.45, S + "attn.paged/paged_decode/pallas_call"),
           pt.DeviceEvent("%copy.9 = bf16[8] copy()", 0.45, 0.5,
                          "pools[0]['k']:"),
           pt.DeviceEvent("%paged_decode.1 = bf16[8] custom-call()", 1.1,
                          1.6, S + "attn.paged/paged_decode/pallas_call")]
    prog = pt.ProgramTrace("made-up", spans, {0: ops})
    trace = ptrace.Trace(
        {0: ptrace.DeviceTrace([ptrace.Event(e.name, e.start, e.end)
                                for e in ops], [])},
        [ptrace.Event("pb:window", 0.0, 2.0)])
    return {"program_trace": prog, "trace": trace, "traced_units": 2,
            "values": {}}


def reducer(name):
    return Manifest().reducer(name).reduce


def test_spans_nest_by_containment_thread_by_thread():
    t = made_up()["program_trace"]
    by = {(s.name, s.start): s for s in t.spans}

    def parent(name, a):
        s = by[(name, a)]
        return t.spans[s.parent].name if s.parent >= 0 else None

    assert parent("cmn_serve_tick", 0.0) == "pb:window"
    assert parent("cmn_serve_build", 0.05) == "cmn_serve_decode"
    assert parent("cmn_child_b", 0.65) == "cmn_serve_emit"  # not child a
    assert parent("cmn_other_thread", 0.0) is None
    assert parent("cmn_serve_emit", 1.65) == "cmn_serve_decode"


def test_self_time_takes_overlapping_children_once():
    facts = made_up()
    # emit: (0.3 - the children's union 0.6..0.75 = 0.15) + 0.25, two ticks
    assert reducer("span_self_time")(
        facts, {"span": "cmn_serve_emit", "per": "unit"}) == \
        pytest.approx(1e3 * (0.15 + 0.25) / 2)
    # decode minus build, readback and emit
    assert reducer("span_self_time")(
        facts, {"span": "cmn_serve_decode", "per": "unit"}) == \
        pytest.approx(1e3 * ((0.8 - 0.05 - 0.4 - 0.3)
                             + (0.88 - 0.08 - 0.5 - 0.25)) / 2)
    assert reducer("span_self_time")(
        facts, {"span": "cmn_serve_tick", "per": "unit",
                "where": {"iter": 1}}) == pytest.approx(1e3 * 0.07 / 2)
    assert reducer("span_self_time")(
        facts, {"span": "cmn_absent", "per": "unit"}) is None


def test_stat_ratio_sums_before_it_divides():
    facts = made_up()
    assert reducer("span_stat_ratio")(
        facts, {"span": "cmn_serve_decode", "num": "kv_blocks_resident",
                "den": "kv_blocks_grid"}) == pytest.approx(100 * 14 / 32)
    assert reducer("span_stat_ratio")(
        facts, {"span": "cmn_serve_decode", "num": "kv_blocks_resident",
                "den": "no_such_count"}) is None


def test_an_idle_gap_belongs_to_the_innermost_span_over_its_middle():
    facts = made_up()
    table = Manifest().reducer("idle_in_span").idle_by_span(
        facts["program_trace"], 2)
    # gaps: 0-0.1 (middle 0.05: build starts there, the shortest cover),
    # 0.5-1.1 (middle 0.8: emit), 1.6-2.0 (middle 1.8: emit)
    assert table == {"cmn_serve_emit": pytest.approx(1e3 * 1.0 / 2),
                     "cmn_serve_build": pytest.approx(1e3 * 0.1 / 2)}
    assert reducer("idle_in_span")(facts, {"span": "cmn_serve_emit"}) == \
        pytest.approx(500.0)
    assert reducer("idle_in_span")(
        facts, {"span": "cmn_engine_readback"}) == 0.0  # there, never idle
    assert reducer("idle_in_span")(facts, {"span": "cmn_absent"}) is None
    # a gap whose middle no program span covers is nobody's
    t = facts["program_trace"]
    only = pt.ProgramTrace("x", [s for s in t.spans if s.name in (
        "pb:window", "cmn_serve_build")], t.devices)
    assert Manifest().reducer("idle_in_span").idle_by_span(only, 2) == {
        "outside": pytest.approx(500.0),
        "cmn_serve_build": pytest.approx(50.0)}


def test_scope_time_by_scope_by_row_and_the_unscoped_rest():
    facts = made_up()
    scope_time = reducer("scope_time")
    assert scope_time(facts, {"scope": r"attn\.paged", "per": "unit"}) == \
        pytest.approx(1e3 * 0.75 / 2)
    assert scope_time(facts, {"scope": "kv_write", "per": "busy"}) == \
        pytest.approx(100 * 0.1 / 0.9)
    assert scope_time(facts, {"key": "unscoped", "per": "busy"}) == \
        pytest.approx(100 * 0.05 / 0.9)
    assert scope_time(facts, {"scope": "ffn", "per": "unit"}) is None
    assert scope_time(facts, {"scope": "kv_write", "phase": "bwd",
                              "per": "unit"}) is None
    table = pt.by_scope(facts["program_trace"], 2)
    assert pt.busy_seconds(facts["program_trace"]) == pytest.approx(
        ptrace.busy_seconds(facts["trace"])[0])
    assert table["busy_ms"] == pytest.approx(450.0)
    assert table["sum_ms"] == pytest.approx(450.0)
    assert table["nested_ms"] == pytest.approx(0.0, abs=1e-9)
    assert list(table["rows"]) == ["attn.paged", "kv_write", "unscoped"]
    assert table["ops"]["copy:copy:bf16[8]"] == {
        "unscoped/fwd": pytest.approx(25.0)}


def test_an_operation_under_a_configurations_scope_leaves_unscoped(
        with_standin):
    """``configs/<name>.json`` may list ``"scopes"``, the tokens its block
    names device work by; the stand-in lists one.  Without the key the
    operation is booked as missing instrumentation."""
    standin = with_standin.config("gated-standin")
    assert standin["scopes"] == ["mix.shift"]
    facts = made_up()
    ops = facts["program_trace"].devices[0]
    ops.append(pt.DeviceEvent("%fusion.7 = f32[8] fusion()", 1.6, 1.7,
                              "jit(step_impl)/GatedLM/block_1/mix.shift/mul"))
    scope_time = reducer("scope_time")
    unscoped = {"key": "unscoped", "per": "busy"}
    assert scope_time(facts, unscoped) == pytest.approx(100 * 0.15 / 1.0)
    assert scope_time(dict(facts, config={"model": {}}), unscoped) == \
        pytest.approx(100 * 0.15 / 1.0)
    mine = dict(facts, config=standin)
    assert pt.scopes_of(mine) == ("mix.shift",)
    assert scope_time(mine, unscoped) == pytest.approx(100 * 0.05 / 1.0)
    assert scope_time(mine, {"key": "mix.shift", "per": "unit"}) == \
        pytest.approx(1e3 * 0.1 / 2)
    rows = pt.by_scope(facts["program_trace"], 2, ("mix.shift",))["rows"]
    assert set(rows) == {"attn.paged", "kv_write", "mix.shift", "unscoped"}
    assert set(pt.by_scope(facts["program_trace"], 2)["rows"]) == {
        "attn.paged", "kv_write", "unscoped"}


def test_with_nothing_of_the_program_in_the_trace_every_reducer_is_silent():
    """The parent of the PR that added spans and scopes; an untraced run."""
    facts = made_up()
    bare = pt.ProgramTrace("bare", [pt.Span("pb:window", 0.0, 2.0)],
                           facts["program_trace"].devices)
    for f in (dict(facts, program_trace=bare),
              {"trace": None, "values": {}, "traced_units": 2}):
        assert reducer("span_self_time")(
            f, {"span": "cmn_serve_emit", "per": "unit"}) is None
        assert reducer("span_stat_ratio")(
            f, {"span": "cmn_serve_decode", "num": "kv_blocks_resident",
                "den": "kv_blocks_grid"}) is None
        assert reducer("idle_in_span")(f, {"span": "cmn_serve_emit"}) is None
    assert reducer("scope_time")(
        {"trace": None, "values": {}, "traced_units": 2},
        {"scope": "ffn", "per": "unit"}) is None


def test_the_scope_is_read_from_the_event_metadata_of_an_xspace(tmp_path):
    """Source (a): the HLO ``op_name`` is the ``tf_op`` stat of the
    *metadata* of a device event — what ``ProfileData`` drops — and the
    program's counts are stats of the host events themselves."""
    pb = pt._xplane_pb2()
    if pb is None:
        pytest.skip("no xplane_pb2 in this installation")
    space = pb.XSpace()
    dev = space.planes.add(name="/device:TPU:0", id=1)
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = "jit(f)/block_0/ffn/ff1/dot_general"
    md = dev.event_metadata[7]
    md.id, md.name = 7, "%fusion.3 = bf16[8] fusion()"
    md.stats.add(metadata_id=1, ref_value=2)
    md = dev.event_metadata[8]
    md.id, md.name = 8, "%copy.1 = bf16[8] copy()"
    md.stats.add(metadata_id=1, str_value="pools[0]['k']:")
    line = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    line.events.add(metadata_id=7, offset_ps=2_000_000, duration_ps=500_000)
    line.events.add(metadata_id=8, offset_ps=3_000_000, duration_ps=250_000)
    host = space.planes.add(name="/host:CPU", id=2)
    host.stat_metadata[1].name = "live"
    host.stat_metadata[2].name = "program"
    host.stat_metadata[3].name = "decode_step"
    host.event_metadata[1].name = "cmn_serve_decode"
    host.event_metadata[2].name = "cmn_engine_dispatch"
    host.event_metadata[3].name = "not_ours"
    hl = host.lines.add(id=1, name="main", timestamp_ns=1000)
    e = hl.events.add(metadata_id=1, offset_ps=1_000_000,
                      duration_ps=4_000_000)
    e.stats.add(metadata_id=1, int64_value=3)
    e = hl.events.add(metadata_id=2, offset_ps=1_500_000,
                      duration_ps=1_000_000)
    e.stats.add(metadata_id=2, ref_value=3)
    hl.events.add(metadata_id=3, offset_ps=0, duration_ps=9_000_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    t = pt.load(str(path))
    assert pt.load(str(path)) is t  # parsed once per process
    [a, b] = t.devices[0]
    assert (a.scope, pt.scope_key(a.scope)) == (
        "jit(f)/block_0/ffn/ff1/dot_general", "ffn")
    assert (b.scope, pt.scope_key(b.scope)) == ("pools[0]['k']:", "unscoped")
    assert a.start == pytest.approx(1000e-9 + 2e-6)
    assert a.dur == pytest.approx(0.5e-6)
    assert [s.name for s in t.spans] == ["cmn_serve_decode",
                                         "cmn_engine_dispatch"]
    assert t.spans[0].stats == {"live": 3}
    assert t.spans[1].stats == {"program": "decode_step"}
    assert t.spans[1].parent == 0 and t.spans[0].children == [1]
    # the same clock as perfbench.trace
    same = ptrace.load(str(path))
    assert same.devices[0].ops[0].start == pytest.approx(a.start)


# ------------------------------------------------ recorded on the chip
BACKLOG = os.path.join(HERE, "fixtures",
                       "serve_backlog_2ticks_program.xplane.pb.gz")
TRAIN = os.path.join(HERE, "fixtures", "train_2steps_program.xplane.pb.gz")


#: sha256 of ``json.dumps(by_scope(trace, 2), sort_keys=True)``, read on the
#: parent of PR 27 (cdb41d9), before a configuration could add scopes
BY_SCOPE = {
    BACKLOG: "8472f4b002ee80aeaf6193b3b3dddc37f9485f12ec4b8244764b397229f2dd67",
    TRAIN: "9d2146ab6332b2955c81bba85b67a74312b5013c2dd213b4487b51340a7858e1",
}


@pytest.mark.parametrize("path", sorted(BY_SCOPE), ids=os.path.basename)
def test_without_a_scopes_key_the_by_scope_table_did_not_move(path):
    import hashlib
    import json

    t = pt.load(path)
    if t is None:
        pytest.skip("no xplane_pb2 in this installation")
    table = pt.by_scope(t, 2)
    assert hashlib.sha256(json.dumps(table, sort_keys=True).encode()) \
        .hexdigest() == BY_SCOPE[path]
    # nor does a scope that no operation of the trace carries
    assert pt.by_scope(t, 2, ("mix.shift",)) == table


def _metrics(cell, facts):
    man = Manifest()
    got = {}
    for m in man.metrics_for(cell, "per_layer"):
        spec = man.metric_file(m["name"])
        if spec["reducer"] in ("scope_time", "span_self_time",
                               "span_stat_ratio", "idle_in_span"):
            got[m["name"]] = man.reducer(spec["reducer"]).reduce(
                facts, spec.get("args", {}))
    return got


@pytest.fixture(scope="module")
def backlog():
    t = pt.load(BACKLOG)
    if t is None:
        pytest.skip("no xplane_pb2 in this installation")
    return t, ptrace.load(BACKLOG)


@pytest.fixture(scope="module")
def train():
    t = pt.load(TRAIN)
    if t is None:
        pytest.skip("no xplane_pb2 in this installation")
    return t, ptrace.load(TRAIN)


def test_both_readers_see_one_clock_one_window_and_one_busy_time(backlog,
                                                                 train):
    for t, old in (backlog, train):
        assert t.window == pytest.approx(old.window)
        # (that reader has nanoseconds, this one picoseconds)
        assert pt.busy_seconds(t) == pytest.approx(
            ptrace.busy_seconds(old)[0], rel=1e-4)
        assert sorted(t.devices) == sorted(old.devices)


def test_recorded_backlog_ticks_close_on_the_device_and_on_the_host(backlog):
    t, old = backlog
    table = pt.by_scope(t, 2)
    # by scope: the rows add up to the busy time
    assert table["sum_ms"] == pytest.approx(table["busy_ms"], rel=0.02)
    assert sum(r["all"] for r in table["rows"].values()) == pytest.approx(
        table["busy_ms"], rel=0.02)
    # the kernel is found by its scope and by its own name
    assert "attn.paged" in table["rows"] and "kv_write" in table["rows"]
    assert any(name.startswith("paged_decode:mosaic:")
               for name in table["ops"])
    # host: the program's tick spans are the benchmark's, to 1%
    ticks, pb = t.named("cmn_serve_tick"), t.named("pb:tick")
    assert len(ticks) == len(pb) == 2
    assert sum(s.dur for s in ticks) == pytest.approx(
        sum(s.dur for s in pb), rel=0.01)
    # outside the wait for the device, >= 90% of a tick is a named phase
    wait = sum(s.dur for s in t.named("cmn_engine_readback"))
    wall = sum(s.dur for s in ticks)
    unnamed = sum(t.self_seconds(s) for s in ticks + t.named(
        "cmn_serve_decode") + t.named("cmn_serve_prefill_round"))
    assert unnamed <= 0.10 * (wall - wait)


def test_recorded_backlog_counts_and_every_new_metric(backlog):
    t, _ = backlog
    decodes = t.named("cmn_serve_decode")
    assert len(decodes) == 2
    for s in decodes:
        assert s.stats["live"] == 32 and s.stats["table_width"] == 64
        assert s.stats["kv_blocks_grid"] == 32 * 64
        assert 32 <= s.stats["kv_blocks_resident"] <= s.stats["kv_blocks_grid"]
    got = _metrics("gpt2-xl_serve_backlog",
                   {"program_trace": t, "traced_units": 2})
    # at least these: a later PR may add a metric to the cell, and the
    # trace recorded here need not hold what that one reads
    for name in ("serve_kv_write_ms_tick", "serve_attn_ms_tick",
                 "serve_unscoped_pct",
                 "tick_build_ms", "tick_emit_ms", "tick_publish_ms",
                 "tick_readback_ms", "tick_idle_build_ms",
                 "tick_idle_emit_ms"):
        assert got.get(name) is not None, (name, got)
    # the share of the tables that holds a token, from the two counts (the
    # metric that priced it, paged_grid_useful_pct, went with PR 43: since
    # PR 31 neither read walks the whole table)
    assert reducer("span_stat_ratio")(
        {"program_trace": t}, {"span": "cmn_serve_decode",
                               "num": "kv_blocks_resident",
                               "den": "kv_blocks_grid"}) == pytest.approx(
        100.0 * sum(s.stats["kv_blocks_resident"] for s in decodes)
        / (2 * 32 * 64))
    assert got["serve_attn_ms_tick"] > 5 * got["serve_kv_write_ms_tick"] > 0
    # the whole-pool copies: a third under kv_write (XLA gives that one the
    # scatter's op_name), two thirds at the program's edge with no scope
    assert 20 < got["serve_unscoped_pct"] < 25
    assert 0 < got["tick_build_ms"] < 5 and 0 < got["tick_emit_ms"] < 5
    assert got["tick_readback_ms"] > 100  # the wait for the device


def test_recorded_train_steps_split_into_forward_remat_backward_optimizer(
        train):
    t, _ = train
    table = pt.by_scope(t, 2)
    ph = table["phases"]
    # own seconds: the chunked loss's ``while`` is not counted again for
    # its body (plain durations would add 40 ms a step)
    assert table["sum_ms"] == pytest.approx(table["busy_ms"], rel=1e-9)
    assert table["nested_ms"] == pytest.approx(40.4, abs=0.5)
    # the rest is XLA's own data movement (copy-done, slice-done), which
    # carries no op_name: 2.8% of the step
    assert ph["fwd"] + ph["remat"] + ph["bwd"] + ph["opt"] + ph["other"] == \
        pytest.approx(table["busy_ms"], rel=1e-9)
    assert ph["other"] == pytest.approx(table["rows"]["unscoped"]["all"])
    assert ph["other"] < 0.03 * table["busy_ms"]
    assert ph["bwd"] > ph["remat"] > 0 and ph["fwd"] > 0 and ph["opt"] > 0
    got = _metrics("sc2-3b_train_1chip",
                   {"program_trace": t, "traced_units": 2})
    for name in (  # at least these, as for the backlog cell
            "train_fwd_ms_step", "train_remat_ms_step", "train_bwd_ms_step",
            "train_opt_ms_step", "train_attn_ms_step", "train_ffn_ms_step",
            "train_norm_ms_step", "train_ce_ms_step", "train_unscoped_pct",
            "train_dispatch_self_ms_step"):
        assert got.get(name) is not None, (name, got)
    assert got["train_unscoped_pct"] <= 5
    for name in ("fwd", "remat", "bwd", "opt"):
        assert got[f"train_{name}_ms_step"] == pytest.approx(ph[name],
                                                             rel=1e-6)
    # one chip: no gradient all-reduce to find
    spec = Manifest().metric_file("train_allreduce_ms_step")
    assert Manifest().reducer("scope_time").reduce(
        {"program_trace": t, "traced_units": 2}, spec["args"]) is None
    # the flash kernels answer to their names
    names = {e.name.split(" ")[0].rstrip(".0123456789").lstrip("%")
             for e in t.devices[0] if "tpu_custom_call" in e.name}
    assert names == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}


def _kernel_events(man, metric, trace):
    """need -> the device events that the metric's patterns match."""
    return {k["need"]: ptrace.matching(trace.devices[0].ops, k["pattern"])
            for k in man.metric_file(metric)["args"]["kernels"]}


def test_the_rooflines_find_their_kernels_by_name(train, backlog):
    """Every Mosaic kernel of the two recorded traces is matched once, by
    the name its ``pallas_call`` gave it.  (By result type, as before PR
    27, ``flash_bwd_dq`` — a bf16 result — was booked as a third forward
    call of every layer, and its operations counted twice.)"""
    man = Manifest()
    old = train[1]
    found = _kernel_events(man, "flash_head_dim_roofline", old)
    mosaic = [e for e in old.devices[0].ops if "tpu_custom_call" in e.name]
    assert sorted(map(id, sum(found.values(), []))) == sorted(map(id, mosaic))
    assert all(e.name.startswith("%flash_fwd.")
               for e in found["flash_head_dim_forward"])
    # 2 steps x 30 layers: the forward and its recomputation; a backward
    # call is its ONE dQ launch, and the dK/dV launches (two a call in this
    # trace, recorded before PR 36; one since) add their time and no need
    assert len(found["flash_head_dim_forward"]) == 2 * 30 * 2
    assert len(found["flash_head_dim_backward"]) == 2 * 30
    assert all(e.name.startswith("%flash_bwd_dq.")
               for e in found["flash_head_dim_backward"])
    assert len(found["flash_head_dim_time_only"]) == 2 * 30 * 2
    assert all(e.name.startswith("%flash_bwd_dkv.")
               for e in found["flash_head_dim_time_only"])
    cfg = man.config("starcoder2-3b")
    assert "head_dim" not in cfg["model"]  # 3072 // 24, not stated
    got = man.reducer("kernel_roofline").reduce(
        {"trace": old, "manifest": man, "config": cfg,
         "peaks": device.peaks("TPU v5 lite")},
        man.metric_file("flash_head_dim_roofline")["args"])
    # 9 matmul units a layer and step (2 + 2 forward, 5 backward) of
    # 2 * 128 * T(T+1)/2 * 24 heads, over 197 TFLOP/s and the kernels' time
    need = 2 * 30 * 9 * 2.0 * 128 * (4096 * 4097 / 2) * 24
    assert got == pytest.approx(
        100 * need / 197e12 / sum(e.dur for e in mosaic), rel=1e-9)
    assert 40.9 < got < 41.1
    paged = _kernel_events(man, "paged_roofline", backlog[1])["paged_decode"]
    assert len(paged) == 2 * 48
    assert [e for e in backlog[1].devices[0].ops
            if "tpu_custom_call" in e.name] == paged
    # a second kernel in the step is nobody's calls
    other = ptrace.Event('%scan_chunk.3 = bf16[24,4096,128]{2,1,0} '
                         'custom-call(bf16[8] %x), '
                         'custom_call_target="tpu_custom_call"', 0.0, 1.0)
    for metric in ("flash_head_dim_roofline", "paged_roofline"):
        for k in man.metric_file(metric)["args"]["kernels"]:
            assert not ptrace.matching([other], k["pattern"]), k

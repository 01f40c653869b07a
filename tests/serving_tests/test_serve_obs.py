"""Serving-plane observability: request-lifecycle timeline + Perfetto
export, ``serve.mixed_ms`` attribution, SLO monitor wiring, the
``"serving"`` flight-record provider, and the exact 2-rank merge of every
``serve.*`` histogram through the existing aggregation path.

One contended module-scoped run (evictions guaranteed, multi-chunk
prefill guaranteed) feeds most assertions; later tests reuse its engine
(fresh schedulers share the compiled programs — the recompile guard must
hold under the full observability layer too).
"""

import json
from collections import defaultdict

import pytest

from chainermn_tpu.observability import MetricsRegistry, RequestTimeline
from chainermn_tpu.observability.aggregate import MetricsAggregator
from chainermn_tpu.observability.metrics import DEFAULT_MS_EDGES
from chainermn_tpu.observability.slo import SLOMonitor
from chainermn_tpu.serving import DecodeEngine, Request, Scheduler

pytestmark = [pytest.mark.tier1, pytest.mark.serving]


@pytest.fixture(scope="module")
def obs_run(make_model, tiny_params, prompts):
    """4 requests through 3 slots over a 7-allocatable-block pool (the
    eviction geometry), prompts up to 17 tokens over an 8-token prefill
    chunk (multi-chunk prefill => mixed iterations guaranteed), full
    observability on explicit objects."""
    model = make_model()
    eng = DecodeEngine(
        model, tiny_params, capacity=3, num_blocks=8, block_len=8,
        prefill_chunk=8,
    )
    reg = MetricsRegistry()
    timeline = RequestTimeline(capacity=4096)
    slo = SLOMonitor(registry=reg, window=64, min_samples=8,
                     tolerance=0.5, check_every=4)
    sched = Scheduler(eng, registry=reg, slo=slo, timeline=timeline)
    comps = sched.run([
        Request(id=i, prompt=prompts[i], max_new_tokens=14)
        for i in range(4)
    ])
    return eng, reg, timeline, slo, sched, comps


def test_lifecycle_events_complete_and_monotonic(obs_run):
    _, _, timeline, _, _, comps = obs_run
    evs = timeline.events()
    assert timeline.dropped == 0
    by_req = defaultdict(list)
    for e in evs:
        if e.req is not None:
            by_req[e.req].append(e)
    for rid in range(4):
        kinds = [e.kind for e in by_req[rid]]
        assert kinds[0] == "submit", kinds
        assert "admit" in kinds
        assert kinds[-1] == "retire", kinds
        ts = [e.t for e in by_req[rid]]
        assert ts == sorted(ts), f"req {rid} timestamps not monotonic"
        finals = [e for e in by_req[rid]
                  if e.kind == "prefill" and e.info["final"]]
        assert finals, f"req {rid} never finished a prefill"
    # Per-iteration decode events exist and carry the active slot->req
    # map (the exporter fans them out to slot tracks).
    dec = [e for e in evs if e.kind == "decode"]
    assert dec
    assert all(e.info["reqs"] for e in dec)
    assert all(e.dur_ms > 0 for e in dec)


def test_eviction_readmission_ordering(obs_run):
    _, _, timeline, _, _, comps = obs_run
    evicted = [c.id for c in comps if c.evictions > 0]
    assert evicted, "eviction geometry saw no evictions"
    for rid in evicted:
        evs = [e for e in timeline.events() if e.req == rid]
        kinds = [e.kind for e in evs]
        i_evict = kinds.index("evict")
        assert "admit" in kinds[:i_evict], "evicted before any admission"
        readmits = [e for e in evs[i_evict + 1:] if e.kind == "admit"]
        assert readmits, "eviction without a later readmission"
        assert readmits[0].t >= evs[i_evict].t
        assert readmits[0].info and readmits[0].info["readmit"] is True
        assert kinds[-1] == "retire"


def test_chrome_export_valid_and_structured(obs_run, tmp_path):
    _, _, _, _, sched, comps = obs_run
    path = sched.export_trace(str(tmp_path / "trace.json"))
    data = json.load(open(path))  # strict JSON or this raises
    evs = data["traceEvents"]
    assert isinstance(evs, list) and evs
    assert data["displayTimeUnit"] == "ms"
    for e in evs:
        assert "ph" in e and "pid" in e
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    # Evictions render as instant events; an evicted request has one
    # residency slice per admission.
    assert [e for e in evs if e["ph"] == "i" and e["name"] == "evict"]
    rid = [c.id for c in comps if c.evictions > 0][0]
    residencies = [e for e in evs
                   if e["ph"] == "X" and e["name"] == f"req {rid}"]
    assert len(residencies) >= 2
    # Queue + slot tracks are named.
    tracks = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "queue" in tracks
    assert any(t.startswith("slot ") for t in tracks)
    # Queue-wait slices precede the matching residency.
    q = [e for e in evs if e["ph"] == "X"
         and e["name"] == f"queue req {rid}"]
    assert q and min(e["ts"] for e in q) <= min(
        e["ts"] for e in residencies
    )


def test_mixed_vs_decode_attribution(obs_run):
    """The serve.decode_ms quirk fix: iterations that absorb un-synced
    prefill dispatches book to serve.mixed_ms, so decode p95 (and the
    SLO token stream) read only clean iterations."""
    _, reg, _, _, sched, _ = obs_run
    snap = reg.snapshot()
    mixed, dec = snap["serve.mixed_ms"], snap["serve.decode_ms"]
    assert tuple(mixed["edges"]) == tuple(DEFAULT_MS_EDGES)
    assert mixed["count"] > 0, (
        "multi-chunk prefill geometry produced no mixed iterations — "
        "the tag went dead"
    )
    assert dec["count"] > 0
    assert mixed["count"] + dec["count"] == sched._iterations
    assert snap["serve.slo.token_ms"]["count"] == dec["count"]


def test_slo_streams_wired(obs_run):
    _, reg, _, slo, _, comps = obs_run
    snap = reg.snapshot()
    # Exactly one TTFT and one queue-wait sample per request — evictions
    # and readmissions never double-book either.
    assert snap["serve.slo.ttft_ms"]["count"] == len(comps)
    assert snap["serve.slo.queue_wait_ms"]["count"] == len(comps)
    rep = slo.last_report
    assert set(rep) == {"ttft", "queue_wait", "token"}
    assert snap["serve.slo.token.p95_ms"]["value"] is not None
    # No faults injected => the drift detector stays quiet.
    assert snap["serve.slo.token.breaches"]["value"] == 0


def test_flight_provider_names_live_state(obs_run, prompts, tmp_path):
    from chainermn_tpu.observability import tracer
    from chainermn_tpu.observability.flight import FlightRecorder

    eng = obs_run[0]
    sched = Scheduler(eng, registry=MetricsRegistry())
    sched.submit(Request(id=7, prompt=prompts[0], max_new_tokens=4))
    sched.submit(Request(id=8, prompt=prompts[3], max_new_tokens=4))
    while sched._try_admit():
        pass
    sched._prefill_round()
    path = FlightRecorder(str(tmp_path), rank=0).record("sigusr1")
    entry = json.loads(open(path).read().splitlines()[-1])
    srv = entry["resilience"]["serving"]
    assert set(srv["in_flight_requests"]) == {7, 8}
    assert srv["queue_depth"] == 0
    live = [s for s in srv["slots"] if s is not None]
    assert {s["req"] for s in live} == {7, 8}
    assert all(s["blocks"] >= 1 for s in live)
    assert srv["engine"]["blocks_in_use"] >= 2
    assert 0.0 < srv["engine"]["block_occupancy"] <= 1.0
    assert srv["engine"]["decode_compiles"] == 1
    # The default timeline mirrors lifecycle spans into the process span
    # ring, so the flight record's span dump shows serving activity too.
    ops = [s["op"] for s in tracer().ring.snapshot()]
    assert "serve.admit" in ops
    # Drain (and drop the prefix trie's retained blocks) so the shared
    # engine's pool is clean for the next test.
    sched.run([])
    eng.drop_prefix_cache()
    assert eng.free_blocks() == eng.pool.num_blocks - 1


def test_flight_provider_releases_dropped_scheduler(obs_run, tmp_path):
    """The provider holds the scheduler via weakref: dropping the last
    strong reference must free it (and through it the engine's device
    pools), not pin it in the provider registry forever."""
    import gc

    from chainermn_tpu.observability.flight import FlightRecorder

    eng = obs_run[0]
    sched = Scheduler(eng, registry=MetricsRegistry())
    del sched
    gc.collect()
    path = FlightRecorder(str(tmp_path), rank=1).record("test")
    entry = json.loads(open(path).read().splitlines()[-1])
    assert entry["resilience"]["serving"] == {"released": True}


def test_request_timeline_bounded_o1():
    tl = RequestTimeline(capacity=4)
    for i in range(10):
        tl.record("decode", t=float(i))
    assert len(tl) == 4 and tl.dropped == 6
    assert [e.t for e in tl.events()] == [6.0, 7.0, 8.0, 9.0]


def test_two_rank_serve_merge_exact(obs_run, prompts, tmp_path):
    """serve.* histograms merge exactly through the existing rank-0
    aggregation path (bucketwise sums, same fixed edges)."""
    eng, reg_a = obs_run[0], obs_run[1]
    reg_b = MetricsRegistry()
    sched_b = Scheduler(eng, registry=reg_b)
    sched_b.run([
        Request(id=100 + i, prompt=prompts[i], max_new_tokens=5)
        for i in range(2)
    ])
    snap_a, snap_b = reg_a.snapshot(), reg_b.snapshot()

    class _Comm:
        rank, size = 0, 2

        def gather_obj(self, entry, root=0):
            return [{"rank": 0, "registry": snap_a},
                    {"rank": 1, "registry": snap_b}]

    agg = MetricsAggregator(comm=_Comm(), out_dir=str(tmp_path),
                            quantiles=(0.95,))
    line = agg.collect(1, {"rank": 0, "registry": snap_a})
    merged = line["merged"]
    assert merged["serve.tokens"]["value"] == (
        snap_a["serve.tokens"]["value"] + snap_b["serve.tokens"]["value"]
    )
    for h in ("serve.prefill_ms", "serve.decode_ms", "serve.mixed_ms",
              "serve.slo.token_ms", "serve.slo.ttft_ms"):
        assert merged[h]["counts"] == [
            x + y for x, y in zip(snap_a[h]["counts"],
                                  snap_b[h]["counts"])
        ], h
        assert merged[h]["count"] == (
            snap_a[h]["count"] + snap_b[h]["count"]
        )
        assert merged[h]["edges"] == list(DEFAULT_MS_EDGES)
    # The fleet p95 section rides the same line.
    assert line["quantiles"]["serve.decode_ms"]["p95"] is not None


def test_skew_fault_fires_drift_detector(obs_run, prompts, monkeypatch):
    """CMN_FAULT skew@serve_step stretches decode iterations from hit 17
    on; the SLO monitor calibrates on the clean prefix and must flag the
    drift (the quiet control is test_slo_streams_wired's zero-breach
    assertion on the unfaulted run)."""
    from chainermn_tpu.resilience import faults as faults_mod

    inj = faults_mod.FaultInjector(
        faults_mod.parse_fault_spec("skew@serve_step:17:25ms")
    )
    monkeypatch.setitem(faults_mod._process_injector, "built", True)
    monkeypatch.setitem(faults_mod._process_injector, "inj", inj)
    eng = obs_run[0]
    reg = MetricsRegistry()
    slo = SLOMonitor(registry=reg, window=32, min_samples=8,
                     tolerance=0.5, check_every=4)
    sched = Scheduler(eng, registry=reg, slo=slo)
    sched.run([Request(id=0, prompt=prompts[0], max_new_tokens=32)])
    snap = reg.snapshot()
    assert snap["serve.slo.token.breaches"]["value"] >= 1
    assert snap["serve.slo.p95_drift"]["value"] > 0.5
    rep = slo.last_report["token"]
    assert rep["breached"] is True and rep["calibrated"] is True
    # Host-side instrumentation + injection never recompiled the step.
    assert eng.decode_compiles == 1


def test_skew_fault_files_one_deduped_incident(obs_run, prompts,
                                               monkeypatch, tmp_path):
    """Incident plane (ISSUE 12): the same skew@serve_step fault that
    fires the drift detector must file exactly ONE debug bundle — the
    breach persists across every later evaluation, and latching +
    fingerprint dedupe keep a sustained breach from filling the disk —
    and its manifest names the firing rule and the correlated
    serve.slo.* signals.

    What the monitor sees of a step without the planted 25 ms is a steady
    2 ms, as in the unfaulted twin below and for its reason: in the
    driver's run of PR 41 (six xdist workers) the host's own jitter read a
    drift of 0.56 at iteration 12, five steps before the fault, and the
    bundle this test holds to "filed after the fault" was that one.  A
    step that carries the stretch is seen as the scheduler timed it."""
    import gc

    from chainermn_tpu.observability.incident import IncidentManager
    from chainermn_tpu.resilience import faults as faults_mod

    inj = faults_mod.FaultInjector(
        faults_mod.parse_fault_spec("skew@serve_step:17:25ms")
    )
    monkeypatch.setitem(faults_mod._process_injector, "built", True)
    monkeypatch.setitem(faults_mod._process_injector, "inj", inj)
    eng = obs_run[0]
    reg = MetricsRegistry()
    inc_dir = tmp_path / "incidents"
    mgr = IncidentManager(registry=reg, directory=str(inc_dir))

    class StretchOnlySLO(SLOMonitor):
        def observe(self, stream, value_ms):
            super().observe(stream, value_ms if value_ms >= 25.0 else 2.0)

    slo = StretchOnlySLO(registry=reg, window=32, min_samples=8,
                         tolerance=0.5, check_every=4)
    sched = Scheduler(eng, registry=reg, slo=slo, incidents=mgr)
    sched.run([Request(id=0, prompt=prompts[0], max_new_tokens=32)])
    bundles = sorted(p for p in inc_dir.iterdir()
                     if p.name.startswith("incident-"))
    assert len(bundles) == 1, [p.name for p in bundles]
    assert mgr.count == 1
    manifest = json.loads((bundles[0] / "manifest.json").read_text())
    assert manifest["rule"]["name"] == "slo_p95_drift"
    assert manifest["rule"]["metric"] == "serve.slo.p95_drift"
    assert manifest["severity"] == "warning"
    assert manifest["first_mover"] == "serving"
    assert manifest["signals"]["serve.slo.p95_drift"] > 0.5
    assert any(k.startswith("serve.slo.") for k in manifest["signals"])
    # The bundle's signal sections carry the scheduler's live state and
    # the newest SLO report (the weakref'd sources the scheduler wired).
    signals = json.loads((bundles[0] / "signals.json").read_text())
    assert signals["serving"]["iterations"] >= 17
    assert signals["slo"]["report"]["token"]["breached"] is True
    assert reg.snapshot()["incident.count"]["value"] == 1
    # Host-side watching + capture never recompiled the step.
    assert eng.decode_compiles == 1
    # Weakref discipline: dropping the scheduler releases its sections.
    del sched
    gc.collect()
    forced = mgr.file_incident("probe", severity="info")
    with open(forced["bundle"] + "/signals.json") as f:
        sig2 = json.load(f)
    assert sig2["serving"] == {"released": True}
    assert sig2["slo"] == {"released": True}


def test_unfaulted_twin_files_zero_incidents(obs_run, prompts, tmp_path):
    """The quiet control for the incident plane: the identical workload
    without the fault breaches nothing and files nothing.

    "Without the fault" is what this twin controls, so that is what it
    asserts on: the latencies its monitor sees come from a steady clock of
    the test's own (every observation reads 2 ms), not from wall-clock
    decode times — under six xdist workers on a loaded CPU those jitter by
    more than the 50% tolerance all by themselves and the twin filed a
    drift incident about the host it ran on.  Everything downstream of the
    observation is the real thing: the scheduler's check cadence, the
    baseline/window arithmetic, the incident manager's rule evaluation."""
    from chainermn_tpu.observability.incident import IncidentManager

    class SteadyClockSLO(SLOMonitor):
        seen = 0

        def observe(self, stream, value_ms):
            self.seen += 1
            super().observe(stream, 2.0)

    eng = obs_run[0]
    reg = MetricsRegistry()
    inc_dir = tmp_path / "incidents"
    mgr = IncidentManager(registry=reg, directory=str(inc_dir))
    slo = SteadyClockSLO(registry=reg, window=32, min_samples=8,
                         tolerance=0.5, check_every=4)
    sched = Scheduler(eng, registry=reg, slo=slo, incidents=mgr)
    sched.run([Request(id=1, prompt=prompts[0], max_new_tokens=32)])
    # the monitor was fed by the real loop and judged on its cadence
    assert slo.seen >= 32 and slo.last_report["token"]["breached"] is False
    assert mgr.count == 0 and mgr.dropped == 0
    assert not inc_dir.is_dir() or not any(inc_dir.iterdir())
    snap = reg.snapshot()
    assert snap["serve.slo.token.breaches"]["value"] == 0
    assert snap["incident.open"]["value"] == 0


def test_observability_off_disables_lifecycle_layer(obs_run):
    import chainermn_tpu.observability as obs

    eng = obs_run[0]
    obs.set_enabled(False)
    try:
        sched = Scheduler(eng)
        assert sched.timeline is None and sched.slo is None
        assert sched.memory is None and sched.incidents is None
        assert sched.export_trace("/tmp/unused_trace.json") is None
    finally:
        obs.set_enabled(None)


# ------------------------------------------------- device-memory plane
def test_memory_monitor_samples_kv_pool(obs_run):
    """The scheduler feeds the memory monitor on the SLO check cadence
    plus a closing drain sample: mem.* gauges carry the pool accounting,
    the timeline is non-empty, and the final sample shows the drained
    state (no live slots, trie pins only)."""
    eng, reg, _, _, sched, _ = obs_run
    snap = reg.snapshot()
    assert snap["mem.in_use_bytes"]["value"] > 0
    assert snap["mem.kv.used_blocks"]["value"] is not None
    assert 0.0 <= snap["mem.kv.occupancy"]["value"] <= 1.0
    assert 0.0 <= snap["mem.kv.fragmentation"]["value"] <= 1.0
    assert sched.memory is not None and len(sched.memory) >= 1
    kv = sched.memory.last_kv
    assert kv["bytes_per_block"] == eng.pool.bytes_per_block
    assert kv["live_slots"] == 0  # closing sample: drained
    assert kv["used_blocks"] == kv["cached_blocks"]  # only trie pins


def test_flight_record_from_serving_process_has_memory_section(
        obs_run, prompts, tmp_path):
    """Acceptance: a flight record taken from a serving process carries
    the ``"memory"`` provider section — HBM watermarks + the KV-pool
    sample of the engine that was serving."""
    from chainermn_tpu.observability.flight import FlightRecorder

    eng = obs_run[0]
    sched = Scheduler(eng, registry=MetricsRegistry())
    sched.run([Request(id=60, prompt=prompts[1], max_new_tokens=4)])
    path = FlightRecorder(str(tmp_path), rank=0).record("sigusr1")
    entry = json.loads(open(path).read().splitlines()[-1])
    mem = entry["resilience"]["memory"]
    assert mem["device"]["in_use_bytes"] > 0
    assert mem["device"]["source"] in ("device", "host_rss")
    assert mem["kv"]["num_blocks"] == eng.pool.num_blocks
    assert mem["kv"]["block_len"] == eng.block_len
    assert mem["timeline_samples"] >= 1


def test_serving_drain_zero_leak_baseline(obs_run):
    """Acceptance: after a full drain, the leak detector confirms the
    PR-7 zero-leak baseline — a prefix-cache gc returns EVERY allocatable
    block to the free list, and the gauge reads 0."""
    eng, reg, _, _, sched, _ = obs_run
    leaked = sched.memory.check_drained(eng)
    assert leaked == 0
    assert eng.free_blocks() == eng.pool.num_blocks - 1
    assert reg.snapshot()["mem.kv.leaked_blocks"]["value"] == 0
    # The post-gc resample reflects the empty pool.
    assert sched.memory.last_kv["used_blocks"] == 0


@pytest.mark.parametrize("kv_heads, a_step", [(2, 8), (4, 1)],
                         ids=["gqa", "mha"])
def test_decode_span_counts_the_kernels_loop_steps(make_model, kv_heads,
                                                   a_step, monkeypatch):
    """``cmn_serve_decode.kv_steps`` beside ``kv_blocks_resident``: the
    loop steps ONE layer's paged kernel takes this tick — a step folds
    ``blocks_a_step`` of a slot's resident blocks (eight at ``block_len``
    16 for grouped queries: Σ ceil(blocks / 8) over the live slots; one for
    the row body a ``G == 1`` model takes, where the two counts are
    equal).  Both are callables, evaluated only where a span is recorded:
    the stand-in below records every one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.ops.decode_attention import blocks_a_step
    from chainermn_tpu.serving import scheduler as sched_mod

    BL = 16
    model = make_model(max_len=320, n_kv_heads=kv_heads)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 12), jnp.int32))["params"]
    eng = DecodeEngine(model, params, capacity=4, num_blocks=64,
                       block_len=BL, prefill_chunk=32)
    assert eng.pool.blocks_a_step == a_step == blocks_a_step(
        BL, model.dtype, model.n_heads // kv_heads)
    seen, real = [], sched_mod._annotate

    class Recorded:
        def __init__(self, span):
            self.span = span

        def __enter__(self):
            self.span.__enter__()
            return self

        def __exit__(self, *exc):
            return self.span.__exit__(*exc)

        def __getattr__(self, name):
            return getattr(self.span, name)

        def set_metadata(self, **counts):
            seen.append(({k: v() if callable(v) else v
                          for k, v in counts.items()},
                         [s.pos for s in sched._slots
                          if s is not None and not s.prefilling]))
            self.span.set_metadata(**counts)

    def annotate(name, **kw):
        span = real(name, **kw)
        return Recorded(span) if name == "cmn_serve_decode" else span

    monkeypatch.setattr(sched_mod, "_annotate", annotate)
    rng = np.random.RandomState(3)
    sched = Scheduler(eng)
    sched.run([
        Request(id=i, prompt=rng.randint(1, 128, size=n).tolist(),
                max_new_tokens=m)
        for i, (n, m) in enumerate(((5, 40), (130, 30), (140, 12), (270, 6)))
    ])
    assert max(len(pos) for _, pos in seen) >= 3
    for st, pos in seen:
        blocks = [-(-(p + 1) // BL) for p in pos]
        assert st["live"] == len(pos)
        assert st["kv_blocks_resident"] == sum(blocks)
        assert st["kv_steps"] == sum(-(-b // a_step) for b in blocks)
    # a context of 271 positions is 17 blocks: three steps of eight
    assert max(st["kv_blocks_resident"] - st["kv_steps"]
               for st, _ in seen) >= (17 - 3 if a_step == 8 else 0)
    assert eng.kv_steps([4, 15, 16, 127, 128]) == (
        1 + 1 + 1 + 1 + 2 if a_step == 8 else 1 + 1 + 2 + 8 + 9)

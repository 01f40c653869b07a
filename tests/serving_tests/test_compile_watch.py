"""The engine's recompile guard, migrated onto the compile watcher
(ISSUE 11).

The old guard was a hand-rolled ``_cache_size()`` read; the watcher now
backs it with the same number PLUS the why: every compile carries the
triggering argument signature, a recompile emits a structured blame
diff, and the declared budgets (``decode_step <= 1``, ``cow <= 1``,
``prefill <= len(ladder)``) feed the ``compile.budget_exceeded`` gauge.
Pinned here:

* watcher-backed counts read IDENTICALLY to ``_cache_size()`` under
  slot churn with sharing + speculation on (the ISSUE 7 workload);
* the budget gauge stays 0 through the churn;
* an intentionally induced shape-change recompile on a live engine
  yields a blame record naming the changed axis and flips the gauge
  (on a private watch — the process gauge must stay clean);
* the serving scheduler publishes ``device.*`` roofline gauges for the
  engine's hot program on the check cadence.
"""

import numpy as np
import pytest

from chainermn_tpu.observability import device as odev
from chainermn_tpu.observability.metrics import MetricsRegistry
from chainermn_tpu.serving import DecodeEngine, Request, Scheduler

pytestmark = [pytest.mark.tier1, pytest.mark.serving]


@pytest.fixture(scope="module")
def violations_before():
    """The watch is the process's, and another file of this xdist worker
    may have gone over a budget on purpose before this one runs
    (``test_elastic.py``'s rolling deploy does): the gauge is held to
    what it read before this file's engine was built."""
    return odev.watch().budget_violations


@pytest.fixture(scope="module")
def churn_engine_run(make_model, tiny_params, prompts, violations_before):
    """Sharing + speculative engine over the churny 5-requests / 3-slots
    workload (the ISSUE 7 guard geometry), with a long enough tail that
    the scheduler crosses its device-publish cadence."""
    from chainermn_tpu.observability.slo import SLOMonitor

    model = make_model()
    eng = DecodeEngine(
        model, tiny_params, capacity=3, num_blocks=32, block_len=8,
        prefill_chunk=8, draft_model=model, draft_params=tiny_params,
        spec_k=3,
    )
    reg = MetricsRegistry()
    # check_every=4: the device publish rides the SLO/memory cadence,
    # and an ideal self-draft retires 12-token requests in ~3 rounds —
    # the default 16 would end the run before the first publish.
    sched = Scheduler(eng, registry=reg,
                      slo=SLOMonitor(registry=reg, check_every=4))
    comps = sched.run([
        Request(id=i, prompt=p, max_new_tokens=12)
        for i, p in enumerate(prompts)
    ])
    return eng, sched, reg, comps


def test_watcher_counts_identical_to_cache_size(churn_engine_run):
    """The back-compat contract: every ``*_compiles`` property reads the
    SAME value through the watcher as the raw jit cache reports — under
    slot churn with sharing + spec on."""
    eng, _, _, comps = churn_engine_run
    assert len(comps) == 5
    for wf, prop in ((eng._spec, eng.decode_compiles),
                     (eng._prefill, eng.prefill_compiles),
                     (eng._cow, eng.cow_compiles)):
        assert isinstance(wf, odev.WatchedFunction)
        assert wf.compiles == wf._fn._cache_size() == prop
    assert eng.decode_compiles == 1  # the one-compile contract held
    assert eng.verify_compiles == 1
    assert eng.prefill_compiles == 1
    assert eng.cow_compiles <= 1
    # The plain step exists but was never dispatched (spec_round IS the
    # hot loop).
    assert eng._step.compiles == 0


def test_budgets_hold_and_gauge_reads_zero(churn_engine_run,
                                           violations_before):
    eng, _, _, _ = churn_engine_run
    for wf in (eng._step, eng._spec, eng._prefill, eng._cow):
        assert not wf.over_budget, wf.program
    assert eng._prefill.budget == len(eng.prefill_ladder)
    # Process-level accounting: nothing in this file ever exceeded a
    # declared budget (induced-recompile tests run on private watches).
    w = odev.watch()
    assert w.budget_violations == violations_before
    assert "compile_over_budget" not in eng.stats()
    sec = w.flight_section()
    by_name = {}
    for p in sec["programs"]:
        by_name.setdefault(p["program"], []).append(p)
    assert any(p["compiles"] == 1 and p["budget"] == 1
               for p in by_name.get("spec_round", ()))


def test_scheduler_publishes_device_roofline(churn_engine_run):
    """The serving scheduler's device plane: ``device.spec_round.*``
    gauges landed in the scheduler's registry at the check cadence
    (achieved TFLOP/s + arithmetic intensity always; MFU needs a peak
    table entry, absent on CPU)."""
    eng, sched, reg, _ = churn_engine_run
    snap = reg.snapshot()
    assert snap["device.spec_round.tflops"]["value"] > 0
    assert snap["device.spec_round.ai"]["value"] > 0
    # The cost model the gauges derive from is the watcher's capture.
    cost = eng.hot_program.cost_analysis()
    assert cost and cost["flops"] > 0


def test_induced_recompile_blames_axis_and_flips_gauge(
    make_model, tiny_params, monkeypatch
):
    """Drive a REAL engine's decode step with a wrong-shaped control
    vector: the watcher must record the recompile, name the changed
    axis in the blame diff, and flip ``compile.budget_exceeded`` — on a
    private watch/registry so the process-wide gauge stays pinned at 0
    for the tests above."""
    reg = MetricsRegistry()
    priv = odev.CompileWatch(registry=reg)
    monkeypatch.setattr(odev, "_watch", priv)
    try:
        eng = DecodeEngine(
            make_model(), tiny_params, capacity=2, num_blocks=8,
            block_len=8, prefill_chunk=8, prefix_cache=False,
        )
    finally:
        monkeypatch.undo()
    S, M = eng.capacity, eng.max_blocks
    tokens = np.zeros(S, np.int32)
    pos = np.zeros(S, np.int32)
    active = np.zeros(S, bool)
    eng.step(tokens, pos, np.zeros((S, M), np.int32), active)
    assert eng.decode_compiles == 1
    assert reg.snapshot()["compile.budget_exceeded"]["value"] == 0
    # The induced churn: a wider block table (all-zero tail rows park on
    # reserved block 0, so the step still traces) — exactly the
    # shape-drift class the one-compile contract exists to catch.
    eng.step(tokens, pos, np.zeros((S, M + 1), np.int32), active)
    assert eng.decode_compiles == 2
    assert eng._step.over_budget
    assert reg.snapshot()["compile.budget_exceeded"]["value"] == 1
    blame = [r for r in priv.blames()
             if r["program"] == "decode_step"][-1]
    assert blame["budget_exceeded"] is True
    changed = [c for c in blame["diff"] if c.get("axes") == [1]]
    assert changed, blame["diff"]
    assert changed[0]["before"]["shape"] == [S, M]
    assert changed[0]["after"]["shape"] == [S, M + 1]
    assert eng.stats()["compile_over_budget"] == ["decode_step"]

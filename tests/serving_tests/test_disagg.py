"""Disaggregated prefill/decode serving (ISSUE 14): the KV-block
migration primitive and the role-split topology over an in-process
queue-pair comm (the PR-8 fleet-test rig's shape, packaged as
``serving.disagg.LocalComm``).

Covers the tentpole contracts tier-1:

* byte-identical KV round-trip through pack → framed send → recv →
  install (target and spec-draft pools alike);
* block-table rewrite against a COLLIDING destination allocator
  (same physical ids already owned by live destination work);
* shared/refcounted blocks migrating ONCE with no double-free;
* post-migration prefix-trie insertion giving a hit on the destination;
* the role-split acceptance: prefill role + decode role greedy
  token-identical to the single-engine oracle with prefix sharing AND
  speculation ON, ``decode_compiles == 1`` on the decode role under
  migration churn, and ZERO mixed iterations on its histograms;
* ``drop@migrate`` / torn-frame detection → :class:`MigrationError` +
  ``serve.migration.failed``, with the ``migration_failed`` default
  incident rule pinned (critical severity);
* preemption drain: every live slot and queued entry migrates to a
  peer, zero in-flight requests lost, completions greedy-identical to
  the unpreempted oracle (the real-SIGTERM 2-OS-rank acceptance lives
  in ``tests/multiprocess_tests/test_disagg_preempt.py``);
* the Router's role-aware dispatch (decode replicas take no fresh
  admissions).
"""

import numpy as np
import pytest

from chainermn_tpu.observability.metrics import MetricsRegistry
from chainermn_tpu.serving import (
    DecodeEngine,
    DecodeRole,
    LocalComm,
    MigrationError,
    MigrationTransport,
    PrefillRole,
    Request,
    Router,
    Scheduler,
    drain_all,
    serve_disaggregated,
)
from chainermn_tpu.serving import disagg as dz
from chainermn_tpu.serving.scheduler import _Clock

pytestmark = pytest.mark.tier1


def _engine(make_model, tiny_params, capacity=3, num_blocks=48, **kw):
    return DecodeEngine(
        make_model(), tiny_params, capacity=capacity,
        num_blocks=num_blocks, block_len=8, prefill_chunk=16, **kw,
    )


def _pair(make_model, tiny_params, **eng_kw):
    """A prefill/decode role pair over a 2-rank LocalComm on one clock,
    plus each side's registry."""
    pe = _engine(make_model, tiny_params, **eng_kw)
    de = _engine(make_model, tiny_params, **eng_kw)
    comm = LocalComm(2)
    clock = _Clock()
    regp, regd = MetricsRegistry(), MetricsRegistry()
    pr = PrefillRole(
        Scheduler(pe, registry=regp, clock=clock),
        MigrationTransport(comm.endpoint(0), registry=regp),
        decode_ranks=[1],
    )
    dr = DecodeRole(
        Scheduler(de, registry=regd, clock=clock),
        MigrationTransport(comm.endpoint(1), registry=regd),
        prefill_ranks=[0],
    )
    return pr, dr, regp, regd


def _prefill_until_ready(sched):
    """Tick admission+prefill (never decode) until every live slot
    finished its ladder; returns the live decode-ready slots."""
    for _ in range(64):
        while sched._try_admit():
            pass
        sched._prefill_round()
        live = [s for s in sched._slots if s is not None]
        if live and all(not s.prefilling for s in live):
            return live
    raise AssertionError("prefill never finished")


def _block_bytes(engine, block):
    data = engine.read_block(block)
    out = b""
    for pool in ("target", "draft"):
        if data[pool] is None:
            continue
        for layer in data[pool]:
            for name in sorted(layer):
                out += layer[name].tobytes()
    return out


# ----------------------------------------------------------- primitive
def test_migration_roundtrip_byte_identical(make_model, tiny_params,
                                            prompts):
    """pack → framed send_obj → recv → install: the destination's
    physical blocks re-read as EXACTLY the source bytes, and the
    ``serve.migration.*`` family accounts the move."""
    pr, dr, regp, regd = _pair(make_model, tiny_params)
    src, dst = pr.sched, dr.sched
    for i in range(2):
        src.submit(Request(id=i, prompt=prompts[i], max_new_tokens=8))
    slots = _prefill_until_ready(src)
    want = {
        s.entry.req.id: [_block_bytes(src.engine, b) for b in s.blocks]
        for s in slots
    }
    src_tables = {s.entry.req.id: list(s.blocks) for s in slots}
    n = dz.migrate_slots(src, pr.transport, 1, slots)
    assert n == 2
    frame = dr.transport.recv(0)
    installed, queued, rest = dz.install_payload(dst, frame["body"])
    assert (installed, queued, rest) == (2, 0, None)
    # Source side released its references; destination slots carry
    # REWRITTEN tables whose blocks hold byte-identical KV.
    for s in dst._slots:
        if s is None:
            continue
        rid = s.entry.req.id
        got = [_block_bytes(dst.engine, b) for b in s.blocks]
        assert got == want[rid]
        assert s.pos == len(s.text)
        assert not s.prefilling
    assert regp.peek("serve.migration.slots_migrated").value == 2
    assert regp.peek("serve.migration.bytes").value > 0
    assert regp.peek("serve.migration.migrate_ms").count == 1
    assert regp.peek("serve.migration.failed").value == 0
    # src_tables kept alive for flake triage readability
    assert set(src_tables) == set(want)


def test_table_rewrite_under_colliding_allocator(make_model, tiny_params,
                                                 prompts):
    """The destination allocator already owns the source's physical ids:
    the installer must map onto FRESH ids and leave the destination's
    existing blocks untouched."""
    pr, dr, _, _ = _pair(make_model, tiny_params)
    src, dst = pr.sched, dr.sched
    src.submit(Request(id=0, prompt=prompts[4], max_new_tokens=8))
    slots = _prefill_until_ready(src)
    src_ids = list(slots[0].blocks)
    # Pre-claim every id the source used (plus change) on the dest and
    # plant a sentinel pattern in one of them.
    held = dst.engine.alloc_blocks(max(src_ids) + 1)
    sentinel_block = src_ids[0]
    sent = dst.engine.read_block(sentinel_block)
    planted = {
        "target": [
            {n: np.full_like(a, 3) for n, a in layer.items()}
            for layer in sent["target"]
        ],
        "draft": None,
    }
    dst.engine.write_block(sentinel_block, planted)
    before = _block_bytes(dst.engine, sentinel_block)
    want = [_block_bytes(src.engine, b) for b in src_ids]
    dz.migrate_slots(src, pr.transport, 1, slots)
    install = dz.install_payload(dst, dr.transport.recv(0)["body"])
    assert install[0] == 1
    slot = next(s for s in dst._slots if s is not None)
    assert all(b not in held for b in slot.blocks), (slot.blocks, held)
    assert [_block_bytes(dst.engine, b) for b in slot.blocks] == want
    assert _block_bytes(dst.engine, sentinel_block) == before


def test_shared_blocks_migrate_once_without_double_free(make_model,
                                                        tiny_params):
    """Two slots sharing prefix blocks (refcounted) migrate in one
    payload: the shared physical block ships ONCE, lands as ONE
    destination block mapped into both tables via ``share``, and both
    retirements + a trie gc return the destination allocator to its
    construction baseline — no double-free, no leak."""
    rng = np.random.RandomState(7)
    base = rng.randint(1, 128, size=16).tolist()  # two full blocks
    p1 = base + rng.randint(1, 128, size=3).tolist()
    p2 = base + rng.randint(1, 128, size=4).tolist()
    pr, dr, _, _ = _pair(make_model, tiny_params)
    src, dst = pr.sched, dr.sched
    # Seed the source trie so both admissions MAP the shared prefix.
    src.run([Request(id=100, prompt=base + [5], max_new_tokens=1)])
    src.submit(Request(id=0, prompt=p1, max_new_tokens=8))
    src.submit(Request(id=1, prompt=p2, max_new_tokens=8))
    slots = _prefill_until_ready(src)
    shared = set(slots[0].blocks) & set(slots[1].blocks)
    assert shared, "prefix sharing never happened — test setup rotted"
    body = dz.pack_slots(src, slots)
    total_refs = sum(len(s.blocks) for s in slots)
    assert len(body["blocks"]) < total_refs  # deduped on the wire
    dz.migrate_slots(src, pr.transport, 1, slots)
    dz.install_payload(dst, dr.transport.recv(0)["body"])
    dslots = [s for s in dst._slots if s is not None]
    dshared = set(dslots[0].blocks) & set(dslots[1].blocks)
    assert len(dshared) == len(shared)
    for b in dshared:
        # Both slots + the trie insert hold it.
        assert dst.engine.pool.allocator.refcount(b) >= 2
    # Retire both on the destination, gc the trie: baseline exactly.
    dst.run([])
    assert len(dst.completions) == 2
    dst.engine.drop_prefix_cache()
    assert dst.engine.free_blocks() == dst.engine.pool.num_blocks - 1


def test_migrated_prefix_hits_destination_trie(make_model, tiny_params,
                                               prompts, oracle):
    """Hot-prefix sharing survives migration: after a slot lands on the
    destination, an identical prompt admitted THERE maps the migrated
    blocks instead of recomputing them."""
    pr, dr, _, _ = _pair(make_model, tiny_params)
    src, dst = pr.sched, dr.sched
    prompt = prompts[4]  # 17 tokens -> two full blocks cacheable
    src.submit(Request(id=0, prompt=prompt, max_new_tokens=4))
    slots = _prefill_until_ready(src)
    dz.migrate_slots(src, pr.transport, 1, slots)
    dz.install_payload(dst, dr.transport.recv(0)["body"])
    blocks, matched = dst.engine.prefix.match(prompt)
    assert matched >= 16 and blocks
    # And an actual admission on the destination uses it + still
    # produces the oracle's tokens.
    cs = dst.run([Request(id=1, prompt=prompt, max_new_tokens=4)])
    hit = next(c for c in cs if c.id == 1)
    assert hit.prefix_hit_tokens > 0
    model = make_model()
    assert hit.tokens == oracle(model, tiny_params, prompt, 4)


# ----------------------------------------------------------- role split
def test_role_split_oracle_with_sharing_and_spec(make_model, tiny_params,
                                                 oracle):
    """The acceptance pin: requests prefilled on a prefill role and
    decoded on a decode role are greedy token-identical to the
    single-engine oracle with prefix sharing + speculation ON; the
    decode role compiles its hot program exactly ONCE under migration
    churn, books ZERO mixed iterations, and the migration device
    programs stay one-variant."""
    draft = make_model(n_layers=1)
    import jax
    import jax.numpy as jnp

    dparams = draft.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 12), jnp.int32)
    )["params"]
    kw = dict(draft_model=draft, draft_params=dparams, spec_k=2,
              num_blocks=64)
    pr, dr, regp, regd = _pair(make_model, tiny_params, **kw)
    rng = np.random.RandomState(2)
    base = rng.randint(1, 128, size=12).tolist()
    reqs_p = [base + rng.randint(1, 128, size=3).tolist()
              for _ in range(4)]
    reqs_p += [rng.randint(1, 128, size=9).tolist() for _ in range(3)]
    reqs = [Request(id=i, prompt=p, max_new_tokens=7)
            for i, p in enumerate(reqs_p)]
    cs = serve_disaggregated(pr, dr, reqs)
    assert sorted(c.id for c in cs) == list(range(len(reqs)))
    model = make_model()
    for c in cs:
        assert c.tokens == oracle(model, tiny_params, reqs_p[c.id], 7), c.id
    de = dr.sched.engine
    assert de.decode_compiles == 1
    assert de.gather_compiles <= 1 and de.put_compiles == 1
    pe = pr.sched.engine
    assert pe.gather_compiles == 1
    # Clean decode role: every iteration is a clean decode iteration.
    mixed = regd.peek("serve.mixed_ms")
    assert (mixed.count if mixed is not None else 0) == 0
    assert regd.peek("serve.decode_ms").count > 0
    # The prefill role never decoded.
    dm = regp.peek("serve.decode_ms")
    assert (dm.count if dm is not None else 0) == 0
    assert regp.peek("serve.migration.slots_migrated").value == len(reqs)
    # Prefix sharing engaged on the prefill role (4 shared-template
    # prompts) — the feature was ON, not vacuously green.
    assert regp.peek("serve.prefix.hit_tokens").value > 0


def test_decode_role_defers_when_full_never_prefills(make_model,
                                                     tiny_params):
    """More in-flight work than decode slots: the decode role DEFERS
    surplus migration bodies host-side (the KV is already paid for)
    instead of re-prefilling them — its histograms stay clean and
    nothing is lost."""
    pr, dr, regp, regd = _pair(make_model, tiny_params, capacity=2,
                               num_blocks=64)
    rng = np.random.RandomState(3)
    reqs_p = [rng.randint(1, 128, size=int(n)).tolist()
              for n in rng.randint(4, 18, size=7)]
    reqs = [Request(id=i, prompt=p, max_new_tokens=9)
            for i, p in enumerate(reqs_p)]
    cs = serve_disaggregated(pr, dr, reqs)
    assert sorted(c.id for c in cs) == list(range(len(reqs)))
    pf = regd.peek("serve.prefill_ms")
    assert (pf.count if pf is not None else 0) == 0
    mixed = regd.peek("serve.mixed_ms")
    assert (mixed.count if mixed is not None else 0) == 0
    assert dr.sched.engine.decode_compiles == 1


# ----------------------------------------------------- fault + incident
def test_drop_migrate_fault_detected_and_counted(make_model, tiny_params,
                                                 prompts):
    """``CMN_FAULT=drop@migrate:1``: the first migration frame is lost
    on the wire; the receiver's sequence validation raises
    :class:`MigrationError` on the next frame and counts
    ``serve.migration.failed``."""
    from chainermn_tpu.resilience.faults import (
        FaultInjector,
        parse_fault_spec,
    )

    comm = LocalComm(2)
    reg0, reg1 = MetricsRegistry(), MetricsRegistry()
    inj = FaultInjector(parse_fault_spec("drop@migrate:1"))
    t0 = MigrationTransport(comm.endpoint(0), registry=reg0,
                            injector=inj)
    t1 = MigrationTransport(comm.endpoint(1), registry=reg1)
    eng = _engine(make_model, tiny_params)
    src = Scheduler(eng, registry=reg0)
    src.submit(Request(id=0, prompt=prompts[0], max_new_tokens=4))
    src.submit(Request(id=1, prompt=prompts[1], max_new_tokens=4))
    slots = _prefill_until_ready(src)
    dz.migrate_slots(src, t0, 1, slots[:1])   # frame 0: dropped
    dz.migrate_slots(src, t0, 1, slots[1:])   # frame 1: arrives
    with pytest.raises(MigrationError, match="dropped"):
        t1.recv(0)
    assert reg1.peek("serve.migration.failed").value == 1
    # The stream recovers: a third frame validates cleanly.
    src.submit(Request(id=2, prompt=prompts[2], max_new_tokens=4))
    slots = _prefill_until_ready(src)
    dz.migrate_slots(src, t0, 1, slots)
    assert t1.recv(0)["kind"] == "slots"


def test_decode_role_drain_includes_deferred(make_model, tiny_params,
                                             prompts, oracle):
    """A decode rank's preemption drain (``DecodeRole.drain``) forwards
    its DEFERRED migration backlog too — those bodies hold requests no
    other rank knows about, so skipping them would silently break the
    zero-loss contract.  The receiver is wired the way a real
    ``roles=[prefill, decode, decode]`` fleet is: rank 1's default
    drain peer is rank 2 (``drain_peer_from_env(1, 3, roles) == 2``),
    and rank 2 polls the drain through ``peer_ranks`` — NOT by listing
    the decode peer as a prefill source."""
    from chainermn_tpu.serving.scheduler import _Clock

    roles = ["prefill", "decode", "decode"]
    assert dz.drain_peer_from_env(1, 3, roles) == 2
    comm, clock = LocalComm(3), _Clock()
    regs = [MetricsRegistry() for _ in range(3)]
    tr = [
        MigrationTransport(comm.endpoint(i), registry=regs[i])
        for i in range(3)
    ]
    pr = PrefillRole(
        Scheduler(_engine(make_model, tiny_params), registry=regs[0],
                  clock=clock), tr[0], decode_ranks=[1],
    )
    d1 = DecodeRole(
        Scheduler(_engine(make_model, tiny_params, capacity=1),
                  registry=regs[1], clock=clock), tr[1],
        prefill_ranks=[0], peer_ranks=[2],
    )
    d2 = DecodeRole(
        Scheduler(_engine(make_model, tiny_params), registry=regs[2],
                  clock=clock), tr[2], prefill_ranks=[], peer_ranks=[1],
    )
    for i in range(3):
        pr.submit(Request(id=i, prompt=prompts[i], max_new_tokens=6))
    # Ship everything BEFORE the decode rank ticks: its single slot can
    # hold one migrated request, the other two defer host-side.
    while pr.pending:
        pr.tick()
    pr.finish()
    d1.tick()
    assert d1._deferred, "deferral never happened — test setup rotted"
    summary = d1.drain(2)
    assert summary.get("deferred_forwarded", 0) >= 2
    assert not d1._deferred and not d1.sched.pending
    cs = d2.run_loop(poll_ms=0)
    done = sorted(
        list(pr.sched.completions) + list(d1.sched.completions) + cs,
        key=lambda c: c.id,
    )
    assert [c.id for c in done] == [0, 1, 2]
    model = make_model()
    for c in done:
        assert c.tokens == oracle(model, tiny_params, prompts[c.id], 6)


def test_peer_ranks_never_gate_healthy_termination(make_model,
                                                   tiny_params, prompts,
                                                   oracle):
    """A decode rank wired with ``peer_ranks`` (potential drain
    sources) terminates a HEALTHY run normally: the silent peer never
    sends an eof and must not be waited on — listing it as a prefill
    source instead is the deadlock :func:`drain_peer_from_env`'s
    docstring warns about."""
    comm, clock = LocalComm(3), _Clock()
    regs = [MetricsRegistry() for _ in range(2)]
    pr = PrefillRole(
        Scheduler(_engine(make_model, tiny_params), registry=regs[0],
                  clock=clock),
        MigrationTransport(comm.endpoint(0), registry=regs[0]),
        decode_ranks=[1],
    )
    dr = DecodeRole(
        Scheduler(_engine(make_model, tiny_params), registry=regs[1],
                  clock=clock),
        MigrationTransport(comm.endpoint(1), registry=regs[1]),
        prefill_ranks=[0], peer_ranks=[2],  # rank 2: healthy, silent
    )
    reqs = [Request(id=i, prompt=prompts[i], max_new_tokens=5)
            for i in range(3)]
    cs = serve_disaggregated(pr, dr, reqs)
    assert sorted(c.id for c in cs) == [0, 1, 2]
    model = make_model()
    for c in cs:
        assert c.tokens == oracle(model, tiny_params, prompts[c.id], 5)
    assert dr.done  # the silent peer did not gate termination
    # Install cost books to its own histogram (the installer syncs, so
    # serve.decode_ms never absorbs kv_put work), and the decode role's
    # histograms stay clean.
    snap = regs[1].snapshot()
    assert snap["serve.migration.install_ms"]["count"] > 0
    assert snap.get("serve.mixed_ms", {}).get("count", 0) == 0


def test_prefill_drain_eofs_every_decode_rank(make_model, tiny_params,
                                              prompts, oracle):
    """A preempted prefill rank feeding TWO decode ranks: its drain
    sends the stream to one peer but the eof to BOTH — the other decode
    rank must terminate its loop cleanly and finish its residents
    (zero loss fleet-wide).  Also pins the per-slot round-robin: both
    decode ranks received work."""
    from chainermn_tpu.serving.scheduler import _Clock

    comm, clock = LocalComm(3), _Clock()
    regs = [MetricsRegistry() for _ in range(3)]
    tr = [
        MigrationTransport(comm.endpoint(i), registry=regs[i])
        for i in range(3)
    ]
    pr = PrefillRole(
        Scheduler(_engine(make_model, tiny_params), registry=regs[0],
                  clock=clock), tr[0], decode_ranks=[1, 2],
    )
    roles = [
        DecodeRole(
            Scheduler(_engine(make_model, tiny_params),
                      registry=regs[i], clock=clock), tr[i],
            prefill_ranks=[0],
        )
        for i in (1, 2)
    ]
    n = 4
    for i in range(n):
        pr.submit(Request(id=i, prompt=prompts[i], max_new_tokens=5))
    ticks = 0
    while pr.pending:
        ticks += 1
        pr.tick()
        if ticks >= 3:
            break
        for r in roles:
            r.tick()
    pr.drain(1)  # the preemption path: stream to rank 1, eof to BOTH
    done = []
    for r in roles:
        done.extend(r.run_loop(poll_ms=0))
    done = sorted(done + list(pr.sched.completions), key=lambda c: c.id)
    assert [c.id for c in done] == list(range(n))
    model = make_model()
    for c in done:
        assert c.tokens == oracle(model, tiny_params, prompts[c.id], 5)
    # Per-slot round-robin spread the stream over both decode ranks.
    served = [len(r.sched.completions) for r in roles]
    assert all(s > 0 for s in served), served


def test_decode_role_survives_dropped_frame(make_model, tiny_params,
                                            prompts, oracle):
    """A lost migration frame must not take the decode rank down: the
    failure is counted, the rank keeps serving its residents, and the
    intact frame that reported the gap still installs its slots (only
    the DROPPED frame's requests are lost)."""
    from chainermn_tpu.resilience.faults import (
        FaultInjector,
        parse_fault_spec,
    )
    from chainermn_tpu.serving.scheduler import _Clock

    comm = LocalComm(2)
    clock = _Clock()
    reg0, reg1 = MetricsRegistry(), MetricsRegistry()
    inj = FaultInjector(parse_fault_spec("drop@migrate:1"))
    t0 = MigrationTransport(comm.endpoint(0), registry=reg0,
                            injector=inj)
    pr = PrefillRole(
        Scheduler(_engine(make_model, tiny_params), registry=reg0,
                  clock=clock),
        t0, decode_ranks=[1],
    )
    dr = DecodeRole(
        Scheduler(_engine(make_model, tiny_params), registry=reg1,
                  clock=clock),
        MigrationTransport(comm.endpoint(1), registry=reg1),
        prefill_ranks=[0],
    )
    # Two requests far enough apart in arrival that they migrate in two
    # separate frames: the first frame drops, the second survives.
    pr.submit(Request(id=0, prompt=prompts[0], max_new_tokens=4))
    while not pr.sched.completions and any(
        s is not None for s in pr.sched._slots
    ) or pr.sched._queue:
        if not pr.tick():
            break
    pr.submit(Request(id=1, prompt=prompts[1], max_new_tokens=4))
    cs = serve_disaggregated(pr, dr, [])
    assert reg1.peek("serve.migration.failed").value == 1
    # Request 0 rode the dropped frame and is gone; request 1 was
    # salvaged off the gap-reporting frame and completed correctly.
    assert [c.id for c in cs] == [1]
    model = make_model()
    assert cs[0].tokens == oracle(model, tiny_params, prompts[1], 4)


def test_torn_frame_checksum_detected(make_model, tiny_params, prompts):
    """A frame whose KV bytes were corrupted in flight fails the CRC —
    refused, counted, never installed."""
    comm = LocalComm(2)
    reg1 = MetricsRegistry()
    t0 = MigrationTransport(comm.endpoint(0))
    t1 = MigrationTransport(comm.endpoint(1), registry=reg1)
    eng = _engine(make_model, tiny_params)
    src = Scheduler(eng)
    src.submit(Request(id=0, prompt=prompts[0], max_new_tokens=4))
    slots = _prefill_until_ready(src)
    body = dz.pack_slots(src, slots)
    t0.send(body, 1)
    # Tear the queued frame: flip one KV byte inside the pickled blob.
    import pickle

    q = comm.queues[(0, 1)]
    frame = pickle.loads(q.popleft())
    layer = frame["body"]["blocks"][slots[0].blocks[0]]["target"][0]
    arr = layer["kv"]
    flat = arr.reshape(-1).view(np.uint8)
    flat[0] ^= 0xFF
    q.append(pickle.dumps(frame))
    with pytest.raises(MigrationError, match="checksum"):
        t1.recv(0)
    assert reg1.peek("serve.migration.failed").value == 1


def test_migration_failed_default_incident_rule(tmp_path):
    """Satellite pin (like ``router_backlog``'s): the shipped rule set
    watches ``serve.migration.failed`` at severity critical and files
    exactly one bundle on a breach."""
    from chainermn_tpu.observability.incident import (
        IncidentManager,
        default_rules,
    )

    rules = [r for r in default_rules() if r.name == "migration_failed"]
    assert rules and rules[0].metric == "serve.migration.failed"
    assert rules[0].severity == "critical"
    reg = MetricsRegistry()
    mgr = IncidentManager(
        registry=reg, rules=rules, directory=str(tmp_path),
        cooldown_s=0.0,
    )
    assert mgr.evaluate() == []  # instrument absent: never fires
    reg.counter("serve.migration.failed").inc()
    fired = mgr.evaluate()
    assert len(fired) == 1
    assert fired[0]["rule"]["name"] == "migration_failed"
    assert fired[0]["rule"]["severity"] == "critical"
    assert mgr.evaluate() == []  # latched while breaching


# ----------------------------------------------------------- preemption
@pytest.mark.slow  # tier-1 wall budget: the 2-OS-rank SIGTERM drain
# acceptance (multiprocess_tests/test_disagg_preempt.py) keeps the
# zero-loss contract tier-1; this is the in-process twin
def test_preemption_drain_zero_loss_oracle(make_model, tiny_params,
                                           oracle):
    """SIGTERM-shaped drain (programmatic ``request()`` through the real
    guard): every live slot and queued entry migrates to the peer, the
    rank exits 75, the peer finishes EVERYTHING, and the union of
    completions is greedy-identical to the unpreempted oracle."""
    from chainermn_tpu.resilience.preemption import (
        PREEMPTION_EXIT_CODE,
        PreemptionGuard,
        PreemptionInterrupt,
    )

    src_e = _engine(make_model, tiny_params)
    dst_e = _engine(make_model, tiny_params)
    comm = LocalComm(2)
    clock = _Clock()
    reg0, reg1 = MetricsRegistry(), MetricsRegistry()
    t0 = MigrationTransport(comm.endpoint(0), registry=reg0)
    src = Scheduler(src_e, registry=reg0, clock=clock)
    peer = DecodeRole(
        Scheduler(dst_e, registry=reg1, clock=clock),
        MigrationTransport(comm.endpoint(1), registry=reg1),
        prefill_ranks=[0],
    )
    rng = np.random.RandomState(1)
    reqs_p = [rng.randint(1, 128, size=int(n)).tolist()
              for n in (5, 12, 9, 3, 17, 12, 7)]
    for i, p in enumerate(reqs_p):
        src.submit(Request(id=i, prompt=p, max_new_tokens=8))
    guard = PreemptionGuard()
    guard.attach_drain(lambda: drain_all(src, t0, dest=1))
    ticks = 0
    with pytest.raises(PreemptionInterrupt) as ei:
        while src.pending:
            ticks += 1
            if ticks == 5:
                guard.request()  # the SIGTERM handler's exact effect
            guard.poll_serving(ticks)
            src.tick()
    assert ei.value.code == PREEMPTION_EXIT_CODE
    # Mid-run: some slots were live, some queue remained — the drain
    # had real work (otherwise the test pins nothing).
    assert reg0.peek("serve.migration.slots_migrated").value > 0
    cs = peer.run_loop(poll_ms=0)
    merged = sorted(
        list(src.completions) + list(cs), key=lambda c: c.id
    )
    assert [c.id for c in merged] == list(range(len(reqs_p)))
    model = make_model()
    for c in merged:
        assert c.tokens == oracle(model, tiny_params, reqs_p[c.id], 8), c.id
    # Source pool fully released (prefix pins aside).
    src_e.drop_prefix_cache()
    assert src_e.free_blocks() == src_e.pool.num_blocks - 1


# --------------------------------------------------------------- router
def test_router_dispatches_by_role(make_model, tiny_params, prompts):
    """A disaggregated fleet behind the Router: decode-role replicas
    take NO fresh admissions — every dispatch lands on the admitting
    replicas; an all-decode fleet is rejected outright."""
    e0 = _engine(make_model, tiny_params, capacity=2)
    e1 = _engine(make_model, tiny_params, capacity=2)
    router = Router([e0, e1], roles=["mixed", "decode"], max_queue=8)
    reqs = [Request(id=i, prompt=prompts[i % len(prompts)],
                    max_new_tokens=3) for i in range(5)]
    cs = router.run(reqs)
    assert len(cs) == 5
    assert all(reps == [0] for reps in router.assignments.values())
    stats = router.replica_stats()
    assert [s["role"] for s in stats] == ["mixed", "decode"]
    assert stats[1]["completions"] == 0
    with pytest.raises(ValueError, match="decode-role"):
        Router([e0, e1], roles=["decode", "decode"])
    with pytest.raises(ValueError, match="unknown role"):
        Router([e0], roles=["speculate"])


def test_roles_and_drain_peer_env_parsing(monkeypatch):
    monkeypatch.delenv("CMN_DISAGG_ROLES", raising=False)
    assert dz.roles_from_env(3) == ["mixed"] * 3
    monkeypatch.setenv("CMN_DISAGG_ROLES", "prefill,decode")
    assert dz.roles_from_env(4) == [
        "prefill", "decode", "decode", "decode"
    ]
    monkeypatch.setenv("CMN_DISAGG_ROLES", "prefill,flying")
    with pytest.raises(ValueError, match="unknown role"):
        dz.roles_from_env(2)
    monkeypatch.delenv("CMN_DISAGG_DRAIN_PEER", raising=False)
    assert dz.drain_peer_from_env(0, 2) == 1
    assert dz.drain_peer_from_env(1, 2) == 0
    assert dz.drain_peer_from_env(0, 1) is None
    # Role-aware default: a prefill rank never polls the migration
    # plane, so it is never chosen as the drain destination.
    roles = ["prefill", "decode", "decode"]
    assert dz.drain_peer_from_env(2, 3, roles) == 1
    assert dz.drain_peer_from_env(1, 3, roles) == 2
    assert dz.drain_peer_from_env(0, 2, ["prefill", "prefill"]) is None
    monkeypatch.setenv("CMN_DISAGG_DRAIN_PEER", "0")
    assert dz.drain_peer_from_env(1, 2) == 0
    with pytest.raises(ValueError):
        dz.drain_peer_from_env(0, 2)
    with pytest.raises(ValueError, match="prefill"):
        dz.drain_peer_from_env(1, 3, roles)

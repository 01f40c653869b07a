"""A model that keeps recurrent state by slot (``HybridLM`` of ``F`` layers:
the Falcon-H1 block) through ``DecodeEngine`` and ``Scheduler``, on CPU in
float32 against the plain reference's full forward
(``perfbench/reference/falcon_h1.py``: the recurrence one position at a time,
no cache):

* prefill in chunks, then decode, agrees with the reference **on logits** at
  every served position (the served token's logit is the reference's best),
  over chunk sizes and short tails;
* a chunk that rides the decode step serves what ``prefill`` alone serves,
  token for token, two slots prefilling in one tick among them;
* a slot used twice serves its second request as a fresh engine does, and an
  evicted and recomputed request serves the same tokens: a chunk at position
  0 starts the slot's state from zeros;
* everything that moves or shares blocks is refused, each with its reason;
* one compile a program; a ``TransformerLM`` engine's ``pools`` tree and
  compile counts are what they were.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.models import HybridLM
from chainermn_tpu.serving import DecodeEngine, Request, Scheduler
from chainermn_tpu.serving import disagg
from perfbench import weights
from perfbench.manifest import Manifest
from perfbench.reference import falcon_h1 as ref
from perfbench.weights import falcon_h1 as tree

pytestmark = [pytest.mark.tier1, pytest.mark.serving]

M = dict(vocab=128, n_layers=2, d_model=32, layer_kinds="FF", n_heads=5,
         n_kv_heads=1, head_dim=16, ssm_heads=4, ssm_head_dim=8,
         ssm_groups=2, ssm_state=16, ssm_chunk=8, conv_kernel=4, d_ff=48,
         norm_eps=1e-5, rope_theta=100000000000,
         **{k: v for k, v in Manifest().config(
             "Falcon-H1-34B-Instruct")["published"].items()
            if "multiplier" in k})
_RNG = np.random.RandomState(7)
_TOKENS = _RNG.randint(1, 128, size=600).tolist()


@pytest.fixture(scope="module")
def model():
    return HybridLM(dtype=jnp.float32, param_dtype=jnp.float32,
                    decode_attention="fused", **M)


@pytest.fixture(scope="module")
def params():
    return weights.make_params(tree.param_specs(M), 2**31 + 5, jnp.float32)


def _engine(model, params, capacity=3, chunk=8, num_blocks=80, **kw):
    return DecodeEngine(model, params, capacity=capacity,
                        num_blocks=num_blocks, block_len=4,
                        max_blocks_per_slot=20, prefill_chunk=chunk,
                        prefix_cache=False, **kw)


def _requests(shapes):
    return [Request(id=i, prompt=_TOKENS[41 * i: 41 * i + n],
                    max_new_tokens=m) for i, (n, m) in enumerate(shapes)]


def _serve(eng, reqs, ride=True):
    sched = Scheduler(eng)
    if not ride:  # the two-call path alone: never told a step follows
        round_ = sched._prefill_round
        sched._prefill_round = lambda decode_follows=False: round_()
    done = {c.id: c for c in sched.run(reqs)}
    assert all(c.status == "ok" for c in done.values())
    return {i: list(c.tokens) for i, c in done.items()}, sched


def _gaps(params, reqs, served):
    """Per request, the widest gap between the reference's best logit and
    the served token's, over every served position — and the logits'
    spread."""
    out = []
    for r in reqs:
        text = list(r.prompt) + served[r.id]
        logits = np.asarray(ref.forward_logits(
            params, jnp.asarray([text[:-1]]), M))[0]
        a = len(r.prompt) - 1
        rows = logits[a:a + len(served[r.id])]
        got = np.asarray(served[r.id])
        out.append((float(np.max(rows.max(-1)
                                 - rows[np.arange(len(got)), got])),
                    float(rows.std())))
    return out


SHAPES = [(13, 6), (8, 9), (21, 5), (5, 7), (17, 4), (30, 8), (16, 3)]


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunks_then_decode_serve_the_references_best_token(model, params,
                                                            chunk):
    """Seven requests over three slots (every slot is used again; tails of
    5, 1, 6 ... rows; prompts of exactly one and two chunks): at every
    served position the served token's logit is the reference's best, to
    float32's rounding of logits whose spread is one."""
    eng = _engine(model, params, chunk=chunk)
    reqs = _requests(SHAPES)
    served, _ = _serve(eng, reqs)
    assert [len(served[r.id]) for r in reqs] == [m for _, m in SHAPES]
    for gap, spread in _gaps(params, reqs, served):
        assert gap <= 1e-5 and 0.5 < spread < 2.0
    assert (eng.decode_compiles, eng.mixed_compiles) == (1, 1)
    assert eng.prefill_compiles <= 1


def test_a_riding_chunk_serves_what_prefill_alone_serves(model, params):
    """The mixed step against the two-call path, token for token — with
    capacity 4 and seven requests due at once two slots prefill in one tick
    (one rides, one is a call of its own) again and again."""
    reqs = _requests(SHAPES)
    together, sched = _serve(_engine(model, params, capacity=4), reqs)
    apart, _ = _serve(_engine(model, params, capacity=4), reqs, ride=False)
    assert together == apart
    units = obs.unit_ledger("serve_tick").units()
    assert units  # the two-call run's ledger is the newest
    ledger = sched._units.units()
    rode = sum(u.counts.get("cmn_serve_prefill.rode", 0) for u in ledger)
    calls = sum(u.calls.get("cmn_serve_prefill", 0) for u in ledger)
    assert 0 < rode < calls
    assert any(u.calls.get("cmn_serve_prefill", 0) >= 2
               and u.counts.get("cmn_serve_prefill.rode", 0) == 1
               for u in ledger)
    # the counts a stateful engine adds: every request's first chunk resets
    # its slot, and a step updates its live rows and the rider's slot
    assert sum(u.counts.get("cmn_serve_prefill.state_reset", 0)
               for u in ledger) == len(reqs)
    for u in ledger:
        if u.calls.get("cmn_serve_decode"):
            assert u.counts["cmn_serve_decode.state_rows"] == \
                u.counts["cmn_serve_decode.live"] + \
                u.counts.get("cmn_serve_prefill.rode", 0)


def test_a_slot_used_twice_serves_as_a_fresh_engine_does(model, params):
    """One slot: the second request finds the first one's state in it, and
    its first chunk starts from zeros all the same."""
    first, second = _requests([(19, 6), (11, 7)])
    eng = _engine(model, params, capacity=1)
    served, _ = _serve(eng, [first, second])
    alone, _ = _serve(_engine(model, params, capacity=1), [second])
    assert served[second.id] == alone[second.id]
    # the state the first left was not nothing
    assert float(jnp.max(jnp.abs(eng.pools[0]["ssm"]))) > 0


def test_an_evicted_request_is_recomputed_to_the_same_tokens(model, params):
    """A pool too small for three long requests at once: the youngest is
    evicted, requeued with its tokens carried, prefilled again from
    position 0 — and serves what a roomy pool serves."""
    reqs = _requests([(30, 20), (28, 20), (26, 20)])
    tight = _engine(model, params, num_blocks=30)
    served, sched = _serve(tight, reqs)
    assert sum(e for e in (c.evictions for c in sched.completions)) > 0
    roomy, _ = _serve(_engine(model, params), reqs)
    assert served == roomy


def test_the_pools_tree_holds_the_state_by_slot(model, params):
    eng = _engine(model, params, capacity=3)
    assert eng.stateful and len(eng.pools) == 2
    assert {k: (v.shape, v.dtype) for k, v in eng.pools[0].items()} == {
        "kv": ((80, 4, 32), jnp.float32),
        "ssm": ((3, 4, 8, 16), jnp.float32),
        "conv": ((3, 3, 96), jnp.float32)}
    per_slot = 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert eng.pool.state_bytes == 3 * per_slot == eng.stats()["state_bytes"]
    assert eng.pool.bytes_per_block == 2 * 4 * 32 * 4  # the blocks' alone


# --------------------------------------------------------------- refusals
def test_what_moves_or_shares_blocks_is_refused_with_its_reason(model,
                                                                params):
    kw = dict(capacity=2, num_blocks=20, block_len=4, max_blocks_per_slot=8,
              prefill_chunk=8)
    with pytest.raises(NotImplementedError, match="prefix_cache=True.*state"):
        DecodeEngine(model, params, **kw)  # the default is a prefix cache
    with pytest.raises(NotImplementedError, match="speculative.*state"):
        DecodeEngine(model, params, prefix_cache=False, draft_model=model,
                     draft_params=params, spec_k=2, **kw)
    with pytest.raises(NotImplementedError, match="mesh=.*state"):
        DecodeEngine(model, params, prefix_cache=False, mesh=object(), **kw)
    with pytest.raises(ValueError, match="max_blocks_per_slot"):
        DecodeEngine(model, params, capacity=2, num_blocks=20,
                     prefix_cache=False)
    eng = DecodeEngine(model, params, prefix_cache=False, **kw)
    assert eng.prefix is None and eng.drop_prefix_cache() == 0
    for call in (lambda: eng.read_block(1),
                 lambda: eng.write_block(1, {"target": [], "draft": None}),
                 lambda: eng.cow_copy(1, 2)):
        with pytest.raises(NotImplementedError, match="state by slot"):
            call()
    assert (eng.gather_compiles, eng.put_compiles, eng.cow_compiles) \
        == (0, 0, 0)
    sched = Scheduler(eng)
    comm = disagg.LocalComm(2)
    for role, args in ((disagg.PrefillRole, ([1],)),
                       (disagg.DecodeRole, ([0],))):
        with pytest.raises(NotImplementedError, match="migration frame"):
            role(sched, disagg.MigrationTransport(comm.endpoint(0)), *args)
    # the doors of drain / recovery / elastic all pack slots into frames
    reqs = _requests([(9, 6)])
    sched.submit(reqs[0])
    while not sched.ready_slots():
        sched.tick()
    with pytest.raises(NotImplementedError, match="state by slot"):
        disagg.pack_slots(sched, sched.ready_slots())


# --------------------------------------------- the model without state
def test_a_transformer_engine_is_what_it_was(make_model, tiny_params):
    """No state entry in its ``pools``, no new count in its ledger, the same
    programs compiled once each."""
    eng = DecodeEngine(make_model(), tiny_params, capacity=3, num_blocks=40,
                       block_len=4, prefill_chunk=8)
    assert not eng.stateful and eng.pool.state_bytes == 0
    assert [sorted(layer) for layer in eng.pools] == [["kv"], ["kv"]]
    assert eng.pools[0]["kv"].shape == (40, 4, 2 * 2 * 16)
    assert "state_bytes" not in eng.stats()
    sched = Scheduler(eng)
    done = sched.run([Request(id=i, prompt=_TOKENS[9 * i: 9 * i + n],
                              max_new_tokens=5)
                      for i, n in enumerate((13, 7, 20, 9))])
    assert all(c.status == "ok" for c in done)
    assert (eng.decode_compiles, eng.mixed_compiles) == (1, 1)
    assert eng.prefill_compiles <= 1
    for u in sched._units.units():
        assert not any("state" in k for k in u.counts)
    eng.read_block(1)  # the migration doors stay open
    assert eng.gather_compiles == 1

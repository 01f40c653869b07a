"""A model with window layers (``HybridLM`` of ``W`` / ``G`` layers: the
Command A+ parallel block) through ``DecodeEngine`` and ``Scheduler``, on CPU
in float32 against the plain reference's full forward
(``perfbench/reference/command_a_plus.py``: no cache, no ring) — beside
``test_engine_state.py``'s cases for a model with state:

* the full forward is the reference's, on logits;
* prefill in chunks, then decode, agrees with the reference **on logits** at
  every served position, with contexts **past the window and past one turn
  of the ring**, over chunk sizes, short tails, the kernel and the gathered
  read;
* a chunk that rides the decode step serves what ``prefill`` alone serves;
* a slot used twice serves its second request as a fresh engine does, and an
  evicted and recomputed request serves the same tokens: a ring is masked by
  absolute position and never zeroed;
* two kinds of cache in one engine: the window layers hold O(window) a slot
  whatever the table's width, the allocator's blocks are the full layer's;
* everything that moves or shares blocks is refused, each with its reason;
* the routing counts leave the step with its tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import HybridLM
from chainermn_tpu.ops.decode_attention import ring_blocks
from chainermn_tpu.serving import DecodeEngine, Request, Scheduler
from chainermn_tpu.serving import disagg
from perfbench import weights
from perfbench.reference import command_a_plus as ref
from perfbench.weights import command_a_plus as tree

pytestmark = [pytest.mark.tier1, pytest.mark.serving]

M = dict(vocab=128, n_layers=4, d_model=32, layer_kinds="WWWG", n_heads=4,
         n_kv_heads=2, head_dim=8, window=12, rope_theta=50000.0,
         rope_interleaved=True, norm="layer", norm_eps=1e-5,
         tie_embeddings=True, lm_head_multiplier=1, experts_held=4, ep_of=2,
         ep_index=0, experts_per_tok=2, d_expert=16, n_shared=2, d_shared=32)
_RNG = np.random.RandomState(11)
_TOKENS = _RNG.randint(1, 128, size=900).tolist()


@pytest.fixture(scope="module")
def model():
    return HybridLM(dtype=jnp.float32, param_dtype=jnp.float32,
                    decode_attention="fused", **M)


@pytest.fixture(scope="module")
def params():
    return weights.make_params(tree.param_specs(M), 2**31 + 5, jnp.float32)


def _engine(model, params, capacity=3, chunk=8, num_blocks=80, width=20,
            **kw):
    return DecodeEngine(model, params, capacity=capacity,
                        num_blocks=num_blocks, block_len=4,
                        max_blocks_per_slot=width, prefill_chunk=chunk,
                        prefix_cache=False, **kw)


def _requests(shapes):
    return [Request(id=i, prompt=_TOKENS[83 * i: 83 * i + n],
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


def test_the_weight_tree_is_the_programs_own(model, params):
    init = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    shapes = jax.tree_util.tree_map(lambda a: a.shape, params)
    assert shapes == jax.tree_util.tree_map(lambda a: a.shape, init)
    assert "lm_head" not in params  # the head is the embedding


def test_the_full_forward_is_the_references(model, params):
    """Two rows of 50 positions, four times the window: logits to float32's
    rounding, their spread about one."""
    toks = jnp.asarray(np.asarray(_TOKENS[:100]).reshape(2, 50))
    got = np.asarray(model.apply({"params": params}, toks))
    want = np.asarray(ref.forward_logits(params, toks, M))
    assert np.abs(got - want).max() < 2e-5 and 0.5 < want.std() < 2.0


#: contexts to 70: the window is 12, a ring's turn 20 (chunk 8) or 28 (16)
SHAPES = [(13, 6), (30, 20), (21, 9), (5, 30), (17, 40), (48, 22), (16, 3)]


@pytest.mark.parametrize("chunk,attention", [(8, "fused"), (16, "fused"),
                                             (8, "einsum")])
def test_chunks_then_decode_serve_the_references_best_token(model, params,
                                                            chunk, attention):
    """Seven requests over three slots (every slot is used again; tails of
    5, 6, 1 ... rows; a prompt of exactly one and of three chunks; contexts
    past the window and past a turn of the ring): at every served position
    the served token's logit is the reference's best — decode rows through
    the kernel (``fused``) and through the gathered read (``einsum``)."""
    eng = _engine(model.clone(decode_attention=attention), params,
                  chunk=chunk)
    reqs = _requests(SHAPES)
    served, sched = _serve(eng, reqs)
    assert [len(served[r.id]) for r in reqs] == [m for _, m in SHAPES]
    for gap, spread in _gaps(params, reqs, served):
        assert gap <= 1e-5 and 0.5 < spread < 2.0
    assert (eng.decode_compiles, eng.mixed_compiles) == (1, 1)
    assert eng.prefill_compiles <= 1
    # the step's own counts rode behind its tokens
    units = sched._units.units()
    for name in ("moe_pairs_held", "moe_experts_touched", "moe_layers"):
        assert sum(u.counts.get(f"cmn_engine_readback.{name}", 0)
                   for u in units) > 0
    for u in units:
        layers = u.counts.get("cmn_engine_readback.moe_layers", 0)
        assert layers % 4 == 0
        assert u.counts.get("cmn_engine_readback.moe_experts_touched",
                            0) <= layers * M["experts_held"]
        assert u.counts.get("cmn_engine_readback.moe_pairs_dropped", 0) == 0
        if u.calls.get("cmn_serve_decode"):
            live = u.counts["cmn_serve_decode.live"]
            resident = u.counts["cmn_serve_decode.ring_blocks_resident"]
            # a slot reads its window's blocks: 1 .. ceil(12 / 4) + 1
            assert live <= resident <= 4 * live


def test_a_riding_chunk_serves_what_prefill_alone_serves(model, params):
    reqs = _requests(SHAPES)
    together, sched = _serve(_engine(model, params, capacity=4), reqs)
    apart, _ = _serve(_engine(model, params, capacity=4), reqs, ride=False)
    assert together == apart
    ledger = sched._units.units()
    rode = sum(u.counts.get("cmn_serve_prefill.rode", 0) for u in ledger)
    calls = sum(u.calls.get("cmn_serve_prefill", 0) for u in ledger)
    assert 0 < rode < calls


def test_a_slot_used_twice_serves_as_a_fresh_engine_does(model, params):
    """One slot: the second request finds the first one's keys all over its
    ring, and sees none of them."""
    first, second = _requests([(40, 30), (11, 25)])
    eng = _engine(model, params, capacity=1)
    served, _ = _serve(eng, [first, second])
    alone, _ = _serve(_engine(model, params, capacity=1), [second])
    assert served[second.id] == alone[second.id]
    assert float(jnp.max(jnp.abs(eng.pools[0]["ring"]))) > 0


def test_an_evicted_request_is_recomputed_to_the_same_tokens(model, params):
    """A pool too small for three long requests at once (the full layer's:
    the rings cannot run out): the youngest is evicted, requeued, prefilled
    again from position 0 into a ring that still holds its first try."""
    reqs = _requests([(30, 20), (28, 20), (26, 20)])
    served, sched = _serve(_engine(model, params, num_blocks=30), reqs)
    assert sum(c.evictions for c in sched.completions) > 0
    roomy, _ = _serve(_engine(model, params), reqs)
    assert served == roomy


def test_two_kinds_of_cache_in_one_engine(model, params):
    """A window layer's entry is a ring by slot, R blocks whatever the
    table's width; the full layer's is the paged pool, and the allocator's
    blocks are its alone."""
    R = ring_blocks(12, 8, 4)
    assert R == 5 and ring_blocks(4096, 256, 128) == 34
    assert ring_blocks(12, 6, 4) == 6  # chunks that start inside a block
    eng = _engine(model, params, capacity=3)
    assert eng.ringed and eng.stateful
    assert [{k: v.shape for k, v in layer.items()} for layer in eng.pools] \
        == [{"ring": (3, R, 4, 32)}] * 3 + [{"kv": (80, 4, 32)}]
    ring_bytes = 3 * 3 * R * 4 * 32 * 4
    assert eng.pool.ring_bytes == ring_bytes == eng.stats()["ring_bytes"]
    assert eng.pool.state_bytes == 0
    assert eng.pool.bytes_per_block == 4 * 32 * 4  # one layer's blocks
    wide = _engine(model, params, capacity=3, width=200, num_blocks=700)
    assert wide.pool.ring_bytes == ring_bytes  # not the contexts'
    assert eng.ring_resident([0, 3, 4, 11, 12, 40]) == 1 + 1 + 2 + 3 + 4 + 4


def test_what_moves_or_shares_blocks_is_refused_with_its_reason(model,
                                                                params):
    kw = dict(capacity=2, num_blocks=20, block_len=4, max_blocks_per_slot=8,
              prefill_chunk=8)
    with pytest.raises(NotImplementedError, match="prefix_cache=True.*ring"):
        DecodeEngine(model, params, **kw)  # the default is a prefix cache
    with pytest.raises(NotImplementedError, match="speculative.*ring"):
        DecodeEngine(model, params, prefix_cache=False, draft_model=model,
                     draft_params=params, spec_k=2, **kw)
    with pytest.raises(NotImplementedError, match="mesh=.*ring"):
        DecodeEngine(model, params, prefix_cache=False, mesh=object(), **kw)
    eng = DecodeEngine(model, params, prefix_cache=False, **kw)
    for call in (lambda: eng.read_block(1),
                 lambda: eng.write_block(1, {"target": [], "draft": None}),
                 lambda: eng.cow_copy(1, 2)):
        with pytest.raises(NotImplementedError, match="ring by slot"):
            call()
    assert (eng.gather_compiles, eng.put_compiles, eng.cow_compiles) \
        == (0, 0, 0)
    sched = Scheduler(eng)
    comm = disagg.LocalComm(2)
    for role, args in ((disagg.PrefillRole, ([1],)),
                       (disagg.DecodeRole, ([0],))):
        with pytest.raises(NotImplementedError, match="migration frame"):
            role(sched, disagg.MigrationTransport(comm.endpoint(0)), *args)
    reqs = _requests([(9, 6)])
    sched.submit(reqs[0])
    while not sched.ready_slots():
        sched.tick()
    with pytest.raises(NotImplementedError, match="ring by slot"):
        disagg.pack_slots(sched, sched.ready_slots())


def test_kinds_without_a_decode_path_are_still_refused():
    model = HybridLM(vocab=64, n_layers=2, d_model=16, layer_kinds="W*",
                     window=4)
    with pytest.raises(NotImplementedError, match="decode path"):
        model.state_shapes()
    with pytest.raises(ValueError, match="window"):
        HybridLM(vocab=64, n_layers=1, d_model=16,
                 layer_kinds="W").ring_shapes(2, 4, 8)

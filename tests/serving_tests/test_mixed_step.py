"""A prefill chunk rides the decode step (ISSUE 42).

In a tick that has a live slot and a prefilling one, the oldest prefilling
slot's next chunk is staged by the prefill round and handed to the engine
with the decode step's control vectors: one ``mixed_step`` program for
both.  Sampling is keyed by ``(seed, position)``, so what a row was
batched with must not show in the served tokens.  Pinned here, on CPU in
float32, greedy and seeded sampling side by side:

* the tokens of a run whose chunks ride equal, token for token, those of
  the same requests through ``DecodeEngine.prefill`` alone (a scheduler
  whose prefill round is never told a decode step follows) — over the
  geometries and corners the mechanism has;
* a tail shorter than ``prefill_chunk`` writes nothing past the text,
  riding or handed to ``prefill()`` (a plain engine runs it in the mixed
  step's geometry either way: no program a ladder size);
* a staged slot evicted by the decode build's own allocation dispatches
  nothing and loses no block;
* ``mixed_step`` compiles once and the budget gauge does not move.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.observability import device as odev
from chainermn_tpu.serving import DecodeEngine, Request, Scheduler

pytestmark = [pytest.mark.tier1, pytest.mark.serving]

_RNG = np.random.RandomState(42)
_TOKENS = _RNG.randint(1, 128, size=400).tolist()


def _requests(shapes, prompts=None):
    """``(prompt_len, max_new, temperature)`` a request; odd ids sample."""
    out = []
    for i, (n, m, t) in enumerate(shapes):
        prompt = (prompts[i] if prompts is not None
                  else _TOKENS[37 * i: 37 * i + n])
        out.append(Request(id=i, prompt=prompt, max_new_tokens=m,
                           temperature=t, seed=100 + i))
    return out


def _serve(eng, reqs, ride):
    """Completions by id, and what the tick ledger and the engine's door
    counted: prefill ``calls``, those that ``rode``, mixed ``steps``, the
    chunk ``rows`` the decode steps carried, and the ledger's ``units``."""
    sched = Scheduler(eng)
    if not ride:
        # The two-call path alone: the round is never told a step follows.
        round_ = sched._prefill_round
        sched._prefill_round = lambda decode_follows=False: round_()
    steps = []
    mixed = eng.mixed_step

    def counted(*a, **k):
        steps.append(1)
        return mixed(*a, **k)

    eng.mixed_step = counted
    comps = {c.id: c for c in sched.run(reqs)}
    units = obs.unit_ledger("serve_tick").units()
    calls = sum(u.calls.get("cmn_serve_prefill", 0) for u in units)
    rode = sum(u.counts.get("cmn_serve_prefill.rode", 0) for u in units)
    rows = sum(u.counts.get("cmn_serve_decode.chunk_rows", 0) for u in units)
    return comps, {"calls": calls, "rode": rode, "steps": len(steps),
                   "rows": rows, "units": units}


#: name -> (model overrides, engine arguments, request shapes)
_CASES = {
    # the shared GQA 4/2 RoPE geometry; three slots, staggered prompts
    "gqa": ({}, dict(capacity=3, num_blocks=40, block_len=8,
                     prefill_chunk=16),
            [(5, 9, 0.0), (40, 6, 0.8), (21, 12, 0.0), (33, 5, 0.7),
             (9, 8, 0.0)]),
    # GPT-2's: one KV head a query head, learned positions
    "mha_learned": (dict(n_kv_heads=None, pos_enc="learned"),
                    dict(capacity=3, num_blocks=40, block_len=8,
                         prefill_chunk=16),
                    [(6, 10, 0.0), (35, 6, 0.9), (20, 9, 0.0),
                     (50, 4, 0.0)]),
    "int8_pool": (dict(kv_dtype=jnp.int8),
                  dict(capacity=3, num_blocks=40, block_len=8,
                       prefill_chunk=16),
                  [(7, 9, 0.0), (30, 6, 0.8), (19, 7, 0.0)]),
    # tails of 3 and 5 tokens under a chunk of 16: inactive rows
    "short_tail": ({}, dict(capacity=2, num_blocks=40, block_len=8,
                            prefill_chunk=16),
                   [(4, 12, 0.0), (19, 5, 0.0), (37, 4, 0.6)]),
    # the kernel (interpret mode here) for the decode rows, the gathered
    # read for the chunk's: the two part at paged_attend
    "fused_kernel": (dict(decode_attention="fused"),
                     dict(capacity=2, num_blocks=24, block_len=8,
                          prefill_chunk=8),
                     [(4, 8, 0.0), (18, 4, 0.0)]),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_riding_chunks_serve_the_same_tokens(make_model, case):
    over, eng_kw, shapes = _CASES[case]
    model = make_model(**over)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 12), jnp.int32)
    )["params"]
    reqs = _requests(shapes)
    rode, seen = _serve(DecodeEngine(model, params, **eng_kw), reqs, True)
    alone, base = _serve(DecodeEngine(model, params, **eng_kw), reqs, False)
    assert seen["rode"] >= 1 and seen["steps"] == seen["rode"]
    assert base["rode"] == 0 and base["steps"] == 0
    # the same tokens go through prefill either way
    tokens = sum(u.counts.get("cmn_serve_prefill.tokens", 0)
                 for u in seen["units"])
    assert tokens == sum(u.counts.get("cmn_serve_prefill.tokens", 0)
                         for u in base["units"])
    assert 0 < seen["rows"] <= tokens
    for r in reqs:
        assert rode[r.id].tokens == alone[r.id].tokens, (case, r.id)
        assert len(rode[r.id].tokens) == r.max_new_tokens


def test_chunk_starting_mid_block_after_a_prefix_hit(make_model,
                                                     tiny_params):
    """Request 2 maps 21 cached tokens of request 1's prompt (two full
    blocks of 8 and five tokens of a third: copy-on-write), so its first
    chunk starts mid-block — and rides, request 0 decoding beside it."""
    shared = _TOKENS[200:221]
    prompts = [_TOKENS[:6], shared + _TOKENS[300:304],
               shared + _TOKENS[310:329]]
    shapes = [(0, 30, 0.0), (0, 3, 0.0), (0, 6, 0.7)]
    kw = dict(capacity=2, num_blocks=40, block_len=8, prefill_chunk=16)
    model = make_model()
    out = {}
    for ride in (True, False):
        eng = DecodeEngine(model, tiny_params, **kw)
        out[ride], seen = _serve(eng, _requests(shapes, prompts), ride)
        assert out[ride][2].prefix_hit_tokens == 21
        assert eng.cow_compiles == 1
        if ride:
            assert seen["rode"] >= 2
            # request 2's first chunk (p0 = 21) was one of them
            assert any(u.counts.get("cmn_serve_prefill.rode")
                       and u.counts.get("cmn_serve_prefill.tokens") == 16
                       for u in seen["units"])
    for i in range(3):
        assert out[True][i].tokens == out[False][i].tokens, i


def test_second_prefilling_slot_takes_the_call_of_its_own(make_model,
                                                          tiny_params):
    """Two slots prefill in one tick beside a live one: the older rides,
    the younger goes through ``DecodeEngine.prefill`` as before."""
    shapes = [(4, 20, 0.0), (40, 5, 0.0), (44, 5, 0.8)]
    kw = dict(capacity=3, num_blocks=40, block_len=8, prefill_chunk=8)
    model = make_model()
    rode, seen = _serve(DecodeEngine(model, tiny_params, **kw),
                        _requests(shapes), True)
    alone, _ = _serve(DecodeEngine(model, tiny_params, **kw),
                      _requests(shapes), False)
    both = [u for u in seen["units"]
            if u.calls.get("cmn_serve_prefill", 0) == 2
            and u.calls.get("cmn_serve_decode")]
    assert both, "no tick held two prefilling slots and a live one"
    for u in both:
        assert u.counts["cmn_serve_prefill.rode"] == 1
        assert u.counts["cmn_serve_decode.chunk_rows"] == 8
        # the rider's dispatch is the step's; the other chunk made its own
        assert u.calls["cmn_engine_dispatch"] == 2
    for i in range(3):
        assert rode[i].tokens == alone[i].tokens, i


def test_speculative_engine_never_rides(make_model, tiny_params):
    model = make_model()
    eng = DecodeEngine(model, tiny_params, capacity=2, num_blocks=40,
                       block_len=8, prefill_chunk=8, draft_model=model,
                       draft_params=tiny_params, spec_k=2)
    comps, seen = _serve(eng, _requests([(4, 12, 0.0), (30, 5, 0.0)]), True)
    assert seen["calls"] >= 4 and seen["rode"] == 0 and seen["steps"] == 0
    assert eng.mixed_compiles == 0
    with pytest.raises(RuntimeError, match="speculative"):
        eng.mixed_step(None, None, None, None, 0, None, 0, None)
    assert [len(comps[i].tokens) for i in (0, 1)] == [12, 5]


def test_tail_rows_write_nothing_past_the_text(make_model, tiny_params):
    """A chunk of 5 tokens at ``prefill_chunk`` 16: the 11 rows past the
    text are inactive, and every pool row of the slot's blocks beyond
    position 5 reads as before the step — as does the parking block."""
    eng = DecodeEngine(make_model(), tiny_params, capacity=2, num_blocks=8,
                       block_len=8, prefill_chunk=16, prefix_cache=False)
    S, MB = 2, eng.max_blocks
    blocks = eng.alloc_blocks(2)
    before = [np.asarray(p["kv"]).copy() for p in eng.pools]
    table = np.zeros((MB,), np.int32)
    table[:2] = blocks
    chunk = np.zeros((16,), np.int32)
    chunk[:5] = _TOKENS[:5]
    eng.seed_slot(1, 7, 0.0)
    out, tok = eng.mixed_step(
        np.zeros((S,), np.int32), np.zeros((S,), np.int32),
        np.zeros((S, MB), np.int32), np.zeros((S,), bool), 1, chunk, 0,
        table, last_idx=4)
    assert out.shape == (S,) and isinstance(tok, int)
    for was, p in zip(before, eng.pools):
        now = np.asarray(p["kv"])
        assert (now[blocks[0], :5] != was[blocks[0], :5]).any()
        assert (now[blocks[0], 5:] == was[blocks[0], 5:]).all()
        others = [b for b in range(8) if b != blocks[0]]
        assert (now[others] == was[others]).all()
    # the chunk's token is what the single-row program samples (a whole
    # chunk's geometry: its pads do write) ...
    ref = DecodeEngine(make_model(), tiny_params, capacity=2, num_blocks=8,
                       block_len=8, prefill_chunk=16, prefix_cache=False)
    table[:2] = ref.alloc_blocks(2)
    ref.seed_slot(1, 7, 0.0)
    assert ref.prefill(1, chunk, 0, table, last_idx=4) == tok
    assert ref.prefill_compiles == 1 and ref.mixed_compiles == 0
    # ... and a tail handed to prefill() at its ladder size takes the mixed
    # step's geometry with no decode row live: no program a ladder size
    own = DecodeEngine(make_model(), tiny_params, capacity=2, num_blocks=8,
                       block_len=8, prefill_chunk=16, prefix_cache=False)
    table[:2] = own.alloc_blocks(2)
    own.seed_slot(1, 7, 0.0)
    was = [np.asarray(p["kv"]).copy() for p in own.pools]
    assert own.prefill(1, chunk[:8], 0, table, last_idx=4) == tok
    assert own.prefill_compiles == 0 and own.mixed_compiles == 1
    for w, p in zip(was, own.pools):
        now = np.asarray(p["kv"])
        assert (now[table[0], 5:] == w[table[0], 5:]).all()
        assert (now[table[0], :5] != w[table[0], :5]).any()


def test_staged_slot_evicted_by_the_decode_build(make_model, tiny_params):
    """Five allocatable blocks, a decoding request that grows into them and
    a younger one that prefills: in some ticks the staged chunk takes the
    last free block and the decode build's allocation then evicts the
    youngest slot — the staged one.  Nothing is dispatched for it, its
    blocks go back, and the tokens are those of the two-call path."""
    shapes = [(6, 20, 0.0), (20, 6, 0.0)]
    kw = dict(capacity=2, num_blocks=6, block_len=8, prefill_chunk=8,
              prefix_cache=False)
    model = make_model()
    eng = DecodeEngine(model, tiny_params, **kw)
    rode, seen = _serve(eng, _requests(shapes), True)
    ref = DecodeEngine(model, tiny_params, **kw)
    alone, _ = _serve(ref, _requests(shapes), False)
    dropped = seen["rode"] - seen["steps"]
    assert dropped >= 1, "no staged chunk was dropped: the case is void"
    assert rode[1].evictions >= dropped
    assert eng.free_blocks() == ref.free_blocks() == 5  # no block lost
    for i in range(2):
        assert rode[i].tokens == alone[i].tokens, i


def test_mixed_step_compiles_once(make_model, tiny_params):
    """One geometry, one program: tails of every length and chunks at
    every depth of the table ride the same ``mixed_step``, within its
    budget of 1 on the compile watch."""
    before = odev.watch().budget_violations
    eng = DecodeEngine(make_model(), tiny_params, capacity=3, num_blocks=48,
                       block_len=8, prefill_chunk=16)
    shapes = [(3, 40, 0.0), (17, 6, 0.0), (41, 5, 0.9), (64, 4, 0.0),
              (29, 7, 0.0), (50, 3, 0.0)]
    _, seen = _serve(eng, _requests(shapes), True)
    assert seen["rode"] >= 8
    assert isinstance(eng._mixed, odev.WatchedFunction)
    assert eng._mixed.compiles == eng.mixed_compiles == 1
    assert eng.decode_compiles == 1
    assert odev.watch().budget_violations == before
    assert "compile_over_budget" not in eng.stats()

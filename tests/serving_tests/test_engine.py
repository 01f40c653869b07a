"""DecodeEngine: the recompile guard and the continuous-batching oracle.

The two acceptance-critical properties of the serving engine:

1. **Zero steady-state recompiles** — the jitted decode step's compiled-
   variant count stays at exactly 1 under arbitrary slot churn (requests
   finishing and being admitted at different lengths).  A second variant
   means some input's shape/dtype varied with occupancy, i.e. the fixed-
   shape contract broke and every admission would pay a compile.
2. **Greedy token identity** — continuous-batched output for every request
   equals a per-request sequential :func:`lm_generate` run.  Interleaving,
   chunked prefill, block-table indirection and the parked writes of idle
   slots must be invisible in the tokens.
"""

import numpy as np
import pytest

from chainermn_tpu.serving import DecodeEngine, Request, Scheduler

pytestmark = [pytest.mark.tier1, pytest.mark.serving]


@pytest.fixture(scope="module")
def fused_engine_run(make_model, tiny_params, prompts):
    """One churny continuous-batching run on the fused engine, shared by
    the recompile guard and the oracle test (compiles amortize)."""
    model = make_model(decode_attention="fused")
    eng = DecodeEngine(
        model, tiny_params, capacity=3, num_blocks=24, block_len=8,
        prefill_chunk=8,
    )
    sched = Scheduler(eng)
    # 5 requests through 3 slots with mixed prompt lengths (5..17): slots
    # retire and re-admit at different positions — the churn the guard is
    # about.
    comps = sched.run([
        Request(id=i, prompt=p, max_new_tokens=10)
        for i, p in enumerate(prompts)
    ])
    return model, eng, comps


def test_steady_state_compiles_exactly_once(fused_engine_run):
    _, eng, comps = fused_engine_run
    assert len(comps) == 5
    assert eng.decode_compiles == 1, (
        f"decode step compiled {eng.decode_compiles} variants — slot "
        "churn changed a traced shape/dtype"
    )
    assert eng.prefill_ladder == (8,)
    assert eng.prefill_compiles == 1, (
        f"prefill compiled {eng.prefill_compiles} variants — chunk "
        "geometries must come from the fixed ladder"
    )


def test_prefill_across_every_context_width_is_one_program_a_chunk_size(
    make_model, tiny_params, oracle
):
    """The gathered read's width follows the chunk's position INSIDE the
    program: a prompt long enough to cross every width of the rehearse
    geometry's ladder (a table of 16 blocks of 8: 2, 4, 8, 16) prefills
    with one compiled program a chunk size, inside the compile watch's
    budget, and serves the tokens the contiguous cache generates.  Since
    PR 42 a plain engine's chunk sizes are two: whole chunks with no decode
    step to ride (``prefill``), and the mixed step's geometry for chunks
    that ride and for every tail, whose rows past the text are inactive."""
    from chainermn_tpu.observability import device as odev
    from chainermn_tpu.ops.decode_attention import (
        context_blocks,
        context_widths,
    )

    model = make_model(max_len=128)
    eng = DecodeEngine(
        model, tiny_params, capacity=2, num_blocks=40, block_len=8,
        max_blocks_per_slot=16, prefill_chunk=16,
    )
    assert eng.prefill_ladder == (8, 16)
    rng = np.random.RandomState(3)
    long, short = (rng.randint(1, 128, size=n).tolist() for n in (100, 21))
    # chunks of 16 from 0, then the tail's ladder size 8 at 96
    crossed = {context_blocks(p0 + size - 1, eng.block_len, eng.max_blocks)
               for p0, size in [(p, 16) for p in range(0, 96, 16)] + [(96, 8)]}
    assert crossed == set(context_widths(16)) == {2, 4, 8, 16}
    # the watch is the process's: other tests of this file recompile on purpose
    violations = odev.watch().budget_violations
    comps = Scheduler(eng).run([
        Request(id=0, prompt=long, max_new_tokens=12),
        Request(id=1, prompt=short, max_new_tokens=12),
    ])
    assert eng.prefill_compiles == 1 and eng.mixed_compiles == 1
    assert eng.decode_compiles == 1
    assert not eng._prefill.over_budget and not eng._step.over_budget
    assert not eng._mixed.over_budget
    assert odev.watch().budget_violations == violations
    assert "compile_over_budget" not in eng.stats()
    for c in comps:
        want = oracle(model, tiny_params, (long, short)[c.id], 12)
        assert c.tokens == want, (c.id, c.tokens, want)


def test_continuous_batching_matches_sequential_greedy(
    fused_engine_run, tiny_params, prompts, oracle
):
    model, _, comps = fused_engine_run
    assert sorted(c.id for c in comps) == list(range(5))
    for c in comps:
        want = oracle(model, tiny_params, prompts[c.id], 10)
        assert c.tokens == want, (c.id, c.tokens, want)
        assert c.reason == "length"


def test_all_blocks_recycled_after_drain(fused_engine_run):
    """After the drain the prefix trie still pins the retired requests'
    full blocks (reuse potential is the point of sharing); dropping the
    cache — the gc/retire pass — returns the allocator to its
    construction baseline, i.e. zero leaked blocks."""
    _, eng, _ = fused_engine_run
    assert eng.prefix.cached_blocks > 0
    assert eng.free_blocks() == (
        eng.pool.num_blocks - 1 - eng.prefix.cached_blocks
    )
    eng.drop_prefix_cache()
    assert eng.free_blocks() == eng.pool.num_blocks - 1


def test_einsum_engine_same_tokens(make_model, tiny_params, prompts, oracle):
    """decode_attention='einsum' engines run the gathered fallback in the
    hot loop — same tokens, same zero-recompile contract."""
    model = make_model()  # einsum default
    eng = DecodeEngine(
        model, tiny_params, capacity=2, num_blocks=24, block_len=8,
        prefill_chunk=8,
    )
    comps = Scheduler(eng).run([
        Request(id=i, prompt=prompts[i], max_new_tokens=6)
        for i in range(3)
    ])
    for c in comps:
        assert c.tokens == oracle(model, tiny_params, prompts[c.id], 6)
    assert eng.decode_compiles == 1


def test_head_width_decides_kernel_or_gathered(make_model, tiny_params,
                                               monkeypatch):
    """The shape decides, not a knob: where the kernel is compiled and not
    interpreted (a chip), a fused model whose ``[k | v]`` panel is not a
    whole number of 128-lane groups (Dh 16: 32 lanes) takes the gathered
    einsum, with no Pallas call in its program; interpreted (this CPU) the
    same model keeps the kernel, as does every width with ``2 * Dh`` a
    multiple of 128 on a chip."""
    import sys

    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops import paged_kernel_takes
    from chainermn_tpu.serving.kv_pool import PagedKVPool

    model = make_model(decode_attention="fused")  # Dh = 64 / 4 = 16
    pools = PagedKVPool(model, num_blocks=8, block_len=8).pools

    def lowered():
        # a new function each time: the trace cache keys on the function,
        # not on what ``_use_interpret`` says
        def step(params, pools, tokens, pos, tables, active):
            return model.apply(
                {"params": params}, tokens[:, None], cache=pools,
                decode_pos=pos, block_tables=tables, slot_mask=active)

        return jax.jit(step).lower(
            tiny_params, pools, jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), jnp.int32), jnp.zeros((2, 4), jnp.int32),
            jnp.ones((2,), bool)).as_text(debug_info=True)

    text = lowered()
    assert "attn.paged" in text and "attn.gathered" not in text
    assert paged_kernel_takes(16)

    mod = sys.modules["chainermn_tpu.ops.decode_attention"]
    monkeypatch.setattr(mod, "_use_interpret", lambda: False)
    assert [d for d in (16, 32, 64, 80, 96, 128, 192, 256)
            if paged_kernel_takes(d)] == [64, 128, 192, 256]
    text = lowered()
    assert "attn.gathered" in text and "attn.paged" not in text
    assert "pallas" not in text and "custom_call" not in text


@pytest.mark.slow  # tier-1 wall budget: the fp and einsum oracle
# twins above stay tier-1; the int8 pool planes are pinned fast by
# the kv_pool battery
def test_int8_paged_engine_matches_sequential_greedy(
    make_model, prompts, oracle
):
    """int8 KV pools: the quant branches of the paged scatter and of both
    decode paths (the Pallas kernel's in-register dequant and the gathered
    einsum fallback) are greedy-identical to the same int8 model's
    contiguous-cache lm_generate.  The fp32-pool tests never touch these
    branches — without this oracle a quant-scatter regression would pass
    tier-1 silently."""
    import jax
    import jax.numpy as jnp

    for attn in ("fused", "einsum"):
        model = make_model(kv_dtype=jnp.int8, decode_attention=attn)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 12), jnp.int32)
        )["params"]
        eng = DecodeEngine(
            model, params, capacity=2, num_blocks=24, block_len=8,
            prefill_chunk=8,
        )
        comps = Scheduler(eng).run([
            Request(id=i, prompt=prompts[i], max_new_tokens=6)
            for i in range(3)
        ])
        for c in comps:
            want = oracle(model, params, prompts[c.id], 6)
            assert c.tokens == want, (attn, c.id, c.tokens, want)
        assert eng.decode_compiles == 1, attn


def test_sampling_deterministic_per_seed(make_model, tiny_params, prompts):
    """Per-slot RNG lanes: same seeds -> same tokens across runs, and the
    lanes are independent of admission order/slot placement."""
    model = make_model(decode_attention="fused")

    def run():
        eng = DecodeEngine(
            model, tiny_params, capacity=2, num_blocks=24, block_len=8,
            prefill_chunk=8,
        )
        comps = Scheduler(eng).run([
            Request(id=i, prompt=prompts[i], max_new_tokens=6,
                    temperature=0.8, seed=42 + i)
            for i in range(3)
        ])
        return {c.id: c.tokens for c in comps}

    assert run() == run()


def test_top_1_sampling_equals_greedy(make_model, tiny_params, prompts,
                                      oracle):
    """top_k=1 with temperature > 0 collapses to argmax: only the top
    logit survives the truncation threshold, so categorical sampling has
    one choice.  Pins the k-th-largest threshold math in the jitted
    sampling branch."""
    model = make_model()
    eng = DecodeEngine(
        model, tiny_params, capacity=2, num_blocks=24, block_len=8,
        prefill_chunk=8, top_k=1,
    )
    comps = Scheduler(eng).run([
        Request(id=i, prompt=prompts[i], max_new_tokens=6,
                temperature=0.9, seed=7 + i)
        for i in range(2)
    ])
    for c in comps:
        assert c.tokens == oracle(model, tiny_params, prompts[c.id], 6)


def test_prefill_rejects_wrong_chunk_shape(make_model, tiny_params):
    eng = DecodeEngine(
        make_model(), tiny_params, capacity=1, num_blocks=8, block_len=8,
        prefill_chunk=8,
    )
    with pytest.raises(ValueError, match="chunk"):
        eng.prefill(0, np.zeros((4,), np.int32), 0,
                    np.zeros((12,), np.int32))


def test_engine_validates_construction(make_model, tiny_params):
    with pytest.raises(ValueError, match="capacity"):
        DecodeEngine(make_model(), tiny_params, capacity=0, num_blocks=8)
    with pytest.raises(ValueError, match="top_k"):
        DecodeEngine(make_model(), tiny_params, capacity=1, num_blocks=8,
                     top_k=-1)

"""``HybridLM``'s ``F`` layer (the Falcon-H1 block: Mamba-2 and attention
side by side of one norm, then a SwiGLU; RoPE; the family's fourteen muP
multipliers) against the plain float32 reference
``perfbench/reference/falcon_h1.py``, which runs the recurrence one position
at a time and attention as a full masked softmax: the whole-row forward,
every multiplier, the decode path (a prefill chunk, decode rows, a chunk
riding decode rows) against the whole row, and the Nemotron kinds left as
they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import HybridLM
from chainermn_tpu.models import hybrid
from chainermn_tpu.ops.decode_attention import pool_shapes
from perfbench import weights
from perfbench.manifest import Manifest
from perfbench.reference import falcon_h1 as ref
from perfbench.reference import nemotron_h
from perfbench.weights import falcon_h1 as tree
from perfbench.weights import nemotron_h as nemotron_tree

pytestmark = pytest.mark.tier1

#: the published multipliers, from the configuration's own file
_PUBLISHED = Manifest().config("Falcon-H1-34B-Instruct")["published"]
MULTIPLIERS = {k: v for k, v in _PUBLISHED.items() if "multiplier" in k}
BASE = dict(vocab=128, n_layers=2, d_model=32, layer_kinds="FF", n_heads=5,
            n_kv_heads=1, head_dim=16, ssm_heads=4, ssm_head_dim=8,
            ssm_groups=2, ssm_state=16, ssm_chunk=8, conv_kernel=4, d_ff=48,
            norm_eps=1e-5, rope_theta=100000000000, **MULTIPLIERS)
T = 32  # four chunks of the scan

#: one case a multiplier: fourteen numbers in nine fields
FOURTEEN = ([(k, None) for k, v in MULTIPLIERS.items()
             if not isinstance(v, list)]
            + [("ssm_multipliers", i) for i in range(5)]
            + [("mlp_multipliers", i) for i in range(2)])


def _model(m):
    return HybridLM(dtype=jnp.float32, param_dtype=jnp.float32,
                    attention="xla", decode_attention="fused", **m)


def _params(m, seed=3):
    return weights.make_params(tree.param_specs(m), seed, jnp.float32)


def _tokens(rows=2, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, T), 0,
                              BASE["vocab"])


def test_the_weight_tree_is_the_programs():
    model = _model(BASE)
    want = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, T), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda s: s[0], tree.param_specs(BASE),
                                 is_leaf=weights._is_spec)
    assert jax.tree_util.tree_map(lambda a: a.shape, want) == got


def test_whole_row_forward_is_the_references():
    params, toks = _params(BASE), _tokens()
    got = _model(BASE).apply({"params": params}, toks)
    want = ref.forward_logits(params, toks, BASE)
    assert got.shape == (2, T, BASE["vocab"]) and got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
    # the init against the multipliers: logits with a spread of order one
    assert 0.5 < float(jnp.std(want)) < 2.0


def test_fourteen_multipliers():
    assert len(FOURTEEN) == 14


@pytest.mark.parametrize("field,index", FOURTEEN)
def test_doubling_a_multiplier_changes_the_logits_as_the_references(field,
                                                                    index):
    """None can be dropped unseen: twice the published value moves the
    logits, and to where the reference's equations put them."""
    m = dict(BASE)
    if index is None:
        m[field] = 2 * BASE[field]
    else:
        m[field] = [2 * v if i == index else v
                    for i, v in enumerate(BASE[field])]
    params, toks = _params(BASE), _tokens(1)
    base = _model(BASE).apply({"params": params}, toks)
    got = _model(m).apply({"params": params}, toks)
    want = ref.forward_logits(params, toks, m)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * scale, field
    assert float(jnp.max(jnp.abs(got - base))) > 1e-3 * scale, field


def test_a_multiplier_left_at_one_adds_no_operation():
    x = jnp.ones((3,))
    assert hybrid._times(x, 1) is x and hybrid._times(x, 1.0) is x


# ------------------------------------------------------------ decode path
S, BL, NB, MB = 3, 4, 24, 12  # slots, block length, blocks, table width


def _cache(model, fill=0.0):
    shape, _ = pool_shapes(NB, BL, BASE["n_kv_heads"], BASE["head_dim"])
    return [{"kv": jnp.zeros(shape, jnp.float32),
             **{k: jnp.full((S,) + sh, fill, dt)
                for k, (sh, dt) in layer.items()}}
            for layer in model.state_shapes()]


def _tables():
    t = np.zeros((S, MB), np.int32)
    t[1, :8] = np.arange(1, 9)
    t[2, :8] = np.arange(9, 17)
    return t


def test_state_shapes_say_what_a_slot_keeps():
    model = HybridLM(dtype=jnp.bfloat16, **BASE)
    assert model.state_shapes() == 2 * [
        {"ssm": ((4, 8, 16), jnp.float32),
         "conv": ((3, 4 * 8 + 2 * 2 * 16), jnp.bfloat16)}]
    with pytest.raises(NotImplementedError, match="decode path"):
        HybridLM(**dict(BASE, layer_kinds="FM")).state_shapes()


@pytest.mark.parametrize("chunk,prompt", [(8, 16), (8, 13), (16, 21), (4, 6)])
def test_chunks_then_decode_rows_are_the_whole_row(chunk, prompt):
    """A prompt prefilled in chunks (the last one short: rows past the text
    are told so by ``chunk_len``) into a slot whose state held something
    else, then decoded a row a step among idle slots: the logits of every
    position are the whole-row forward's, and the other slots' state is
    untouched to the bit."""
    model, params, toks = _model(BASE), _params(BASE), _tokens(1, seed=5)
    whole = model.apply({"params": params}, toks)[0]
    cache, tables = _cache(model, fill=7.0), _tables()
    got = []
    for p0 in range(0, prompt, chunk):
        n = min(chunk, prompt - p0)
        piece = jnp.zeros((1, chunk), jnp.int32).at[0, :n].set(
            toks[0, p0:p0 + n])
        logits, cache = model.apply(
            {"params": params}, piece, cache=cache, decode_pos=jnp.int32(p0),
            block_tables=jnp.asarray(tables[1:2]), state_slot=jnp.int32(1),
            chunk_len=jnp.int32(n))
        got.append(logits[0, :n])
    for p in range(prompt, T):
        tk = jnp.zeros((S, 1), jnp.int32).at[1, 0].set(toks[0, p])
        pos = jnp.zeros((S,), jnp.int32).at[1].set(p)
        logits, cache = model.apply(
            {"params": params}, tk, cache=cache, decode_pos=pos,
            block_tables=jnp.asarray(tables),
            slot_mask=jnp.asarray([False, True, False]))
        got.append(logits[1])
    got = jnp.concatenate(got, 0)
    assert float(jnp.max(jnp.abs(got - whole))) <= 2e-5 * float(
        jnp.max(jnp.abs(whole)))
    for layer in cache:
        for name in ("ssm", "conv"):
            assert np.all(np.asarray(layer[name][0]) == 7.0)
            assert np.all(np.asarray(layer[name][2]) == 7.0)
            assert not np.all(np.asarray(layer[name][1]) == 7.0)


def test_a_chunk_riding_decode_rows_is_the_chunk_alone():
    """``chunk_rows``: slot 2's second chunk (5 rows of 8 hold text) rides
    a decode step of slot 1 — both read what they would alone, hidden
    states and state."""
    model, params = _model(BASE), _params(BASE)
    a, b = _tokens(1, seed=6)[0], _tokens(1, seed=7)[0]
    tables, C = _tables(), 8

    def prefill(cache, toks, slot, p0, n):
        piece = jnp.zeros((1, C), jnp.int32).at[0, :n].set(toks[p0:p0 + n])
        return model.apply(
            {"params": params}, piece, cache=cache, decode_pos=jnp.int32(p0),
            block_tables=jnp.asarray(tables[slot:slot + 1]),
            state_slot=jnp.int32(slot), chunk_len=jnp.int32(n),
            return_hidden=True)

    cache = _cache(model)
    _, cache = prefill(cache, a, 1, 0, 8)      # slot 1 holds 8 positions
    _, cache = prefill(cache, b, 2, 0, 8)      # slot 2 its first chunk
    # apart: slot 1 decodes position 8, then slot 2 takes 5 more rows
    tk = jnp.zeros((S, 1), jnp.int32).at[1, 0].set(a[8])
    pos = jnp.zeros((S,), jnp.int32).at[1].set(8)
    live = jnp.asarray([False, True, False])
    h_dec, apart = model.apply(
        {"params": params}, tk, cache=cache, decode_pos=pos,
        block_tables=jnp.asarray(tables), slot_mask=live, return_hidden=True)
    h_chunk, apart = prefill(apart, b, 2, 8, 5)
    # together
    rows = jnp.concatenate([tk[:, 0], jnp.zeros((C,), jnp.int32).at[:5].set(
        b[8:13])])[:, None]
    row_pos = jnp.concatenate([pos, jnp.minimum(8 + jnp.arange(C), 12)])
    row_tables = jnp.concatenate([
        jnp.asarray(tables), jnp.broadcast_to(jnp.asarray(tables[2:3]),
                                              (C, MB))])
    active = jnp.concatenate([live, jnp.arange(C) < 5])
    h, together = model.apply(
        {"params": params}, rows, cache=cache, decode_pos=row_pos,
        block_tables=row_tables, slot_mask=active, chunk_rows=C,
        state_slot=jnp.int32(2), chunk_len=jnp.int32(5), return_hidden=True)
    np.testing.assert_allclose(h[1, 0], h_dec[1, 0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h[S:S + 5, 0], h_chunk[0, :5], rtol=2e-5,
                               atol=2e-5)
    for x, y in zip(together, apart):
        for name in ("ssm", "conv"):
            np.testing.assert_allclose(x[name], y[name], rtol=2e-5, atol=2e-5)
        # the pool: every position that holds text is the same (slot 2's
        # positions 8..12 are blocks 11 and 12's first row; alone, the
        # chunk's three rows past the text were written too, riding not)
        np.testing.assert_allclose(x["kv"][1:12], y["kv"][1:12], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(x["kv"][12, 0], y["kv"][12, 0], rtol=2e-5,
                                   atol=2e-5)
        assert not np.any(np.asarray(x["kv"][12, 1:]))


def test_a_chunk_at_position_zero_starts_from_zeros():
    model, params, toks = _model(BASE), _params(BASE), _tokens(1, seed=8)
    tables = _tables()

    def first_chunk(fill):
        return model.apply(
            {"params": params}, toks[:, :8], cache=_cache(model, fill),
            decode_pos=jnp.int32(0), block_tables=jnp.asarray(tables[1:2]),
            state_slot=jnp.int32(1), chunk_len=jnp.int32(8))

    (clean, c0), (used, c1) = first_chunk(0.0), first_chunk(3.0)
    np.testing.assert_array_equal(clean, used)
    np.testing.assert_array_equal(c0[0]["ssm"][1], c1[0]["ssm"][1])
    # and one that starts later does not
    later = model.apply(
        {"params": params}, toks[:, :8], cache=_cache(model, 3.0),
        decode_pos=jnp.int32(8), block_tables=jnp.asarray(tables[1:2]),
        state_slot=jnp.int32(1), chunk_len=jnp.int32(8))[1]
    assert float(jnp.max(jnp.abs(later[0]["ssm"][1] - c0[0]["ssm"][1]))) > 0.1


def test_the_cache_is_paged_and_only_f_layers_keep_one():
    model, params, toks = _model(BASE), _params(BASE), _tokens(1)
    with pytest.raises(ValueError, match="block_tables"):
        model.apply({"params": params}, toks[:, :8], cache=_cache(model),
                    decode_pos=jnp.int32(0))
    mixed = dict(BASE, layer_kinds="F*", attention="xla")
    with pytest.raises(NotImplementedError, match="decode path"):
        HybridLM(**mixed).apply(
            {"params": params}, toks[:, :8], cache=_cache(model),
            decode_pos=jnp.int32(0), block_tables=jnp.zeros((1, MB), jnp.int32))


# ------------------------------------------------- the Nemotron kinds stay
def test_the_nemotron_kinds_read_what_their_reference_reads():
    """``M`` / ``*`` / ``E`` layers take the new fields at their neutral
    defaults: the model is still its own reference's, to float32."""
    m = dict(vocab=128, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16,
             attention="xla", ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
             ssm_state=16, ssm_chunk=8, conv_kernel=4, experts_held=4,
             ep_of=2, ep_index=1, experts_per_tok=3, routed_scale=2.5,
             d_expert=24, d_shared=40, norm_eps=1e-5, remat=True,
             n_layers=4, layer_kinds="MEM*")
    params = weights.make_params(nemotron_tree.param_specs(m), 11,
                                 jnp.float32)
    toks = _tokens()
    got = HybridLM(dtype=jnp.float32, param_dtype=jnp.float32, **m).apply(
        {"params": params}, toks)
    want = nemotron_h.forward_logits(params, toks, m)
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(
        jnp.max(jnp.abs(want)))

"""The plain reference and the seeded weights against the program, at a tiny
size on the CPU, both families of block (GQA + RoPE, MHA + learned)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perfbench import weights
from perfbench.reference import adafactor as raf
from perfbench.reference import transformer_lm as ref

pytestmark = [pytest.mark.tier1, pytest.mark.slow]

#: Every case here that traces and compiles a model on the eight virtual
#: devices is `slow`: run beside the serving tests that assert latencies
#: (tests/serving_tests/test_serve_obs.py) under six xdist workers on the
#: 8-core sandbox, they made one of those fail in 5 full runs of 6, and none
#: failed in 3 of 3 without them.  `pytest -m slow tests/perfbench_tests`
#: runs them (about four minutes).
heavy = pytest.mark.slow

SHAPES = {
    # d_ff and d_model >= 128 so that Adafactor factors some leaves
    "gqa_rope": dict(vocab=256, n_layers=2, d_model=128, n_heads=4,
                     n_kv_heads=2, d_ff=256, max_len=32, pos_enc="rope"),
    "mha_learned": dict(vocab=256, n_layers=2, d_model=128, n_heads=4,
                        d_ff=256, max_len=32, pos_enc="learned"),
}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def case(request):
    from chainermn_tpu.models import TransformerLM

    m = SHAPES[request.param]
    with jax.default_matmul_precision("highest"):
        model = TransformerLM(dtype=jnp.float32, param_dtype=jnp.float32,
                              attention="xla", **m)
        params = weights.make_params(m, 2**31 + 7, jnp.float32)
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, 256, (2, 32)), jnp.int32)
        tgts = jnp.asarray(rng.randint(0, 256, (2, 32)), jnp.int32)
        yield m, model, params, toks, tgts


def test_weights_tree_is_the_programs(case):
    m, model, params, toks, _ = case
    spec = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(spec) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(spec),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape
    assert weights.n_params(m) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    again = weights.make_params(m, 2**31 + 7, jnp.float32)
    other = weights.make_params(m, 2**31 + 8, jnp.float32)
    assert all((a == b).all() for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)))
    assert any((a != b).any() for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(other)))


def test_reference_logits_match_the_program(case):
    m, model, params, toks, _ = case
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, toks)
    got = ref.forward_logits(params, toks, use_rope=m["pos_enc"] == "rope")
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(jnp.std(want)) + 1e-5


def test_reference_loss_and_gradients_match_autodiff_of_the_program(case):
    from chainermn_tpu.models import lm_loss

    m, model, params, toks, tgts = case
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(lm_loss(model), has_aux=True)(
            params, (toks, tgts))
    got = {}
    mine = ref.loss_and_grads(
        params, toks, tgts, use_rope=m["pos_enc"] == "rope",
        on_layer_grads=lambda name, gr: got.__setitem__(name, gr))
    assert mine == pytest.approx(float(loss), rel=1e-6)
    assert set(got) == set(g)
    for k in g:
        for a, b in zip(jax.tree_util.tree_leaves(g[k]),
                        jax.tree_util.tree_leaves(got[k])):
            scale = float(jnp.max(jnp.abs(a))) + 1e-12
            assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


def test_plain_adafactor_is_optaxs(case):
    m, _, params, _, _ = case
    tx = optax.adafactor(1e-2)
    st, p = tx.init(params), params
    rp, rst = params, raf.init(params)
    for t in range(3):
        g = jax.tree_util.tree_map(
            lambda x: jnp.cos(x * (37.0 + t)) * 1e-3, params)
        u, st = tx.update(g, st, p)
        p = optax.apply_updates(p, u)
        rp, rst = raf.update(rp, g, rst, t, 1e-2)
    for a, b, c in zip(jax.tree_util.tree_leaves(p),
                       jax.tree_util.tree_leaves(rp),
                       jax.tree_util.tree_leaves(params)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-3 * float(
            jnp.max(jnp.abs(a - c))) + 1e-9


def test_first_gradient_norm_is_recovered_from_adafactor_state(case):
    from perfbench.optim import adafactor as oa

    m, _, params, _, _ = case
    g = jax.tree_util.tree_map(lambda x: jnp.sin(x * 91.0) * 1e-2, params)
    tx = oa.make(1e-2)
    _, st = tx.update(g, tx.init(params), params)
    got = oa.first_grad_norms(st, params)
    want = oa.leaf_dict(jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), g))
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-4), k


def test_int8_control_moves_the_logits_and_float32_does_not(case):
    m, _, params, toks, _ = case
    rope = m["pos_enc"] == "rope"
    a = ref.forward_logits(params, toks, use_rope=rope)
    b = ref.forward_logits(params, toks, use_rope=rope)
    q = ref.forward_logits(params, toks, use_rope=rope, quant="int8")
    assert float(jnp.max(jnp.abs(a - b))) == 0.0
    assert float(jnp.max(jnp.abs(a - q))) > 1e-3

"""``HybridLM`` (a depth of ``M`` / ``*`` / ``E`` layers) against the plain
float32 reference ``perfbench/reference/nemotron_h.py``, which runs the
recurrence over time and one masked expert at a time: each mixer's forward
and gradients, the chunked scan against the recurrence, the expert layer's
shares, its worst routing, and the model through ``make_train_step``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chainermn_tpu as cmn
from chainermn_tpu.models import HybridLM, lm_loss_chunked
from chainermn_tpu.ops.ssd_scan import causal_depthwise_conv, ssd_scan
from chainermn_tpu.parallel.held_experts import (
    held_experts_ffn,
    held_range,
    sigmoid_topk_route,
)
from perfbench import weights
from perfbench.reference import nemotron_h as ref
from perfbench.weights import nemotron_h as tree

pytestmark = pytest.mark.tier1

BASE = dict(vocab=128, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16,
            attention="xla", ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
            ssm_state=16, ssm_chunk=8, conv_kernel=4, experts_held=4,
            ep_of=2, ep_index=1, experts_per_tok=3, routed_scale=2.5,
            d_expert=24, d_shared=40, norm_eps=1e-5, remat=True)
T = 32  # four chunks of the scan


def fields(kinds):
    return dict(BASE, n_layers=len(kinds), layer_kinds=kinds)


def ssd_recurrence(x, dt, A, B, C, *, D=None):
    """What ``ssd_scan`` is held to: the same result one position at a time
    (a ``lax.scan`` over ``T``) in float32.  Returns ``(y, final_state)``."""
    Bsz, T, H, P = x.shape
    R = H // B.shape[2]

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp                   # (b,h,p) (b,h) (b,g,n)
        b_h = jnp.repeat(b_t, R, axis=1)
        c_h = jnp.repeat(c_t, R, axis=1)
        s = (s * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return s, jnp.sum(s * c_h[:, :, None, :], axis=-1)

    seq = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
                for v in (x, dt, B, C))
    final, y = jax.lax.scan(
        step, jnp.zeros((Bsz, H, P, B.shape[3]), jnp.float32), seq)
    y = jnp.moveaxis(y, 0, 1)
    if D is not None:
        y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[:, None]
    return y, final


@pytest.fixture(scope="module", params=["M", "*", "E", "MEM*E"])
def case(request):
    m = fields(request.param)
    with jax.default_matmul_precision("highest"):
        model = HybridLM(dtype=jnp.float32, param_dtype=jnp.float32, **m)
        params = weights.make_params(tree.param_specs(m), 2**31 + 5,
                                     jnp.float32)
        rng = np.random.RandomState(1)
        toks = jnp.asarray(rng.randint(0, 128, (2, T)), jnp.int32)
        tgts = jnp.asarray(rng.randint(0, 128, (2, T)), jnp.int32)
        yield m, model, params, toks, tgts


def test_weight_tree_is_the_programs(case):
    m, model, params, toks, _ = case
    want = jax.eval_shape(
        lambda k: model.init(k, toks)["params"], jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, want) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    assert not any("bias" in jax.tree_util.keystr(p) and "conv" not in
                   jax.tree_util.keystr(p) and "dt_bias" not in
                   jax.tree_util.keystr(p)
                   for p, _ in jax.tree_util.tree_flatten_with_path(want)[0])


def test_forward_matches_the_reference(case):
    m, model, params, toks, _ = case
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, toks)
        want = ref.forward_logits(params, toks, m)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_loss_and_gradients_match_the_reference(case):
    m, model, params, toks, tgts = case
    grads = {}
    with jax.default_matmul_precision("highest"):
        (loss, metrics), got = jax.value_and_grad(
            lm_loss_chunked(model, chunk_size=64), has_aux=True)(
                params, (toks, tgts))
        want = ref.loss_and_grads(params, toks, tgts, m,
                                  on_layer_grads=grads.__setitem__)
    assert abs(float(loss) - want) < 1e-5 * want
    assert set(grads) == set(got)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(
                                {k: grads[k] for k in got})):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * scale + 1e-9, \
            jax.tree_util.keystr(path)
    if "E" in m["layer_kinds"]:
        assert float(metrics["moe_pairs_dropped"]) == 0.0
        assert float(metrics["moe_pairs_held"]) > 0.0
        assert float(metrics["moe_rows_max_over_mean"]) >= 1.0
    else:
        assert "moe_pairs_held" not in metrics


def test_loss_takes_a_head_without_bias(case):
    m, model, params, toks, tgts = case
    assert "bias" not in params["lm_head"]
    logits = model.apply({"params": params}, toks)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits), tgts[..., None], -1)
    loss, _ = lm_loss_chunked(model, chunk_size=48)(params, (toks, tgts))
    np.testing.assert_allclose(loss, jnp.mean(ce), rtol=1e-5)


# ------------------------------------------------------------------ scan
def _scan_inputs(rng, b, t, H, P, G, N):
    x = jnp.asarray(rng.randn(b, t, H, P), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                        (b, t, H))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.randn(b, t, G, N), jnp.float32)
    C = jnp.asarray(rng.randn(b, t, G, N), jnp.float32)
    D = jnp.asarray(rng.randn(H), jnp.float32)
    return x, dt, A, B, C, D


@pytest.mark.parametrize("t,chunk,H,G", [(32, 8, 4, 2), (48, 16, 6, 1),
                                          (16, 16, 2, 2), (64, 8, 8, 8)])
def test_chunked_scan_is_the_recurrence(t, chunk, H, G):
    x, dt, A, B, C, D = _scan_inputs(np.random.RandomState(t + H), 2, t, H,
                                     8, G, 16)
    with jax.default_matmul_precision("highest"):
        got, state = ssd_scan(x, dt, A, B, C, chunk=chunk, D=D,
                              return_state=True)
        want, want_state = ssd_recurrence(x, dt, A, B, C, D=D)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=1e-5)


def test_a_state_carried_across_calls_continues_the_sequence():
    x, dt, A, B, C, D = _scan_inputs(np.random.RandomState(3), 2, 64, 4, 8,
                                     2, 16)
    with jax.default_matmul_precision("highest"):
        want, want_state = ssd_recurrence(x, dt, A, B, C, D=D)
        first, s = ssd_scan(x[:, :24], dt[:, :24], A, B[:, :24], C[:, :24],
                            chunk=8, D=D, return_state=True)
        second, s = ssd_scan(x[:, 24:], dt[:, 24:], A, B[:, 24:], C[:, 24:],
                             chunk=8, D=D, initial_state=s, return_state=True)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), want,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s, want_state, rtol=1e-4, atol=1e-5)


def test_chunked_scan_gradients_are_the_recurrences():
    x, dt, A, B, C, D = _scan_inputs(np.random.RandomState(4), 1, 32, 4, 8,
                                     2, 16)
    w = jnp.asarray(np.random.RandomState(5).randn(1, 32, 4, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(w * ssd_scan(
            *a[:5], chunk=8, D=a[5])), argnums=range(6))(x, dt, A, B, C, D)
        want = jax.grad(lambda *a: jnp.sum(w * ssd_recurrence(
            *a[:5], D=a[5])[0]), argnums=range(6))(x, dt, A, B, C, D)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-4,
                                   atol=2e-5 * float(jnp.max(jnp.abs(r))))


def test_scan_refuses_a_length_that_is_no_whole_number_of_chunks():
    x, dt, A, B, C, _ = _scan_inputs(np.random.RandomState(0), 1, 12, 2, 4,
                                     1, 8)
    with pytest.raises(ValueError, match="multiple of chunk"):
        ssd_scan(x, dt, A, B, C, chunk=8)


def test_causal_depthwise_conv_sees_only_the_past():
    rng = np.random.RandomState(0)
    x, k, b = rng.randn(2, 10, 6), rng.randn(4, 6), rng.randn(6)
    want = np.zeros_like(x)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += k[j] * x[:, t - 3 + j]
    got = causal_depthwise_conv(jnp.asarray(x), jnp.asarray(k), jnp.asarray(b))
    np.testing.assert_allclose(got, want + b, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------- experts
def _layer(rng, n=40, D=16, F=12, S=20, n_all=8):
    p = {"router": rng.randn(D, n_all) * 0.5,
         "experts_up": rng.randn(n_all, D, F) * 0.3,
         "experts_down": rng.randn(n_all, F, D) * 0.3,
         "shared_up": {"kernel": rng.randn(D, S) * 0.3},
         "shared_down": {"kernel": rng.randn(S, D) * 0.3}}
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)
    return p, jnp.asarray(rng.randn(1, n, D), jnp.float32)


def _geometry(held, of, index, k=3):
    return {"experts_held": held, "ep_of": of, "ep_index": index,
            "experts_per_tok": k, "routed_scale": 2.5}


@pytest.mark.parametrize("of", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(of):
    """Every share's routed part, plus the shared expert counted once, is
    what the uncut reference gives for the whole layer."""
    p, u = _layer(np.random.RandomState(of))
    n_all, flat = 8, u[0]
    held = n_all // of
    e_bias = ref.router_bias(2, n_all)
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(p, u, e_bias, _geometry(n_all, 1, 0), None)[0]
        shared = ref.relu2(flat @ p["shared_up"]["kernel"]) \
            @ p["shared_down"]["kernel"]
        experts, w = sigmoid_topk_route(flat, p["router"], e_bias, 3,
                                        scale=2.5)
        total, pairs = shared, 0.0
        for i in range(of):
            lo, hi = held_range(i, of, held)
            part, counters = held_experts_ffn(
                flat, experts, w, p["experts_up"][lo:hi],
                p["experts_down"][lo:hi], lo=lo)
            # the reference handed the same share leaves out the same pairs
            mine = ref.experts(p | {"experts_up": p["experts_up"][lo:hi],
                                    "experts_down": p["experts_down"][lo:hi]},
                               u, e_bias, _geometry(held, of, i), None)[0]
            np.testing.assert_allclose(part + shared, mine, rtol=1e-4,
                                       atol=1e-5)
            assert float(counters["moe_pairs_dropped"]) == 0.0
            total, pairs = total + part, pairs + counters["moe_pairs_held"]
    assert float(pairs) == flat.shape[0] * 3  # every pair lives somewhere
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


def test_dropless_under_the_worst_routing():
    """Every token sent to ONE held expert (and to two experts elsewhere):
    that expert's group holds every row, and none is dropped."""
    p, u = _layer(np.random.RandomState(9))
    flat, n = u[0], u.shape[1]
    lo, hi = held_range(1, 2, 4)
    experts = jnp.tile(jnp.asarray([[lo + 2, 0, 1]], jnp.int32), (n, 1))
    w = jnp.asarray(np.random.RandomState(1).rand(n, 3), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, counters = held_experts_ffn(
            flat, experts, w, p["experts_up"][lo:hi],
            p["experts_down"][lo:hi], lo=lo)
        want = w[:, :1] * (ref.relu2(flat @ p["experts_up"][lo + 2])
                           @ p["experts_down"][lo + 2])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert float(counters["moe_pairs_held"]) == n
    assert float(counters["moe_pairs_dropped"]) == 0.0
    assert float(counters["moe_rows_max_over_mean"]) == 4.0
    # and its gradients are those of the dense sum
    g = jax.grad(lambda x: jnp.sum(held_experts_ffn(
        x, experts, w, p["experts_up"][lo:hi], p["experts_down"][lo:hi],
        lo=lo)[0] ** 2))(flat)
    r = jax.grad(lambda x: jnp.sum((w[:, :1] * (
        ref.relu2(x @ p["experts_up"][lo + 2])
        @ p["experts_down"][lo + 2])) ** 2))(flat)
    np.testing.assert_allclose(g, r, rtol=1e-3, atol=1e-5)


def test_a_shard_that_holds_no_chosen_expert_adds_nothing():
    p, u = _layer(np.random.RandomState(2))
    experts = jnp.tile(jnp.asarray([[0, 1, 2]], jnp.int32), (u.shape[1], 1))
    got, counters = held_experts_ffn(
        u[0], experts, jnp.ones(experts.shape, jnp.float32),
        p["experts_up"][4:], p["experts_down"][4:], lo=4)
    assert float(jnp.max(jnp.abs(got))) == 0.0
    assert float(counters["moe_pairs_held"]) == 0.0


def test_held_range_is_contiguous_by_rank():
    assert [held_range(i, 8, 16) for i in (0, 3, 7)] == \
        [(0, 16), (48, 64), (112, 128)]
    with pytest.raises(ValueError):
        held_range(8, 8, 16)


# ------------------------------------------------------------- train step
def test_one_compile_and_a_falling_loss_through_make_train_step():
    import optax

    m = fields("MEM*E")
    model = HybridLM(dtype=jnp.float32, param_dtype=jnp.float32, **m)
    comm = cmn.create_communicator("xla", devices=jax.devices()[:1])
    opt = cmn.create_multi_node_optimizer(optax.adafactor(1e-2), comm)
    state = opt.init(weights.make_params(tree.param_specs(m), 11,
                                         jnp.float32))
    step = opt.make_train_step(lm_loss_chunked(model, chunk_size=64),
                               has_aux=True)
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randint(0, 128, (1, T + 1)), jnp.int32)
    batch = (rows[:, :-1], rows[:, 1:])
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert step._cache_size() == 1
    assert losses[-1] < losses[0] - 0.05, losses
    assert float(metrics["moe_pairs_dropped"]) == 0.0


def test_layer_string_is_checked():
    m = dict(fields("MEM"), layer_kinds="MX")
    model = HybridLM(dtype=jnp.float32, param_dtype=jnp.float32, **m)
    with pytest.raises(ValueError, match="layer_kinds"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

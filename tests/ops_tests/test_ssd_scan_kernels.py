"""The chunked scan's Pallas kernels (``ops/ssd_scan.py``: ``ssd_fwd``,
``ssd_bwd``) in interpret mode on the CPU, at small shapes that keep the
structure — three or more chunks, groups of several heads, two heads of 64
side by side in one stretch of lanes and a head of 128 alone — against the
``jax.numpy`` body (what every other call runs, and the kernels' oracle) and
against the recurrence one position at a time; and the rule by which
``ssd_scan`` picks a body.  On the chip the kernels compile through Mosaic:
``test_tpu_compile.py`` holds that, at the hybrid cell's shape."""

import inspect
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chainermn_tpu.ops.ssd_scan  # noqa: F401  (the module, below)
from chainermn_tpu.models import HybridLM

pytestmark = pytest.mark.tier1

S = sys.modules["chainermn_tpu.ops.ssd_scan"]

#: name -> (batch, T, chunk, H, P, G, N)
GEOMETRIES = {
    "pairs_of_64": (2, 48, 16, 4, 64, 2, 16),   # two heads a lane tile
    "head_of_128": (2, 32, 8, 2, 128, 1, 8),    # one head a lane tile
    "narrow_heads": (2, 40, 8, 6, 8, 2, 16),    # three heads of 8 a group
    "groups_are_heads": (2, 24, 8, 3, 16, 3, 8),
}


def ssd_recurrence(x, dt, A, B, C, *, D=None):
    """``test_hybrid_lm.py``'s oracle: the recurrence one position at a
    time in float32."""
    Bsz, T, H, P = x.shape
    R = H // B.shape[2]

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, R, axis=1)
        c_h = jnp.repeat(c_t, R, axis=1)
        s = (s * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return s, jnp.sum(s * c_h[:, :, None, :], axis=-1)

    seq = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
                for v in (x, dt, B, C))
    _, y = jax.lax.scan(
        step, jnp.zeros((Bsz, H, P, B.shape[3]), jnp.float32), seq)
    y = jnp.moveaxis(y, 0, 1)
    if D is not None:
        y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[:, None]
    return y


def _inputs(name, dtype=jnp.float32, seed=0):
    b, t, chunk, H, P, G, N = GEOMETRIES[name]
    rng = np.random.RandomState(seed + t + H)
    x = jnp.asarray(rng.randn(b, t, H, P), dtype)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                        (b, t, H))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.randn(b, t, G, N), dtype)
    C = jnp.asarray(rng.randn(b, t, G, N), dtype)
    D = jnp.asarray(rng.randn(H), jnp.float32)
    w = jnp.asarray(rng.randn(b, t, H, P), jnp.float32)
    return (x, dt, A, B, C, D), w, chunk


def _close(got, want, rtol, atol_of_scale):
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=rtol,
        atol=atol_of_scale * float(np.max(np.abs(want))))


@pytest.mark.parametrize("with_D", [True, False], ids=["D", "no_D"])
@pytest.mark.parametrize("name", list(GEOMETRIES))
def test_forward_is_the_jnp_body_and_the_recurrence(name, with_D):
    (x, dt, A, B, C, D), _, chunk = _inputs(name)
    D = D if with_D else None
    with jax.default_matmul_precision("highest"):
        got = S._ssd_scan_kernels(x, dt, A, B, C, D, chunk=chunk)
        body = S._ssd_scan_xla(x, dt, A, B, C, chunk=chunk, D=D)
        want = ssd_recurrence(x, dt, A, B, C, D=D)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    np.testing.assert_allclose(got, body, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _grads(fn, args, w, with_D=True):
    n = 6 if with_D else 5

    def loss(*a):
        return jnp.sum(w * fn(*a[:5], a[5] if with_D else None))

    return jax.grad(loss, argnums=tuple(range(n)))(*args[:n])


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "under_checkpoint"])
@pytest.mark.parametrize("with_D", [True, False], ids=["D", "no_D"])
@pytest.mark.parametrize("name", list(GEOMETRIES))
def test_every_gradient_is_autodiffs_of_the_jnp_body(name, with_D,
                                                     checkpoint):
    args, w, chunk = _inputs(name, seed=1)

    def kernels(x, dt, A, B, C, D):
        return S._ssd_scan_kernels(x, dt, A, B, C, D, chunk=chunk)

    def body(x, dt, A, B, C, D):
        return S._ssd_scan_xla(x, dt, A, B, C, chunk=chunk, D=D)

    mine = jax.checkpoint(kernels) if checkpoint else kernels
    with jax.default_matmul_precision("highest"):
        got = _grads(mine, args, w, with_D)
        want = _grads(body, args, w, with_D)
    for g, r, leaf in zip(got, want, "x dt A B C D".split()):
        assert g.shape == r.shape and g.dtype == r.dtype, leaf
        np.testing.assert_allclose(
            g, r, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(r))),
            err_msg=leaf)


@pytest.mark.parametrize("name", ["pairs_of_64", "narrow_heads"])
def test_gradients_are_the_recurrences(name):
    args, w, chunk = _inputs(name, seed=2)
    with jax.default_matmul_precision("highest"):
        got = _grads(lambda *a: S._ssd_scan_kernels(*a, chunk=chunk),
                     args, w)
        want = _grads(lambda x, dt, A, B, C, D: ssd_recurrence(
            x, dt, A, B, C, D=D), args, w)
    for g, r, leaf in zip(got, want, "x dt A B C D".split()):
        np.testing.assert_allclose(
            g, r, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(r))),
            err_msg=leaf)


@pytest.mark.parametrize("name", ["pairs_of_64", "head_of_128"])
def test_bfloat16_operands_stay_beside_the_jnp_body(name):
    """bfloat16 ``x``, ``B`` and ``C`` (the cell's dtypes): both bodies
    round their matmul operands to bfloat16, at different places, so they
    stand as near each other as each to the float32 recurrence."""
    args, w, chunk = _inputs(name, jnp.bfloat16, seed=3)
    f32 = tuple(a.astype(jnp.float32) for a in args)
    got_y = S._ssd_scan_kernels(*args, chunk=chunk)
    body_y = S._ssd_scan_xla(*args[:5], chunk=chunk, D=args[5])
    want_y = ssd_recurrence(*f32[:5], D=f32[5])
    assert got_y.dtype == jnp.float32
    _close(got_y, want_y, 2e-2, 1e-2)
    _close(got_y, body_y, 2e-2, 1e-2)
    got = _grads(lambda *a: S._ssd_scan_kernels(*a, chunk=chunk), args, w)
    want = _grads(lambda x, dt, A, B, C, D: ssd_recurrence(
        x, dt, A, B, C, D=D), f32, w)
    for g, a, r, leaf in zip(got, args, want, "x dt A B C D".split()):
        assert g.dtype == a.dtype and g.shape == a.shape, leaf
        err = np.linalg.norm(np.asarray(g, np.float32) - np.asarray(r))
        assert err <= 2e-2 * np.linalg.norm(np.asarray(r)), (leaf, err)


@pytest.mark.parametrize("seed", [0, 1])
def test_bfloat16_gradients_are_as_near_the_truth_as_the_jnp_bodys(seed):
    """Strong decays (``A`` down to -16, as `test_hybrid_lm.py` draws them)
    in bfloat16: a running sum's gradient through the decay tile is a
    difference of two sums that all but cancel, and a kernel that takes the
    two from differently rounded numbers reads ``A``'s gradient 5-12% off
    and ``dt``'s 1% (a first version here did, on the chip too) where the
    ``jax.numpy`` body reads 0.2%.  Held leaf by leaf to the float32
    recurrence's gradient, beside the body's own distance."""
    b, T, Q, H, P, G, N = 1, 256, 64, 4, 64, 2, 32
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(b, T, H, P), jnp.bfloat16)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                        (b, T, H))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.randn(b, T, G, N), jnp.bfloat16)
    C = jnp.asarray(rng.randn(b, T, G, N), jnp.bfloat16)
    D = jnp.asarray(rng.randn(H), jnp.float32)
    w = jnp.asarray(rng.randn(b, T, H, P), jnp.float32)
    args = (x, dt, A, B, C, D)
    f32 = tuple(a.astype(jnp.float32) for a in args)
    with jax.default_matmul_precision("highest"):
        truth = _grads(lambda x, dt, A, B, C, D: S._ssd_scan_xla(
            x, dt, A, B, C, chunk=Q, D=D), f32, w)
    got = _grads(lambda *a: S._ssd_scan_kernels(*a, chunk=Q), args, w)
    body = _grads(lambda x, dt, A, B, C, D: S._ssd_scan_xla(
        x, dt, A, B, C, chunk=Q, D=D), args, w)

    def off(g, r):
        r = np.asarray(r, np.float32)
        return np.linalg.norm(np.asarray(g, np.float32) - r) / np.linalg.norm(r)

    for g, o, r, leaf in zip(got, body, truth, "x dt A B C D".split()):
        assert off(g, r) <= 1.5 * off(o, r) + 1e-4, (leaf, off(g, r),
                                                      off(o, r))


# ------------------------------------------------------------ which body
_TILES = dict(x=(1, 512, 16, 64), B=(1, 512, 2, 128), chunk=128)


def _pick(monkeypatch, *, on_tpu, x, B, chunk, dtype=jnp.bfloat16,
          b_dtype=None, **state):
    """Which body ``ssd_scan`` hands a call of these shapes to."""
    picked = []
    monkeypatch.setattr(S, "_use_interpret", lambda: not on_tpu)
    monkeypatch.setattr(S, "_ssd_scan_kernels",
                        lambda *a, **k: picked.append("kernels"))
    monkeypatch.setattr(S, "_ssd_scan_xla",
                        lambda *a, **k: picked.append("jnp"))
    T, H = x[1], x[2]
    S.ssd_scan(jnp.zeros(x, dtype), jnp.zeros((1, T, H), jnp.float32),
               jnp.zeros((H,), jnp.float32),
               jnp.zeros(B, b_dtype or dtype), jnp.zeros(B, b_dtype or dtype),
               chunk=chunk, **state)
    return picked


_STATE = jnp.zeros((1, 16, 64, 128), jnp.float32)


@pytest.mark.parametrize("case,want", [
    # the training call on the chip: whole sequence, four chunks that tile
    (dict(on_tpu=True, **_TILES), "kernels"),
    (dict(on_tpu=True, **_TILES, dtype=jnp.float32), "kernels"),
    # any backend but the TPU (every test, rehearsal and reference here)
    (dict(on_tpu=False, **_TILES), "jnp"),
    # serving's prefill chunk: from a slot's state to a slot's state
    (dict(on_tpu=True, **_TILES, initial_state=_STATE, return_state=True),
     "jnp"),
    (dict(on_tpu=True, **_TILES, initial_state=_STATE), "jnp"),
    (dict(on_tpu=True, **_TILES, return_state=True), "jnp"),
    # one chunk only
    (dict(on_tpu=True, x=(1, 128, 16, 64), B=(1, 128, 2, 128), chunk=128),
     "jnp"),
    # shapes that do not fill the kernels' tiles
    (dict(on_tpu=True, x=(1, 512, 16, 64), B=(1, 512, 2, 128), chunk=64),
     "jnp"),
    (dict(on_tpu=True, x=(1, 512, 16, 64), B=(1, 512, 2, 64), chunk=128),
     "jnp"),
    (dict(on_tpu=True, x=(1, 512, 2, 64), B=(1, 512, 2, 128), chunk=128),
     "jnp"),
    # two matmul dtypes
    (dict(on_tpu=True, **_TILES, b_dtype=jnp.float32), "jnp"),
], ids=["tpu_training_call", "tpu_float32", "cpu", "tpu_state_in_and_out",
        "tpu_state_in", "tpu_state_out", "tpu_one_chunk", "tpu_chunk_64",
        "tpu_state_64_columns", "tpu_one_head_of_64_a_group",
        "tpu_mixed_dtypes"])
def test_which_body_a_call_takes(monkeypatch, case, want):
    assert _pick(monkeypatch, **case) == [want]


def test_a_group_whose_states_overflow_vmem_takes_the_jnp_body(monkeypatch):
    """T = 64k at the cell's widths: 512 chunk states of 256 KB a group."""
    monkeypatch.setattr(S, "_use_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((1, 65536, 8, 64), jnp.bfloat16)
    B = jax.ShapeDtypeStruct((1, 65536, 1, 128), jnp.bfloat16)
    assert not S._kernels_take(x, B, B, chunk=128, initial_state=None,
                               return_state=False)
    x = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16)
    B = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)
    assert S._kernels_take(x, B, B, chunk=128, initial_state=None,
                           return_state=False)


def test_nobody_chooses_a_body():
    """No environment variable, no keyword: the signature is the parent's
    and neither the module nor the model reads a ``CMN_*`` name for it."""
    assert list(inspect.signature(S.ssd_scan).parameters) == [
        "x", "dt", "A", "B", "C", "chunk", "D", "initial_state",
        "return_state"]
    hybrid = sys.modules["chainermn_tpu.models.hybrid"]
    for module in (S, hybrid):
        source = inspect.getsource(module)
        assert "CMN_" not in source and "environ" not in source, module
    fields = set(HybridLM.__dataclass_fields__)
    assert not {f for f in fields
                if "ssd" in f or "scan" in f or "pallas" in f}, fields

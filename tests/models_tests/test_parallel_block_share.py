"""The parallel block's share and its whole (``HybridLM``'s ``W`` / ``G``
layers; ``perfbench/reference/command_a_plus.py``):

* the 8 shares' routed parts — each chip's held experts, the router all
  experts wide — with attention and the shared experts counted once, add up
  to the uncut reference's layer: what the cut leaves out of a chip's sum is
  exactly what the other chips hold;
* the program's layer at one share is the reference's at that share;
* the gated activation did not change the program the training cell lowers:
  ``held_experts_ffn``'s ``relu2`` call lowers to the parent's text;
* interleaved RoPE is the reference's, and is not the half-split rotation.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import HybridLM
from chainermn_tpu.ops.rope import apply_rope
from chainermn_tpu.parallel.held_experts import (
    held_experts_ffn,
    relu2,
    swiglu,
)
from perfbench import weights
from perfbench.reference import command_a_plus as ref
from perfbench.weights import command_a_plus as tree

pytestmark = pytest.mark.tier1

WHOLE = dict(vocab=64, n_layers=2, d_model=32, layer_kinds="WG", n_heads=4,
             n_kv_heads=2, head_dim=8, window=6, rope_theta=50000.0,
             rope_interleaved=True, norm="layer", norm_eps=1e-5,
             tie_embeddings=True, lm_head_multiplier=1, experts_held=16,
             ep_of=1, ep_index=0, experts_per_tok=4, d_expert=16, n_shared=2,
             d_shared=32)
SHARES = 8


def _share(params, index):
    """Chip ``index``'s parameters: its 2 of the 16 routed experts, the
    router all 16 wide, everything else whole."""
    held = WHOLE["experts_held"] // SHARES
    out = {}
    for name, leaf in params.items():
        if name.startswith("block_"):
            leaf = dict(leaf)
            for key in ("experts_gate_up", "experts_down"):
                leaf[key] = leaf[key][index * held:(index + 1) * held]
        out[name] = leaf
    return out, dict(WHOLE, experts_held=held, ep_of=SHARES, ep_index=index)


@pytest.fixture(scope="module")
def params():
    return weights.make_params(tree.param_specs(WHOLE), 2**31 + 7,
                               jnp.float32)


@pytest.mark.parametrize("kind", ["W", "G"])
def test_the_shares_add_up_to_the_uncut_layer(params, kind):
    h = jax.random.normal(jax.random.PRNGKey(3), (24, 32))
    p = params["block_0"]
    whole = ref.block(p, h, geometry=ref._geometry(WHOLE),
                      windowed=kind == "W")
    parts, routed = [], []
    for i in range(SHARES):
        sp, sm = _share(params, i)
        g = ref._geometry(sm)
        parts.append(ref.block(sp["block_0"], h, geometry=g,
                               windowed=kind == "W"))
        u = ref.layer_norm(h, sp["block_0"]["norm"], 1e-5)
        routed.append(ref.experts(sp["block_0"], u, dict(g), None))
    # each share = h + attention + shared + its routed part; attention and
    # the shared experts are the same in all eight
    u = ref.layer_norm(h, p["norm"], 1e-5)
    sp, sm = _share(params, 0)  # a range no token can choose: shared alone
    none = ref.experts(sp["block_0"], u,
                       dict(ref._geometry(dict(sm, ep_index=SHARES))), None)
    once = parts[0] - (routed[0] - none)  # h + attention + shared
    total = once + sum(r - none for r in routed)
    assert float(jnp.max(jnp.abs(total - whole))) < 2e-5
    # and no share is the whole: the cut leaves something out
    assert all(float(jnp.max(jnp.abs(x - whole))) > 1e-3 for x in parts)


@pytest.mark.parametrize("index", [0, 5])
def test_the_programs_share_is_the_references(params, index):
    sp, sm = _share(params, index)
    model = HybridLM(dtype=jnp.float32, param_dtype=jnp.float32, **sm)
    toks = jnp.asarray(np.random.RandomState(1).randint(1, 64, size=(2, 20)))
    got = model.apply({"params": sp}, toks)
    want = ref.forward_logits(sp, toks, sm)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    whole = ref.forward_logits(params, toks, WHOLE)
    assert float(jnp.max(jnp.abs(want - whole))) > 1e-2


def _lowered(**kw):
    N, D, F, E, k = 64, 32, 48, 4, 2

    # (a lambda: the digests were read of one, and the text names it)
    f = lambda x, e, w, up, down: held_experts_ffn(  # noqa: E731
        x, e, w, up, down, lo=4, row_bound=3 * N * k // 2, **kw)

    args = (jnp.zeros((N, D)), jnp.zeros((N, k), jnp.int32),
            jnp.zeros((N, k)), jnp.zeros((E, D, F)), jnp.zeros((E, F, D)))
    fwd = jax.jit(f).lower(*args).as_text()
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(f(*a)[0]),
                            argnums=(0, 3, 4))).lower(*args).as_text()
    return fwd, grad


def test_the_relu2_call_lowers_to_the_parents_text():
    """sha256 of the lowered text (CPU, the kernels in Pallas's interpreter)
    of the call the training cell makes, forward and with its backward, as
    read on the parent commit of PR 47 with the script this PR's ``PERF.md``
    names — the same digests here, so the gated activation added no
    operation to it; named or left out, ``relu2`` is one program."""
    fwd, grad = _lowered()
    assert (fwd, grad) == _lowered(activation=relu2)
    if jax.__version__ == "0.9.0":  # the text is this JAX's
        digest = [hashlib.sha256(t.encode()).hexdigest()[:16]
                  for t in (fwd, grad)]
        assert digest == ["94c3c75fa034d9d7", "cbcb454ebf9d279b"]
    assert "stablehlo.exponential" not in fwd  # no SiLU in it
    # the gated call is another program, of the same kernels
    N, D, F, E, k = 64, 32, 48, 4, 2
    gated = jax.jit(lambda x, e, w, up, down: held_experts_ffn(
        x, e, w, up, down, lo=4, row_bound=None, activation=swiglu)).lower(
        jnp.zeros((N, D)), jnp.zeros((N, k), jnp.int32), jnp.zeros((N, k)),
        jnp.zeros((E, D, 2 * F)), jnp.zeros((E, F, D))).as_text()
    assert "stablehlo.exponential" in gated


def test_interleaved_rope_is_the_references_and_not_half_split():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 3, 8))
    pos = jnp.arange(9)
    got = apply_rope(x, pos, theta=50000.0, interleaved=True)
    want = ref.rope_interleaved(x[0], pos, 50000.0)[None]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    half = apply_rope(x, pos, theta=50000.0)
    assert float(jnp.max(jnp.abs(got - half))) > 0.1
    # a rotation: norms kept, position 0 unchanged, scores depend on m - n
    assert jnp.allclose(jnp.linalg.norm(got, axis=-1),
                        jnp.linalg.norm(x, axis=-1), atol=1e-5)
    assert jnp.allclose(got[:, 0], x[:, 0])
    q = jnp.broadcast_to(x[:, :1], x.shape)
    rq = apply_rope(q, pos, theta=50000.0, interleaved=True)
    s = jnp.einsum("bthd,bshd->hts", rq, rq)
    assert jnp.allclose(s[:, 2, 5], s[:, 4, 7], atol=1e-4)

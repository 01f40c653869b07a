"""``TransformerLM.decode_attention`` and the contiguous cache.

The field decides one thing: whether PAGED decode steps (the serving
engine's) may run the Pallas kernel.  The contiguous cache — ``init_cache``,
``lm_generate``, the ``rolling`` ring — has one layout and one attention
path and reads the field nowhere: same shapes, same tokens under both
values.  (What the field does to a paged step:
``tests/ops_tests/test_decode_attention.py::test_paged_attend_chooses_by_shape``
and ``tests/serving_tests/test_engine.py::test_einsum_engine_same_tokens``.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import TransformerLM, lm_generate

pytestmark = pytest.mark.tier1

KW = dict(
    vocab=128, n_layers=2, d_model=64, n_heads=4, d_ff=128, max_len=96,
    dtype=jnp.float32, pos_enc="rope",
)


@pytest.fixture(scope="module")
def prompt():
    rng = np.random.RandomState(0)
    return jnp.asarray(rng.randint(1, 128, size=(3, 12)).astype(np.int32))


def _pair(**over):
    """(einsum model, fused model, shared params) for one config: GQA
    changes the parameter tree, the knob itself must not."""
    merged = {**KW, **over}
    m_e = TransformerLM(**merged)
    m_f = TransformerLM(decode_attention="fused", **merged)
    params = m_e.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 12), jnp.int32)
    )["params"]
    return m_e, m_f, params


def test_contiguous_cache_ignores_the_knob(prompt):
    m_e, m_f, params = _pair(n_kv_heads=2, kv_dtype=jnp.int8)
    ce, cf = m_e.init_cache(batch=3, max_len=32), m_f.init_cache(3, 32)
    assert ce[0]["k"].shape == (3, 32, 2, 16)   # (B, L, KH, Dh)
    assert ce[0]["k_scale"].shape == (3, 32, 2)
    assert (jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), ce)
            == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), cf))
    lens = jnp.asarray([5, 12, 9], jnp.int32)
    for kw in ({}, {"prompt_lengths": lens}):
        t_e = np.asarray(lm_generate(m_e, params, prompt, 12, **kw))
        t_f = np.asarray(lm_generate(m_f, params, prompt, 12, **kw))
        np.testing.assert_array_equal(t_e, t_f)


def test_rolling_decode_runs_under_either_value(prompt):
    """The ring-buffer cache is the contiguous cache's: both values run
    it, to the same tokens as each other and as the full cache."""
    m_e, m_f, params = _pair(window=8)
    full = np.asarray(lm_generate(m_e, params, prompt, 16))
    for m in (m_e, m_f):
        ring = np.asarray(lm_generate(m, params, prompt, 16, rolling=True))
        np.testing.assert_array_equal(ring, full)


def test_bad_knob_rejected():
    with pytest.raises(ValueError, match="decode_attention"):
        TransformerLM(decode_attention="pallas", **KW).init_cache(1)

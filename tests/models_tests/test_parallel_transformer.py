"""5-way-parallel transformer LM tests: the DP×PP×TP×SP×EP program on an
8-device mesh must match the dense single-device oracle in forward logits,
loss, and reduced gradients."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import chainermn_tpu as cmn
from chainermn_tpu.models.transformer import (
    ParallelLM,
    ParallelLMConfig,
    dense_lm_reference,
    init_parallel_lm,
    parallel_lm_specs,
)

pytestmark = pytest.mark.slow  # full-CI tier: long-pole battery (see tests/test_repo_health.py marker hygiene)


CFG = ParallelLMConfig(
    vocab=64, n_stages=2, d_model=16, n_heads=4, d_ff=32, max_len=32,
    n_experts=2, moe_k=2,
)


def _build(cfg, devices):
    mesh = cmn.hybrid_mesh(
        {"data": 1, "stage": 2, "model": 2, "seq": 2}, devices=devices
    )
    comm = cmn.XlaCommunicator(mesh)
    lm = ParallelLM(cfg, comm.sub("stage"), n_microbatches=2)
    rng = np.random.RandomState(0)
    params = init_parallel_lm(rng, cfg)
    assert ("pos" in params) == (cfg.pos_enc == "learned")
    B, T = 4, 16
    tokens = rng.randint(0, cfg.vocab, size=(B, T)).astype(np.int32)
    targets = np.concatenate(
        [tokens[:, 1:], np.full((B, 1), -1, np.int32)], axis=1
    )
    return cfg, mesh, lm, params, tokens, targets


@pytest.fixture(params=[
    ("learned", 0), ("rope", 0), ("learned", 2), ("rope", 2),
], ids=["learned", "rope", "learned-gqa", "rope-gqa"])
def setup(request, devices):
    # The full oracle-parity suite runs over both positional schemes AND
    # both attention head layouts: under "rope" each seq shard rotates q/k
    # at its GLOBAL positions before the ring (no "pos" table); under GQA
    # (n_kv_heads=2 < n_heads=4) the kv projections are TP-sharded and
    # repeated to the query head count — rope×GQA pins the rotation-after-
    # repeat ordering against the dense reference.
    pos_enc, n_kv = request.param
    return _build(
        CFG._replace(pos_enc=pos_enc, n_kv_heads=n_kv), devices
    )


def test_parallel_gqa_param_layout_and_validation(devices):
    """GQA structural pins (the numerics run through the whole
    fixture-parametrized suite): the param tree swaps wqkv for wq/wkv,
    and bad head counts fail fast at construction."""
    cfg, mesh, lm, params, _, _ = _build(
        CFG._replace(n_kv_heads=2), devices
    )
    assert "wkv" in params["stages"] and "wqkv" not in params["stages"]
    comm = cmn.XlaCommunicator(mesh)
    for bad in (3, -2, 8):
        with pytest.raises(ValueError, match="n_kv_heads"):
            ParallelLM(
                CFG._replace(n_kv_heads=bad), comm.sub("stage"), 2
            )


@pytest.mark.parametrize("check_vma", [False, True])
def test_parallel_forward_matches_dense(setup, check_vma):
    cfg, mesh, lm, params, tokens, _ = setup
    specs = parallel_lm_specs(cfg)
    f = jax.jit(
        jax.shard_map(
            lm.apply,
            mesh=mesh,
            in_specs=(specs, P("data", "seq")),
            out_specs=P("data", "seq"),
            check_vma=check_vma,
        )
    )
    out = np.asarray(f(params, tokens))
    ref = np.asarray(dense_lm_reference(params, cfg, tokens))
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=1e-3)


def test_parallel_forward_flash_ring_matches_dense(devices):
    """cfg.attention='flash' forces the flash-block ring (interpret mode
    off-TPU); the dense oracle must still hold — the auto policy is a
    perf selection between two exact rings, never a numerics change."""
    cfg, mesh, lm, params, tokens, _ = _build(
        CFG._replace(attention="flash"), devices
    )
    specs = parallel_lm_specs(cfg)
    f = jax.jit(
        jax.shard_map(
            lm.apply, mesh=mesh,
            in_specs=(specs, P("data", "seq")),
            out_specs=P("data", "seq"),
            check_vma=True,
        )
    )
    out = np.asarray(f(params, tokens))
    ref = np.asarray(dense_lm_reference(params, cfg, tokens))
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=1e-3)


@pytest.mark.parametrize("check_vma", [False, True])
def test_parallel_loss_and_grads_match_dense(setup, check_vma):
    """The SAME dense oracle must hold with the checker off AND on: loss
    seeding and the replica convention differ by mode (lm.loss branches on
    the vma type), but reduced grads and the reconstructed global loss are
    mode-invariant — this is the exactness pin for the round-4
    check_vma=True default (VERDICT r3 item 9)."""
    from chainermn_tpu.utils import psum_over_varying

    cfg, mesh, lm, params, tokens, targets = setup
    specs = parallel_lm_specs(cfg)

    def step(params, batch):
        loss, grads = jax.value_and_grad(lm.loss)(params, batch)
        grads = lm.grad_reduce(grads)
        total = (
            psum_over_varying(loss, ("data", "stage", "model", "seq"))
            if check_vma
            else jax.lax.psum(loss, ("data", "stage", "model", "seq"))
        )
        return total, grads

    f = jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(specs, (P("data", "seq"), P("data", "seq"))),
            out_specs=(P(), specs),
            check_vma=check_vma,
        )
    )
    loss, grads = f(params, (tokens, targets))

    def dense_loss(params, batch):
        tokens, targets = batch
        logits = dense_lm_reference(params, cfg, tokens)
        mask = (targets >= 0).astype(jnp.float32)
        safe = jnp.maximum(targets, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return jnp.sum(ce * mask) / jnp.sum(mask)

    ref_loss, ref_grads = jax.value_and_grad(dense_loss)(
        jax.tree_util.tree_map(jnp.asarray, params), (tokens, targets)
    )
    np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5,
                               rtol=1e-4)

    flat = dict(
        (jax.tree_util.keystr(path), g)
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]
    )
    ref_flat = dict(
        (jax.tree_util.keystr(path), g)
        for path, g in jax.tree_util.tree_flatten_with_path(ref_grads)[0]
    )
    assert flat.keys() == ref_flat.keys()
    for name in flat:
        np.testing.assert_allclose(
            np.asarray(flat[name]), np.asarray(ref_flat[name]),
            atol=5e-4, rtol=5e-3, err_msg=name,
        )


def test_parallel_train_steps_decrease_loss(setup):
    """Three SGD steps through the full 5-way-parallel program reduce the
    loss, and sharded params stay internally consistent (replicated leaves
    agree across all shards)."""
    import optax

    from chainermn_tpu.optimizers import optimizer_state_specs

    cfg, mesh, lm, params, tokens, targets = setup
    specs = parallel_lm_specs(cfg)
    tx = optax.sgd(0.5)
    opt_state = tx.init(params)
    opt_specs = optimizer_state_specs(opt_state, params, specs)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lm.loss)(params, batch)
        grads = lm.grad_reduce(grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        import optax as _o

        params = _o.apply_updates(params, updates)
        return params, opt_state, jax.lax.psum(loss, ("data", "stage", "model", "seq"))

    f = jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(specs, opt_specs, (P("data", "seq"), P("data", "seq"))),
            out_specs=(specs, opt_specs, P()),
            check_vma=False,
        )
    )
    losses = []
    state = (params, opt_state)
    for _ in range(3):
        p, o, loss = f(state[0], state[1], (tokens, targets))
        state = (p, o)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses

    # Replicated leaves must agree across every device shard.
    for leaf in [state[0]["embed"], state[0]["lm_head"]]:
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_allclose(s, shards[0], atol=1e-6)

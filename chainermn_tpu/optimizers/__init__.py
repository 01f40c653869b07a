"""Multi-node optimizer integration.

Reference anchors: ``chainermn/optimizers.py`` — ``create_multi_node_optimizer``
(``_MultiNodeOptimizer``: fwd/bwd → ``communicator.allreduce_grad`` → inner
optimizer update) and ``_DoubleBufferingOptimizer`` (allreduce of step-k grads
overlapped with step-k+1 compute; updates use 1-step-stale reduced grads).

TPU-native design: instead of an eager per-iteration allreduce call between
backward and update, the whole update is ONE jitted SPMD program built by
:meth:`MultiNodeOptimizer.make_train_step` — gradients cross devices as a
``lax.pmean`` *inside* the traced step, which XLA schedules and overlaps with
the backward pass automatically (the hand-built side-stream of the reference's
double-buffering is the compiler's job here).  The explicit double-buffering
mode is still provided for parity of *semantics* (1-step-stale updates) via a
pending-gradient carry in the train state.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.comm.base import CommunicatorBase
from chainermn_tpu.comm.xla import XlaCommunicator
from chainermn_tpu.utils import pvary


def _augment_key(seed: int, step: jax.Array, axes) -> jax.Array:
    """Per-step, per-device augmentation key: deterministic from
    ``(seed, step counter, mesh position)`` so replicas draw independent
    transforms while the whole run stays reproducible."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    return jax.random.fold_in(key, lax.axis_index(axes))


def _make_grad_one(loss_fn, has_aux, stateful):
    """Shared per-microbatch gradient closure: ``grad_one(params,
    model_state, mb) -> (loss, aux, new_model_state, grads)`` under the
    three loss contracts (plain / has_aux / stateful)."""

    def grad_one(params, model_state, mb):
        if stateful:
            (loss, (aux, ms)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, model_state, mb)
        elif has_aux:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, mb)
            ms = model_state
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, mb)
            aux, ms = {}, model_state
        return loss, aux, ms, grads

    return grad_one


def _accumulated_grads(grad_one, params, model_state, batch, accum_steps):
    """Gradient accumulation core, shared by both optimizer tiers.

    ``grad_one(params, model_state, mb) -> (loss, aux, new_model_state,
    grads)`` is evaluated over ``accum_steps`` equal microbatches of
    ``batch``'s leading axis; losses/aux/grads are MEAN-accumulated in a
    ``lax.scan`` carry (a stacked scan output would materialize
    ``accum_steps × params``), model state threads sequentially.  With
    ``accum_steps == 1`` this is exactly one ``grad_one`` call.

    Weighting contract: every microbatch contributes 1/k — exact for
    per-sample-mean losses.  A loss that normalizes by a DATA-DEPENDENT
    count (e.g. a masked token mean) is over-weighted on microbatches with
    fewer real tokens; when padding is uneven across microbatches this is
    the standard equal-weight approximation, not the full-batch mean."""
    if accum_steps == 1:
        return grad_one(params, model_state, batch)

    def split(x):
        if x.shape[0] % accum_steps:
            raise ValueError(
                f"per-device batch {x.shape[0]} not divisible by "
                f"accum_steps={accum_steps}"
            )
        return x.reshape(
            accum_steps, x.shape[0] // accum_steps, *x.shape[1:]
        )

    mbs = jax.tree_util.tree_map(split, batch)
    mb0 = jax.tree_util.tree_map(lambda x: x[0], mbs)
    rest = jax.tree_util.tree_map(lambda x: x[1:], mbs)
    # First microbatch outside the scan fixes the aux/grads structure for
    # the carry.
    loss, aux, ms, gacc = grad_one(params, model_state, mb0)

    def mb_body(carry, mb):
        lacc, aacc, ms, gacc = carry
        l, a, ms2, g = grad_one(params, ms, mb)
        gacc = jax.tree_util.tree_map(lambda acc, gi: acc + gi, gacc, g)
        aacc = jax.tree_util.tree_map(lambda acc, ai: acc + ai, aacc, a)
        return (lacc + l, aacc, ms2, gacc), None

    (loss, aux, new_model_state, gacc), _ = lax.scan(
        mb_body, (loss, aux, ms, gacc), rest
    )
    inv = 1.0 / accum_steps
    loss = loss * inv
    aux = jax.tree_util.tree_map(lambda a: a * inv, aux)
    grads = jax.tree_util.tree_map(lambda g: g * inv, gacc)
    return loss, aux, new_model_state, grads


@struct.dataclass
class TrainState:
    """Replicated training state carried across steps."""

    step: jax.Array
    params: Any
    opt_state: Any
    # Double-buffering carry: previous step's reduced grads (zeros at init).
    pending_grads: Any = None
    # Mutable model collections (e.g. sync-BN running stats); None when the
    # model is stateless.  Kept replicated: sync-BN moments are pmean'd
    # in-graph so every device writes identical stats.
    model_state: Any = None
    # int8 error-feedback compression: each device's accumulated
    # quantization error, rankwise ((size, *param.shape) sharded over the
    # mesh — the one device-varying piece of the train state).
    ef_residual: Any = None
    # Exponential moving average of params (``ema_decay`` set): evaluate /
    # export with these for the Polyak-averaged model.  Initialized to the
    # params themselves, so no debias term is needed.
    ema_params: Any = None
    # Training-health carry (``health_check=True`` steps): float32
    # ``[grad_norm_ema, healthy_steps_seen, skipped_total]``, replicated.
    # None when the health guard is off — seeded by
    # ``TrainingHealthGuard.bind`` (resilience/guard.py), so existing
    # checkpoints/states are untouched unless a guard is attached.
    health: Any = None


class MultiNodeOptimizer:
    """Wraps an optax transformation with cross-device gradient averaging.

    ``loss_fn(params, batch) -> scalar`` or ``(scalar, aux_dict)`` when
    ``has_aux=True``.  The batch passed to :meth:`update` is a *global* batch
    whose leading dimension is sharded over the communicator's mesh axes.
    """

    def __init__(
        self,
        tx: optax.GradientTransformation,
        communicator: CommunicatorBase,
        double_buffering: bool = False,
        grad_reduce: Optional[Callable] = None,
        grad_compression: Optional[str] = None,
        ema_decay: Optional[float] = None,
    ):
        self.tx = tx
        self.comm = communicator
        self.double_buffering = double_buffering
        if ema_decay is not None and not 0.0 < ema_decay < 1.0:
            raise ValueError(
                f"ema_decay must be in (0, 1), got {ema_decay}"
            )
        # Polyak/EMA weight averaging: the eval-time smoothing standard for
        # vision models (and common for LMs); the averaged copy rides the
        # train state and updates in-graph after every optimizer step.
        self.ema_decay = ema_decay
        if grad_compression not in (None, "int8_ef"):
            raise ValueError(
                f"grad_compression={grad_compression!r}: expected None or "
                "'int8_ef'"
            )
        # 'int8_ef': 4x-compressed gradient wire with error feedback — the
        # step up from the reference's fp16 allreduce (SURVEY §2.3, gradient
        # compression row).  Per leaf: share one scale via pmax, quantize
        # grad+residual to int8, psum in int32, dequantize; each device
        # carries its local quantization error into the next step, so the
        # compression bias cancels over steps instead of accumulating.
        self.grad_compression = grad_compression
        # Per-leaf in-graph gradient reduction; defaults to the communicator's
        # data-axis mean.  Model-parallel setups pass a custom reducer that
        # also psums owner-localized stage grads over the model axis (see
        # model_parallel_grad_reduce).
        self.grad_reduce = grad_reduce or communicator.grad_reduce_leaf
        self._step_cache: dict = {}

    # ------------------------------------------------------------------ state
    def init(self, params: Any, model_state: Any = None) -> TrainState:
        # Copy leaves: the train step donates its input state, and device_put
        # aliases (no-copy) when the sharding already matches — without the
        # copy, donation would delete arrays the caller still holds.
        params = jax.tree_util.tree_map(jnp.array, params)
        if model_state is not None:
            model_state = jax.tree_util.tree_map(jnp.array, model_state)
        if isinstance(self.comm, XlaCommunicator):
            params = self.comm.replicate(params)
            if model_state is not None:
                model_state = self.comm.replicate(model_state)
        # Pending grads carry in the WIRE dtype when one is set: the
        # reference's fp16 pipeline likewise kept reduced grads in fp16, and
        # the half-width carry halves the extra state the dbuf mode streams
        # through HBM every step.
        wire = getattr(self.comm, "allreduce_grad_dtype", None)
        pending = (
            # zeros_like keeps each leaf's (replicated) sharding — a plain
            # jnp.zeros would come up process-local and break multi-host.
            jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, dtype=wire or p.dtype), params
            )
            if self.double_buffering
            else None
        )
        resid = None
        if self.grad_compression is not None:
            if not isinstance(self.comm, XlaCommunicator):
                raise TypeError(
                    "grad_compression requires a mesh-backed communicator"
                )
            n = self.comm.size
            resid = jax.tree_util.tree_map(
                lambda p: jnp.zeros((n,) + p.shape, p.dtype), params
            )
            resid = self.comm.shard_rankwise(resid)
        step = jnp.zeros((), jnp.int32)
        opt_state = self.tx.init(params)
        if isinstance(self.comm, XlaCommunicator):
            # The step counter and the scalars a transformation creates
            # itself (adam's ``count``) come up uncommitted on the default
            # device, while the train step RETURNS them replicated on the
            # mesh: left alone, the second call sees a new input sharding
            # and compiles the whole step a second time.  Leaves that
            # followed the params onto the mesh (mu, nu) stay as they are.
            mesh = self.comm.mesh

            def settle(x):
                sh = getattr(x, "sharding", None)
                if isinstance(sh, NamedSharding) and sh.mesh == mesh:
                    return x
                return self.comm.replicate(x)

            step, opt_state = jax.tree_util.tree_map(
                settle, (step, opt_state)
            )
        return TrainState(
            step=step,
            params=params,
            opt_state=opt_state,
            pending_grads=pending,
            model_state=model_state,
            ef_residual=resid,
            ema_params=(
                # fp32 regardless of the param dtype: with bf16 params a
                # 0.999-decay increment is ~1000x below bf16's relative
                # resolution — the average would freeze at init.  jnp.array
                # (not asarray): same-dtype asarray ALIASES the param
                # buffers and the donating train step would then see the
                # same buffer twice.
                jax.tree_util.tree_map(
                    lambda p: jnp.array(p, jnp.float32), params
                )
                if self.ema_decay is not None
                else None
            ),
        )

    # ------------------------------------------------------------- allreduce
    def _int8_ef_reduce(self, grads: Any, residual: Any):
        """int8 wire mean with error feedback (in-graph, per leaf).

        The scale is shared across devices (pmax of |grad+residual|), so the
        int8 codes sum exactly in int32 (≤ 127·size per element) and one
        dequantize recovers the mean.  Returns ``(mean_grads, new_residual)``
        — the residual is each device's local code error ``c − q·s``,
        re-injected next step (Seide et al.-style EF, the property that
        makes lossy wires converge)."""
        axes = self.comm.axis_name
        size = self.comm.size

        def one(g, r):
            c = g.astype(jnp.float32) + r[0].astype(jnp.float32)
            amax = lax.pmax(jnp.max(jnp.abs(c)), axes)
            s = jnp.maximum(amax, 1e-30) / 127.0
            q = jnp.clip(jnp.round(c / s), -127, 127)
            tot = lax.psum(q.astype(jnp.int32), axes)
            y = (tot.astype(jnp.float32) * s / size).astype(g.dtype)
            r_new = (c - q * s).astype(r.dtype)[None]
            return y, r_new

        pairs = jax.tree_util.tree_map(one, grads, residual)
        return (
            jax.tree_util.tree_map(lambda pr: pr[0], pairs,
                                   is_leaf=lambda x: isinstance(x, tuple)),
            jax.tree_util.tree_map(lambda pr: pr[1], pairs,
                                   is_leaf=lambda x: isinstance(x, tuple)),
        )

    def _allreduce_grads(self, grads: Any) -> Any:
        """In-graph gradient mean — the ``allreduce_grad`` hot path, delegated
        to the per-leaf reducer (wire-dtype aware; identity for
        DummyCommunicator; model-axis-aware when ``grad_reduce`` was given).

        Named-scoped so the collective region is identifiable in a device
        profile next to the host-side step annotations
        (``docs/observability.md``)."""
        with jax.named_scope("cmn_allreduce_grads"):
            return jax.tree_util.tree_map(self.grad_reduce, grads)

    # ----------------------------------------------------------- train step
    def make_train_step(
        self,
        loss_fn: Callable,
        has_aux: bool = False,
        stateful: bool = False,
        donate: bool = True,
        accum_steps: int = 1,
        augment: Optional[Callable] = None,
        augment_seed: int = 0,
        health_check: bool = False,
        spike_factor: float = 10.0,
        spike_warmup: int = 20,
        spike_ema_beta: float = 0.1,
    ) -> Callable:
        """Build the jitted SPMD train step (reference hot loop §3.2).

        Returns ``step(state, batch) -> (state, metrics)`` where ``metrics``
        contains the globally averaged ``loss`` (and aux scalars).

        ``stateful=True`` threads mutable model collections (e.g. BN running
        stats): ``loss_fn(params, model_state, batch) -> (loss, (aux_dict,
        new_model_state))``.

        ``accum_steps=k`` splits each device's batch into ``k`` microbatches
        and accumulates their mean gradient in a ``lax.scan`` before the
        single cross-device reduction and update — activation memory scales
        with the microbatch while the effective batch (and, for per-sample-
        mean losses, the numerics) matches the unsplit step.  The TPU lever
        for large global batches the reference reached by adding processes.

        ``augment(key, batch) -> batch`` runs on device inside the step
        (before any microbatch split) with a key derived from
        ``(augment_seed, state.step, device mesh position)`` — per-step,
        per-replica randomness, bit-reproducible across runs (see
        ``ops/augment.py``).

        ``health_check=True`` adds the training-health guard's in-graph
        step anomaly detection (``resilience/guard.py``): the step's
        verdict is computed from the globally *reduced* gradients and the
        pmean'd loss — values every device already holds identically, so
        all ranks agree on it with ZERO extra collectives.  A step whose
        loss/gradients are non-finite, or whose fp32 global gradient norm
        exceeds ``spike_factor`` × a running EMA (tracked in
        ``state.health``, armed after ``spike_warmup`` healthy steps), is
        a **no-op**: params, optimizer state, EMA params, model state,
        pending grads, and EF residuals all keep their previous values
        (only ``step`` advances).  The verdict is exported as the
        ``step_ok`` metric (plus ``grad_norm`` / ``health_skipped``) for
        the guard's host-side skip-budget accounting.  Requires
        ``state.health`` to be seeded (``TrainingHealthGuard.bind``).
        """
        comm = self.comm
        if not isinstance(comm, XlaCommunicator):
            raise TypeError("make_train_step requires a mesh-backed communicator")
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        mesh = comm.mesh
        axes = comm.axes
        dbuf = self.double_buffering
        compression = self.grad_compression
        ema_decay = self.ema_decay
        tx = self.tx

        grad_one = _make_grad_one(loss_fn, has_aux, stateful)

        def body(state: TrainState, batch):
            # Differentiate w.r.t. an explicitly device-varying copy of the
            # replicated params.  Under shard_map's vma type system
            # (check_vma=True), differentiating w.r.t. an UNVARYING input
            # auto-inserts a psum in the transpose (the broadcast's adjoint),
            # which would return grads already summed over the axis — and the
            # explicit wire-dtype reduction below would then silently scale
            # them by ``size`` (pmean of an unvarying value is identity).
            # pvary first keeps grads per-device, exactly like the reference's
            # local backward before its allreduce.
            vparams = jax.tree_util.tree_map(
                lambda p: pvary(p, axes), state.params
            )
            if augment is not None:
                batch = augment(_augment_key(augment_seed, state.step, axes),
                                batch)
            with jax.named_scope("loss_and_grad"):
                loss, aux, new_model_state, grads = _accumulated_grads(
                    grad_one, vparams, state.model_state, batch, accum_steps
                )
            if compression is not None:
                with jax.named_scope("cmn_allreduce_grads"):
                    grads, new_resid = self._int8_ef_reduce(
                        grads, state.ef_residual
                    )
            else:
                grads = self._allreduce_grads(grads)
                new_resid = state.ef_residual
            if dbuf:
                # 1-step-stale semantics: apply the PREVIOUS reduced grads,
                # carry the fresh ones (reference: _DoubleBufferingOptimizer
                # swap/update logic).  The carry lives in the wire dtype;
                # cast per-leaf at the boundary.
                apply_grads = jax.tree_util.tree_map(
                    lambda p, g: g.astype(p.dtype),
                    state.params,
                    state.pending_grads,
                )
                pending = jax.tree_util.tree_map(
                    lambda s, g: g.astype(s.dtype),
                    state.pending_grads,
                    grads,
                )
            else:
                apply_grads = grads
                pending = state.pending_grads
            with jax.named_scope("optimizer_update"):
                updates, opt_state = tx.update(
                    apply_grads, state.opt_state, state.params
                )
            with jax.named_scope("apply_updates"):
                params = optax.apply_updates(state.params, updates)
            if ema_decay is not None:
                ema = jax.tree_util.tree_map(
                    lambda e, p: e * ema_decay
                    + p.astype(e.dtype) * (1.0 - ema_decay),
                    state.ema_params,
                    params,
                )
            else:
                ema = state.ema_params
            loss_mean = lax.pmean(loss, comm.axis_name)
            metrics = {"loss": loss_mean}
            for k, v in aux.items():
                metrics[k] = lax.pmean(v, comm.axis_name)
            new_health = state.health
            if health_check:
                if state.health is None:
                    raise ValueError(
                        "health_check=True needs a seeded state.health "
                        "carry — attach the guard via "
                        "TrainingHealthGuard.bind(trainer) (or pass "
                        "state.replace(health=jnp.zeros(3, jnp.float32)))"
                    )
                # Verdict from values already identical on every device
                # (post-psum grads, pmean'd loss): any non-finite leaf
                # makes the fp32 norm-of-squares non-finite, so two
                # isfinite checks cover NaN/Inf anywhere in the tree.
                gnorm = jnp.sqrt(
                    sum(
                        jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(grads)
                    )
                )
                ema_n, seen, skipped = (
                    state.health[0], state.health[1], state.health[2]
                )
                finite = jnp.isfinite(loss_mean) & jnp.isfinite(gnorm)
                spike = (
                    (seen >= spike_warmup)
                    & (ema_n > 0.0)
                    & (gnorm > spike_factor * ema_n)
                )
                ok = finite & ~spike
                okf = ok.astype(jnp.float32)
                # The norm EMA learns only from healthy steps (a skipped
                # spike must not drag the threshold up after itself) and
                # seeds itself on the first healthy step.
                ema_upd = jnp.where(
                    seen > 0.0,
                    ema_n * (1.0 - spike_ema_beta) + gnorm * spike_ema_beta,
                    gnorm,
                )
                new_health = jnp.stack([
                    jnp.where(ok, ema_upd, ema_n),
                    seen + okf,
                    skipped + (1.0 - okf),
                ])

                def _keep(new_tree, old_tree):
                    return jax.tree_util.tree_map(
                        lambda n, o: jnp.where(ok, n, o), new_tree, old_tree
                    )

                # A poisoned step is a full no-op: nothing the bad
                # gradients touched survives — not the params, not the
                # optimizer moments, not the EMA, not the dbuf carry or
                # EF residual (both hold the poison), not the BN stats.
                params = _keep(params, state.params)
                opt_state = _keep(opt_state, state.opt_state)
                pending = _keep(pending, state.pending_grads)
                new_resid = _keep(new_resid, state.ef_residual)
                ema = _keep(ema, state.ema_params)
                new_model_state = _keep(new_model_state, state.model_state)
                metrics["step_ok"] = okf
                metrics["grad_norm"] = gnorm
                metrics["health_skipped"] = new_health[2]
            return (
                TrainState(
                    step=state.step + 1,
                    params=params,
                    opt_state=opt_state,
                    pending_grads=pending,
                    model_state=new_model_state,
                    ef_residual=new_resid,
                    ema_params=ema,
                    health=new_health,
                ),
                metrics,
            )

        batch_spec = P(axes)
        # DummyCommunicator's identity "reduce" leaves grads device-varying
        # on purpose (comm-cost ablation); the vma checker rightly rejects
        # the replicated out_specs there, so the ablation runs unchecked.
        from chainermn_tpu.comm.xla import DummyCommunicator

        # The state is replicated except the EF residual, which is rankwise
        # (each device's own quantization error) — a per-field spec tree.
        state_spec = TrainState(
            step=P(), params=P(), opt_state=P(), pending_grads=P(),
            model_state=P(),
            ef_residual=P(axes) if compression is not None else P(),
            ema_params=P(),
            health=P(),
        )
        mapped = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, P()),
            check_vma=not isinstance(comm, DummyCommunicator),
        )
        donate_argnums = (0,) if donate else ()
        # The step rides the compile watcher (PR 11): every compilation
        # is recorded with its triggering argument signature, a batch-
        # shape-change recompile emits a structured blame diff, and
        # MetricsReport(device=True) reads the captured cost model for
        # the device.* MFU/roofline gauges.  No budget: several variants
        # are legitimate (ladder of loss closures, uneven final batch);
        # churn still shows up as compile.count + blame records.  With
        # CMN_OBS=0 this returns the raw jit (the wrap-time latch).
        from chainermn_tpu.observability import device as _odevice

        return _odevice.watch().wrap(
            jax.jit(mapped, donate_argnums=donate_argnums),
            program="train_step",
        )

    # --------------------------------------------------------------- update
    def update(
        self,
        state: TrainState,
        batch: Any,
        loss_fn: Callable,
        has_aux: bool = False,
        stateful: bool = False,
        accum_steps: int = 1,
        augment: Optional[Callable] = None,
        augment_seed: int = 0,
        health_check: bool = False,
        spike_factor: float = 10.0,
        spike_warmup: int = 20,
        spike_ema_beta: float = 0.1,
    ) -> Tuple[TrainState, dict]:
        """Eager-style API mirroring ``_MultiNodeOptimizer.update``: caches the
        jitted step per ``loss_fn``."""
        return _eager_update(
            self, state, batch, loss_fn, has_aux, stateful, accum_steps,
            augment, augment_seed, health_check, spike_factor, spike_warmup,
            spike_ema_beta,
        )


def _eager_update(opt, state, batch, loss_fn, has_aux, stateful,
                  accum_steps=1, augment=None, augment_seed=0,
                  health_check=False, spike_factor=10.0, spike_warmup=20,
                  spike_ema_beta=0.1):
    """Shared eager-style update: cache the jitted step per (loss_fn, flags)
    — keyed by the FUNCTION OBJECT (holding a reference), not ``id()``,
    which can be recycled after gc — and serialize steps on the CPU
    simulation mesh: XLA:CPU's in-process collective rendezvous can
    deadlock when launches overlap across the virtual device pool.  The CPU
    mesh exists only to SIMULATE a pod; real TPU/GPU paths keep async
    dispatch and compiler overlap."""
    key = (loss_fn, has_aux, stateful, accum_steps, augment, augment_seed,
           health_check, spike_factor, spike_warmup, spike_ema_beta)
    step = opt._step_cache.get(key)
    if step is None:
        # Health kwargs only when armed: this helper is shared with tiers
        # whose make_train_step has no in-graph health check (ZeRO), and
        # they must keep working un-guarded.
        health_kwargs = (
            dict(health_check=True, spike_factor=spike_factor,
                 spike_warmup=spike_warmup, spike_ema_beta=spike_ema_beta)
            if health_check else {}
        )
        step = opt._step_cache[key] = opt.make_train_step(
            loss_fn, has_aux, stateful, accum_steps=accum_steps,
            augment=augment, augment_seed=augment_seed, **health_kwargs,
        )
        if len(opt._step_cache) == 9:  # warn once, at the 9th variant
            import warnings

            warnings.warn(
                "9+ distinct train-step variants compiled on one optimizer: "
                "loss_fn/augment must be the SAME callable across update() "
                "calls (build closures like random_crop_flip() once, outside "
                "the loop) or every step pays a fresh jit compile.",
                stacklevel=3,
            )
    batch = opt.comm.shard_batch(batch)
    out = step(state, batch)
    if jax.default_backend() == "cpu":
        jax.block_until_ready(out[0])
    return out


def create_multi_node_optimizer(
    actual_optimizer: optax.GradientTransformation,
    communicator: CommunicatorBase,
    double_buffering: bool = False,
    grad_reduce: Optional[Callable] = None,
    grad_compression: Optional[str] = None,
    ema_decay: Optional[float] = None,
) -> MultiNodeOptimizer:
    """Reference anchor: ``chainermn/optimizers.py — create_multi_node_optimizer
    (opt, comm, double_buffering=False)``.  ``grad_compression='int8_ef'``
    extends the reference's fp16-wire idea (§2.3) to a 4x-compressed int8
    wire with error feedback.  ``ema_decay`` maintains a Polyak-averaged
    copy of the params on the train state (``state.ema_params``) for
    eval/export."""
    return MultiNodeOptimizer(
        actual_optimizer,
        communicator,
        double_buffering=double_buffering,
        grad_reduce=grad_reduce,
        grad_compression=grad_compression,
        ema_decay=ema_decay,
    )


def optimizer_state_specs(opt_state: Any, params: Any, param_specs: Any) -> Any:
    """PartitionSpecs for an optax state, mirroring the params' specs.

    Structural matching, not positional periodicity: any subtree of the
    state that is exactly param-shaped (same tree structure AND same leaf
    shapes — momentum/variance buffers) gets ``param_specs``; every other
    leaf (step counters from ``scale_by_schedule``/``scale_by_adam``,
    EMA scalars, …) replicates (``P()``).  Handles arbitrarily chained/
    injected transforms without the param-periodic assumption.
    """
    from jax.sharding import PartitionSpec as P

    pdef = jax.tree_util.tree_structure(params)
    pshapes = [
        getattr(leaf, "shape", None)
        for leaf in jax.tree_util.tree_leaves(params)
    ]

    def param_shaped(sub) -> bool:
        if jax.tree_util.tree_structure(sub) != pdef:
            return False
        return [
            getattr(leaf, "shape", None)
            for leaf in jax.tree_util.tree_leaves(sub)
        ] == pshapes

    def rec(sub):
        if param_shaped(sub):
            return param_specs
        # One-level decomposition: every proper child is treated as a leaf.
        children, one_level = jax.tree_util.tree_flatten(
            sub, is_leaf=lambda y: y is not sub
        )
        if len(children) == 1 and children[0] is sub:
            return P()  # a true leaf not shaped like params: replicate
        return jax.tree_util.tree_unflatten(
            one_level, [rec(c) for c in children]
        )

    return rec(opt_state)


def model_parallel_grad_reduce(data_comm, model_comm) -> Callable:
    """Per-leaf reducer for hybrid DP×MP training with owner-localized stage
    gradients (e.g. :class:`chainermn_tpu.links.MultiNodeChainList`).

    Assumes the loss is computed identically on every model rank (the usual
    pattern: ``F.bcast`` the chain output, then loss everywhere).  AD's
    collective transposes then deliver ``model_size ×`` the true gradient on
    each stage's owner rank and zero elsewhere, so a PMEAN over the model
    axis simultaneously (a) restores the owner's update on every shard —
    without it non-owner shards silently keep stale params — and (b) cancels
    the replicated-loss multiplicity.  Then the usual mean over data.

    .. note:: the multiplicity in (b) is the ``check_vma=False`` seeding
       semantics; the ``MultiNodeChainList`` flows that use this reducer
       run with the checker off (their spmd wrappers pass
       ``check_vma=False``).  Under ``check_vma=True`` the vma-aware
       transpose seeds once and this pmean would under-scale — the
       checker-on path uses vma-aware reducers instead
       (``ParallelLM.grad_reduce`` keys on ``jax.typeof(...).vma``)."""

    def reduce_leaf(g):
        g = lax.pmean(g, model_comm.axis_name)
        return data_comm.grad_reduce_leaf(g)

    return reduce_leaf


# ZeRO tier (sharded params/grads/optimizer state) lives in its own module.
from chainermn_tpu.optimizers.zero import (  # noqa: E402
    ZeroMultiNodeOptimizer,
    ZeroTrainState,
    create_zero_optimizer,
    reshard_zero_state,
    zero_clip_by_global_norm,
)

# Large-batch recipe (LARS/LAMB + linear scaling + warmup) — the reference's
# headline 32k-batch regime as a first-class tier.
from chainermn_tpu.optimizers.large_batch import (  # noqa: E402
    kernel_mask,
    lamb,
    lars,
    linear_scaled_lr,
    warmup_cosine_schedule,
)

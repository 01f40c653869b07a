"""Distributed checkpointing — restart-based fault tolerance.

Reference anchor: ``chainermn/extensions/checkpoint.py`` —
``create_multi_node_checkpointer(name, comm)`` / ``class
_MultiNodeCheckpointer``: each rank snapshots its local state with rank-tagged
filenames, the ranks ``allgather_obj`` their saved iteration lists and agree
on the latest iteration *common to all ranks*, stale files are
garbage-collected, and ``maybe_load`` resumes from the consistent set on
restart.  World size is fixed (restart-based, not elastic).

TPU-native: orbax's ``CheckpointManager`` already provides exactly the hard
parts — sharded async saves, cross-host atomicity (every host commits or the
step is not visible, which IS the "latest common iteration" agreement),
retention-based gc, and ``latest_step``.  This module wraps it in the
reference's extension + ``maybe_load`` shape and adds iterator/trainer state
so resume is exact.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import shutil
from collections import deque
from typing import Any, List, Optional, Tuple

import jax
import numpy as np

from chainermn_tpu import observability as _obs
from chainermn_tpu.observability import metrics as _omet
from chainermn_tpu.observability import tracing as _otrace
from chainermn_tpu.resilience.policy import RetryPolicy
from chainermn_tpu.training import Extension


def capture_loop_state(trainer) -> dict:
    """Snapshot the loop-resume state (trainer iteration/epoch, iterator
    cursor + RNG) as a flat dict of numpy leaves.  Module-level because two
    planes snapshot it: the orbax checkpointer (durable tier) and the
    peer-replication plane (``resilience/replicate.py``, fast tier) — both
    must carry identical loop state for a restore to be bit-exact."""
    out = {
        "iteration": np.zeros((), np.int64),
        "epoch": np.zeros((), np.int64),
        "it_pos": np.zeros((), np.int64),
    }
    if trainer is None:
        return out
    it = trainer.train_iter
    out["iteration"] = np.asarray(trainer.iteration, np.int64)
    out["epoch"] = np.asarray(getattr(it, "epoch", 0), np.int64)
    # Iterators with lookahead (PrefetchIterator's native ring) expose an
    # explicit consumption-granular cursor — their raw attributes must
    # not be snapshotted (the submission cursor runs depth batches ahead).
    st = (
        it.checkpoint_loop_state()
        if hasattr(it, "checkpoint_loop_state")
        else None
    )
    if st is not None:
        out["it_pos"] = np.asarray(st["pos"], np.int64)
        out["it_order"] = np.asarray(st["order"], np.int64)
        out["rng_keys"] = np.asarray(st["rng_keys"], np.uint32)
        out["rng_pos"] = np.asarray(st["rng_pos"], np.int64)
        out["rng_has_gauss"] = np.asarray(st["rng_has_gauss"], np.int64)
        out["rng_cached"] = np.asarray(st["rng_cached"], np.float64)
        # Degraded-cursor flag (see DevicePrefetchIterator): > 0 means
        # the snapshot may replay/skip up to this many samples on
        # restore.  ALWAYS present so the orbax tree structure is
        # deterministic (StandardRestore templates must match).
        out["it_inexact"] = np.asarray(st.get("inexact", 0), np.int64)
        return out
    out["it_pos"] = np.asarray(getattr(it, "_pos", 0), np.int64)
    # Exact mid-epoch resume needs the iterator's in-flight permutation
    # and RNG state (restoring _pos into a FRESH permutation would skip
    # and duplicate samples).  SerialIterator-shaped iterators only.
    if hasattr(it, "_order") and hasattr(it, "_rng"):
        mt, keys, pos, has_gauss, cached = it._rng.get_state()
        out["it_order"] = np.asarray(it._order, np.int64)
        out["rng_keys"] = np.asarray(keys, np.uint32)
        out["rng_pos"] = np.asarray(pos, np.int64)
        out["rng_has_gauss"] = np.asarray(has_gauss, np.int64)
        out["rng_cached"] = np.asarray(cached, np.float64)
    return out


def apply_loop_state(trainer, new_state, loop) -> None:
    """Push restored trainer/iterator/extension state — shared by the
    checkpointer's template and elastic restore paths and by the
    peer-replication fast restore (``resilience/replicate.py``)."""
    if trainer is None:
        return
    trainer.state = new_state
    trainer.iteration = int(loop["iteration"])
    it = trainer.train_iter
    if hasattr(it, "restore_loop_state") and "it_order" in loop:
        it.restore_loop_state(
            int(loop["epoch"]),
            {
                "pos": int(loop["it_pos"]),
                "order": loop["it_order"],
                "rng_keys": loop["rng_keys"],
                "rng_pos": int(loop["rng_pos"]),
                "rng_has_gauss": int(loop["rng_has_gauss"]),
                "rng_cached": float(loop["rng_cached"]),
            },
        )
    else:
        if hasattr(it, "epoch"):
            it.epoch = int(loop["epoch"])
        if hasattr(it, "_pos"):
            it._pos = int(loop["it_pos"])
        if "it_order" in loop and hasattr(it, "_order"):
            it._order = np.asarray(loop["it_order"]).astype(np.int64)
            it._rng.set_state((
                "MT19937",
                np.asarray(loop["rng_keys"]).astype(np.uint32),
                int(loop["rng_pos"]),
                int(loop["rng_has_gauss"]),
                float(loop["rng_cached"]),
            ))
    # Sync trigger state so interval extensions don't all re-fire on
    # the first post-resume iteration (which would burn a retention
    # slot on a duplicate checkpoint and log a one-iteration window).
    for ext in trainer.extensions:
        ext._last_fired = (
            int(loop["epoch"])
            if ext.unit == "epoch"
            else int(loop["iteration"])
        )


class MultiNodeCheckpointer(Extension):
    """Trainer extension that snapshots (TrainState, iterator state, trainer
    iteration) every trigger, keeps ``max_to_keep`` checkpoints, and restores
    the newest complete one via :meth:`maybe_load`."""

    def __init__(
        self,
        name: str,
        comm,
        path: str = "checkpoints",
        max_to_keep: int = 5,
        trigger=(1, "epoch"),
        async_save: bool = True,
        known_good_keep: int = 3,
    ):
        super().__init__(self._fire, trigger=trigger, name=f"checkpointer/{name}")
        import orbax.checkpoint as ocp

        self.comm = comm
        self._dir = os.path.abspath(os.path.join(path, name))
        # Deterministic bounded retries around snapshot I/O: a transient
        # filesystem hiccup (GCS 5xx, NFS stall) must not cost a whole-job
        # restart.  Saves retry broadly (the partial commit is clobbered
        # with force=True); restores retry only OS-level I/O errors —
        # template/structure mismatches are NOT transients and must reach
        # maybe_load's fallback logic untouched.
        self._save_retry = RetryPolicy(
            max_attempts=3, base_delay_s=0.2, multiplier=2.0, max_delay_s=2.0
        )
        self._restore_retry = RetryPolicy(
            max_attempts=3, base_delay_s=0.2, multiplier=2.0,
            max_delay_s=2.0, retry_on=(OSError,),
        )
        self._mngr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                create=True,
                enable_async_checkpointing=async_save,
            ),
        )
        # Known-good ring (training-health guard, resilience/guard.py): the
        # last K snapshot steps that survived a clean cross-rank
        # consistency vote.  A snapshot's mere existence only proves the
        # job was ALIVE at the trigger; membership here proves the
        # replicas still agreed — the only steps rollback recovery may
        # target.  Persisted next to the snapshots so a supervised
        # relaunch after a health escalation resumes from verified state.
        self._known_good: deque = deque(maxlen=int(known_good_keep))
        for s in self._load_known_good():
            self._known_good.append(int(s))
        # Newest step save() committed in THIS life — rank-invariant (the
        # trigger fires at the same iterations everywhere), so blessing
        # can skip its async flush deterministically when nothing new
        # could possibly be on disk.
        self._last_saved_step: Optional[int] = None

    # ----------------------------------------------------------------- save
    def _fire(self, trainer):
        self.save(trainer.state, trainer)

    def save(self, state, trainer=None):
        import orbax.checkpoint as ocp

        step = int(trainer.iteration if trainer is not None else state.step)
        loop = self._loop_state(trainer)
        inexact = int(loop.get("it_inexact", 0))
        if inexact > 0:
            # Warn at SAVE time only — _loop_state also runs during restore
            # to build the orbax template, where this condition is noise.
            import warnings

            warnings.warn(
                "checkpoint saved with prefetch lookahead skew: the "
                f"iterator cursor is inexact by up to {inexact} samples "
                "(epoch boundary or shallow cursor in the prefetch "
                "queue); a restore from this snapshot may replay or skip "
                "that many samples.",
                stacklevel=2,
            )
        payload = {"train_state": state, "loop": loop}
        attempts = [0]

        def _commit():
            # Retry attempts force-overwrite: the failed attempt may have
            # left a partial step directory that a plain save would
            # reject.  Counted at ENTRY — a failed save must still mark
            # the attempt, or every retry would re-run force=False.
            attempt = attempts[0]
            attempts[0] += 1
            self._mngr.save(
                step,
                args=ocp.args.StandardSave(payload),
                force=attempt > 0,
            )

        # Span + counter: the save DISPATCH is what blocks the loop
        # (async commits flush later); the span records that cost and
        # the flight recorder can name a rank dying mid-save.
        obs_on = _obs.enabled()
        with (_otrace.tracer().span("ckpt_save", detail=f"step={step}")
              if obs_on else contextlib.nullcontext()):
            self._save_retry.call(_commit)
        if obs_on:
            _omet.registry().counter("ckpt.saves").inc()
        self._last_saved_step = step

    def emergency_save(self, trainer) -> int:
        """Preemption entry point (:class:`PreemptionGuard`): one
        *synchronous* snapshot at the trainer's current iteration —
        flushes any in-flight async commit first, skips the write when
        that step is already the newest snapshot (idempotent under
        repeated signals), and blocks until the new step is durable.
        Returns the step saved."""
        step = int(trainer.iteration)
        self._mngr.wait_until_finished()
        if self._mngr.latest_step() != step:
            self.save(trainer.state, trainer)
            self._mngr.wait_until_finished()
        return step

    @staticmethod
    def _loop_state(trainer) -> dict:
        return capture_loop_state(trainer)

    # -------------------------------------------------------------- restore
    def _restore(self, step, template):
        import orbax.checkpoint as ocp

        obs_on = _obs.enabled()
        with (_otrace.tracer().span("ckpt_restore", detail=f"step={step}")
              if obs_on else contextlib.nullcontext()):
            out = self._restore_retry.call(
                self._mngr.restore, step,
                args=ocp.args.StandardRestore(template),
            )
        if obs_on:
            _omet.registry().counter("ckpt.restores").inc()
        return out

    def maybe_load(self, state, trainer=None) -> Tuple[Any, int]:
        """Reference anchor: ``_MultiNodeCheckpointer.maybe_load`` — restore
        the latest complete snapshot if one exists; otherwise return the
        inputs unchanged.  Returns ``(state, iteration)``."""
        step = self._mngr.latest_step()
        if step is None:
            return state, 0
        return self.restore_step(step, state, trainer)

    def restore_step(self, step, state, trainer=None) -> Tuple[Any, int]:
        """Restore a SPECIFIC snapshot step into ``state``/``trainer`` —
        the rollback-recovery entry point (``maybe_load`` is this at
        ``latest_step``).  Collective: every rank restores together."""
        import orbax.checkpoint as ocp

        template = {
            "train_state": jax.tree_util.tree_map(
                ocp.utils.to_shape_dtype_struct, state
            ),
            "loop": self._loop_state(trainer),
        }
        try:
            restored = self._restore(step, template)
        except Exception:
            # Backward-compatible retries: snapshots predating leaves the
            # CURRENT template carries (it_inexact; ema_params when the
            # user enables EMA on an existing run; the health carry when a
            # TrainingHealthGuard is newly attached) restore against a
            # template without those leaves, then the new leaves re-seed.
            # The snapshot may be missing ANY subset, so every drop
            # combination is tried independently (dropping a leaf the
            # snapshot HAS would hit the opposite structure mismatch).
            # Ordered LEAST-destructive first (ADVICE r3): {it} costs only
            # a counter re-seed, {health} resets the guard's anomaly
            # counters, {ema} discards a trained average — if a future
            # orbax version ever tolerates an extra checkpoint subtree,
            # trying {ema} first would silently throw away a saved EMA
            # from a snapshot that merely predates it_inexact.
            ts = template["train_state"]
            optional = []
            if "it_inexact" in template["loop"]:
                optional.append("it")
            if getattr(ts, "health", None) is not None:
                optional.append("health")
            if getattr(ts, "ema_params", None) is not None:
                optional.append("ema")
            drop_sets = [
                set(c)
                for k in range(1, len(optional) + 1)
                for c in itertools.combinations(optional, k)
            ]
            if not drop_sets:
                raise
            restored = None
            dropped = set()
            for drops in drop_sets:
                ts2 = ts
                if "ema" in drops:
                    ts2 = ts2.replace(ema_params=None)
                if "health" in drops:
                    ts2 = ts2.replace(health=None)
                t2 = {
                    "train_state": ts2,
                    "loop": (
                        {k: v for k, v in template["loop"].items()
                         if k != "it_inexact"}
                        if "it" in drops else template["loop"]
                    ),
                }
                try:
                    restored = self._restore(step, t2)
                    dropped = drops
                    break
                except Exception:
                    continue
            if restored is None:
                raise
            if "ema" in dropped:
                # Seed the average from the restored params (the same
                # no-debias init a fresh EMA run uses), in fp32.
                rs = restored["train_state"]
                restored["train_state"] = rs.replace(
                    ema_params=jax.tree_util.tree_map(
                        lambda p: np.asarray(p, np.float32), rs.params
                    )
                )
            if "health" in dropped:
                # Fresh guard counters, exactly as a first bind seeds them.
                restored["train_state"] = restored["train_state"].replace(
                    health=np.zeros(3, np.float32)
                )
        new_state = restored["train_state"]
        # Re-place on the communicator's mesh, honoring each INPUT leaf's
        # sharding (ZeRO states carry 1/N shards — blanket replication would
        # momentarily materialize N full copies).  Orbax may hand back leaves
        # with mixed placements (single-device scalars vs mesh arrays), which
        # jit rejects; leaves whose input sharding is unknown replicate.
        from jax.sharding import NamedSharding

        def _replace(restored_leaf, input_leaf):
            sh = getattr(input_leaf, "sharding", None)
            # Only mesh shardings count — single-device placements (fresh
            # uncommitted scalars like `step`) must re-replicate or jit sees
            # mixed device sets.
            if isinstance(sh, NamedSharding):
                return jax.device_put(restored_leaf, sh)
            if hasattr(self.comm, "replicate"):
                return self.comm.replicate(restored_leaf)
            return restored_leaf

        new_state = jax.tree_util.tree_map(_replace, new_state, state)
        loop = restored["loop"]
        self._apply_loop(trainer, new_state, loop)
        return new_state, int(loop["iteration"])

    def maybe_load_elastic(
        self, opt, params_template, trainer=None, model_state_template=None
    ) -> Tuple[Any, int]:
        """Elastic restore for the ZeRO tier: resume the latest snapshot even
        when it was saved under a DIFFERENT device count.

        The reference's checkpointer was restart-based with a fixed world
        size (SURVEY §2.8); ZeRO state is padded per device count, so the
        template path of :meth:`maybe_load` cannot reshard it.  This restores
        template-free and re-lays the state onto ``opt``'s mesh via
        :func:`chainermn_tpu.optimizers.zero.reshard_zero_state`.

        ``opt`` is the target :class:`ZeroMultiNodeOptimizer`;
        ``params_template`` a logical parameter pytree (e.g. a fresh
        ``model.init``).  Returns ``(state, iteration)`` — a fresh
        ``opt.init(params_template)`` state when no checkpoint exists.
        """
        import orbax.checkpoint as ocp

        from chainermn_tpu.optimizers.zero import reshard_zero_state

        step = self._mngr.latest_step()
        if step is None:
            return (
                opt.init(
                    params_template, model_state=model_state_template
                ),
                0,
            )
        # Restore to HOST numpy via a metadata-derived template: a
        # template-free restore (and the manager's own item_metadata, which
        # is None on a fresh manager) would rebuild the SAVED device
        # topology — orbax pins shardings to device ids, which by
        # definition no longer exist when the world size changed.  The
        # array metadata tree (shapes/dtypes only) lives under the step's
        # item directory; numpy leaves in the template force a host-RAM
        # restore with no device placement at all.
        item_dir = os.path.join(self._dir, str(step), "default")
        meta = ocp.StandardCheckpointer().metadata(item_dir)
        # Orbax moved the tree around across versions: current wraps it as
        # .item_metadata.tree, 0.7.x returns the metadata pytree directly.
        if hasattr(meta, "item_metadata"):
            meta = meta.item_metadata
        meta = getattr(meta, "tree", meta)
        template = jax.tree_util.tree_map(
            lambda m: np.zeros(m.shape, m.dtype), meta
        )
        raw = self._restore(step, template)
        new_state = reshard_zero_state(
            raw["train_state"], opt, params_template,
            model_state_template=model_state_template,
        )
        loop = raw["loop"]
        self._apply_loop(trainer, new_state, loop)
        return new_state, int(loop["iteration"])

    def _apply_loop(self, trainer, new_state, loop) -> None:
        apply_loop_state(trainer, new_state, loop)

    # ------------------------------------------------- known-good ring
    # (training-health guard rollback recovery — see resilience/guard.py)
    def mark_known_good_upto(self, iteration: int) -> List[int]:
        """Bless every saved snapshot step ≤ ``iteration`` not yet in the
        ring.  Called by the guard after a CLEAN consistency vote at that
        iteration: a vote only vouches for state it actually inspected, so
        snapshots from the future (or from before a rollback) never enter.
        Flushes in-flight async commits first so every rank blesses the
        same step set — skipped (deterministically: the gate depends only
        on rank-invariant state) when no save since the newest blessed
        step means there is nothing new to flush or bless.  Returns the
        newly blessed steps."""
        newest_blessed = max(self._known_good, default=None)
        if self._last_saved_step is None or (
            newest_blessed is not None
            and self._last_saved_step <= newest_blessed
        ):
            return []
        self._mngr.wait_until_finished()
        eligible = sorted(
            int(s) for s in self._mngr.all_steps() if s <= int(iteration)
        )
        # Only the newest ring-capacity's worth: blessing older steps just
        # to evict them immediately would make the return value (and the
        # persisted ring) churn.
        new = []
        for s in eligible[-self._known_good.maxlen:]:
            if s not in self._known_good:
                self._known_good.append(s)
                new.append(s)
        if new:
            self._persist_known_good()
        return new

    def latest_known_good(self) -> Optional[int]:
        """Newest step that survived a clean consistency vote AND still
        exists on disk (orbax's ``max_to_keep`` gc may have reaped an old
        blessed step), or None when no rollback target exists."""
        on_disk = {int(s) for s in self._mngr.all_steps()}
        good = [s for s in self._known_good if s in on_disk]
        return max(good) if good else None

    def known_good_steps(self) -> List[int]:
        return sorted(self._known_good)

    def discard_after(self, step: int) -> List[int]:
        """Delete every snapshot NEWER than ``step`` — they were taken on
        (potentially) poisoned state between the last blessing vote and an
        escalation.  Collective: call on every rank together — orbax's
        ``delete`` is itself a cross-process op (the primary host removes
        the directory, then ALL processes barrier-sync), so gating it to
        one rank would deadlock that rank in the sync.  The re-run of the
        rolled-back iterations re-saves those steps cleanly.  Returns the
        deleted steps."""
        self._mngr.wait_until_finished()
        doomed = sorted(int(s) for s in self._mngr.all_steps() if s > step)
        fell_back = False
        for s in doomed:
            try:
                self._mngr.delete(s)
            except Exception:
                # Last-resort path (orbax sync hiccup): the primary
                # removes the directory; the barrier below resynchronizes
                # and reload() refreshes every rank's step cache.
                fell_back = True
                if jax.process_index() == 0:
                    shutil.rmtree(
                        os.path.join(self._dir, str(s)), ignore_errors=True
                    )
        while self._known_good and max(self._known_good) > step:
            self._known_good.remove(max(self._known_good))
        if self._last_saved_step is not None:
            self._last_saved_step = min(self._last_saved_step, int(step))
        self._persist_known_good()
        if fell_back:
            if jax.process_count() > 1 and hasattr(self.comm, "barrier"):
                self.comm.barrier()
            self._mngr.reload()
        return doomed

    def _known_good_path(self) -> str:
        return os.path.join(self._dir, "known_good.json")

    def _load_known_good(self) -> List[int]:
        try:
            with open(self._known_good_path()) as f:
                return [int(s) for s in json.load(f)["steps"]]
        except Exception:
            return []

    def _persist_known_good(self) -> None:
        if jax.process_index() != 0:
            return
        try:
            tmp = self._known_good_path() + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"steps": sorted(self._known_good)}, f)
            os.replace(tmp, self._known_good_path())
        except OSError:  # best-effort: the ring also lives in memory
            pass

    # ------------------------------------------------------------------ misc
    def all_steps(self):
        return list(self._mngr.all_steps())

    def finalize(self, trainer=None):
        self._mngr.wait_until_finished()

    def close(self):
        self._mngr.wait_until_finished()
        self._mngr.close()


def create_multi_node_checkpointer(
    name: str,
    comm,
    path: str = "checkpoints",
    max_to_keep: int = 5,
    trigger=(1, "epoch"),
    async_save: bool = True,
    known_good_keep: int = 3,
) -> MultiNodeCheckpointer:
    """Reference anchor: ``create_multi_node_checkpointer(name, comm)``.

    ``async_save=False`` commits synchronously at the trigger — use when a
    crash immediately after the trigger must still find that snapshot
    complete (fault-injection tests; final pre-shutdown saves).

    ``known_good_keep`` bounds the ring of vote-blessed snapshots kept for
    the training-health guard's rollback recovery (``docs/resilience.md``);
    it should not exceed ``max_to_keep`` or blessed steps may already be
    garbage-collected when a rollback wants them."""
    return MultiNodeCheckpointer(
        name, comm, path=path, max_to_keep=max_to_keep, trigger=trigger,
        async_save=async_save, known_good_keep=known_good_keep,
    )

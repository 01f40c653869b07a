"""Device-side batch prefetching: overlap host→device transfer with compute.

Reference analog: the staging half of ``_memory_utility.py``'s pinned host
buffers (SURVEY.md §2.1) — the reference overlapped H2D copies with compute
via pinned memory + CUDA streams.  The TPU-native equivalent exploits JAX's
asynchronous dispatch: ``device_put`` returns immediately with the transfer
in flight, so submitting batch *k+depth* while the step consumes batch *k*
hides the transfer entirely behind compute.  No threads are needed — the
queue discipline alone creates the overlap.

Composes with :class:`~chainermn_tpu.iterators.prefetch.PrefetchIterator`
(native worker threads assemble batches from dataset rows) to cover the full
input path: rows → host batch (C++ ring, ahead of time) → device batch
(async transfer, ahead of time) → jitted step.
"""

from __future__ import annotations

from collections import deque
from typing import Any, NamedTuple, Optional

import numpy as np

from chainermn_tpu.observability import enabled as _obs_enabled
from chainermn_tpu.observability.tracing import UnitLedger as _UnitLedger
from chainermn_tpu.observability.tracing import annotate as _annotate


def _nbytes(batch: Any) -> int:
    if isinstance(batch, (tuple, list)):
        return sum(_nbytes(b) for b in batch)
    return int(getattr(batch, "nbytes", 0))


def _leading_dim(batch: Any) -> int:
    if isinstance(batch, (tuple, list)):
        return _leading_dim(batch[0])
    return int(np.shape(batch)[0])


class _Entry(NamedTuple):
    batch: Any
    epoch: int
    is_new_epoch: bool
    iteration: int
    epoch_detail: float
    n_samples: int
    # Inner iterator's checkpoint state captured just BEFORE this batch was
    # pulled: restoring from it replays this batch and everything after it —
    # the exact resume point while the batch sits unconsumed in the queue.
    resume: Optional[dict]


class DevicePrefetchIterator:
    """Keeps up to ``depth`` batches resident on device, mesh-sharded.

    Wraps any epoch-aware host iterator (:class:`SerialIterator`,
    :class:`PrefetchIterator`, …); each yielded batch is already the result
    of ``comm.shard_batch`` — device arrays whose transfer was issued one or
    more steps ago.  Epoch bookkeeping (``epoch`` / ``is_new_epoch`` /
    ``iteration`` / ``epoch_detail``) reflects the CONSUMED batch, not the
    wrapped iterator's (submission-time) cursor, so trainer triggers fire at
    the same ticks as without the wrapper.
    """

    def __init__(self, iterator, comm, depth: int = 2):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._it = iterator
        self._comm = comm
        self._depth = depth
        self._queue: deque = deque()
        self._exhausted = False
        self.epoch = int(getattr(iterator, "epoch", 0))
        self.iteration = int(getattr(iterator, "iteration", 0))
        self.is_new_epoch = False
        self._epoch_detail = float(getattr(iterator, "epoch_detail", 0.0))
        #: Unit ledger: every ``__next__`` leaves a record of its wait by
        #: phase (host batch, device put), joined to a profiler's trace by
        #: the ``n=`` ordinal; rides the ``CMN_OBS`` switch, latched here.
        self._waits = 0
        self._units = (
            _UnitLedger("input_wait", ordinal="n") if _obs_enabled()
            else None
        )
        self._fill()

    # ------------------------------------------------------------- pipeline
    def _fill(self) -> None:
        while not self._exhausted and len(self._queue) < self._depth:
            resume = self._snapshot_inner()
            try:
                host = next(self._it)
            except StopIteration:
                self._exhausted = True
                return
            # Async: the transfer is in flight the moment shard_batch
            # returns; it completes while earlier batches are consumed.
            with _annotate("cmn_input_device_put",
                           bytes=lambda: _nbytes(host)):
                on_device = self._comm.shard_batch(host)
            self._queue.append(
                _Entry(
                    batch=on_device,
                    epoch=int(getattr(self._it, "epoch", 0)),
                    is_new_epoch=bool(
                        getattr(self._it, "is_new_epoch", False)
                    ),
                    iteration=int(getattr(self._it, "iteration", 0)),
                    epoch_detail=float(
                        getattr(self._it, "epoch_detail", 0.0)
                    ),
                    n_samples=_leading_dim(host),
                    resume=resume,
                )
            )

    def __iter__(self):
        return self

    def __next__(self):
        if not self._queue:
            raise StopIteration
        n = self._waits
        self._waits += 1
        with _annotate("cmn_input_wait", ledger=self._units, n=n):
            e = self._queue.popleft()
            self.epoch = e.epoch
            self.is_new_epoch = e.is_new_epoch
            self.iteration = e.iteration
            self._epoch_detail = e.epoch_detail
            self._fill()
        return e.batch

    @property
    def epoch_detail(self) -> float:
        return self._epoch_detail

    # ---------------------------------------------------------- delegation
    def reset(self) -> None:
        self._it.reset()
        self._queue.clear()
        self._exhausted = False
        self.epoch = int(getattr(self._it, "epoch", 0))
        self.iteration = int(getattr(self._it, "iteration", 0))
        self.is_new_epoch = False
        self._epoch_detail = 0.0
        self._fill()

    def close(self) -> None:
        self._queue.clear()
        close = getattr(self._it, "close", None)
        if close is not None:
            close()

    def __getattr__(self, name):
        # Passthrough for batch_size/_n/dataset/... (ProgressBar totals etc).
        it = self.__dict__.get("_it")
        if it is None:  # guard against recursion before __init__ ran
            raise AttributeError(name)
        return getattr(it, name)

    @property
    def _pos(self):
        """Consumption-adjusted cursor.  The checkpointer's raw-attribute
        fallback (for inner iterators exposing ``_pos`` but neither
        checkpoint protocol) must not see the inner SUBMISSION cursor —
        it runs up to ``depth`` batches ahead of what the trainer consumed."""
        pos = getattr(self.__dict__["_it"], "_pos", 0)
        queued = sum(e.n_samples for e in self._queue)
        boundary = any(e.is_new_epoch for e in self._queue)
        if queued and not boundary and pos >= queued:
            return pos - queued
        return pos

    @_pos.setter
    def _pos(self, value):
        setattr(self.__dict__["_it"], "_pos", value)

    # ------------------------------------------------------- checkpointing
    def _snapshot_inner(self) -> Optional[dict]:
        """Inner iterator's current checkpoint state.  Works over both
        protocols: an inner ``checkpoint_loop_state`` (PrefetchIterator) is
        delegated to; a SerialIterator-shaped inner
        (``_pos``/``_order``/``_rng``) has the equivalent state synthesized
        here.  ``None`` when the inner is neither (checkpointer falls back
        to raw attributes)."""
        inner = getattr(self._it, "checkpoint_loop_state", None)
        if inner is not None:
            return inner()
        if hasattr(self._it, "_order") and hasattr(self._it, "_rng"):
            it = self._it
            mt, keys, pos, has_gauss, cached = it._rng.get_state()
            return {
                "pos": int(it._pos),
                "order": np.asarray(it._order, np.int64),
                "rng_keys": np.asarray(keys, np.uint32),
                "rng_pos": int(pos),
                "rng_has_gauss": int(has_gauss),
                "rng_cached": float(cached),
            }
        return None

    def checkpoint_loop_state(self) -> Optional[dict]:
        """Consumption-granular cursor for the multi-node checkpointer.

        EXACT at every tick: each queue entry carries the inner state
        captured just before that batch was pulled, so the snapshot for the
        oldest unconsumed batch replays the queue's contents precisely —
        epoch boundaries in flight included.  (The former pos-arithmetic
        adjustment degraded to a flagged best-effort cursor whenever a
        queued batch crossed an epoch boundary.)"""
        if self._queue:
            return self._queue[0].resume
        return self._snapshot_inner()

    def restore_loop_state(self, epoch: int, state: dict) -> None:
        self._queue.clear()
        self._exhausted = False
        inner = getattr(self._it, "restore_loop_state", None)
        if inner is not None:
            inner(epoch, state)
        else:
            it = self._it
            it.epoch = int(epoch)
            it.is_new_epoch = False
            it._pos = int(state["pos"])
            it._order = np.asarray(state["order"]).astype(np.int64)
            it._rng.set_state((
                "MT19937",
                np.asarray(state["rng_keys"]).astype(np.uint32),
                int(state["rng_pos"]),
                int(state["rng_has_gauss"]),
                float(state["rng_cached"]),
            ))
        self.epoch = int(getattr(self._it, "epoch", epoch))
        self.iteration = int(getattr(self._it, "iteration", 0))
        self.is_new_epoch = False
        self._fill()


def create_device_prefetch_iterator(iterator, communicator, depth: int = 2):
    """Wrap ``iterator`` so batches are mesh-sharded device arrays whose
    host→device transfer overlaps the previous steps' compute."""
    return DevicePrefetchIterator(iterator, communicator, depth=depth)

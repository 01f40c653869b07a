"""Minimal trainer loop.

The reference delegates its loop to Chainer's ``Trainer``/``StandardUpdater``
(see SURVEY.md §3.2); examples attach ``LogReport``/``PrintReport``/
``ProgressBar`` on rank 0 only.  This module provides just enough of that
shape for the stock example structure to run: a Trainer driving the jitted
SPMD update, interval-triggered extensions, and rank-0-gated reporting
(``jax.process_index() == 0`` — the SPMD analog of ``if comm.rank == 0:`` in
every reference example).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from chainermn_tpu import observability as _obs
from chainermn_tpu.observability import aggregate as _oagg
from chainermn_tpu.observability import flight as _oflight
from chainermn_tpu.observability import metrics as _omet
from chainermn_tpu.resilience import faults as _faults


#: True while a ProgressBar \r-line is open on stderr; printers that emit
#: full lines (LogReport) break the line first so output never interleaves.
_progress_line_open = False


def _close_progress_line():
    global _progress_line_open
    if _progress_line_open:
        print(file=sys.stderr, flush=True)
        _progress_line_open = False


class Extension:
    """An interval-triggered trainer hook (Chainer extension analog)."""

    def __init__(self, fn: Callable, trigger: Tuple[int, str] = (1, "epoch"),
                 name: Optional[str] = None):
        self.fn = fn
        self.interval, self.unit = trigger
        assert self.unit in ("epoch", "iteration")
        self.name = name or getattr(fn, "__name__", "extension")
        self._last_fired = 0

    def should_fire(self, trainer: "Trainer") -> bool:
        tick = trainer.epoch if self.unit == "epoch" else trainer.iteration
        if tick // self.interval > self._last_fired // self.interval:
            self._last_fired = tick
            return True
        return False

    def __call__(self, trainer: "Trainer"):
        return self.fn(trainer)

    def finalize(self, trainer: "Trainer"):
        """Called once when training ends; default no-op (LogReport flushes
        its pending window here so a mid-epoch stop still reports)."""


def make_extension(trigger=(1, "epoch"), name=None):
    def deco(fn):
        return Extension(fn, trigger=trigger, name=name)
    return deco


class LogReport(Extension):
    """Collects metric means per interval; prints/records on rank 0 only."""

    def __init__(self, trigger=(1, "epoch"), out: Optional[str] = None,
                 print_report: bool = True):
        super().__init__(self._fire, trigger=trigger, name="LogReport")
        self.log: List[dict] = []
        self._out = out
        self._print = print_report
        self._t0 = time.time()

    def _fire(self, trainer: "Trainer"):
        window = trainer.drain_observations()
        if not window:
            return
        # Device arrays are converted to floats only here, at the trigger
        # interval — the hot loop never blocks on metric values.
        means = {k: float(np.mean([np.asarray(o[k]) for o in window if k in o]))
                 for k in window[-1]}
        entry = {
            "epoch": trainer.epoch,
            "iteration": trainer.iteration,
            "elapsed_time": time.time() - self._t0,
            **means,
        }
        self.log.append(entry)
        self._report(means, entry)

    def finalize(self, trainer: "Trainer"):
        self._fire(trainer)

    def _report(self, means, entry):
        if jax.process_index() == 0:
            if self._print:
                _close_progress_line()
                parts = [f"epoch {entry['epoch']}", f"iter {entry['iteration']}"]
                parts += [f"{k} {v:.4f}" for k, v in means.items()]
                print("  ".join(parts), flush=True)
            if self._out:
                os.makedirs(os.path.dirname(self._out) or ".", exist_ok=True)
                with open(self._out, "w") as f:
                    json.dump(self.log, f, indent=1)


class MetricsReport(Extension):
    """Observability counterpart of :class:`LogReport`: publishes the
    newest step metrics into the per-rank registry, writes a per-rank
    JSONL feed, and (collectively) ships the same entry to rank 0's
    merged feed over the host object plane.

    Where :class:`LogReport` prints rank-0 interval means and discards the
    rest, this extension keeps every rank's view: each tick it

    1. converts the trainer's newest metrics to floats (at the trigger
       interval only — the hot loop never syncs on metric values, same
       policy as LogReport) and sets them as ``train.<name>`` gauges;
    2. takes a stamped registry sample (the flight recorder's last-K ring);
    3. appends ``{"step", "rank", "metrics", "registry"}`` to
       ``<out_dir>/metrics.rank<R>.jsonl``;
    4. with a communicator, gathers every rank's entry to rank 0, which
       appends one merged line to ``<out_dir>/metrics.merged.jsonl``
       (``per_rank`` carries each entry verbatim — byte-comparable with
       the per-rank feeds) and optionally a Prometheus textfile
       (see :class:`~chainermn_tpu.observability.MetricsAggregator`).

    The gather is a collective: attach with the same ``trigger`` on every
    rank (interval triggers fire at identical iterations by construction).
    ``CMN_OBS=0`` turns the whole extension into a no-op — set it for the
    *job*, never for a subset of ranks, or the enabled ranks block in a
    gather the disabled ones skip.

    Fleet plane (``docs/observability.md`` "Fleet tracing"): with
    ``fleet_trace`` set, the first tick runs an NTP-style clock sync
    over the host object plane (re-run every ``fleet_resync`` ticks to
    track drift), and ``finalize`` gathers every rank's span ring to
    rank 0 and writes ONE offset-corrected, Perfetto-loadable merged
    trace at that path — collective spans aligned across ranks,
    ``fleet.collective_skew_ms`` / ``fleet.straggler_rank`` published.
    Both steps are collectives on the same cadence contract as the
    metrics gather.  ``memory=True`` (default) also publishes the
    ``mem.*`` device watermarks each tick, so the merged feed carries
    HBM alongside step time.  ``device=True`` (opt-in — the one-time
    cost capture re-lowers the step) publishes the train step's
    ``device.*`` MFU/roofline gauges each tick from the compile
    watcher's cost model (``docs/observability.md`` "Device roofline").

    Incident plane (``docs/observability.md`` "Incidents"): each tick
    also evaluates the process
    :class:`~chainermn_tpu.observability.incident.IncidentManager`'s
    watch rules against the live registry — a breaching headline signal
    (straggler named, compile budget blown, KV leak) captures ONE
    deduplicated debug bundle at that moment, per-rank and host-side
    only.
    """

    def __init__(self, comm=None, trigger=(10, "iteration"),
                 out_dir: str = "obs", prometheus: bool = False,
                 aggregate: bool = True, memory: bool = True,
                 device: bool = False,
                 fleet_trace: Optional[str] = None,
                 fleet_probes: int = 8, fleet_resync: int = 64):
        super().__init__(self._fire, trigger=trigger, name="MetricsReport")
        self.comm = comm
        self.out_dir = out_dir
        self._rank = int(getattr(comm, "rank", 0)) if comm is not None \
            else int(jax.process_index())
        self._agg = (
            _oagg.MetricsAggregator(comm, out_dir=out_dir,
                                    prometheus=prometheus)
            if aggregate else None
        )
        self._last_step: Optional[int] = None
        self._memory = bool(memory)
        self._mem_monitor = None
        #: Device/compile plane (PR 11): each tick, publish the train
        #: step's ``device.*`` MFU/roofline gauges from the compile
        #: watcher's captured cost model and the mean ``train.step_ms``
        #: since the last tick.  Opt-in: the one-time cost capture
        #: lowers the step program once more, which on a big model is a
        #: real compile.
        self._device = bool(device)
        self._dev_last = (0.0, 0)  # (sum_ms, count) of train.step_ms
        self.fleet_trace = fleet_trace
        self._fleet_probes = int(fleet_probes)
        self._fleet_resync = max(int(fleet_resync), 1)
        self._fleet_clock = None
        self._fires = 0

    @property
    def rank_path(self) -> str:
        return os.path.join(self.out_dir, f"metrics.rank{self._rank}.jsonl")

    def _fire(self, trainer: "Trainer"):
        if not _obs.enabled():
            return
        it = int(trainer.iteration)
        if it == self._last_step:  # finalize after an on-trigger last step
            return
        self._last_step = it
        self._fires += 1
        # Fleet clock: startup sync on the first tick, re-sync on a slow
        # cadence (drift tracking).  Collective — same-iteration firing
        # on every rank is the extension's existing contract.
        if self.fleet_trace is not None and (
                self._fleet_clock is None
                or self._fires % self._fleet_resync == 0):
            from chainermn_tpu.observability import fleet as _ofleet

            if self._fleet_clock is None:
                self._fleet_clock = _ofleet.FleetClock(
                    self.comm, probes=self._fleet_probes
                )
            self._fleet_clock.sync()
        # Device-memory watermarks land as gauges BEFORE the registry
        # sample below, so this tick's feed line carries them.
        if self._memory:
            if self._mem_monitor is None:
                from chainermn_tpu.observability import memory as _omem

                self._mem_monitor = _omem.MemoryMonitor()
            self._mem_monitor.sample()
        # Device-plane roofline gauges for the train step, from the
        # compile watcher's cost model + the step-time histogram's delta
        # since the last tick — landed BEFORE the registry sample so
        # this tick's feed line carries them (like the memory gauges).
        if self._device:
            self._publish_device_gauges()
        means = {}
        if trainer.last_metrics is not None:
            for k, v in trainer.last_metrics.items():
                try:
                    means[k] = float(np.asarray(v))
                except (TypeError, ValueError):
                    continue
        reg = _omet.registry()
        for k, v in means.items():
            reg.gauge(f"train.{k}").set(v)
        sample = reg.sample(it)
        entry = {
            "step": it,
            "rank": self._rank,
            "metrics": means,
            "registry": sample["metrics"],
        }
        os.makedirs(self.out_dir, exist_ok=True)
        # Same strict-JSON sanitization the merged-feed writer applies
        # (non-finite → null), keeping the two feeds verbatim-comparable
        # even on NaN-loss steps.
        with open(self.rank_path, "a") as f:
            f.write(json.dumps(_oagg.sanitize_json(entry)) + "\n")
        if self._agg is not None:
            self._agg.collect(it, entry)
        # Incident plane (ISSUE 12): evaluate the process watch rules on
        # this already-paid cadence — per rule, one registry lookup + a
        # predicate; a breach captures its debug bundle NOW, before the
        # gauge resets or the window rolls over.
        from chainermn_tpu.observability import incident as _oincident

        mgr = _oincident.manager()
        if self._fleet_clock is not None:
            mgr.note_fleet_clock(self._fleet_clock)
        mgr.evaluate()

    def _publish_device_gauges(self) -> None:
        """Best-effort ``device.*`` publish for the newest live
        ``train_step`` program: mean step wall ms since the last tick ×
        the watcher's captured cost model (one extra lowering the first
        time, memoized) → achieved TFLOP/s, MFU, arithmetic intensity,
        roofline gap.  MFU reads None (gauge absent) off the
        ``PEAK_BF16_FLOPS`` table — e.g. CPU CI."""
        from chainermn_tpu.observability import device as _odevice

        wf = _odevice.watch().find("train_step")
        if wf is None:
            return
        h = _omet.registry().histogram("train.step_ms").to_dict()
        d_sum = h["sum"] - self._dev_last[0]
        d_n = h["count"] - self._dev_last[1]
        self._dev_last = (h["sum"], h["count"])
        if d_n <= 0:
            return
        try:
            _odevice.watch().publish_roofline(
                wf, d_sum / d_n, n_devices=len(jax.devices())
            )
        except Exception:
            pass

    def finalize(self, trainer: "Trainer"):
        """Flush a final tick so a stop between triggers still lands the
        closing window (skipped when the last iteration already fired —
        a duplicate step would desync feed consumers); then, with
        ``fleet_trace`` configured, export the merged fleet trace
        (collective — every rank reaches finalize at the same loop
        exit)."""
        self._fire(trainer)
        if self.fleet_trace is not None and _obs.enabled():
            from chainermn_tpu.observability import fleet as _ofleet

            summary = _ofleet.export_fleet_trace(
                self.comm, path=self.fleet_trace,
                clock=self._fleet_clock, probes=self._fleet_probes,
            )
            if summary is not None and jax.process_index() == 0:
                _close_progress_line()
                who = summary.get("straggler_rank")
                print(
                    f"[chainermn_tpu.fleet] merged trace -> "
                    f"{summary['path']} ({summary['nranks']} ranks, "
                    f"max skew {summary['max_skew_ms']} ms, straggler "
                    f"{'none' if who is None else f'rank {who}'})",
                    flush=True,
                )


class PrintReport(Extension):
    """Prints a fixed-column table of selected LogReport entries (reference:
    Chainer's ``PrintReport``, attached ``if comm.rank == 0``).

    Reads the newest entries of the trainer's :class:`LogReport` (located
    automatically, or pass ``log_report=``); fires on the same cadence so a
    row appears per LogReport interval.  With a LogReport that also prints,
    set its ``print_report=False`` to avoid double output."""

    def __init__(self, entries: Sequence[str], log_report: "LogReport" = None,
                 trigger=(1, "epoch")):
        super().__init__(self._fire, trigger=trigger, name="PrintReport")
        self._keys = list(entries)
        if not self._keys:
            raise ValueError("PrintReport needs at least one entry key")
        self._log = log_report
        self._shown = 0
        self._header_done = False

    def _find_log(self, trainer: "Trainer") -> Optional["LogReport"]:
        if self._log is not None:
            return self._log
        for ext in trainer.extensions:
            if isinstance(ext, LogReport):
                return ext
        return None

    def should_fire(self, trainer: "Trainer") -> bool:
        # Fire AFTER the LogReport regardless of registration order: the
        # trainer walks extensions in list order, so an earlier-registered
        # PrintReport would read log.log before this tick's entry lands
        # (rows one interval late, final row dropped at finalize).  Instead
        # of an ordering contract, fire whenever there are unshown entries.
        log = self._find_log(trainer)
        if log is not None and len(log.log) > self._shown:
            return True
        return False

    def _fire(self, trainer: "Trainer"):
        if jax.process_index() != 0:
            return
        log = self._find_log(trainer)
        if log is None:
            return
        _close_progress_line()
        width = max(12, max(len(k) for k in self._keys) + 2)
        if not self._header_done:
            print("".join(k.ljust(width) for k in self._keys), flush=True)
            self._header_done = True
        for entry in log.log[self._shown:]:
            cells = []
            for k in self._keys:
                v = entry.get(k, "")
                cells.append(
                    (f"{v:.6g}" if isinstance(v, float) else str(v)).ljust(width)
                )
            print("".join(cells), flush=True)
        self._shown = len(log.log)

    def finalize(self, trainer: "Trainer"):
        self._fire(trainer)


class ProgressBar(Extension):
    """Rank-0 progress line with rate + ETA (reference: Chainer's
    ``ProgressBar``, attached ``if comm.rank == 0`` in every example).
    Writes a carriage-returned status line to stderr every
    ``update_interval`` iterations — never on the metric hot path."""

    def __init__(self, update_interval: int = 10):
        super().__init__(self._fire, trigger=(update_interval, "iteration"),
                         name="ProgressBar")
        self._t0 = time.time()

    def _fire(self, trainer: "Trainer"):
        if jax.process_index() != 0:
            return
        elapsed = time.time() - self._t0
        rate = trainer.iteration / elapsed if elapsed > 0 else 0.0
        total = self._total_iters(trainer)
        if total:
            frac = min(trainer.iteration / total, 1.0)
            bar = "#" * int(frac * 20)
            eta = (total - trainer.iteration) / rate if rate > 0 else 0.0
            msg = (f"[{bar:<20}] {frac:6.1%}  iter {trainer.iteration}"
                   f"  {rate:.2f} it/s  eta {eta:.0f}s")
        else:
            msg = (f"iter {trainer.iteration}  epoch {trainer.epoch}"
                   f"  {rate:.2f} it/s")
        # Pad to the widest line so a shrinking eta/rate never leaves stale
        # trailing characters, and \r only after the payload.
        self._width = max(getattr(self, "_width", 0), len(msg))
        print("\r" + msg.ljust(self._width), end="", file=sys.stderr,
              flush=True)
        global _progress_line_open
        _progress_line_open = True

    @staticmethod
    def _total_iters(trainer: "Trainer") -> Optional[int]:
        if trainer.stop_unit == "iteration":
            return trainer.stop_n
        it = trainer.train_iter
        n, bs = getattr(it, "_n", None), getattr(it, "batch_size", None)
        if n and bs:
            return trainer.stop_n * math.ceil(n / bs)
        return None

    def finalize(self, trainer: "Trainer"):
        if jax.process_index() == 0:
            _close_progress_line()


class Trainer:
    """Drives ``optimizer.update`` over a train iterator.

    Args:
      optimizer: a :class:`chainermn_tpu.optimizers.MultiNodeOptimizer`.
      state: initial TrainState (from ``optimizer.init``).
      loss_fn: ``loss_fn(params, batch) -> scalar`` (or ``(scalar, aux)``).
      train_iter: yields global batches (tuples of stacked arrays).
      stop: ``(n, 'epoch'|'iteration')`` stop trigger.
      preemption_guard: optional
        :class:`~chainermn_tpu.resilience.PreemptionGuard`, polled once per
        iteration — converts SIGTERM into a rank-synchronized emergency
        checkpoint + distinguished exit (see ``docs/resilience.md``).
      health_guard: optional
        :class:`~chainermn_tpu.resilience.TrainingHealthGuard` — adds
        in-graph step anomaly detection (the guard's kwargs merge into
        ``step_kwargs`` and its health carry is seeded on the state),
        cadenced cross-rank consistency votes, rollback recovery, and
        step-time/straggler stats (see ``docs/resilience.md``).

    The loop is also a ``CMN_FAULT`` hook point: ``crash@iter:N`` raises an
    :class:`~chainermn_tpu.resilience.InjectedFault` at iteration N through
    the exact path a user exception would take, and the fail-silent kinds
    corrupt this loop's values at the same per-iteration hook points —
    ``nan@grad:N``/``spike@loss:N`` poison the incoming batch,
    ``flip@param:N`` corrupts the local replica after the update,
    ``skew@step:N:ms`` stretches every step from N on (fail-slow).
    """

    def __init__(self, optimizer, state, loss_fn, train_iter,
                 stop: Tuple[int, str] = (1, "epoch"),
                 extensions: Optional[List[Extension]] = None,
                 has_aux: bool = False, stateful: bool = False,
                 step_kwargs: Optional[dict] = None,
                 preemption_guard=None, health_guard=None):
        self.optimizer = optimizer
        self.state = state
        self.loss_fn = loss_fn
        self.train_iter = train_iter
        self.stop_n, self.stop_unit = stop
        assert self.stop_unit in ("epoch", "iteration")
        self.extensions = list(extensions or [])
        self.has_aux = has_aux
        self.stateful = stateful
        # Extra make_train_step options threaded through optimizer.update
        # (accum_steps, augment, ...).
        self.step_kwargs = dict(step_kwargs or {})
        self.preemption_guard = preemption_guard
        # Process-wide injector, shared with HostComm's hook sites: a
        # hang@iter must also freeze the heartbeat threads whose freeze
        # callbacks live on the data plane's (same) injector.
        self._fault_injector = _faults.process_injector()
        self.iteration = 0
        self._observations: List[dict] = []
        #: Newest step's raw metrics dict (device arrays — no host sync);
        #: what MetricsReport converts at ITS cadence without consuming
        #: the LogReport observation window.
        self.last_metrics: Optional[dict] = None
        # Per-step observability publishers, resolved once (default-on,
        # CMN_OBS=0 removes even the instrument lookups): a host-side
        # counter + step-time histogram per iteration, nothing that could
        # sync the device stream.
        self._obs_on = _obs.enabled()
        if self._obs_on:
            _reg = _omet.registry()
            self._obs_iterations = _reg.counter("train.iterations")
            self._obs_step_ms = _reg.histogram("train.step_ms")
        # Arm the flight recorder (installs the SIGUSR1 live-snapshot
        # handler) UNGATED by CMN_OBS: the recorder is governed by its own
        # knobs (CMN_OBS_FLIGHT_DIR / CMN_OBS_FLIGHT), matching the
        # crash path, which builds it lazily regardless of CMN_OBS.
        _oflight.recorder()
        # Bind LAST: the guard merges its in-graph kwargs into step_kwargs
        # and seeds state.health on the state set above.
        self.health_guard = health_guard
        if health_guard is not None:
            health_guard.bind(self)

    @property
    def epoch(self) -> int:
        return getattr(self.train_iter, "epoch", 0)

    def extend(self, ext: Extension):
        self.extensions.append(ext)

    def drain_observations(self) -> List[dict]:
        obs, self._observations = self._observations, []
        return obs

    def _done(self) -> bool:
        tick = self.epoch if self.stop_unit == "epoch" else self.iteration
        return tick >= self.stop_n

    def run(self):
        inj = self._fault_injector
        while not self._done():
            t0 = time.perf_counter()
            batch = next(self.train_iter)
            if inj is not None:
                # Fail-silent injection, pre-step: nan@grad / spike@loss
                # poison THIS iteration's batch (counted 1-based like the
                # iter site).
                batch = _faults.poison_batch(inj, batch, self.iteration + 1)
            # The step is a watched program: its dispatch is on the
            # profiler's clock as ``cmn_dispatch(program=train_step)``
            # whoever calls it (observability/device.py).
            self.state, metrics = self.optimizer.update(
                self.state, batch, self.loss_fn, has_aux=self.has_aux,
                stateful=self.stateful, **self.step_kwargs,
            )
            self.iteration += 1
            if inj is not None:
                # Fail-silent injection, post-step: flip@param corrupts the
                # local replica (checkpoints taken this iteration snapshot
                # the corruption, exactly like real silent divergence);
                # skew@step stretches the step (fail-slow straggler).
                self.state = _faults.corrupt_params(
                    inj, self.state, self.iteration
                )
                inj.hook("step", count=self.iteration)
            # Keep raw device arrays — no host sync on the hot path.
            self._observations.append(dict(metrics))
            self.last_metrics = dict(metrics)
            if self._obs_on:
                self._obs_iterations.inc()
                self._obs_step_ms.observe(
                    (time.perf_counter() - t0) * 1000.0
                )
            for ext in self.extensions:
                if ext.should_fire(self):
                    ext(self)
            if inj is not None:
                inj.hook("iter", count=self.iteration)
            # Health guard AFTER the interval extensions: a checkpoint
            # saved this very iteration exists before the vote that may
            # bless it as known-good (or roll back over it).
            if self.health_guard is not None:
                self.health_guard.post_step(
                    self, metrics, time.perf_counter() - t0
                )
            # Preemption poll LAST: a periodic checkpoint that fired this
            # very iteration makes the guard's emergency save an
            # idempotent no-op.
            if self.preemption_guard is not None:
                self.preemption_guard.poll(self)
        for ext in self.extensions:
            ext.finalize(self)
        if self.health_guard is not None:
            self.health_guard.finalize(self)
        return self.state

"""Device-plane observability — compile watching, FLOPs, MFU/roofline.

The fourth observability plane.  The host plane (PR 3) watches *time on
this rank*, the serving plane (PR 6) watches *requests*, the fleet +
memory planes (PR 8) watch *the pod* and *bytes*; this module watches the
**compiler and the chip**: which programs compiled, what argument
signature triggered each compilation, how much of the hardware roofline
each compiled program achieves.

Three pieces:

* **Compile watch** — :class:`CompileWatch` wraps jitted callables
  (:meth:`CompileWatch.wrap`) and records every compilation into a
  bounded ring (``CMN_OBS_COMPILE_RING``) + ``compile.*`` metrics:
  which program, the abstract argument signature (shapes / dtypes /
  static args) that triggered it, and the backend compile wall time
  (fed by a ``jax.monitoring`` duration listener —
  ``/jax/core/compile/backend_compile_duration`` in jax 0.4.37).  On a
  recompile it emits **blame**: a structured diff of the triggering
  signature against the previous one, naming the changed argument and
  axis — the thing previously reconstructed by hand when an engine's
  ``decode_compiles`` read 2.  Wrapped programs may declare a compile
  **budget** (the serving engine declares ``decode_step <= 1``,
  ``cow <= 1``, ``prefill <= len(ladder)``); exceeding it bumps the
  ``compile.budget_exceeded`` gauge the recompile-guard tests pin at 0.
* **MFU / roofline attribution** — the per-program cost model XLA
  already computes (``compiled.cost_analysis()``: FLOPs + bytes
  accessed) is captured lazily per compiled signature (one extra
  backend compile, memoized process-wide per ``(program, signature)``)
  and folded with a measured step time into :func:`roofline`:
  achieved TFLOP/s, MFU against :data:`PEAK_BF16_FLOPS`, arithmetic
  intensity, and the roofline gap — published as ``device.*`` gauges by
  ``MetricsReport(device=True)`` (train step) and the serving scheduler
  (decode / speculative round).  Pallas custom calls are opaque to
  XLA's FLOP counter, so callers running flash kernels pass the
  analytic :func:`attention_core_flops` correction via ``extra_flops``
  and the result is the inclusive number (same accounting convention as
  ``bench.py``).
* **Flight provider** — a keyed ``"compile"`` provider puts per-program
  compile counts, declared budgets, and the most recent blame records
  into every crash / exit-75 / SIGUSR1 flight record, so a post-mortem
  names compile churn next to the in-flight span.

The FLOP helpers (:data:`PEAK_BF16_FLOPS`, :func:`compiled_flops`,
:func:`attention_core_flops`) moved here from ``chainermn_tpu.utils``
(PR 11); ``utils`` keeps importable re-exports.

Publishing follows the stack's latch rules: :meth:`CompileWatch.wrap`
consults the ``CMN_OBS`` master switch at wrap time (disabled → the raw
jitted callable is returned untouched, zero added overhead); an
explicitly passed registry always publishes.  The per-call steady-state
cost of a watched program is one ``_cache_size()`` read and an int
compare — no locks taken, nothing allocated — which is how the plane
stays inside the <1 % overhead contract.
"""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

from chainermn_tpu.observability import metrics as _metrics
from chainermn_tpu.observability.tracing import annotate as _annotate

#: Compile-record ring capacity — ``CMN_OBS_COMPILE_RING``.
DEFAULT_COMPILE_RING = 256

#: Signature entries kept per compile record (a train state has hundreds
#: of parameter leaves; the ring must stay bounded in bytes, not just
#: records).
MAX_SIGNATURE_LEAVES = 512

#: bf16 peak matmul throughput per chip by jax ``device_kind`` (public
#: specs) — the MFU denominator.  ``bench.py``, the device gauges, and
#: user code share this one table so a headline MFU and a live gauge can
#: never disagree.  (Moved from ``chainermn_tpu.utils`` in PR 11.)
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def cost_dict(compiled) -> Optional[dict]:
    """The backend's full cost analysis as one plain dict (``flops``,
    ``bytes accessed``, per-operand utilization), or ``None`` when the
    backend reports nothing usable."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        cost = dict(cost)
        return cost if cost else None
    except Exception:
        return None


def compiled_flops(compiled) -> Optional[float]:
    """Per-execution FLOP count from XLA's own cost analysis of a lowered-
    and-compiled function (``jax.jit(f).lower(...).compile()``), or ``None``
    when the backend does not report it."""
    cost = cost_dict(compiled)
    if cost is None:
        return None
    try:
        f = float(cost.get("flops", 0.0))
    except (TypeError, ValueError):
        return None
    return f if f > 0 else None


def attention_core_flops(batch: int, heads: int, q_len: int,
                         head_dim: int, kv_len: Optional[int] = None,
                         causal: bool = False, n_forward: int = 1,
                         n_backward: int = 1) -> float:
    """Analytic FLOPs of the attention-core matmuls (``QKᵀ`` and ``AV``)
    for one attention call — the term XLA's ``cost_analysis`` CANNOT see
    when the core runs as a Pallas flash kernel (custom calls are opaque
    to the compiler's FLOP counter, so every flash MFU in this repo is a
    lower bound without this correction).

    Accounting (MAC-based, the convention the XLA counter itself uses for
    the materialized-scores arm, cross-checked against the measured
    flash-vs-XLA ``tflops_per_step`` gap — 1.93 TF measured vs 1.8 TF
    analytic at the seq2seq T=512 geometry, `result/seq2seq_tpu_packed.json`):

    * forward = ``4·B·H·Tq·Tkv·Dh`` (two matmuls), halved for causal
      (only the lower-triangular area is computed by both the flash
      kernel and XLA's masked arm);
    * backward = 2.5× forward (five matmuls: score recompute, dV, dP,
      dQ, dK — the flash backward recomputes scores internally);
    * ``n_forward=2`` when the surrounding block is rematerialized
      (``jax.checkpoint`` re-runs the forward kernel for the backward
      pass — matching how the XLA count includes remat recompute of the
      non-flash matmuls).

    GQA/MQA leave the core count unchanged (every query head still
    attends the full key length); ``heads`` is the QUERY head count.
    """
    if kv_len is None:
        kv_len = q_len
    area = q_len * kv_len
    if causal:
        area *= 0.5
    fwd = 4.0 * batch * heads * area * head_dim
    return n_forward * fwd + n_backward * 2.5 * fwd


def mfu_pct(flops: float, step_time_s: float, n_devices: int = 1,
            device_kind: Optional[str] = None,
            peak_flops: Optional[float] = None) -> Optional[float]:
    """THE utilization formula: per-execution FLOPs ÷ (step time ·
    per-chip peak · n_devices), as a percent.  ``bench.py``,
    ``utils.mfu`` and the ``device.*`` gauges all route through this one
    implementation so the convention can never drift between a headline
    artifact and a live gauge.  ``None`` when the device kind has no
    :data:`PEAK_BF16_FLOPS` entry (and no explicit ``peak_flops``), or
    the inputs are degenerate."""
    if peak_flops is None:
        if device_kind is None:
            import jax

            device_kind = jax.devices()[0].device_kind
        peak_flops = PEAK_BF16_FLOPS.get(device_kind)
    if peak_flops is None or not flops or step_time_s <= 0:
        return None
    return 100.0 * flops / (step_time_s * peak_flops * n_devices)


def roofline(cost: dict, step_time_s: float, n_devices: int = 1,
             device_kind: Optional[str] = None,
             peak_flops: Optional[float] = None,
             extra_flops: float = 0.0) -> Optional[dict]:
    """Roofline attribution for one compiled program's measured step:

    * ``tflops_per_device`` — achieved TFLOP/s per chip, including
      ``extra_flops`` (the analytic flash-kernel correction — XLA's
      counter cannot see inside Pallas custom calls);
    * ``mfu_pct`` — achieved vs :data:`PEAK_BF16_FLOPS` (None off the
      table, unless ``peak_flops`` is given explicitly);
    * ``arithmetic_intensity`` — XLA-counted FLOPs / bytes accessed
      (the roofline x-coordinate; the analytic correction is excluded
      here because the kernel's HBM traffic is equally uncounted);
    * ``roofline_gap_x`` — peak / achieved (how many times below the
      compute roof the program runs; 1.0 = at the roof).

    ``cost`` is a :func:`cost_dict` / ``compiled.cost_analysis()`` dict;
    returns ``None`` when it carries no FLOPs.
    """
    counted = float(cost.get("flops", 0.0) or 0.0)
    if counted <= 0 or step_time_s <= 0:
        return None
    flops = counted + float(extra_flops or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    per_dev = flops / step_time_s / max(n_devices, 1)
    out = {
        "flops_per_exec": counted,
        "extra_flops_per_exec": float(extra_flops or 0.0),
        "bytes_per_exec": nbytes or None,
        "step_time_ms": step_time_s * 1e3,
        "tflops_per_device": per_dev / 1e12,
        "arithmetic_intensity": (counted / nbytes) if nbytes else None,
    }
    pct = mfu_pct(flops, step_time_s, n_devices,
                  device_kind=device_kind, peak_flops=peak_flops)
    out["mfu_pct"] = pct
    out["roofline_gap_x"] = (100.0 / pct) if pct else None
    return out


# --------------------------------------------------- compile-time listener
#: Cumulative backend-compile seconds / count observed in this process,
#: fed by the ``jax.monitoring`` duration listener.  Read UNLOCKED on the
#: hot path (single float/int reads are atomic under the GIL); written
#: only inside the compiler, which is never the steady state.
_mon_state = {"secs": 0.0, "count": 0}
_mon_installed = False
_mon_lock = threading.Lock()

#: The duration event jax 0.4.37 emits around every backend compile.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _install_monitoring() -> None:
    global _mon_installed
    with _mon_lock:
        if _mon_installed:
            return
        try:
            import jax.monitoring

            def _on_duration(event, secs, **kw):
                if event == _BACKEND_COMPILE_EVENT:
                    _mon_state["secs"] += float(secs)
                    _mon_state["count"] += 1

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration
            )
        except Exception:  # pragma: no cover - jax API drift
            pass
        _mon_installed = True


# -------------------------------------------------------------- signatures
def _leaf_signature(x) -> dict:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            return {"shape": [int(s) for s in shape], "dtype": str(dtype)}
        except Exception:
            pass
    if isinstance(x, (bool, int, float)):
        # Traced weak-typed scalars: the VALUE never retriggers a compile,
        # so recording it would litter every blame diff with false
        # "changed" entries (e.g. a prefill start offset).
        return {"py": type(x).__name__}
    return {"static": repr(x)[:80]}


def call_signature(args: tuple, kwargs: dict) -> Dict[str, dict]:
    """Abstract signature of one call: ``{arg path: {shape, dtype} |
    {py} | {static}}`` over the flattened ``(args, kwargs)`` pytree —
    what the compile ring records and the blame diff compares.  Bounded
    at :data:`MAX_SIGNATURE_LEAVES` entries (a ``"...truncated"`` marker
    carries the overflow count)."""
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves, _ = tree_flatten_with_path((args, kwargs))
    sig: Dict[str, dict] = {}
    for path, leaf in leaves[:MAX_SIGNATURE_LEAVES]:
        sig[keystr(path)] = _leaf_signature(leaf)
    if len(leaves) > MAX_SIGNATURE_LEAVES:
        sig["...truncated"] = {
            "static": f"+{len(leaves) - MAX_SIGNATURE_LEAVES} leaves"
        }
    return sig


def signature_diff(prev: Dict[str, dict],
                   cur: Dict[str, dict]) -> List[dict]:
    """Structured blame diff between two :func:`call_signature` s: one
    record per changed argument, naming the changed axes (shape),
    ``dtype_changed``, rank changes, and added/removed leaves."""
    changed: List[dict] = []
    for path, now in cur.items():
        was = prev.get(path)
        if was is None:
            changed.append({"arg": path, "change": "added", "now": now})
            continue
        if was == now:
            continue
        rec: dict = {"arg": path, "before": was, "after": now}
        sa, sb = was.get("shape"), now.get("shape")
        if sa is not None and sb is not None:
            if len(sa) == len(sb):
                rec["axes"] = [
                    i for i, (a, b) in enumerate(zip(sa, sb)) if a != b
                ]
            else:
                rec["rank_changed"] = True
        if was.get("dtype") != now.get("dtype"):
            rec["dtype_changed"] = True
        changed.append(rec)
    for path, was in prev.items():
        if path not in cur:
            changed.append({"arg": path, "change": "removed", "was": was})
    return changed


def _sig_digest(sig: Dict[str, dict]) -> str:
    import hashlib

    return hashlib.blake2b(
        json.dumps(sig, sort_keys=True).encode(), digest_size=8
    ).hexdigest()


# ------------------------------------------------------------- the watcher
class WatchedFunction:
    """One wrapped jitted callable.  Transparent: ``__call__`` /
    ``lower`` / ``_cache_size`` (and any other attribute) forward to the
    underlying ``jax.jit`` object, so existing callers — the engine's
    back-compat ``decode_compiles`` properties, ``step.lower(...).
    compile()`` in the benches — keep working unchanged.

    Steady-state per-call cost: the underlying dispatch plus ONE
    ``_cache_size()`` read, an int compare and the profiler's enabled
    flag (``cmn_dispatch``).  Everything else
    (signature walk, ring append, metrics) happens only on the calls
    that actually compiled — never in the hot loop the budgets guard.
    """

    def __init__(self, fn, program: str, watch: "CompileWatch",
                 budget: Optional[int] = None):
        if not hasattr(fn, "_cache_size"):
            raise TypeError(
                f"CompileWatch.wrap needs a jitted callable exposing "
                f"_cache_size() (got {type(fn).__name__})"
            )
        self._fn = fn
        self.program = program
        self.budget = budget
        self._watch = watch
        self._seen = int(fn._cache_size())
        self._last_signature: Optional[Dict[str, dict]] = None
        #: abstract args of the newest compile (jax.ShapeDtypeStruct
        #: pytree) — what lazy cost capture lowers with.
        self._abstract: Optional[Tuple[tuple, dict]] = None
        self._cost: Optional[dict] = None
        self._cost_failed = False

    # ------------------------------------------------------------ dispatch
    def __call__(self, *args, **kwargs):
        mark = _mon_state["secs"]
        # On the profiler's clock (free while none runs): the dispatch of
        # every watched program, whoever calls it, and inside the dispatch
        # that compiled a ``cmn_compile`` child saying which variant it was
        # and how long the backend took — a recompile inside a traced
        # window is a span, not a long dispatch to puzzle over.
        with _annotate("cmn_dispatch", program=self.program):
            out = self._fn(*args, **kwargs)
            n = int(self._fn._cache_size())
            if n != self._seen:
                with _annotate(
                    "cmn_compile", program=self.program, n=n,
                    backend_ms=lambda: 1e3 * (_mon_state["secs"] - mark),
                ):
                    self._watch._record_compile(self, n, args, kwargs, mark)
                self._seen = n
        return out

    # ------------------------------------------------------ transparency
    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        return int(self._fn._cache_size())

    def __getattr__(self, name):
        return getattr(self._fn, name)

    # ------------------------------------------------------------- state
    @property
    def compiles(self) -> int:
        """Compiled-variant count — identical to ``_cache_size()`` (the
        hand-rolled counters this watcher replaced)."""
        return int(self._fn._cache_size())

    @property
    def over_budget(self) -> bool:
        return self.budget is not None and self.compiles > self.budget

    def cost_analysis(self, capture: bool = True) -> Optional[dict]:
        """XLA's cost model for the newest compiled signature (lazy: ONE
        extra backend compile via ``lower(abstract args).compile()``,
        memoized process-wide per ``(program, signature)`` so N engines
        on one geometry pay once).  ``None`` before the first compile or
        when the backend reports nothing.

        ``capture=False`` never triggers that extra compile — it returns
        the already-captured/memoized model or ``None``.  Latency-
        sensitive callers (the serving scheduler's on-cadence publish,
        which runs BETWEEN decode iterations of live requests) pass
        False and leave the capture to a drain/warmup moment; a
        synchronous backend compile mid-traffic would stall every
        in-flight request and page the SLO monitor on the observability
        plane itself."""
        if self._cost is not None:
            return self._cost
        if self._cost_failed or self._abstract is None:
            return None
        sig_key = (self.program,
                   _sig_digest(self._last_signature or {}))
        memo = self._watch._cost_memo
        cost = memo.get(sig_key)
        if cost is None:
            if not capture:
                return None
            try:
                a, kw = self._abstract
                cost = cost_dict(self._fn.lower(*a, **kw).compile())
            except Exception:
                cost = None
            if cost is None:
                self._cost_failed = True
                return None
            memo[sig_key] = cost
        self._cost = cost
        return cost


class CompileWatch:
    """Per-process compile observer: wrapped programs, a bounded ring of
    compile records, blame diffs, budget accounting, ``compile.*``
    metrics, and the ``"compile"`` flight-record section.

    Publishing: an explicit ``registry`` always wraps and publishes
    (caller intent); ``registry=None`` resolves to the global registry
    with the ``CMN_OBS`` master switch consulted at **wrap** time — a
    program born while observability is off stays a raw jit forever
    (the latch rule, applied at the only moment that matters for a
    compile observer).
    """

    def __init__(self, registry=None, ring: Optional[int] = None):
        cap = int(
            ring if ring is not None
            else os.environ.get("CMN_OBS_COMPILE_RING",
                                str(DEFAULT_COMPILE_RING))
        )
        if cap < 1:
            raise ValueError(f"compile ring capacity must be >= 1: {cap}")
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=cap)
        self._blames: deque = deque(maxlen=32)
        self.total_compiles = 0
        self.budget_violations = 0
        self._fns: List["weakref.ref[WatchedFunction]"] = []
        self._cost_memo: Dict[tuple, dict] = {}
        #: An explicitly passed registry always wraps+publishes (caller
        #: intent); registry=None resolves to the global registry with
        #: the CMN_OBS gate applied per wrap() call — so the process
        #: singleton keeps working across an A/B bench's set_enabled
        #: flips (the off arm's engines get raw jits, the on arm's get
        #: watched ones, from the same watch).  Instruments are resolved
        #: per EVENT, not latched: compile events are rare by definition
        #: (never the steady state), and late resolution keeps the
        #: singleton honest across ``registry().reset()`` between bench
        #: arms and the test suite's fresh-registry isolation.
        self._explicit = registry is not None
        self._registry_fn = (
            (lambda: registry) if registry is not None
            else _metrics.registry
        )
        _install_monitoring()
        _install_provider()

    def _reg(self):
        return self._registry_fn()

    # ------------------------------------------------------------ wrapping
    def wrap(self, fn, program: str,
             budget: Optional[int] = None):
        """Wrap a jitted callable; every compilation it ever performs is
        recorded under ``program``.  ``budget`` declares the allowed
        compiled-variant count (exceeding it is a budget violation —
        gauged, blamed, and pinned by the recompile-guard tests).

        Consults the ``CMN_OBS`` master switch at wrap time: disabled →
        returns ``fn`` untouched (zero added overhead — the publisher
        latch, applied at the moment the program is born).  A watch
        built on an explicit registry always wraps (caller intent)."""
        import chainermn_tpu.observability as _obs

        if not self._explicit and not _obs.enabled():
            return fn
        wf = WatchedFunction(fn, program, self, budget=budget)
        with self._lock:
            self._fns.append(weakref.ref(wf))
        exceeded = self._reg().gauge("compile.budget_exceeded")
        if exceeded.value is None:
            exceeded.set(0)
        return wf

    def find(self, program: str) -> Optional[WatchedFunction]:
        """Newest live watched function for ``program`` (preferring one
        that has compiled) — how ``MetricsReport(device=True)`` locates
        the trainer's step program."""
        live = [wf for wf in self.functions() if wf.program == program]
        for wf in reversed(live):
            if wf.compiles:
                return wf
        return live[-1] if live else None

    def functions(self) -> List[WatchedFunction]:
        """Live watched functions, oldest first (dead refs pruned)."""
        with self._lock:
            out, keep = [], []
            for ref in self._fns:
                wf = ref()
                if wf is not None:
                    out.append(wf)
                    keep.append(ref)
            self._fns = keep
        return out

    # ----------------------------------------------------------- recording
    def _record_compile(self, wf: WatchedFunction, n: int, args, kwargs,
                        mon_mark: float) -> None:
        """One detected compilation of ``wf`` (cache size moved to
        ``n``).  Runs on the triggering call's thread, off the
        steady-state path by construction."""
        try:
            import jax

            compile_s = max(_mon_state["secs"] - mon_mark, 0.0)
            sig = call_signature(args, kwargs)
            abstract = jax.tree_util.tree_map(
                lambda x: (
                    jax.ShapeDtypeStruct(x.shape, x.dtype)
                    if hasattr(x, "shape") and hasattr(x, "dtype") else x
                ),
                (args, kwargs),
            )
            rec = {
                "program": wf.program,
                "n_compiles": n,
                "budget": wf.budget,
                "t_mono": time.perf_counter(),
                "compile_s": round(compile_s, 6),
                "signature": sig,
            }
            prev = wf._last_signature
            if prev is not None:
                rec["diff"] = signature_diff(prev, sig)
            over = wf.budget is not None and n > wf.budget
            if over:
                rec["budget_exceeded"] = True
            wf._last_signature = sig
            wf._abstract = abstract
            wf._cost = None  # newest signature owns the cost slot
            wf._cost_failed = False
            with self._lock:
                self._ring.append(rec)
                self.total_compiles += 1
                if prev is not None or over:
                    # Recompiles (and any over-budget first compile, which
                    # cannot happen with sane budgets) are the blame-worthy
                    # events; the very first compile of a program is just
                    # its birth record.
                    self._blames.append(rec)
                if over:
                    self.budget_violations += 1
                    exceeded = self.budget_violations
                else:
                    exceeded = None
            reg = self._reg()
            reg.counter("compile.count").inc()
            reg.histogram("compile.ms").observe(compile_s * 1e3)
            if exceeded is not None:
                reg.gauge("compile.budget_exceeded").set(exceeded)
        except Exception:  # pragma: no cover - observers never raise
            pass

    # --------------------------------------------------------- inspection
    def records(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def blames(self) -> List[dict]:
        """Recompile records (signature diffs attached), newest last."""
        with self._lock:
            return list(self._blames)

    # ------------------------------------------------------------ roofline
    def publish_roofline(self, wf: WatchedFunction, step_time_ms: float,
                         n_devices: int = 1,
                         device_kind: Optional[str] = None,
                         peak_flops: Optional[float] = None,
                         extra_flops: float = 0.0,
                         registry=None,
                         capture: bool = True) -> Optional[dict]:
        """Compute :func:`roofline` for ``wf``'s newest compiled program
        at the measured ``step_time_ms`` and publish the ``device.*``
        gauges (``registry`` overrides this watch's own — the serving
        scheduler passes its latched one).  Returns the roofline dict,
        or ``None`` when no cost model is available.  ``capture=False``
        publishes only off an already-captured cost model (see
        :meth:`WatchedFunction.cost_analysis`)."""
        cost = wf.cost_analysis(capture=capture)
        if cost is None:
            return None
        r = roofline(cost, step_time_ms / 1e3, n_devices,
                     device_kind=device_kind, peak_flops=peak_flops,
                     extra_flops=extra_flops)
        if r is None:
            return None
        reg = registry if registry is not None else self._reg()
        p = wf.program
        reg.gauge(f"device.{p}.tflops").set(r["tflops_per_device"])
        if r["arithmetic_intensity"] is not None:
            reg.gauge(f"device.{p}.ai").set(r["arithmetic_intensity"])
        if r["mfu_pct"] is not None:
            reg.gauge(f"device.{p}.mfu_pct").set(r["mfu_pct"])
            reg.gauge(f"device.{p}.roofline_gap_x").set(
                r["roofline_gap_x"]
            )
        return r

    # -------------------------------------------------------------- flight
    def flight_section(self) -> dict:
        """The ``"compile"`` flight-record section: per-program compile
        counts vs budgets for every live watched function, plus the most
        recent blame diffs (signatures elided — the diff names the
        changed arguments; full signatures live in the ring)."""
        progs = []
        for wf in self.functions():
            progs.append({
                "program": wf.program,
                "compiles": wf.compiles,
                "budget": wf.budget,
                "over_budget": wf.over_budget,
            })
        with self._lock:
            blames = [
                {k: v for k, v in rec.items() if k != "signature"}
                for rec in list(self._blames)[-4:]
            ]
            return {
                "programs": progs,
                "total_compiles": self.total_compiles,
                "budget_violations": self.budget_violations,
                "ring_records": len(self._ring),
                "recent_blames": blames,
            }


# ------------------------------------------------------ process-wide wiring
_watch: Optional[CompileWatch] = None
_watch_lock = threading.Lock()
_provider_installed = False
#: Separate from ``_watch_lock``: the provider install runs inside
#: ``CompileWatch.__init__``, which ``watch()`` enters while holding
#: ``_watch_lock`` — sharing the (non-reentrant) lock would deadlock.
_provider_lock = threading.Lock()


def watch() -> CompileWatch:
    """THE per-process compile watch (lazy, like the metrics registry).
    It always binds the global registry; the ``CMN_OBS`` latch is applied
    per :meth:`CompileWatch.wrap` call, so an A/B bench flipping
    ``set_enabled`` between engine constructions gets a raw jit in the
    off arm and a watched one in the on arm from the same singleton."""
    global _watch
    if _watch is None:
        with _watch_lock:
            if _watch is None:
                _watch = CompileWatch()
    return _watch


def _install_provider() -> None:
    """Keyed ``"compile"`` flight provider reading the PROCESS watch
    (installed once, on first CompileWatch construction — private
    test watches trigger the install but the section always reflects
    :func:`watch`)."""
    global _provider_installed
    with _provider_lock:
        if _provider_installed:
            return
        from chainermn_tpu.observability import flight as _flight

        _flight.register_provider(
            "compile", lambda: watch().flight_section()
        )
        _provider_installed = True

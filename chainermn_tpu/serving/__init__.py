"""Continuous-batching serving engine over a paged KV cache.

The inference-side answer to the ROADMAP's "heavy traffic" north star:
instead of one static ``lm_generate`` batch that pads every request to the
longest member, a fixed-shape decode step runs ``capacity`` slots forever
while the scheduler streams requests through them — admission the moment a
slot and pool blocks free up, retirement the moment EOS lands (Orca-style
iteration-level scheduling over a vLLM-style paged KV pool).

Six layers:

* :mod:`~chainermn_tpu.serving.kv_pool` — the fixed device-resident block
  pool + host-side REFCOUNTED free-list allocator (zero device syncs;
  one physical block can back many block tables).
* :mod:`~chainermn_tpu.serving.prefix_cache` — the prefix trie: hot
  prompt prefixes (system prompts, few-shot templates, multi-turn
  history) are MAPPED into new requests' block tables instead of
  recomputed, with copy-on-write at the first divergent write into a
  shared partial block.
* :mod:`~chainermn_tpu.serving.engine` — the jitted fixed-capacity decode
  step (compiles exactly once; slot churn never recompiles) + chunked
  prefill; optionally one jitted SPECULATIVE round instead (K draft
  proposals verified by one multi-position target forward — up to K+1
  tokens per sequential step, greedy-exact).
* :mod:`~chainermn_tpu.serving.scheduler` — admission queue, prefill/decode
  interleaving, eviction-based backpressure, ``serve.*`` metrics, plus the
  request-lifecycle observability layer: per-request timeline events
  (exportable as Perfetto-loadable Chrome trace JSON via
  :meth:`~chainermn_tpu.serving.scheduler.Scheduler.export_trace`), the
  streaming SLO monitor (``serve.slo.*`` — see
  :mod:`chainermn_tpu.observability.slo`), and a ``"serving"``
  flight-record provider (live slot map + allocator occupancy in every
  crash/preemption/SIGUSR1 snapshot).
* :mod:`~chainermn_tpu.serving.sharding` — the pod-scale GSPMD plan: one
  engine tensor-parallel over a 1-D ``Mesh(("model",))`` — params on the
  Megatron cut, the paged KV pools (target and draft) sharded
  on KV heads (whole ``[k | v]`` lane groups of their last axis), all
  host-side bookkeeping untouched (``DecodeEngine(mesh=...)``).
* :mod:`~chainermn_tpu.serving.router` — N engines × M chips behind
  least-loaded dispatch off each replica's live gauges, per-replica
  admission backpressure (zero requests lost), queued-work rebalance,
  ``serve.router.*`` metrics, and a merged fleet trace that shows one
  request's life across replicas.  Role-aware: a disaggregated fleet's
  decode ranks take migrated slots only, never fresh admissions.
* :mod:`~chainermn_tpu.serving.recovery` — the serving-fleet failure
  plane: the router's per-replica fault boundary state (live /
  probation / dead), retry budgets with poison quarantine, per-request
  deadlines + router load shedding, the ``serve.health.*`` metric
  family, and the seeded chaos harness that proves the terminal
  invariant (every submitted request terminates exactly once).
* :mod:`~chainermn_tpu.serving.elastic` — the elastic fleet: a
  closed-loop :class:`~chainermn_tpu.serving.elastic.Autoscaler`
  (watch-rule signals → scale-up behind probation / scale-down via
  zero-loss drain, hysteresis + cooldown against flapping) and a
  :class:`~chainermn_tpu.serving.elastic.RollingDeploy` controller
  (fence → drain → revive, one replica at a time, health-gated on
  probation graduation; a mid-rollout death pauses and files a
  critical incident).
* :mod:`~chainermn_tpu.serving.policy` — the multi-tenant policy plane:
  one :class:`~chainermn_tpu.serving.policy.PolicyPlane` the Scheduler
  and Router consult at every admission/eviction/steal decision —
  weighted fair queuing over a VTC-style service clock charged from the
  ledger's cost seams, priority preemption through the
  recompute-requeue path, drift-driven chunked-prefill budgeting
  (Sarathi-style, hysteresis-latched), and per-tenant isolation knobs
  (rate limits, prefix-cache quotas, deadline/shed defaults).  All
  host-side: ``decode_compiles == 1`` holds with policy ON.
* :mod:`~chainermn_tpu.serving.disagg` — disaggregated prefill/decode:
  the KV-block migration primitive (live blocks + block table + carried
  tokens shipped as framed ``send_obj`` payloads over the hostcomm p2p
  plane, tables rewritten against the destination allocator —
  byte-identical KV, sharing and hot prefixes survive the move), the
  prefill/decode role loops on top of it, and preemption-aware draining
  (SIGTERM → migrate every live slot to a peer instead of dropping
  requests).

See ``docs/serving.md`` and ``benchmarks/serving.py``.
"""

from chainermn_tpu.serving.disagg import (
    DecodeRole,
    LocalComm,
    MigrationError,
    MigrationTransport,
    PrefillRole,
    drain_all,
    serve_disaggregated,
)
from chainermn_tpu.serving.elastic import Autoscaler, RollingDeploy
from chainermn_tpu.serving.engine import DecodeEngine
from chainermn_tpu.serving.policy import PolicyPlane, TenantPolicy
from chainermn_tpu.serving.kv_pool import (
    BlockAllocator,
    PagedKVPool,
    PoolExhausted,
    blocks_for,
)
from chainermn_tpu.serving.prefix_cache import PrefixCache
from chainermn_tpu.serving.recovery import (
    ChaosHarness,
    FleetHealth,
    chaos_schedule,
    verify_terminal_invariant,
)
from chainermn_tpu.serving.router import Router
from chainermn_tpu.serving.scheduler import (
    Completion,
    Request,
    Scheduler,
)
from chainermn_tpu.serving.sharding import serving_mesh

__all__ = [
    "BlockAllocator",
    "PagedKVPool",
    "PoolExhausted",
    "PrefixCache",
    "blocks_for",
    "DecodeEngine",
    "DecodeRole",
    "LocalComm",
    "MigrationError",
    "MigrationTransport",
    "PrefillRole",
    "Autoscaler",
    "ChaosHarness",
    "RollingDeploy",
    "Completion",
    "FleetHealth",
    "PolicyPlane",
    "Request",
    "Router",
    "Scheduler",
    "TenantPolicy",
    "chaos_schedule",
    "drain_all",
    "serve_disaggregated",
    "serving_mesh",
    "verify_terminal_invariant",
]

"""Fixed-shape continuous-batching decode engine over the paged KV pool.

The TPU-idiomatic serving loop is ONE jitted decode step whose shapes never
change: ``capacity`` slots × 1 token, every iteration, forever.  Slot churn
(requests finishing, new prompts admitted) only changes the *contents* of
the step's inputs — the block tables, position vector, live mask, RNG lanes
and temperatures — never their shapes or dtypes, so the steady-state loop
compiles **exactly once** (``tests/serving_tests/test_engine.py`` pins this
with a compilation-count guard).  Idle slots ride along masked: their cache
writes are parked on reserved block 0 and their sampled tokens discarded.

One step = gather block tables → the pool's write and read
(:mod:`chainermn_tpu.ops.decode_attention`: the Pallas kernel
:func:`~chainermn_tpu.ops.paged_decode_attention` where the model's
``decode_attention="fused"`` allows it, the gathered read otherwise) →
per-slot sampling (independent RNG lanes, per-slot temperature, engine-wide
``top_k``).

**Speculative decoding** (``draft_model``/``spec_k``): the hot loop becomes
one jitted *round* instead — ``k`` sequential draft proposals per slot
(plus one backfill forward for the last proposal's K/V), then ONE target
verify forward over all ``k + 1`` positions (the paged kernel's
multi-query mode — per-position causality inside the chunk), greedy
prefix acceptance per slot.  A round costs ``k + 1`` draft steps + one
target forward and emits 1..``k + 1`` tokens per slot; greedy output is
exactly the target's own generation (speculation changes the schedule,
never the tokens — Leviathan et al. 2023), and sampling slots simply
accept zero drafts and sample the verify step's position-0 logits, which
ARE the plain step's logits under the same stateless RNG key.  The draft
owns its own block pools but **shares the target's block tables and
allocator**, so admission, prefix sharing, eviction and rollback stay one
accounting decision: a rejected tail is rolled back by *not advancing*
the slot's position — its stale K/V (both pools) is causally masked and
overwritten by later writes, never copied.

**Prefix sharing** (``prefix_cache=True``): the engine owns a
:class:`~chainermn_tpu.serving.prefix_cache.PrefixCache` over its
allocator; the scheduler maps cached prompt blocks at admission and COWs
shared partial blocks through :meth:`DecodeEngine.cow_copy` (one jitted
whole-block copy across every layer of every pool — target and draft).

A chunk of a prompt takes one of two ways.  Where the tick also runs a
decode step, one slot's chunk RIDES it (``mixed_step``, a plain engine's
third program): the ``capacity`` decode rows and ``prefill_chunk`` more
single-token rows go through one pass over the weights, one upload and
one dispatch, and part only at attention's read — the kernel for the
decode rows, the gathered read for the chunk's.  One geometry: a tail
shorter than ``prefill_chunk`` rides as inactive rows that write
nothing — and takes that geometry with no decode row live when it is
handed to :meth:`DecodeEngine.prefill`, so a plain engine compiles no
program a ladder size.  Every other chunk (a whole chunk with no decode
step to ride; a speculative engine's, at every size) runs through a
second single-row jitted program (``prefill``) in chunks drawn from a
small fixed **ladder** of geometries (``prefill_ladder``, by
default ``prefill_chunk`` and its halves down to 8 — one slot per call;
prefill compute scales with every padded row, so a capacity-wide
variant would pay the full ``capacity x chunk`` forward even when a
single slot is refilling): each chunk writes its K/V into the slot's
blocks and the final chunk samples the first generated token from the
last real prompt position's logits.  Chunking bounds prefill's latency
footprint so the scheduler can interleave decode steps between chunks
(iteration-level scheduling, Yu et al. 2022, *Orca*); the ladder bounds
the final chunk's padding waste (a short tail pays the nearest ladder
size, not the full ``prefill_chunk``) at a bounded, admission-path-only
compile cost — at most ``len(prefill_ladder)`` prefill variants, ever,
and still exactly ONE decode-step variant.  A speculative engine's
prefill also runs the draft model over the same chunk (headless —
``return_hidden``), so the draft's cache tracks the target's.

Host↔device traffic per decode step: small int32 control vectors up
(tokens/positions/tables/mask) and the sampled tokens down (``(capacity,)``
plain; ``(capacity, k+1)`` + per-slot acceptance for a speculative round).
Pool accounting stays host-side (:mod:`~chainermn_tpu.serving.kv_pool`) —
no device sync beyond the token readback serving fundamentally needs for
EOS detection.
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import numpy as np

from chainermn_tpu.observability.tracing import annotate as _annotate
from chainermn_tpu.serving.kv_pool import PagedKVPool, blocks_for
from chainermn_tpu.serving.prefix_cache import PrefixCache


class DecodeEngine:
    """Continuous-batching decode over a :class:`PagedKVPool`.

    Args:
      model: a :class:`~chainermn_tpu.models.TransformerLM`.  Works with
        either ``decode_attention`` setting — "fused" runs the paged Pallas
        kernel in the hot loop, "einsum" the gathered read (the
        reference path).  Or a model with **state by slot** — one that
        has ``state_shapes()``, :class:`~chainermn_tpu.models.HybridLM`
        of ``F`` layers: each layer's entry of ``pools`` then holds the
        slots' recurrent state beside its ``"kv"`` blocks, the three
        programs are told which slot a chunk belongs to and how many of
        its rows hold text (``state_slot=``, ``chunk_len=``), and a chunk
        that starts at position 0 starts from zeros inside the program.
        A block holds no state, so everything that moves or shares blocks
        is refused for such a model, at construction or at the call:
        ``prefix_cache=True``, ``draft_model`` / ``spec_k``, ``mesh=``,
        :meth:`cow_copy`, :meth:`read_block` / :meth:`write_block`.
        Or a model with **window layers** — one whose ``ring_shapes()``
        names a ring for some layer, ``HybridLM`` of ``W`` / ``G`` layers:
        two kinds of cache in one engine.  A layer that attends its whole
        context pages it as ever (the allocator's blocks, the slots'
        tables); a window layer's entry of ``pools`` is a ring by slot,
        ``{"ring": (capacity, R, block_len, KH * 2 * Dh)}``, O(window) a
        slot whatever ``max_blocks_per_slot`` is, with no allocator, no
        table and nothing to free — the program works its table out from
        the positions.  The scheduler's accounting is the paged layers'
        alone.  A ring is kept by slot too, so the same is refused, each
        with the ring's reason.  Such a model's expert layers count their
        routing, and the counts leave the step with the sampled tokens in
        the one readback (``cmn_engine_readback(moe_pairs_held=,
        moe_experts_touched=, moe_pairs_dropped=, moe_layers=)``).
      params: the model's parameter pytree.
      capacity: decode slots per step (the fixed batch dimension).
      num_blocks: physical blocks in the pool (block 0 stays reserved).
      block_len: positions per block.
      max_blocks_per_slot: block-table width — caps a request at
        ``max_blocks_per_slot * block_len`` total positions.  Defaults to
        covering ``model.max_len``.
      prefill_chunk: largest prompt-tokens-per-prefill-call geometry.
      prefill_ladder: the full set of allowed prefill chunk sizes
        (must contain its max == ``prefill_chunk``).  Defaults to
        ``prefill_chunk`` and its successive halves down to 8.  Each
        size is one compiled prefill variant (admission path only — the
        decode step stays a single variant).
      top_k: engine-wide sampling truncation (0 = off; static — part of
        the compiled program).
      draft_model: optional draft :class:`TransformerLM` for speculative
        decoding (same vocab; depth/width free).  Requires ``spec_k``.
      draft_params: the draft's parameter pytree.
      spec_k: draft proposals per round (0 = speculation off).
      prefix_cache: share identical prompt prefixes through a refcounted
        block trie (on by default).  Cached blocks survive their writers
        until pool pressure or :meth:`drop_prefix_cache` releases them.
      mesh: optional 1-D ``jax.sharding.Mesh`` with a ``"model"`` axis
        (:func:`~chainermn_tpu.serving.sharding.serving_mesh`): the
        engine becomes TENSOR-PARALLEL over it — params sharded per
        :func:`~chainermn_tpu.serving.sharding.param_spec`, the paged KV
        pools (target AND draft) sharded on KV heads (their last axis), block
        tables / allocator / prefix trie untouched (pure host
        bookkeeping over block ids), control vectors uploaded
        replicated.  Both decode paths work under a mesh:
        ``decode_attention="fused"`` (the fast path) runs the paged
        Pallas kernel per shard under ``shard_map`` on the KV-head
        cut — bit-identical to the unsharded kernel, no new
        collectives — while ``"einsum"`` remains the gathered GSPMD
        fallback.  The geometry must divide the mesh on the KV-head
        axis (checked at construction).  The one-compile contract is
        unchanged: input shardings are stable across steps, so the jit
        caches never see a second signature.
      device: optional ``jax.Device`` pinning a single-device engine's
        params, pools and control uploads (the router's
        N-replicas-on-N-chips layout without sharding).  Mutually
        exclusive with ``mesh``.
        Default ``None`` keeps the classic implicit-default-device fast
        path: no extra transfers anywhere.
    """

    def __init__(self, model, params, capacity: int, num_blocks: int,
                 block_len: int = 16,
                 max_blocks_per_slot: Optional[int] = None,
                 prefill_chunk: int = 32, top_k: int = 0,
                 prefill_ladder: Optional[List[int]] = None,
                 draft_model=None, draft_params=None, spec_k: int = 0,
                 prefix_cache: bool = True, mesh=None, device=None):
        import jax
        import jax.numpy as jnp

        from chainermn_tpu.models.transformer import lm_head_logits

        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if (draft_model is None) != (spec_k == 0):
            raise ValueError(
                "speculative decoding needs BOTH draft_model and "
                f"spec_k >= 1 (got draft_model={draft_model is not None}, "
                f"spec_k={spec_k})"
            )
        if draft_model is not None:
            if draft_params is None:
                raise ValueError("draft_model needs draft_params")
            if draft_model.vocab != model.vocab:
                raise ValueError(
                    f"draft vocab {draft_model.vocab} != target vocab "
                    f"{model.vocab} — proposals would be meaningless"
                )
            from chainermn_tpu.ops import MAX_VERIFY_T

            if not 1 <= spec_k <= MAX_VERIFY_T - 1:
                raise ValueError(
                    f"spec_k must be in [1, {MAX_VERIFY_T - 1}] "
                    f"(verify chunk is k + 1 positions), got {spec_k}"
                )
        if mesh is not None and device is not None:
            raise ValueError(
                "mesh and device are mutually exclusive — a sharded "
                "engine's placement IS its mesh"
            )
        #: the model keeps state by slot beside the paged pool
        self.stateful = getattr(model, "state_shapes", None) is not None
        ring_shapes = getattr(model, "ring_shapes", None)
        #: some layer keeps a ring by slot and no blocks of the pool
        self.ringed = ring_shapes is not None and any(
            r is not None
            for r in ring_shapes(capacity, block_len, prefill_chunk))
        # What is kept by slot — a ring, a recurrent state — no block holds:
        # whatever shares or moves blocks is refused, each with its reason.
        if self.ringed:
            kept, whys = "a ring by slot", (
                "a cached block is a full layer's alone: the window layers' "
                "keys for the shared prefix live in the ring of the slot "
                "that wrote them, and a ring is not shared",
                "a verify chunk writes ahead of what is accepted, and a "
                "ring's newest write takes the place of its oldest key: a "
                "rejected tail cannot be rolled back by not advancing the "
                "position",
                "the slots' rings have no sharding rule yet")
        else:
            kept, whys = "state by slot", (
                "a cached block has no state to go with it: the state "
                "after a shared prefix would have to be kept a trie node",
                "a rejected draft cannot be rolled back by not advancing "
                "the position: the state has already taken it",
                "the slots' state has no sharding rule yet")
        if self.ringed or self.stateful:
            for given, what, why in zip(
                    (prefix_cache, draft_model is not None, mesh is not None),
                    ("prefix_cache=True", "speculative decoding", "mesh="),
                    whys):
                if given:
                    raise NotImplementedError(
                        f"{what} with a model that keeps {kept} "
                        f"({type(model).__name__}): {why}"
                    )
        self.mesh = mesh
        self.device = device
        placement = None
        if mesh is not None:
            from chainermn_tpu.serving import sharding as _sharding

            _sharding.validate_geometry(model, mesh)
            params = _sharding.shard_params(params, mesh)
            # Fused engines run the Pallas decode kernels per shard
            # under shard_map (ops.sharded_paged_decode_attention) —
            # the mesh threads into the model's dispatch as a static
            # field.  Einsum engines come back unchanged.
            model = _sharding.attach_decode_mesh(model, mesh)
            if draft_model is not None:
                _sharding.validate_geometry(draft_model, mesh)
                draft_params = _sharding.shard_params(draft_params, mesh)
                draft_model = _sharding.attach_decode_mesh(
                    draft_model, mesh
                )
            placement = _sharding.pool_placement(mesh)
            #: where small per-step host arrays (control vectors, RNG
            #: lanes) go: replicated on the mesh — one upload, every
            #: chip reads the same block tables.
            self._ctrl = _sharding.replicated(mesh)
        elif device is not None:
            placement = (lambda arr: jax.device_put(arr, device))
            self._ctrl = device
            # The replica's own copy of the weights, beside its pools (a
            # no-op when they are there already).
            params, draft_params = jax.device_put(
                (params, draft_params), device
            )
        else:
            self._ctrl = None
        self.model = model
        self.params = params
        self.draft_params = draft_params
        self.capacity = capacity
        self.pool = PagedKVPool(model, num_blocks, block_len,
                                placement=placement, slots=capacity,
                                prefill_chunk=prefill_chunk)
        self.block_len = block_len
        self.spec_k = spec_k
        self.draft_model = draft_model
        if max_blocks_per_slot is None and not hasattr(model, "max_len"):
            raise ValueError(
                f"{type(model).__name__} states no max_len: say how wide a "
                "slot's block table is (max_blocks_per_slot)"
            )
        self.max_blocks = (
            max_blocks_per_slot
            if max_blocks_per_slot is not None
            else max(
                1, math.ceil((model.max_len + spec_k) / block_len)
            )
        )
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        self.prefill_chunk = prefill_chunk
        if prefill_ladder is None:
            ladder = {prefill_chunk}
            c = prefill_chunk // 2
            while c >= 8:
                ladder.add(c)
                c //= 2
        else:
            ladder = set(int(c) for c in prefill_ladder)
            if not ladder or min(ladder) < 1:
                raise ValueError(f"bad prefill_ladder {prefill_ladder}")
            if max(ladder) != prefill_chunk:
                raise ValueError(
                    f"prefill_ladder max ({max(ladder)}) must equal "
                    f"prefill_chunk ({prefill_chunk}) — the scheduler's "
                    "padding bound at submit() assumes it"
                )
        #: allowed prefill chunk geometries, ascending; the scheduler
        #: picks the smallest size covering a prompt's tail so short
        #: remainders don't pay a full ``prefill_chunk`` of padded
        #: compute.
        self.prefill_ladder = tuple(sorted(ladder))
        self.top_k = top_k
        # The engine OWNS the live pool buffers: they are donated through
        # the jitted step every iteration, so any alias held elsewhere
        # (e.g. on the PagedKVPool) would dangle on deleted arrays after
        # the first step.
        self.pools = self.pool.pools
        self.pool.pools = None
        if draft_model is not None:
            # The draft's pools mirror the target's block geometry and
            # SHARE its allocator + block tables: one physical block id
            # addresses both pools, so admission/sharing/eviction/COW
            # remain a single accounting decision.
            dpool = PagedKVPool(draft_model, num_blocks, block_len,
                                placement=placement)
            self.draft_pools = dpool.pools
            #: HBM bytes per block across target + draft pools.
            self.pool.bytes_per_block += dpool.bytes_per_block
        else:
            self.draft_pools = None
        #: prefix trie over this engine's allocator (None = sharing off).
        self.prefix = (
            PrefixCache(block_len, self.pool.allocator)
            if prefix_cache else None
        )
        #: per-slot RNG BASE keys + temperatures, HOST numpy mirrors
        #: written only at admission (never in the steady loop) and
        #: uploaded lazily — an eager device scatter per admission would
        #: cost more than the whole control-vector upload of a step.
        #: Sampling derives each token's key STATELESSLY as
        #: ``fold_in(base, position)``, so a request's sampled sequence
        #: depends only on its seed and its own token positions —
        #: invisible to co-scheduling, slot placement, and
        #: eviction/recompute (the re-admission re-derives the exact
        #: keys the uninterrupted run would have used).
        self.rng = np.zeros((capacity, 2), np.uint32)
        self.temp = np.zeros((capacity,), np.float32)
        self._rng_temp_dev = None  # lazy device copy, dropped on seed_slot

        def pick(logits, base, position, t):
            """One slot's token: greedy at t <= 0, else temperature/top-k
            sampling keyed by (base key, absolute position)."""
            greedy = jnp.argmax(logits).astype(jnp.int32)
            scaled = logits / jnp.maximum(t, 1e-6)
            if self.top_k:
                k = min(self.top_k, logits.shape[-1])
                # lax.top_k, not a full-vocab sort — this runs per slot
                # inside the hot decode step.
                kth = jax.lax.top_k(scaled, k)[0][-1]
                scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
            key = jax.random.fold_in(base, position)
            samp = jax.random.categorical(key, scaled).astype(jnp.int32)
            return jnp.where(t > 0, samp, greedy)

        # Every program that runs the model takes the params as an
        # ARGUMENT.  A closed-over array is lowered as a literal: each
        # program (decode, every prefill ladder size, the speculative
        # round) would carry its own copy of all the weights inside its
        # executable — at GPT-2-small width 279 MB of generated code per
        # program against 260 MB of params, a compile four times as long,
        # and as much again in every persistent-cache entry (chip
        # compiler, PR 21) — and a sharded engine's programs would hold
        # the weights unsharded.
        def of_chunk(last_idx, rows, *slot):
            """What a model with state is told of a chunk besides: whose
            it is, and how many of its rows hold text (a final chunk ends
            at ``last_idx``, any other is whole).  Nothing, for a model
            without (which is handed no ``slot``)."""
            if not self.stateful:
                return {}
            return {"state_slot": slot[0],
                    "chunk_len": jnp.where(last_idx >= 0, last_idx + 1, rows)}

        #: what the model's expert layers count a step (``serve_counters``:
        #: names, each summed over the layers); they ride behind the
        #: sampled tokens, so the one readback brings both
        self.counters = tuple(getattr(model, "serve_counters", ()))

        def apply(params, *args, **kwargs):
            """``model.apply`` and, for a model that counts its routing,
            the counts as int32 (one a name, summed over the layers)."""
            if not self.counters:
                return model.apply({"params": params}, *args, **kwargs), None
            out, sown = model.apply({"params": params}, *args, **kwargs,
                                    mutable=["intermediates"])
            layers = sown["intermediates"].values()
            return out, jnp.stack([
                sum(jnp.sum(jnp.stack(layer[name])) for layer in layers
                    if name in layer)
                for name in self.counters]).astype(jnp.int32)

        def with_counts(nxt, counts):
            return nxt if counts is None else jnp.concatenate([nxt, counts])

        def step_impl(params, pools, tokens, pos, tables, active, rng,
                      temp):
            (logits, new_pools), counts = apply(
                params, tokens[:, None], cache=pools,
                decode_pos=pos, block_tables=tables, slot_mask=active,
            )
            with jax.named_scope("sample"):
                nxt = jax.vmap(pick)(logits[:, 0], rng, pos, temp)
            return new_pools, with_counts(nxt, counts)

        # A prefill chunk RIDING the decode step: the capacity decode rows
        # and one slot's chunk — ``prefill_chunk`` more single-token rows
        # with consecutive positions, the slot's table (row ``capacity``
        # of ``tables``) and ``active`` false past the text — go through
        # every projection, FFN, norm and the pool write together, one
        # pass over the weights, and part only at attention's read
        # (``chunk_rows``: ops.decode_attention.paged_attend).  The head
        # runs on the decode rows and the chunk's one sampled row
        # (``last_idx``; garbage out where it is negative), never on the
        # whole chunk.  One geometry, so one program.
        def mixed_impl(params, pools, tokens, pos, tables, active, rng,
                       temp):
            S, C = capacity, prefill_chunk
            # The chunk's slot and ``last_idx`` ride behind the positions:
            # a transfer of their own costs the host as much as a vector's.
            pos, slot, last_idx = pos[:-2], pos[-2], pos[-1]
            row_tables = jnp.concatenate([
                tables[:S],
                jnp.broadcast_to(tables[S:], (C, tables.shape[1])),
            ])
            (h, new_pools), counts = apply(
                params, tokens[:, None], cache=pools,
                decode_pos=pos, block_tables=row_tables, slot_mask=active,
                chunk_rows=C, return_hidden=True,
                **of_chunk(last_idx, C, slot),
            )
            take = jnp.concatenate(
                [jnp.arange(S), S + jnp.maximum(last_idx, 0)[None]]
            )
            with jax.named_scope("head"):
                logits = lm_head_logits(model, params, h[take, 0])
            with jax.named_scope("sample"):
                nxt = jax.vmap(pick)(
                    logits,
                    jnp.concatenate([rng, rng[slot][None]]),
                    pos[take],
                    jnp.concatenate([temp, temp[slot][None]]),
                )
            return new_pools, with_counts(nxt, counts)

        # Prefill stays a SINGLE-ROW program (one slot's chunk per call):
        # a fixed-capacity variant would pay the full ``capacity x chunk``
        # forward even when one slot is refilling, and prefill compute —
        # unlike the 1-token decode step — scales with every padded row.
        # ``last_idx >= 0`` marks the final chunk; the first generated
        # token is sampled from that in-chunk position's logits.  A
        # speculative engine's prefill ALSO runs the draft model over the
        # chunk (headless) so the draft cache tracks the target's.
        def prefill_impl(params, draft_params, pools, dpools, tokens, p0,
                         table, last_idx, rng, temp, *slot):
            # (``slot``: a stateful engine's one more argument)
            h, new_pools = model.apply(
                {"params": params}, tokens, cache=pools, decode_pos=p0,
                block_tables=table, return_hidden=True,
                **of_chunk(last_idx, tokens.shape[1], *slot),
            )
            if draft_model is not None:
                _, dpools = draft_model.apply(
                    {"params": draft_params}, tokens, cache=dpools,
                    decode_pos=p0, block_tables=table, return_hidden=True,
                )
            li = jnp.maximum(last_idx, 0)
            # LM head at the sampled position ONLY: the other chunk
            # rows' logits are never read, and a full (chunk, vocab)
            # head matmul is a third of prefill compute.  Same manual
            # fp32 head application as models.lm_loss_chunked.
            with jax.named_scope("head"):
                hx = jax.lax.dynamic_slice_in_dim(h, li, 1, axis=1)
                logits = lm_head_logits(model, params, hx[0])
            with jax.named_scope("sample"):
                nxt = pick(logits[0], rng, p0 + li, temp)
            return new_pools, dpools, nxt

        # One speculative ROUND, one jitted program: k + 1 sequential
        # draft steps (the last backfills the final proposal's K/V — a
        # permanent zero-K/V row after an all-accept round would poison
        # the draft's context forever, same hazard
        # models.lm_speculative_generate documents), then ONE target
        # verify forward over the (k + 1)-position chunk with per-row
        # positions, greedy prefix acceptance per slot.  Sampling slots
        # (t > 0) accept zero drafts and sample position-0's logits —
        # which ARE the plain step's logits under the same fold_in key,
        # so sampling semantics are unchanged by speculation.
        def spec_impl(params, draft_params, pools, dpools, tokens, pos,
                      tables, active, rng, temp):
            k = spec_k

            def dstep(carry, i):
                tok, dp = carry
                dlogits, dp = draft_model.apply(
                    {"params": draft_params}, tok[:, None], cache=dp,
                    decode_pos=pos + i, block_tables=tables,
                    slot_mask=active,
                )
                nxt = jnp.argmax(dlogits[:, 0], axis=-1).astype(jnp.int32)
                return (nxt, dp), nxt

            (_, dpools), drafts = jax.lax.scan(
                dstep, (tokens, dpools), jnp.arange(k + 1)
            )
            drafts = drafts[:k]  # step k only backfilled K/V
            chunk = jnp.concatenate(
                [tokens[None], drafts], axis=0
            ).T  # (S, k+1): [last, d1..dk]
            logits, pools = model.apply(
                {"params": params}, chunk, cache=pools, decode_pos=pos,
                block_tables=tables, slot_mask=active,
            )
            with jax.named_scope("sample"):
                g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S, k+1)
                agree = (g[:, :k] == chunk[:, 1:]).astype(jnp.int32)
                n_accept = jnp.cumprod(agree, axis=1).sum(axis=1)
                tok0 = jax.vmap(pick)(logits[:, 0], rng, pos, temp)
                g = g.at[:, 0].set(tok0)
                n_accept = jnp.where(temp > 0.0, 0, n_accept)
            return pools, dpools, g, n_accept

        # KV-block migration device half (serving/disagg.py): read ONE
        # physical block's contents out of every layer of every pool
        # (target + draft), and write one back.  Traced block index —
        # one compiled variant each, ever, so migration churn can never
        # threaten the decode step's one-compile contract.  The gather
        # does NOT donate (the pools stay live for the next step); the
        # put donates exactly like the cow copy.
        def gather_impl(pools, dpools, idx):
            def one(layer):
                return {
                    n: jax.lax.dynamic_index_in_dim(
                        layer[n], idx, axis=0, keepdims=False
                    )
                    for n in layer
                }

            with jax.named_scope("kv_gather"):
                t = [one(p) for p in pools]
                d = (
                    [one(p) for p in dpools]
                    if draft_model is not None else None
                )
            return t, d

        def put_impl(pools, dpools, idx, tdata, ddata):
            def one(layer, data):
                return {n: layer[n].at[idx].set(data[n]) for n in layer}

            with jax.named_scope("kv_put"):
                pools = [one(p, x) for p, x in zip(pools, tdata)]
                if draft_model is not None:
                    dpools = [one(p, x) for p, x in zip(dpools, ddata)]
            return pools, dpools

        # Copy-on-write: duplicate ONE physical block across every layer
        # of every pool (target + draft) so a borrower of a shared
        # partial block can diverge without scribbling the cached
        # original.  Traced src/dst — one compiled variant, ever.
        def cow_impl(pools, dpools, src, dst):
            def dup(layer):
                return {
                    n: layer[n].at[dst].set(layer[n][src]) for n in layer
                }

            with jax.named_scope("cow_copy"):
                pools = [dup(p) for p in pools]
                if draft_model is not None:
                    dpools = [dup(p) for p in dpools]
            return pools, dpools

        # Every engine program rides the compile watcher (PR 11): each
        # compilation is recorded with the triggering argument signature,
        # a recompile emits a structured blame diff instead of a bare
        # counter bump, and the declared budgets below feed the
        # ``compile.budget_exceeded`` gauge the recompile-guard tests
        # pin at 0.  The watcher consults CMN_OBS at wrap time — with
        # observability off these are the raw jits (zero overhead) and
        # the ``*_compiles`` properties read ``_cache_size()`` exactly
        # as before.
        from chainermn_tpu.observability import device as _odevice

        _w = _odevice.watch()
        self._step = _w.wrap(
            jax.jit(step_impl, donate_argnums=(1,)),
            program="decode_step", budget=1,
        )
        self._prefill = _w.wrap(
            jax.jit(prefill_impl, donate_argnums=(2, 3)),
            program="prefill", budget=len(self.prefill_ladder),
        )
        self._spec = (
            _w.wrap(
                jax.jit(spec_impl, donate_argnums=(2, 3)),
                program="spec_round", budget=1,
            )
            if draft_model is not None else None
        )
        # A speculative engine's chunks never ride: its prefill also feeds
        # the draft's cache, and its hot loop is the round, not the step.
        self._mixed = (
            _w.wrap(
                jax.jit(mixed_impl, donate_argnums=(1,)),
                program="mixed_step", budget=1,
            )
            if draft_model is None else None
        )
        self._cow = _w.wrap(
            jax.jit(cow_impl, donate_argnums=(0, 1)),
            program="cow", budget=1,
        )
        self._gather = _w.wrap(
            jax.jit(gather_impl), program="kv_gather", budget=1,
        )
        self._put = _w.wrap(
            jax.jit(put_impl, donate_argnums=(0, 1)),
            program="kv_put", budget=1,
        )

    # ----------------------------------------------------------- uploads
    def _up(self, x):
        """One control-vector upload: committed to the engine's injected
        placement (replicated on the mesh / pinned device) when one was
        given, else the classic uncommitted ``jnp.asarray`` fast path.
        A stable upload sharding is part of the one-compile contract —
        the jit caches key on input shardings.  The placed path goes
        host→target directly (``device_put`` on the host array) — an
        intermediate ``jnp.asarray`` would land on the DEFAULT device
        first and pay a second device→device hop per step."""
        import jax
        import jax.numpy as jnp

        if self._ctrl is None:
            return jnp.asarray(x)
        return jax.device_put(np.asarray(x), self._ctrl)

    # ------------------------------------------------------------- slots
    def seed_slot(self, slot: int, seed: int, temperature: float) -> None:
        """Arm a slot's RNG base key + temperature (admission-time only)."""
        # The key derivation itself (threefry seed hash) stays jax's so
        # fold_in(base, position) matches any other PRNGKey(seed) user.
        import jax

        self.rng[slot] = np.asarray(jax.random.PRNGKey(seed), np.uint32)
        self.temp[slot] = float(temperature)
        self._rng_temp_dev = None

    def _rng_temp(self):
        import jax.numpy as jnp

        if self._rng_temp_dev is None:
            self._rng_temp_dev = (
                self._up(self.rng), self._up(self.temp)
            )
        return self._rng_temp_dev

    # ----------------------------------------------------------- prefill
    def prefill(self, slot: int, chunk: np.ndarray, p0: int,
                table: np.ndarray, last_idx: int = -1) -> Optional[int]:
        """Run one prefill chunk for ``slot``.

        ``chunk`` is one of the ``prefill_ladder`` geometries
        (right-padded past the prompt — pad positions inside the slot's
        allocated blocks are masked by ``valid_len`` until real tokens
        overwrite them; pads past the allocation fall through the
        zero-initialized tail of ``table`` into reserved parking block
        0, which is never read).  ``p0`` may start mid-block (a
        prefix-cache hit resumes at the first unmatched token).
        ``last_idx >= 0`` marks the final chunk: the first generated
        token is sampled from the logits at that in-chunk index and
        returned.  A plain engine runs a final chunk smaller than
        ``prefill_chunk`` in the mixed step's geometry (:meth:`mixed_step`,
        no decode row live): there its pads are inactive rows and write
        nothing at all.
        """
        if chunk.ndim != 1 or chunk.shape[0] not in self.prefill_ladder:
            raise ValueError(
                f"chunk must be 1-D with a ladder size "
                f"{self.prefill_ladder}, got {chunk.shape}"
            )
        if (self._mixed is not None and last_idx >= 0
                and chunk.shape[0] < self.prefill_chunk):
            # A tail (the ladder's smaller sizes are final chunks) has the
            # mixed step's geometry whether or not a decode row is live:
            # its rows past the text ride inactive there, so a plain
            # engine compiles no program a ladder size — one for whole
            # chunks with nothing to ride, one for everything else.
            S = self.capacity
            return self._dispatch_mixed(
                "prefill", np.zeros((S,), np.int32), np.zeros((S,), np.int32),
                np.zeros((S, self.max_blocks), np.int32),
                np.zeros((S,), bool), slot, chunk, p0, table, last_idx,
            )[1]
        with _annotate("cmn_engine_upload"):
            chunk_d = self._up(np.asarray(chunk, np.int32)[None])
            table_d = self._up(np.asarray(table, np.int32)[None])
        with _annotate("cmn_engine_dispatch", program="prefill"):
            self.pools, self.draft_pools, tok = self._prefill(
                self.params,
                self.draft_params,
                self.pools,
                self.draft_pools,
                chunk_d,
                np.int32(p0),
                table_d,
                np.int32(last_idx),
                self.rng[slot],
                np.float32(self.temp[slot]),
                *((np.int32(slot),) if self.stateful else ()),
            )
        if last_idx < 0:
            return None
        with _annotate("cmn_engine_readback"):
            return int(tok)

    # ------------------------------------------------------------ decode
    def _upload(self, tokens, pos, tables, active) -> tuple:
        """The decode step's six device arguments after the weights and
        pools: the four control vectors uploaded, then rng and temp."""
        with _annotate("cmn_engine_upload"):
            rng, temp = self._rng_temp()
            return (
                self._up(np.asarray(tokens, np.int32)),
                self._up(np.asarray(pos, np.int32)),
                self._up(np.asarray(tables, np.int32)),
                self._up(np.asarray(active, bool)),
                rng, temp,
            )

    def step(self, tokens: np.ndarray, pos: np.ndarray,
             tables: np.ndarray, active: np.ndarray) -> np.ndarray:
        """One fixed-capacity decode iteration.

        Args (all host arrays, shapes fixed by construction):
          tokens: ``(capacity,)`` int32 — each slot's last token.
          pos: ``(capacity,)`` int32 — each slot's current length (the
            position this step writes).
          tables: ``(capacity, max_blocks)`` int32 block tables.
          active: ``(capacity,)`` bool — live slots.

        Returns ``(capacity,)`` int32 sampled tokens (garbage at inactive
        slots — callers must mask by ``active``).
        """
        ctrl = self._upload(tokens, pos, tables, active)
        with _annotate("cmn_engine_dispatch", program="decode_step"):
            self.pools, nxt = self._step(self.params, self.pools, *ctrl)
        return self._readback(nxt)

    def _readback(self, nxt) -> np.ndarray:
        """The wait for the device — everything dispatched drains here —
        and the step's tokens; the routing counts that rode behind them
        (``counters``) become the span's counts."""
        with _annotate("cmn_engine_readback") as span:
            out = np.asarray(nxt)
            n = len(self.counters)
            if n:
                span.set_metadata(**{
                    k: int(v) for k, v in zip(self.counters, out[-n:])})
                out = out[:-n]
        return out

    def mixed_step(self, tokens: np.ndarray, pos: np.ndarray,
                   tables: np.ndarray, active: np.ndarray, slot: int,
                   chunk: np.ndarray, p0: int, table: np.ndarray,
                   last_idx: int = -1
                   ) -> Tuple[np.ndarray, Optional[int]]:
        """One decode iteration with ``slot``'s next prefill chunk riding
        it: one upload, one dispatch, one readback for both (a plain
        engine's; a speculative engine's chunks go through
        :meth:`prefill`).

        :meth:`step`'s arguments, then :meth:`prefill`'s: ``chunk`` holds
        up to ``prefill_chunk`` tokens of ``slot``'s text from position
        ``p0``, ``table`` is the slot's, and ``last_idx >= 0`` marks the
        final chunk, whose tokens end there — a non-final chunk is whole.
        The rows past the text ride inactive and write nothing.

        Returns ``(:meth:`step`'s tokens, the chunk's first generated
        token or None)``.
        """
        if self._mixed is None:
            raise RuntimeError(
                "mixed_step on a speculative engine — its chunks go "
                "through prefill()"
            )
        # The tick's one decode dispatch, with a chunk aboard: the span
        # keeps the step's name (what a `cmn_serve_decode` holds is one
        # `program=decode_step` dispatch), `chunk=1` tells the two apart,
        # and the watch's own `cmn_dispatch` / `cmn_compile` inside it
        # say `program=mixed_step`.
        return self._dispatch_mixed(
            "decode_step", tokens, pos, tables, active, slot, chunk, p0,
            table, last_idx,
        )

    def _dispatch_mixed(self, program, tokens, pos, tables, active, slot,
                        chunk, p0, table, last_idx):
        """The mixed program's vectors: the decode rows', then the chunk's
        ``prefill_chunk`` rows — its tokens at their consecutive
        positions, the rows past the text inactive at the last position
        the text has — the slot's table once, and the slot and
        ``last_idx`` behind the positions (a transfer of their own costs
        the host as much as a vector's)."""
        C = self.prefill_chunk
        n = last_idx + 1 if last_idx >= 0 else C
        row_tokens = np.zeros((C,), np.int32)
        row_tokens[:n] = chunk[:n]
        row_pos = np.full((C + 2,), p0 + n - 1, np.int32)
        row_pos[:n] = p0 + np.arange(n)
        row_pos[C:] = slot, last_idx
        ctrl = self._upload(
            np.concatenate([tokens, row_tokens]),
            np.concatenate([pos, row_pos]),
            np.concatenate([tables, np.asarray(table, np.int32)[None]]),
            np.concatenate([active, np.arange(C) < n]),
        )
        with _annotate("cmn_engine_dispatch", program=program, chunk=1):
            self.pools, nxt = self._mixed(self.params, self.pools, *ctrl)
        out = self._readback(nxt)
        return out[:-1], (int(out[-1]) if last_idx >= 0 else None)

    def spec_step(self, tokens: np.ndarray, pos: np.ndarray,
                  tables: np.ndarray, active: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """One speculative round (requires a draft; same fixed shapes as
        :meth:`step`).  The slot at ``pos`` must have block-table
        coverage for positions up to ``pos + spec_k`` (the verify chunk's
        writes) — the scheduler allocates ahead.

        Returns ``(tokens, n_accept)``: ``(capacity, spec_k + 1)`` int32
        round tokens and ``(capacity,)`` int32 per-slot accepted draft
        counts — slot ``s`` emits ``tokens[s, :n_accept[s] + 1]``
        (greedy: accepted drafts + the target's correction/bonus;
        sampling slots always emit exactly ``tokens[s, :1]``).
        """
        if self._spec is None:
            raise RuntimeError(
                "spec_step on a non-speculative engine — construct with "
                "draft_model/draft_params/spec_k"
            )
        ctrl = self._upload(tokens, pos, tables, active)
        with _annotate("cmn_engine_dispatch", program="spec_round"):
            self.pools, self.draft_pools, toks, n_accept = self._spec(
                self.params, self.draft_params, self.pools,
                self.draft_pools, *ctrl,
            )
        with _annotate("cmn_engine_readback"):
            return np.asarray(toks), np.asarray(n_accept)

    # ----------------------------------------------------- prefix sharing
    def _refuse_with_state(self, what: str) -> None:
        """A block of a model with state by slot is half of what a position
        needs: the other half is the slot's recurrent state *at that
        position*, which nothing keeps."""
        if self.ringed:
            raise NotImplementedError(
                f"{what} with a model that keeps a ring by slot "
                f"({type(self.model).__name__}): a block holds the full "
                "layers' keys and values alone — the window layers' are in "
                "the slot's ring, which no block id names — such a request "
                "moves as a recompute entry (its text), not as blocks"
            )
        if self.stateful:
            raise NotImplementedError(
                f"{what} with a model that keeps state by slot "
                f"({type(self.model).__name__}): a block's keys and values "
                "are no use without the recurrent state at its last "
                "position, and no pool keeps that — such a request moves "
                "as a recompute entry (its text), not as blocks"
            )

    def cow_copy(self, src: int, dst: int) -> None:
        """Copy physical block ``src`` onto ``dst`` across every layer of
        every pool (target + draft) — the device half of copy-on-write.
        Pure block-table/refcount surgery stays with the caller."""
        self._refuse_with_state("cow_copy (sharing a block)")
        self.pools, self.draft_pools = self._cow(
            self.pools, self.draft_pools, np.int32(src), np.int32(dst)
        )

    def drop_prefix_cache(self) -> int:
        """Release every trie-held block reference (gc/retire pass);
        returns the number of blocks released.  With no live slots the
        allocator is back at its construction baseline afterwards."""
        return self.prefix.clear() if self.prefix is not None else 0

    # ------------------------------------------------------- kv migration
    def read_block(self, block: int) -> dict:
        """One physical block's live KV contents as HOST numpy arrays:
        ``{"target": [per-layer {"kv": (block_len, KH * 2 * Dh)}...],
        "draft": same or None}`` (int8 pools add ``"kv_scale":
        (KH, 2, block_len)``) — the serializable unit
        :mod:`~chainermn_tpu.serving.disagg` ships over the hostcomm p2p
        plane.  Pure read: the pools stay live for the next step."""
        import jax

        self._refuse_with_state("read_block (KV-block migration)")
        t, d = self._gather(
            self.pools, self.draft_pools, np.int32(block)
        )
        return jax.tree_util.tree_map(np.asarray, {"target": t, "draft": d})

    def write_block(self, block: int, data: dict) -> None:
        """Install :meth:`read_block` data into physical ``block`` across
        every layer of every pool — the destination half of a KV-block
        migration.  Byte-preserving: the written block re-reads exactly
        as the source's :meth:`read_block` bytes (same dtypes, same
        layout).  A plain engine refuses draft data and vice versa —
        migration requires role-homogeneous engine geometry."""
        self._refuse_with_state("write_block (KV-block migration)")
        if (data.get("draft") is not None) != (self.draft_model is not None):
            raise ValueError(
                "migration payload draft pools do not match this engine "
                f"(payload draft={data.get('draft') is not None}, engine "
                f"draft={self.draft_model is not None}) — prefill and "
                "decode roles must run the same engine construction"
            )
        self.pools, self.draft_pools = self._put(
            self.pools, self.draft_pools, np.int32(block),
            data["target"], data["draft"],
        )

    def sync(self) -> None:
        """Block until every dispatched program against the KV pools has
        retired (``kv_put`` installs included).  Migration installers
        call this so the NEXT decode step's token readback cannot absorb
        install work into its timed window — ``serve.decode_ms`` stays
        pure decode."""
        import jax

        jax.block_until_ready(self.pools)
        if self.draft_pools is not None:
            jax.block_until_ready(self.draft_pools)

    # ------------------------------------------------------- introspection
    @property
    def hot_program(self):
        """The steady-state loop's (watched) program: the speculative
        round when a draft is armed — the plain step is never dispatched
        then — else the decode step.  What the scheduler's ``device.*``
        roofline gauges attribute to."""
        return self._spec if self._spec is not None else self._step

    @property
    def decode_compiles(self) -> int:
        """Compiled-variant count of the hot-loop decode program — the
        recompile guard's subject: must stay 1 under arbitrary slot
        churn.  Backed by the compile watcher since PR 11 (same number
        as the jit cache's ``_cache_size()`` — the watcher additionally
        records WHAT signature change triggered any recompile); for a
        speculative engine the hot loop is the fused draft+verify round
        program, so that is what is counted."""
        return int(self.hot_program._cache_size())

    @property
    def verify_compiles(self) -> int:
        """Speculative round variants (0 on a plain engine) — the "at
        most one additional cached executable" the speculation feature
        is allowed."""
        return int(self._spec._cache_size()) if self._spec else 0

    @property
    def cow_compiles(self) -> int:
        """Copy-on-write block-copy variants (must stay <= 1)."""
        return int(self._cow._cache_size())

    @property
    def gather_compiles(self) -> int:
        """KV-block gather variants (migration export; must stay <= 1)."""
        return int(self._gather._cache_size())

    @property
    def put_compiles(self) -> int:
        """KV-block put variants (migration import; must stay <= 1)."""
        return int(self._put._cache_size())

    @property
    def prefill_compiles(self) -> int:
        return int(self._prefill._cache_size())

    @property
    def mixed_compiles(self) -> int:
        """Variants of the decode step a prefill chunk rides (must stay
        <= 1; 0 on a speculative engine, which has none)."""
        return int(self._mixed._cache_size()) if self._mixed else 0

    def kv_steps(self, positions) -> int:
        """Loop steps ONE layer's paged kernel takes for slots writing
        ``positions``: a slot's resident blocks, ``blocks_a_step`` of them
        a step (:func:`~chainermn_tpu.ops.decode_attention.blocks_a_step`
        at the pool's geometry) and its last step what is left."""
        C = self.pool.blocks_a_step
        return sum(-(-blocks_for(p + 1, self.block_len) // C)
                   for p in positions)

    def ring_resident(self, positions) -> int:
        """Blocks of ONE window layer's rings that a decode step reads for
        slots writing ``positions``: each slot's blocks from the one that
        holds its oldest visible key to the one it writes — what the kernel
        walks, a count the host has at hand."""
        BL, w = self.block_len, self.model.window
        return sum(p // BL - max(p - w + 1, 0) // BL + 1 for p in positions)

    def free_blocks(self) -> int:
        return self.pool.allocator.free_blocks

    def stats(self) -> dict:
        """Host-side engine state for flight records / dashboards —
        never touches a device buffer."""
        free = self.pool.allocator.free_blocks
        allocatable = self.pool.num_blocks - 1  # block 0 reserved
        out = {
            "capacity": self.capacity,
            "num_blocks": self.pool.num_blocks,
            "block_len": self.block_len,
            "free_blocks": free,
            "blocks_in_use": allocatable - free,
            "block_occupancy": (
                (allocatable - free) / allocatable if allocatable else 0.0
            ),
            "decode_compiles": self.decode_compiles,
            "prefill_compiles": self.prefill_compiles,
        }
        if self.stateful:
            # beside the blocks' budget: what the slots' state holds,
            # whatever the contexts are
            out["state_bytes"] = self.pool.state_bytes
        if self.ringed:
            # and the window layers' rings, O(window) a slot
            out["ring_bytes"] = self.pool.ring_bytes
        if self.prefix is not None:
            out["prefix_cached_blocks"] = self.prefix.cached_blocks
        if self.spec_k:
            out["spec_k"] = self.spec_k
            out["verify_compiles"] = self.verify_compiles
        # Watched programs over their declared compile budget (empty on a
        # healthy engine; absent when CMN_OBS=0 left the programs as raw
        # jits).  The flight record's "compile" section carries the full
        # per-program ledger + blame diffs.
        over = [
            getattr(p, "program", "?")
            for p in (self._step, self._mixed, self._prefill, self._spec,
                      self._cow, self._gather, self._put)
            if p is not None and getattr(p, "over_budget", False)
        ]
        if over:
            out["compile_over_budget"] = over
        return out

    def alloc_blocks(self, n: int) -> Optional[List[int]]:
        return self.pool.allocator.alloc(n)

    def release_blocks(self, blocks) -> None:
        self.pool.allocator.free(blocks)

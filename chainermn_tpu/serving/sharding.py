"""GSPMD sharding plan for the pod-scale serving engine.

One engine spanning chips (ROADMAP item 1): the model's parameters and
the paged KV pools are laid out over a 1-D ``jax.sharding.Mesh`` with a
single ``"model"`` axis, and the engine's jitted programs run unchanged —
XLA's GSPMD partitioner propagates the input shardings through the whole
decode/prefill/verify computation, inserting the (two) cross-chip
reductions tensor parallelism fundamentally needs (the attention output
projection and the FFN down-projection, Megatron-LM's classic cut).

The plan, axis by axis:

* **Attention** is sharded head-major: ``q``/``qkv`` kernels on the query
  -head axis, ``kv`` kernels on the KV-head axis, the output projection on
  its (contracted) head axis.  Each chip computes its own heads end to
  end; the ``proj`` contraction is the first psum.
* **FFN / MoE** is sharded on the hidden axis: ``ff1`` column-parallel,
  ``ff2`` row-parallel (the second psum).  MoE expert weights shard the
  same way on their per-expert hidden axis — every chip holds a slice of
  EVERY expert, so routing stays host-invisible.
* **LM head** is vocab-sharded (column-parallel); greedy argmax over the
  sharded vocab is a cheap per-shard argmax + cross-chip max.
* **The paged KV pool** is sharded on **KV heads**: the pool is
  token-major, ``(num_blocks, block_len, KH * 2 * Dh)`` with each head's
  ``[k | v]`` lanes contiguous (:mod:`~chainermn_tpu.serving.kv_pool`
  says why), so a cut of its LAST axis is a plain block cut that hands
  each chip ``KH / n`` whole heads of EVERY physical block.  Block ids
  mean the same thing on every chip, which is what keeps the host-side
  bookkeeping replicated-trivially:
* **Block tables, the refcounted allocator and the prefix-cache trie
  stay host-side and replicated** — they are pure Python accounting over
  physical block *ids* (never touching pool bytes), so sharding the
  pools leaves them untouched.  The same table upload drives every
  chip's scatter.
* **Everything small** (embeddings, layernorms, positional tables,
  biases of row-parallel layers, control vectors, RNG lanes) is
  replicated.

Embeddings are deliberately replicated rather than vocab-sharded: the
decode step gathers one row per slot per token, and a sharded gather
would turn that into a collective on the hot path for a table that is a
rounding error next to the KV pool.

The Pallas paged decode kernel carries no GSPMD partitioning rule, so
GSPMD alone cannot propagate through ``pallas_call`` — instead a
sharded ``decode_attention="fused"`` engine runs the kernel **per
shard under** ``shard_map`` (:func:`~chainermn_tpu.ops.
sharded_paged_decode_attention`): queries cut on the head axis, pools on
KV heads (the placement above), block tables replicated.
Attention never crosses KV heads, so the per-shard outputs are
bit-identical to the unsharded kernel's and no new collective lands on
the decode hot path — the row-parallel ``proj`` psum that already exists
completes the reduction.  :func:`attach_decode_mesh` wires the mesh into
the model, which hands it to the pool's dispatch
(``ops/decode_attention.py`` ``paged_attend``); ``decode_attention=
"einsum"`` keeps every paged step on the gathered read, which partitions
cleanly under plain GSPMD.
"""

from __future__ import annotations

from typing import Optional, Sequence

__all__ = [
    "serving_mesh",
    "mesh_model_size",
    "validate_geometry",
    "attach_decode_mesh",
    "param_spec",
    "shard_params",
    "pool_placement",
    "replicated",
]

#: The serving mesh's single axis name.  The training-side 3-D mesh
#: (ROADMAP item 5) reuses this vocabulary — ``"model"`` means tensor
#: parallel there too.
MODEL_AXIS = "model"


def serving_mesh(n_model: int, devices: Optional[Sequence] = None):
    """A 1-D ``Mesh`` of ``n_model`` devices on the ``"model"`` axis.

    ``devices`` defaults to the first ``n_model`` of ``jax.devices()``;
    pass an explicit slice to pin a replica to its own device group
    (the router's N-engines-by-M-chips layout).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if n_model < 1:
        raise ValueError(f"n_model must be >= 1, got {n_model}")
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if len(devices) < n_model:
        raise ValueError(
            f"serving_mesh(n_model={n_model}) needs {n_model} devices, "
            f"only {len(devices)} available — on CPU, force a pod with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    return Mesh(np.asarray(devices[:n_model]), (MODEL_AXIS,))


def mesh_model_size(mesh) -> int:
    """The ``"model"`` axis extent (1 = effectively unsharded)."""
    return int(mesh.shape[MODEL_AXIS])


def validate_geometry(model, mesh) -> None:
    """Fail fast when ``model``'s geometry cannot split ``n`` ways.

    Only the KV-head axis is MANDATORY: :func:`pool_placement` shards
    every pool on its KV heads and the per-shard Pallas kernel
    (``decode_attention="fused"``) needs a whole number of local KV
    heads, so ``KH % n`` must hold (and with GQA, ``H = KH * groups``,
    so the query heads divide whenever KH does).  Any OTHER indivisible
    parameter axis (an odd vocab, a prime ``d_ff``) simply falls back to
    replication leaf-by-leaf in :func:`shard_params` — correct, just
    less parallel — rather than refusing the model.  Both decode paths
    ("fused": the shard_map kernel; "einsum": the gathered read) are
    legal under a mesh.
    """
    n = mesh_model_size(mesh)
    if n == 1:
        return
    kvh = model.n_kv_heads or model.n_heads
    if kvh % n:
        raise ValueError(
            f"model kv heads ({kvh}, the pools' shard axis) are not "
            f"divisible by the mesh's '{MODEL_AXIS}' axis ({n}) — the "
            "paged pools shard on KV heads and the per-shard decode "
            "kernels need whole local KV heads, so KH is the one axis "
            "that must split"
        )


def attach_decode_mesh(model, mesh):
    """Return ``model`` with the serving mesh wired into its decode
    dispatch (``decode_mesh`` static field), so ``decode_attention=
    "fused"`` steps run the paged Pallas kernel per shard under
    ``shard_map``.

    A no-op (the same model comes back) for size-1 meshes and for
    einsum engines — their decode path never consults the mesh.
    """
    if mesh_model_size(mesh) == 1 or model.decode_attention != "fused":
        return model
    return model.clone(decode_mesh=mesh)


def param_spec(path: Sequence[str], leaf):
    """``PartitionSpec`` for one parameter leaf, by its flax path.

    The rules mirror the Megatron cut described in the module docstring;
    anything unrecognized is replicated (safe — GSPMD only needs the big
    tensors annotated, propagation does the rest).
    """
    from jax.sharding import PartitionSpec as P

    name = path[-2] if len(path) >= 2 else ""
    leafname = path[-1]
    M = MODEL_AXIS
    if leafname == "kernel":
        if name == "qkv":        # (D, 3, H, Dh) — fused MHA projection
            return P(None, None, M, None)
        if name == "q":          # (D, H, Dh)
            return P(None, M, None)
        if name == "kv":         # (D, 2, KH, Dh)
            return P(None, None, M, None)
        if name == "proj":       # (H, Dh, D) — row-parallel (psum)
            return P(M, None, None)
        if name == "ff1":        # (D, F) — column-parallel
            return P(None, M)
        if name == "ff2":        # (F, D) — row-parallel (psum)
            return P(M, None)
        if name == "lm_head":    # (D, V) — vocab-sharded head
            return P(None, M)
    elif leafname == "bias":
        if name == "qkv":        # (3, H, Dh)
            return P(None, M, None)
        if name == "q":          # (H, Dh)
            return P(M, None)
        if name == "kv":         # (2, KH, Dh)
            return P(None, M, None)
        if name == "ff1":        # (F,)
            return P(M)
        if name == "lm_head":    # (V,)
            return P(M)
        # proj / ff2 biases add AFTER the psum — replicated.
    elif leafname == "moe_w1":   # (E, D, F) — per-expert column cut
        return P(None, None, M)
    elif leafname == "moe_b1":   # (E, F)
        return P(None, M)
    elif leafname == "moe_w2":   # (E, F, D) — per-expert row cut (psum)
        return P(None, M, None)
    # embed / pos / layernorms / router / moe_b2 / scalars: replicated.
    return P()


def shard_params(params, mesh):
    """``device_put`` every parameter leaf onto ``mesh`` under
    :func:`param_spec` — the one-time layout step a sharded engine pays
    at construction.  A leaf whose nominated axis does not divide the
    mesh (odd vocab, prime ``d_ff``) falls back to replication: always
    correct, just less parallel.  Idempotent for already-sharded
    trees."""
    import jax
    from flax import traverse_util
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n = mesh_model_size(mesh)
    flat = traverse_util.flatten_dict(params)
    out = {}
    for path, leaf in flat.items():
        spec = param_spec(path, leaf)
        for dim, axis in enumerate(spec):
            if axis is not None and leaf.shape[dim] % n:
                spec = P()
                break
        out[path] = jax.device_put(leaf, NamedSharding(mesh, spec))
    return traverse_util.unflatten_dict(out)


def pool_placement(mesh):
    """Placement callable for :class:`~chainermn_tpu.serving.kv_pool.
    PagedKVPool`: both pool entries shard on KV heads — the values
    ``(num_blocks, block_len, KH * 2 * Dh)`` on their last axis (whole
    ``[k | v]`` lane groups), the int8 scales ``(num_blocks, KH, 2,
    block_len)`` on axis 1; anything smaller replicates."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def place(arr):
        # No trailing Nones (``P(None, "model")``, not ``P(None, "model",
        # None, None)``): the engine's programs hand the pools back under
        # the short spelling, and the jit cache keys on the spelling —
        # padded, the first call of every program would compile a variant
        # no later call uses.
        spec = {3: P(None, None, MODEL_AXIS), 4: P(None, MODEL_AXIS)}.get(
            arr.ndim, P()
        )
        return jax.device_put(arr, NamedSharding(mesh, spec))

    return place


def replicated(mesh):
    """The replicated ``NamedSharding`` control vectors / RNG lanes ride
    up on (one upload, every chip sees the same tables)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return NamedSharding(mesh, P())

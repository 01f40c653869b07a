"""The main path's Pallas kernels compile for the chip — without the chip.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a chip
that is *described*, not attached (``on-chip-measurement`` guide §2): each
case lowers one kernel at GPT-2-small serving/training widths against a
``v5e:2x2`` topology and asserts Mosaic accepted it (one ``tpu_custom_call``
per kernel launch in the compiled text).  That catches what interpret mode
cannot — a slice off the tiling, a kernel over its VMEM budget — on every PR
at no chip time.  A compile that passes is not a chip run.

The kernels choose interpret mode from ``jax.default_backend()``, which is
the CPU here, so the module steers that choice itself for its duration.
"""

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# libtpu admits one process at a time (/tmp/libtpu_lockfile) to protect a
# chip; nothing here drives one, and parallel test workers must not skip.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.compilation_cache import (  # noqa: E402
    compilation_cache as _cc,
)
from jax.sharding import SingleDeviceSharding  # noqa: E402

import chainermn_tpu.ops.grouped_matmul  # noqa: E402,F401  (steered below)
import chainermn_tpu.ops.ssd_scan  # noqa: E402,F401  (steered below)

from chainermn_tpu.ops import (  # noqa: E402
    flash_attention,
    paged_decode_attention,
)

pytestmark = pytest.mark.tier1

#: GPT-2-small heads; the smoke's pool geometry (32 slots x 64 blocks of 16).
H, DH, S, MB, BL = 12, 64, 32, 64, 16
NB = S * MB + 1


@pytest.fixture(scope="module")
def chip():
    """One described v5e device, with the kernels steered to compile for
    it; the whole module skips where the topology cannot be described."""
    try:  # here, not at import: collection must not load libtpu
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # NB ``chainermn_tpu.ops.flash_attention`` the ATTRIBUTE is the function
    # (ops/__init__ re-exports it over the submodule) — go through
    # sys.modules for the modules.
    mods = [sys.modules["chainermn_tpu.ops.flash_attention"],
            sys.modules["chainermn_tpu.ops.decode_attention"],
            sys.modules["chainermn_tpu.ops.grouped_matmul"],
            sys.modules["chainermn_tpu.ops.ssd_scan"]]
    saved = [m._use_interpret for m in mods]
    for m in mods:
        m._use_interpret = lambda: False
    # A described-device executable can be written to the persistent cache
    # but not read back without a chip (it warns and recompiles).
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()
    yield topo.devices[0]
    for m, f in zip(mods, saved):
        m._use_interpret = f
    jax.config.update("jax_enable_compilation_cache", cache_was)
    _cc.reset_cache()


def _paged(q_shape, kv_dtype, kv_heads=H, nb=NB):
    """The kernel on the serving pool (``serving/kv_pool.py``): token-major,
    each head's ``[k | v]`` in one lane group, a block's row one DMA."""
    dh = q_shape[-1]
    args = [(q_shape, jnp.bfloat16), ((nb, BL, kv_heads * 2 * dh), kv_dtype),
            ((S, MB), jnp.int32), ((S,), jnp.int32)]
    if kv_dtype == jnp.int8:
        args.append(((nb, kv_heads, 2, BL), jnp.float32))
    return paged_decode_attention, args


def _flash(backward):
    qkv = [((8, 2048, H, DH), jnp.bfloat16)] * 3

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd), qkv


#: name -> ((function, [(shape, dtype), ...]), Mosaic kernel launches
#: expected in the compiled text, the stable names those kernels carry —
#: a trace reduction finds a kernel by ``%<name>``, not by its result dtype)
_CASES = {
    "paged_bf16": (_paged((S, H, DH), jnp.bfloat16), 1, ["paged_decode"]),
    "paged_int8": (_paged((S, H, DH), jnp.int8), 1, ["paged_decode"]),
    "paged_verify_t4": (_paged((S, 4, H, DH), jnp.bfloat16), 1,
                        ["paged_decode"]),
    "flash_fwd": (_flash(False), 1, ["flash_fwd"]),
    # forward (for the residuals) + the dq and dk/dv kernels
    "flash_bwd": (_flash(True), 3,
                  ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
}


def _assert_mosaic_took(text, launches, kernels, name):
    assert text.count("tpu_custom_call") == launches, (
        name, text.count("tpu_custom_call")
    )
    called = set(re.findall(r"%([a-z_]+)(?:\.\d+)? = [^\n]*tpu_custom_call",
                            text))
    # (an enclosing transformation wraps the name when no named scope
    # encloses the call: ``transpose_jvp_flash_bwd_dq__``)
    assert len(called) == len(kernels) and all(
        any(k in c for c in called) for k in kernels), (name, called)


def _on(chip, tree):
    """The abstract arguments ``tree`` (arrays, ``ShapeDtypeStruct``s or
    ``(shape, dtype)`` pairs), placed on the described chip."""
    on_chip = SingleDeviceSharding(chip)

    def pair(a):
        return isinstance(a, tuple) and len(a) == 2 and isinstance(a[0], tuple)

    def place(a):
        shape, dtype = a if pair(a) else (a.shape, a.dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    return jax.tree_util.tree_map(place, tree, is_leaf=pair)


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(name, chip):
    (fn, shapes), launches, kernels = _CASES[name]
    text = jax.jit(fn).lower(*_on(chip, shapes)).compile().as_text()
    _assert_mosaic_took(text, launches, kernels, name)


@pytest.mark.parametrize("t, dkv_launches, dq_launches", [
    (4096, 1, 1), (16384, 4, 2),
], ids=["cell_t4096", "chunked_t16384"])
def test_dkv_kernel_sums_the_group_in_one_launch(t, dkv_launches,
                                                 dq_launches, chip):
    """The training cell's attention backward — StarCoder2's 24 query / 2 KV
    heads of 128, bf16, one row of T = 4,096, causal — is ONE
    ``flash_bwd_dkv`` launch beside one ``flash_bwd_dq``: the per-row
    statistics stage lane-dense, so all 4,096 query rows stage at once, and
    the query group passes over a KV head's resident fp32 dK / dV inside the
    launch, so what leaves it is ``f32[2,T,128]`` — no gradient row per
    QUERY head anywhere in the program and no reduction over a group axis
    after the kernel.  Compiling is Mosaic accepting the scoped VMEM (q + do
    double-buffered 4 MB, the two resident outputs single-buffered 4 MB,
    the tiles).  At T = 16,384 the sequence still chunks: four launches
    over quarters of the query rows (each with a grid axis of four kv
    chunks), two of the dQ kernel over halves of the kv rows."""
    qkv = [((1, t, heads, 128), jnp.bfloat16) for heads in (24, 2, 2)]

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on(chip, qkv)).compile().as_text()
    calls = re.findall(
        r"%(\S+) = ([^\n]*?) custom-call\([^\n]*tpu_custom_call", text)
    dkv = [result for name, result in calls if "flash_bwd_dkv" in name]
    assert len(dkv) == dkv_launches, calls
    assert len([n for n, _ in calls if "flash_bwd_dq" in n]) == dq_launches
    for result in dkv:
        assert re.findall(r"(\w+\[[\d,]*\])", result) == [
            "f32[2,%d,128]" % t] * 2, result
    # (a kv-chunked dQ's partials are fp32 a query head: that is dQ's shape)
    assert not [c for c in calls if "f32[24,%d,128]" % t in c[1]
                and "flash_bwd_dq" not in c[0]], calls
    assert not re.search(r"\[(1,)?2,12,\d+,128\]", text)  # no group axis


#: the serving cells' pool: GPT-2 XL heads (25 of 64) and StarCoder2's
#: (24 query / 2 KV heads of 128), 32 slots x 1024 positions in blocks of 16
_XL_NB = 2049


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("heads, kv_heads, dh, t", [
    (25, 25, 64, 1), (24, 2, 128, 1), (25, 25, 64, 4), (20, 4, 128, 1),
], ids=["dh64", "dh128", "dh64_verify_t4", "dh128_group5"])
def test_mosaic_accepts_the_kv_panel(heads, kv_heads, dh, t, kv_dtype, chip):
    """A pool block's whole ``(block_len, KH * 2 * Dh)`` row — every KV
    head's ``[k | v]`` — is what the kernel DMAs (by hand, from the pool
    left in HBM, into its double buffer) and slices at the heads' lane
    groups: Mosaic takes it at both head widths the configurations have,
    as lane-dense arithmetic over the row (25 heads of 64, decode and a
    verify chunk) and as a loop of matmuls over KV heads (24 / 2 of 128;
    Falcon-H1's 20 / 4 of 128, a group of 5 query rows a head, unpadded),
    int8 (the slot's ``(max_blocks, KH, 2, block_len)`` scale panels a
    per-slot block) included — one ``tpu_custom_call`` named
    ``paged_decode``."""
    q_shape = (S, heads, dh) if t == 1 else (S, t, heads, dh)
    fn, shapes = _paged(q_shape, kv_dtype, kv_heads, _XL_NB)
    text = jax.jit(fn).lower(*_on(chip, shapes)).compile().as_text()
    _assert_mosaic_took(text, 1, ["paged_decode"], (dh, t, kv_dtype))


@pytest.mark.parametrize("block_len, dtype, group, blocks", [
    (16, jnp.bfloat16, 5, 8), (32, jnp.bfloat16, 5, 4),
    (128, jnp.bfloat16, 16, 1), (256, jnp.bfloat16, 16, 1),
    (48, jnp.bfloat16, 5, 1), (8, jnp.bfloat16, 5, 1),
    (8, jnp.float32, 5, 16), (16, jnp.int8, 5, 1), (32, jnp.int8, 1, 1),
    (16, jnp.bfloat16, 1, 1),
], ids=["bl16", "bl32", "bl128", "bl256", "bl48", "bl8_half_a_tile",
        "bl8_f32", "int8", "int8_mha", "row_body"])
def test_blocks_a_step_follow_the_pool(block_len, dtype, group, blocks):
    """The tile the per-head body folds a loop step is worked out from the
    pool's block length and dtype and the group alone: 128 positions where
    a block divides them, is shorter, and is whole packed sublane tiles;
    one block for an int8 pool, and for the row body (one query head a KV
    head on a float pool) — whose window form is the per-head body's."""
    from chainermn_tpu.ops.decode_attention import blocks_a_step

    assert blocks_a_step(block_len, dtype, group) == blocks
    if group == 1 and dtype != jnp.int8:
        assert blocks_a_step(block_len, dtype, group, windowed=True) == 8


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
def test_mosaic_accepts_a_tile_of_eight_blocks(windowed, chip):
    """Falcon-H1's decode call — 64 slots, 20 / 4 heads of 128, a table 64
    wide over ``bf16[4097, 16, 1024]`` — folds eight blocks a loop step: the
    eight rows land in aligned 16-row slices of ONE ``(128, 1024)`` buffer,
    four such buffers in VMEM, and the launch is still ONE
    ``tpu_custom_call`` named ``paged_decode`` (``paged_decode_window`` with
    the two bounds, a ring's blocks of 16)."""
    slots, mb = 64, 64
    args = [((slots, 20, 128), jnp.bfloat16),
            ((4097, 16, 4 * 2 * 128), jnp.bfloat16),
            ((slots, mb), jnp.int32), ((slots,), jnp.int32)]
    args += [((slots,), jnp.int32)] * 2 * windowed

    def fn(q, pool, tbl, valid, *bounds):
        kw = dict(zip(("lowest", "first_pos"), bounds))
        return paged_decode_attention(q, pool, tbl, valid, None, **kw)

    text = jax.jit(fn).lower(*_on(chip, args)).compile().as_text()
    name = "paged_decode_window" if windowed else "paged_decode"
    _assert_mosaic_took(text, 1, [name], name)


def _xl_layer_step(tokens_per_slot, per_slot_pos, chunk_rows=0):
    """One GPT-2 XL-wide decoder layer over the backlog cell's pool, as the
    engine's programs call it: the pools donated, (decode, verify) every
    slot at its own position behind a live mask, (prefill) one slot's chunk
    at a scalar position, (mixed) ``chunk_rows`` more single-token rows, one
    slot's chunk, behind the decode rows."""
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.serving.kv_pool import PagedKVPool

    model = TransformerLM(
        vocab=256, n_layers=1, d_model=1600, n_heads=25, d_ff=256,
        max_len=1024, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        decode_attention="fused")
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    pools = jax.eval_shape(lambda: PagedKVPool(model, _XL_NB, BL).pools)
    slots = S + chunk_rows if per_slot_pos else 1

    def step(params, pools, tokens, pos, tables, active):
        return model.apply(
            {"params": params}, tokens, cache=pools,
            decode_pos=pos if per_slot_pos else pos[0], block_tables=tables,
            slot_mask=active if per_slot_pos else None, return_hidden=True,
            chunk_rows=chunk_rows)

    args = (params, pools,
            jax.ShapeDtypeStruct((slots, tokens_per_slot), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, MB), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_))
    return jax.jit(step, donate_argnums=(1,)), args, pools[0]["kv"]


@pytest.mark.parametrize("tokens, per_slot, launches, chunk_rows", [
    (1, True, 1, 0), (4, True, 1, 0), (32, False, 0, 0), (1, True, 1, 32),
], ids=["decode", "verify_t4", "prefill_c32", "mixed_c32"])
def test_pool_write_and_kernel_share_one_layout(tokens, per_slot, launches,
                                                chunk_rows, chip):
    """The guard that keeps a whole-pool copy from coming back without a
    chip run (``serving/kv_pool.py`` has the story): at the backlog cell's
    geometry — 25 heads of 64, 2,049 blocks of 16, 32 slots, a table 64
    wide, the pool donated — the model's own write and the kernel (or, for
    a prefill chunk, the gather; for a chunk riding the decode rows, one
    write, the kernel for those and the gather for it) compile to a program
    in which argument, scatter and Mosaic agree on the pool's layout: no
    ``copy`` of the
    pool's shape, no temporary as large as a pool, and the pool at rest
    row-major (so nothing is padded: 2049 x 16 x 3200 x 2 bytes)."""
    fn, args, pool = _xl_layer_step(tokens, per_slot, chunk_rows)
    compiled = fn.lower(*_on(chip, args)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == launches
    shape = "[%s]" % ",".join(str(d) for d in pool.shape)  # [2049,16,3200]
    pool_bytes = pool.size * pool.dtype.itemsize
    copies = [c for c in re.findall(r"= (\S+) copy\(", text) if shape in c]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= pool_bytes  # updated in place
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    at_rest = [a for a in entry.split(", ") if shape in a]
    assert len(at_rest) == 1 and at_rest[0].startswith(
        "bf16" + shape + "{2,1,0"), at_rest


# ------------------------------------------- a layer with state by slot
def _falcon_layer_step(chunk_rows):
    """One Falcon-H1-wide ``F`` layer over the backlog cell's pools, as the
    engine's decode step (and, with ``chunk_rows``, its mixed step) calls
    it: 64 slots, 4,097 blocks of 16, a table 64 wide, the pools — keys
    and values by block, recurrent state and convolution tail by slot —
    donated."""
    from chainermn_tpu.models import HybridLM
    from chainermn_tpu.serving.kv_pool import PagedKVPool

    slots = 64
    model = HybridLM(
        vocab=256, n_layers=1, d_model=5120, layer_kinds="F", n_heads=20,
        n_kv_heads=4, head_dim=128, ssm_heads=32, ssm_head_dim=128,
        ssm_groups=2, ssm_state=256, ssm_chunk=128, conv_kernel=4,
        d_ff=21504, rope_theta=1e11, embedding_multiplier=5.65,
        ssm_in_multiplier=0.25, ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.35),
        ssm_out_multiplier=0.088, key_multiplier=0.011,
        attention_out_multiplier=0.0375, mlp_multipliers=(0.18, 0.011),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        decode_attention="fused")
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"])
    pools = jax.eval_shape(
        lambda: PagedKVPool(model, 4097, BL, slots=slots).pools)
    rows = slots + chunk_rows

    def step(params, pools, tokens, pos, tables, active, slot, n):
        kw = dict(state_slot=slot, chunk_len=n) if chunk_rows else {}
        return model.apply(
            {"params": params}, tokens, cache=pools, decode_pos=pos,
            block_tables=tables, slot_mask=active, return_hidden=True,
            chunk_rows=chunk_rows, **kw)

    args = (params, pools, jax.ShapeDtypeStruct((rows, 1), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows, MB), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    return jax.jit(step, donate_argnums=(1,)), args, pools[0]


@pytest.mark.parametrize("chunk_rows", [0, 64], ids=["decode", "mixed_c64"])
def test_the_slots_state_is_stepped_in_place(chunk_rows, chip):
    """The guard of the state pool's layout, as the one above is the block
    pool's: at Falcon-H1's widths the decode rows' recurrence is ONE fusion
    that reads the 268 MB ``f32[64,32,128,256]`` state of a layer once and
    writes it once (``y`` comes out of the same pass), the donated state
    and pool are updated in place — no ``copy`` of either's shape, no
    temporary as large as the state — and the decode rows' attention is the
    paged kernel at a group of 5 (one ``tpu_custom_call``), with a chunk
    aboard too."""
    fn, args, pools = _falcon_layer_step(chunk_rows)
    compiled = fn.lower(*_on(chip, args)).compile()
    text = compiled.as_text()
    _assert_mosaic_took(text, 1, ["paged_decode"], chunk_rows)
    state, pool = pools["ssm"], pools["kv"]
    for arr in (state, pool):
        shape = "[%s]" % ",".join(str(d) for d in arr.shape)
        copies = [c for c in re.findall(r"= (\S+) copy\(", text)
                  if shape in c]
        assert not copies, copies
    state_bytes = state.size * state.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < state_bytes, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= state_bytes + pool.size * 2
    # the step: one fusion holds every state-shaped operation under it
    shape = r"f32\[64,32,128,256\]"
    fusions = re.findall(r"(%\S+) = \([^\n]*" + shape
                         + r"[^\n]*\) fusion\([^\n]*ssm\.step", text)
    assert len(fusions) == 1, fusions


# ------------------------------------------ a window layer's ring by slot
def test_mosaic_accepts_the_window_form_at_blocks_of_128(chip):
    """The paged kernel's window form at the fifth configuration's shapes:
    128 query / 8 KV heads of 128 (a group of 16), a ring of 32 slots x 34
    blocks of 128 positions flattened to ``(1088, 128, 2048)`` — a 512 KB
    block a DMA, four of them in VMEM — five prefetched scalars a slot: one
    ``tpu_custom_call`` named ``paged_decode_window``."""
    slots, R, bl = 32, 34, 128
    args = [((slots, 128, 128), jnp.bfloat16),
            ((slots * R, bl, 8 * 2 * 128), jnp.bfloat16),
            ((slots, R), jnp.int32), ((slots,), jnp.int32)]
    bounds = [((slots,), jnp.int32)] * 2

    def fn(q, pool, tbl, valid, lowest, first_pos):
        return paged_decode_attention(q, pool, tbl, valid, None,
                                      lowest=lowest, first_pos=first_pos)

    text = jax.jit(fn).lower(*_on(chip, args + bounds)).compile().as_text()
    _assert_mosaic_took(text, 1, ["paged_decode_window"], "window")


def _parallel_layers_step(chunk_rows, prefill=False):
    """A ``W`` and a ``G`` layer at the fifth configuration's widths over
    its pools — 32 slots, a ring of 34 blocks of 128 a slot, 3,585 blocks of
    the paged pool, a table 112 wide, chunks of 256 — as the engine's
    decode step (with ``chunk_rows``, its mixed step; ``prefill``: a chunk
    of its own) calls them, the pools donated."""
    from chainermn_tpu.models import HybridLM
    from chainermn_tpu.serving.kv_pool import PagedKVPool

    slots, chunk, bl, width = 32, 256, 128, 112
    model = HybridLM(
        vocab=256, n_layers=2, d_model=4096, layer_kinds="WG", n_heads=128,
        n_kv_heads=8, head_dim=128, window=4096, rope_theta=50000.0,
        rope_interleaved=True, norm="layer", tie_embeddings=True,
        experts_held=16, ep_of=8, experts_per_tok=8, d_expert=4096,
        n_shared=4, d_shared=16384, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, decode_attention="fused")
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"])
    pools = jax.eval_shape(lambda: PagedKVPool(
        model, 3585, bl, slots=slots, prefill_chunk=chunk).pools)
    rows = 1 if prefill else slots + chunk_rows

    def step(params, pools, tokens, pos, tables, active, slot, n):
        kw = dict(state_slot=slot, chunk_len=n) \
            if chunk_rows or prefill else {}
        return model.apply(
            {"params": params}, tokens, cache=pools,
            decode_pos=pos[0] if prefill else pos, block_tables=tables,
            slot_mask=None if prefill else active, return_hidden=True,
            chunk_rows=chunk_rows, **kw)

    args = (params, pools,
            jax.ShapeDtypeStruct((rows, chunk if prefill else 1), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows, width), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    return jax.jit(step, donate_argnums=(1,)), args, pools


@pytest.mark.parametrize("chunk_rows, prefill, kernels", [
    (0, False, ["grouped_matmul", "paged_decode", "paged_decode_window"]),
    (256, False, ["grouped_matmul", "paged_decode", "paged_decode_window"]),
    (0, True, ["grouped_matmul"]),
], ids=["decode", "mixed_c256", "prefill_c256"])
def test_two_kinds_of_cache_are_updated_in_place(chunk_rows, prefill,
                                                 kernels, chip):
    """The guard of the ring's layout, as the two above are the block
    pool's and the state's: the ring ``bf16[32,34,128,2048]`` and the pool
    ``bf16[3585,128,2048]`` are written (a scatter that drops what a masked
    row would write) and read (the kernels over the flattened ring and the
    pool; a chunk's gathers) with no ``copy`` of either's shape, flattened
    or not, and temporaries far under a ring — in particular no float32
    scores of 128 heads x 256 rows over the context at once (1.9 GB); the
    decode rows of BOTH kinds of layer run a Mosaic kernel, the expert
    layer's two products are ``grouped_matmul`` launches, and a chunk of its
    own launches no paged kernel."""
    fn, args, pools = _parallel_layers_step(chunk_rows, prefill)
    compiled = fn.lower(*_on(chip, args)).compile()
    text = compiled.as_text()
    _assert_mosaic_took(text, 4 + (0 if prefill else 2), kernels, chunk_rows)
    ring, pool = pools[0]["ring"], pools[1]["kv"]
    for shape in (ring.shape, (ring.shape[0] * ring.shape[1],) + ring.shape[2:],
                  pool.shape):
        shape = "[%s]" % ",".join(str(d) for d in shape)
        copies = [c for c in re.findall(r"= (\S+) copy\(", text)
                  if shape in c]
        assert not copies, copies
    ring_bytes = ring.size * 2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < ring_bytes, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= ring_bytes + pool.size * 2


# ------------------------------------------------- the hybrid cell's layers
def _hybrid_attention():
    """A ``*`` layer's attention at the cell's shape: 32 query / 2 KV heads
    of 128, one row of T = 8,192."""
    qkv = [((1, 8192, heads, 128), jnp.bfloat16) for heads in (32, 2, 2)]

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), qkv


def _hybrid_scan():
    """An ``M`` layer's chunked scan, forward and backward: 64 heads of 64,
    8 groups of 128 state columns, chunks of 128, T = 8,192."""
    from chainermn_tpu.ops.ssd_scan import ssd_scan

    T, Hm, P, G, N = 8192, 64, 64, 8, 128
    shapes = [((1, T, Hm, P), jnp.bfloat16), ((1, T, Hm), jnp.float32),
              ((Hm,), jnp.float32), ((1, T, G, N), jnp.bfloat16),
              ((1, T, G, N), jnp.bfloat16), ((Hm,), jnp.float32)]

    def loss(x, dt, A, B, C, D):
        return jnp.sum(ssd_scan(x, dt, A, B, C, chunk=128, D=D) ** 2)

    return jax.grad(loss, argnums=tuple(range(6))), shapes


def _hybrid_experts():
    """An ``E`` layer's routed part, forward and backward: 8,192 tokens,
    top-6 of 128, the 16 experts held, 2688 -> 1856 -> 2688."""
    from chainermn_tpu.parallel.held_experts import held_experts_ffn

    N, D, F, E, k = 8192, 2688, 1856, 16, 6
    shapes = [((N, D), jnp.bfloat16), ((N, k), jnp.int32),
              ((N, k), jnp.float32), ((E, D, F), jnp.bfloat16),
              ((E, F, D), jnp.bfloat16)]

    def loss(x, experts, weights, up, down):
        return jnp.sum(held_experts_ffn(
            x, experts, weights, up, down, lo=0,
            row_bound=3 * N * k // 8)[0] ** 2)

    return jax.grad(loss, argnums=(0, 2, 3, 4)), shapes


def test_hybrid_attention_backward_walks_two_kv_chunks(chip):
    """T = 8,192 is two resident 4,096-row chunks of the dK/dV kernel (two
    launches, results ``f32[2,8192,128]``: the group of 16 summed inside)
    beside ONE dQ launch — the geometry the hybrid cell alone runs."""
    fn, shapes = _hybrid_attention()
    text = jax.jit(fn).lower(*_on(chip, shapes)).compile().as_text()
    calls = re.findall(
        r"%(\S+) = ([^\n]*?) custom-call\([^\n]*tpu_custom_call", text)
    assert len([n for n, _ in calls if "flash_bwd_dkv" in n]) == 2, calls
    assert len([n for n, _ in calls if "flash_bwd_dq" in n]) == 1, calls
    assert not re.search(r"f32\[32,8192,128\]", " ".join(
        r for n, r in calls if "flash_bwd_dkv" in n))


def test_hybrid_scan_compiles_with_its_backward(chip):
    """The training call at the cell's shape (T 8,192, 64 heads of 64, 8
    groups, state 128, chunks of 128) is the scan's own kernels, and Mosaic
    takes each launch: ONE ``ssd_fwd`` (the forward rule's) and ONE
    ``ssd_bwd``, which works the chunk states out again into 16 MB of VMEM
    under a raised limit.  No ``(heads, chunks, 128, 128)`` decay tiles and
    no chunk states are left in HBM: the temporaries are ``dy``, ``y`` and
    the relaid 2 MB running sums, a third of a GB where the ``jax.numpy``
    body took one."""
    fn, shapes = _hybrid_scan()
    compiled = jax.jit(fn).lower(*_on(chip, shapes)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\S+) = [^\n]*tpu_custom_call", text)
    assert sorted(c.split(".")[0] for c in calls) == ["ssd_bwd", "ssd_fwd"], \
        calls
    assert not re.search(r"f32\[[0-9,]*128,128,128\]", text)  # decay tiles
    assert not re.search(r"f32\[1,64,8,\d+,\d+,128\]", text)  # chunk states
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29


def _serving_chunk(fn):
    """Falcon-H1's prefill chunk as ``HybridLM._recur`` calls the scan: one
    sequence of 64 positions, 32 heads of 128, 2 groups, state 256, ONE
    chunk, from a slot's state to a slot's state."""
    shapes = [((1, 64, 32, 128), jnp.bfloat16), ((1, 64, 32), jnp.float32),
              ((32,), jnp.float32), ((1, 64, 2, 256), jnp.bfloat16),
              ((1, 64, 2, 256), jnp.bfloat16), ((32,), jnp.float32),
              ((1, 32, 128, 256), jnp.float32)]

    def chunk(x, dt, A, B, C, D, s0):
        return fn(x, dt, A, B, C, chunk=64, D=D, initial_state=s0,
                  return_state=True)

    return chunk, shapes


def test_serving_chunk_lowers_to_the_jnp_body_alone(chip):
    """The second served model's call takes no kernel on the chip either
    (``_kernels_take``: a state comes in and goes out, one chunk): what
    ``ssd_scan`` lowers to for the TPU is the ``jax.numpy`` body's own text,
    character for character — the body the parent ran, unedited."""
    mod = sys.modules["chainermn_tpu.ops.ssd_scan"]
    texts = []
    for fn in (mod.ssd_scan, mod._ssd_scan_xla):
        chunk, shapes = _serving_chunk(fn)
        texts.append(jax.jit(chunk).lower(*_on(chip, shapes)).as_text())
    assert texts[0] == texts[1]
    assert "tpu_custom_call" not in texts[0]


def test_two_mamba_layers_share_one_lowering_of_the_kernels(chip):
    """A two-``M``-layer training step (remat on, shapes that tile: 2 heads
    of 64, state 128, two chunks of 128).  Lowered, the module holds the
    kernels THREE times whatever the depth — the forward, the forward rule
    under differentiation and the backward, inside functions the layers
    call (``_ssd_scan_kernels`` is one ``jax.jit``) — and compiled, XLA
    launches 2 x (forward + its recompute) ``ssd_fwd`` and 2 ``ssd_bwd``:
    a change that adds a call site a layer shows here, without the chip."""
    from chainermn_tpu.models import HybridLM

    model = HybridLM(
        vocab=256, n_layers=2, d_model=128, layer_kinds="MM", n_heads=2,
        n_kv_heads=2, head_dim=64, ssm_heads=2, ssm_head_dim=64,
        ssm_groups=1, ssm_state=128, ssm_chunk=128, conv_kernel=4,
        remat=True, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32))["params"])

    def loss(params, ids):
        return model.apply({"params": params}, ids).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss)).lower(*_on(chip, (params, ids)))
    assert lowered.as_text().count("tpu_custom_call") == 3
    calls = re.findall(r"%(\S+) = [^\n]*tpu_custom_call",
                       lowered.compile().as_text())
    names = sorted(c.split(".")[0] for c in calls)
    assert names == ["ssd_bwd"] * 2 + ["ssd_fwd"] * 4, calls


def test_hybrid_expert_layer_is_grouped_matmuls_and_gathers(chip):
    """The routed part is six launches of the repository's own grouped
    matmul (``ops/grouped_matmul.py``: up and down forward, their two
    ``dy . W^T`` and two ``x^T . dy``), found by name, at the cell's widths
    (a group's 2688 x 1856 weights double-buffered: Mosaic takes the raised
    VMEM limit); both widths of the row buffer compile, the usual one and
    the one that holds every pair."""
    fn, shapes = _hybrid_experts()
    text = jax.jit(fn).lower(*_on(chip, shapes)).compile().as_text()
    calls = re.findall(r"%(\S+) = [^\n]*tpu_custom_call", text)
    mm = [c for c in calls if "grouped_matmul" in c and "_dw" not in c]
    dw = [c for c in calls if "grouped_matmul_dw" in c]
    # in each of the two branches of the layer's ``lax.cond`` (the usual
    # buffer, and all pairs): up and down, the same again where the backward
    # works the branch's forward out anew, their two ``dy . W^T``; and the
    # two ``x^T . dy``
    assert (len(mm), len(dw)) == (2 * 6, 2 * 2), calls

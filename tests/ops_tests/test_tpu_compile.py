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

from chainermn_tpu.ops import (  # noqa: E402
    flash_attention,
    fused_decode_attention,
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
            sys.modules["chainermn_tpu.ops.decode_attention"]]
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


def _paged(q_shape, kv_dtype):
    pool = ((H, NB, BL, DH), kv_dtype)
    args = [(q_shape, jnp.bfloat16), pool, pool,
            ((S, MB), jnp.int32), ((S,), jnp.int32)]
    if kv_dtype == jnp.int8:
        args += [((H, NB, BL), jnp.float32)] * 2
    return paged_decode_attention, args


def _fused():
    cache = ((S, H, 1024, DH), jnp.bfloat16)
    return fused_decode_attention, [
        ((S, H, DH), jnp.bfloat16), cache, cache, ((S,), jnp.int32)
    ]


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
    "fused_l1024": (_fused(), 1, ["fused_decode"]),
    "flash_fwd": (_flash(False), 1, ["flash_fwd"]),
    # forward (for the residuals) + the dq and dk/dv kernels
    "flash_bwd": (_flash(True), 3,
                  ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
}


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(name, chip):
    (fn, shapes), launches, kernels = _CASES[name]
    on_chip = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == launches, (
        name, text.count("tpu_custom_call")
    )
    import re

    called = set(re.findall(r"%([a-z_]+)(?:\.\d+)? = [^\n]*tpu_custom_call",
                            text))
    # (an enclosing transformation wraps the name when no named scope
    # encloses the call: ``transpose_jvp_flash_bwd_dq__``)
    assert len(called) == len(kernels) and all(
        any(k in c for c in called) for k in kernels), (name, called)

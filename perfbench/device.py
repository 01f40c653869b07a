"""The device the run is on: what JAX reports, the peaks table, memory."""

from __future__ import annotations

import os
from typing import Any, Dict

from perfbench.manifest import HERE, load_json


class NoAccelerator(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


def describe(chips: int, rehearse: bool = False) -> Dict[str, Any]:
    """``{"platform", "kind", "count"}`` for the ``chips`` devices a cell
    uses.  Without ``rehearse`` anything but a TPU with enough chips raises
    :class:`NoAccelerator`: there is no CPU fallback."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if not rehearse and (d0.platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"need {chips} TPU chip(s), JAX reports {len(devs)} x "
            f"{d0.platform}:{d0.device_kind}"
        )
    if len(devs) < chips:
        raise NoAccelerator(f"need {chips} devices, have {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``kind``; an unknown kind is an error,
    never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise KeyError(
            f"device kind {kind!r} is not in perfbench/peaks.json "
            f"({', '.join(table)})"
        )
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips (0 where the
    backend reports none, as the CPU does)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak

"""Least time a chip could take for given operations and bytes."""

from __future__ import annotations

from typing import Dict, Tuple


def least_seconds(need: Dict[str, float], peaks: Dict[str, float]) -> Tuple[float, str]:
    by_flops = need["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "bytes")

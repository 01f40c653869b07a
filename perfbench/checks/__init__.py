"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference, each number beside its limit."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def compare(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, List[Dict[str, Any]]]:
    """Each number beside its limit; correct when none passes its own."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and good
        rows.append({"number": name, "value": value, "limit": limit,
                     "ok": bool(good)})
    return ok, rows

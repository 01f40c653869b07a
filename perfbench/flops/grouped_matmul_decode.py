"""What the expert layer's grouped matmuls need in a served step
(``ops/grouped_matmul.py``: ``%grouped_matmul``, two launches a layer a
program run — the fused ``[gate | up]`` product ``(rows, D) x (D, 2 F)`` and
``down`` ``(rows, F) x (F, D)``).

**Bandwidth-side at these rows.**  Bytes: the weights of the tiles in use —
every held expert's, in every launch: an expert no row chose still takes one
tile of zeros (``aligned_groups``), so all ``experts_held`` matrices are
streamed whatever the routing; a launch is one of the two kinds, ``1.5 D F``
elements an expert on average — plus the rows in and out.  Operations: three
``D x F`` products a routed pair.  The pairs are the program's own count where
it hands one over (``cmn_engine_readback.moe_pairs_held`` summed over the
traced ticks of the unit ledger: what rode behind the step's tokens); a
program without it is priced at the weights alone.
"""

import numpy as np

from perfbench.reducers import unit_ledger

LEDGER = {"ledger": "serve_tick", "span": "cmn_serve_tick", "ordinal": "tick",
          "from": "trace_from_tick"}


def traced_count(facts, flat: str) -> float:
    """A ledger count summed over the traced ticks; 0 where there is none."""
    found = unit_ledger.window(facts, LEDGER)
    if found is None:
        return 0.0
    units, traced = found
    if traced is not None:
        units = [u for u in units if u.ordinal in traced]
    return float(sum(u.counts.get(flat, 0) for u in units))


def need(facts, calls):
    cfg = facts["config"]
    m = cfg["model"]
    D, F, E = m["d_model"], m["d_expert"], m["experts_held"]
    itemsize = np.dtype("float32" if cfg["dtype"]["compute"] == "float32"
                        else "float16").itemsize
    pairs = traced_count(facts, "cmn_engine_readback.moe_pairs_held")
    return {"flops": 2.0 * pairs * 3 * D * F,
            "bytes": itemsize * (calls * E * 1.5 * D * F
                                 + pairs * (2 * D + 3 * F))}

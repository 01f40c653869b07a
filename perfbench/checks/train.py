"""The first training steps against the float32 reference.

Three kinds of number, each with its own limit (in the configuration's file,
``check.train``, with the readings it was set from in PERF.md):

- ``loss_rel_gap``: each followed step's loss, |program - reference| over the
  reference's.  There to catch a part of the batch left out.
- ``grad_norm_rel_gap``: the norm of the first gradient *as the optimizer got
  it* (worked out from its second-moment state after one step), leaf by leaf:
  the gap between the program's norm and the reference's, over the
  reference's norm of that leaf or of the median leaf, whichever is larger
  (some gradients are all but zero).  The worst leaf counts.  This is the
  number a lower precision moves.
- ``loose_grad_norm_rel_gap``: the same for the leaves the configuration
  lists under ``loose_leaves``, held only against a gradient that is missing.
- ``param_change_rel_gap``: the norm of each leaf's change over the followed
  steps, same arithmetic.  There to catch a step that returns its state
  unchanged."""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float]) -> Dict[str, float]:
    """Both map leaf path -> norm; the relative gap of every leaf."""
    if set(prog) != set(ref):
        missing = sorted(set(ref) ^ set(prog))[:4]
        raise ValueError(f"leaf sets differ: {missing}")
    med = float(np.median(list(ref.values())))
    out = {}
    for k, r in ref.items():
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float]) -> Tuple[float, str]:
    """The worst relative gap and the leaf it is at."""
    gaps = leaf_gaps(prog, ref)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def numbers(prog: Dict[str, Any], ref: Dict[str, Any],
            loose: Sequence[str] = ()) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """``loose`` names the leaves whose first-gradient norm is judged apart,
    under ``loose_grad_norm_rel_gap``: leaves whose gradient the program
    accumulates in a way that makes its norm swing from seed to seed (the
    embedding table's scatter-add in the parameters' dtype), so that they do
    not hide what the other leaves say."""
    n = min(len(prog["losses"]), len(ref["losses"]))
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"][:n], ref["losses"][:n]))
    gaps = leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    tight = {k: v for k, v in gaps.items() if k not in loose}
    g_at = max(tight, key=tight.get)
    d, d_at = worst_leaf_gap(prog["param_change"], ref["param_change"])
    out = {"loss_rel_gap": float(loss_gap), "grad_norm_rel_gap": tight[g_at],
           "param_change_rel_gap": d}
    if loose:
        out["loose_grad_norm_rel_gap"] = max(gaps[k] for k in loose)
    return (out,
            {"grad_leaf": g_at, "change_leaf": d_at, "steps": n,
             "worst_leaves": sorted(((round(v, 5), k) for k, v in gaps.items()),
                                    reverse=True)[:6],
             "median_leaf_gap": float(np.median(list(gaps.values()))),
             "prog_losses": prog["losses"][:n], "ref_losses": ref["losses"][:n]})

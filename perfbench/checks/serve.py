"""Served tokens against the float32 reference.

For a sample of the window's requests (the one with the longest context, the
rest drawn from the seed) the reference runs once over prompt + served tokens
and reads, at every served position, how far the served token's logit lies
below the reference's best.  Greedy decoding serves the reference's best
token unless rounding overturns a near-tie, so these gaps measure the
arithmetic of the whole served path — prefill through the paged cache, then
every decode step.  Two numbers, each with its own limit (the configuration's
``check.serve``; the readings they were set from are in PERF.md):

- ``served_gap_widest``: the widest gap.  It swings from seed to seed by its
  nature (one near-tie more or less), so its limit is held against the fault
  it is there to catch: a token that is not the model's at all lies several
  standard deviations of the logits below the best, a rounded near-tie a
  small fraction of one.
- ``served_gap_mean``: the mean gap over all compared tokens.  Rounding
  noise of relative size e overturns ties about e wide about e of the time,
  so the mean grows with e squared: it is steady from seed to seed and is
  the number that a lower precision fails."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench import traffic_gen
from perfbench.checks import compare


def pick_sample(reqs: Sequence[traffic_gen.Req], tokens: Dict[int, List[int]],
                n: int, seed: int) -> List[traffic_gen.Req]:
    have = [r for r in reqs if tokens.get(r.id)]
    if not have:
        return []
    longest = max(have, key=lambda r: (len(r.prompt) + len(tokens[r.id]), -r.id))
    rest = [r for r in have if r.id != longest.id]
    rng = traffic_gen.rng_for(seed, 21)
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(ref, model: Dict[str, Any], params, sample, tokens,
                pad_to: int, quant: Optional[str] = None) -> Dict[str, Any]:
    """The gaps over all served positions of the sample, by the
    configuration's plain reference ``ref`` (``Manifest.reference``) handed
    the ``model`` fields.  With ``quant`` (the control) the token judged at
    each position is the one the lower-precision forward puts first, not the
    served one.  The reference is asked for one ``(1, pad_to)`` row a call —
    one compiled shape — so the check holds one row's logits however wide
    the vocabulary is."""
    import jax.numpy as jnp

    widest, total, n, flips = 0.0, 0.0, 0, 0
    for r in sample:  # one row a call: the check holds one row's logits
        text = list(r.prompt) + list(tokens[r.id])
        row = np.zeros((1, pad_to), np.int32)
        row[0, : len(text) - 1] = text[:-1]  # the last token is never fed
        toks = jnp.asarray(row)
        got = np.asarray(tokens[r.id], np.int64)
        a, b = len(r.prompt) - 1, len(r.prompt) - 1 + len(got)
        logits = np.asarray(
            ref.forward_logits(params, toks, model)[0, a:b], np.float32)
        if quant:
            got = np.argmax(np.asarray(
                ref.forward_logits(params, toks, model, quant=quant)[0, a:b],
                np.float32), axis=-1)
        best = logits.max(axis=-1)
        gap = best - logits[np.arange(len(got)), got]
        widest = max(widest, float(gap.max()))
        total += float(gap.sum())
        flips += int((gap > 0).sum())
        n += len(got)
    return {"served_gap_widest": widest,
            "served_gap_mean": total / n if n else float("nan"),
            "tokens": n, "not_best": flips, "requests": len(sample)}


def judge(numbers: Dict[str, Any], limits: Dict[str, float]):
    """The served-token numbers beside their limits; no token compared is
    not correct."""
    ok, rows = compare({k: numbers[k] for k in limits}, limits)
    rows.append({"tokens": numbers["tokens"], "requests": numbers["requests"],
                 "not_best": numbers["not_best"]})
    return ok and numbers["tokens"] > 0, rows

"""The one general traffic generator.  A traffic mix is a data file
(``traffic/<name>.json``); nothing here knows a mix by name.

Seed-invariance: every size a run's work depends on is drawn as a **fixed
multiset** — the quantiles of the stated distribution — so prompt tokens,
output tokens, the context resident when the window opens, the number of
arrivals and their gaps are the same for every seed.

**A backlog's schedule is the mix's, not the seed's**: :func:`backlog_lengths`
orders every wave by a fixed permutation, so each run admits the same
lengths in the same order, runs the same chunks in the same ticks and
decodes against the same contexts; the seed draws token ids (and, elsewhere,
the weights) and nothing else.  :func:`replay_backlog` is that schedule
worked out on the host, tick by tick, with no device.  The open-loop
generator keeps a seeded order (a shuffle inside consecutive groups, so the
shape of the load over time stays the same too).
"""

from __future__ import annotations

import math
from collections import deque
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


# ------------------------------------------------------------ multisets
def quantile_values(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` mid-point quantiles ((i + 0.5) / n) of ``dist``, ascending,
    as floats.  ``loguniform(min, max)``, ``lognormal(median, sigma)``
    clipped to ``[min, max]``, ``exponential(mean)``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        v = np.exp(lo + u * (hi - lo))
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.clip(dist["median"] * np.exp(dist["sigma"] * z),
                    dist["min"], dist["max"])
    elif kind == "exponential":
        v = -dist["mean"] * np.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return v


def quantile_ints(dist: Dict[str, Any], n: int) -> np.ndarray:
    return np.maximum(1, np.rint(quantile_values(dist, n))).astype(np.int64)


def spread(n: int, salt: int = 0) -> np.ndarray:
    """A fixed permutation of ``range(n)`` — the same for every seed — that
    scatters a sorted multiset over positions."""
    return np.random.default_rng(1_000_003 + salt).permutation(n)


def group_shuffle(n: int, group: int, rng: np.random.Generator) -> np.ndarray:
    """A seeded permutation that only moves items inside consecutive groups
    of ``group`` (``group <= 0``: one group)."""
    idx = np.arange(n)
    g = n if group <= 0 else group
    for a in range(0, n, g):
        rng.shuffle(idx[a:a + g])
    return idx


# -------------------------------------------------------------- requests
@dataclass
class Req:
    id: int
    prompt: List[int]
    max_new: int
    due: float = 0.0  # seconds after the generator starts
    burst: bool = False


def _pairs(tr: Dict[str, Any], n: int, salt: int) -> np.ndarray:
    """``(n, 2)`` prompt/output lengths: both multisets fixed, their pairing
    and order over positions fixed (not seeded)."""
    p = quantile_ints(tr["prompt"], n)[spread(n, salt)]
    o = quantile_ints(tr["output"], n)[spread(n, salt + 1)]
    return np.stack([p, o], axis=1)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, size=int(n)).tolist()


def backlog_lengths(tr: Dict[str, Any]) -> List[Tuple[int, int]]:
    """The backlog's ``slots * (1 + queue_sets)`` (prompt, output) lengths
    in the order they are queued.  Each wave of ``slots`` requests holds the
    same multiset of pairs; wave ``w`` is ordered by the fixed permutation
    ``spread(slots, order_salt + w)``, so consecutive waves differ and no
    seed has a say."""
    S = tr["slots"]
    wave = _pairs(tr, S, 0)
    salt = tr.get("order_salt", 100)
    return [(int(p), int(o)) for w in range(1 + tr["queue_sets"])
            for p, o in wave[spread(S, salt + w)]]


def decode_backlog(tr: Dict[str, Any], vocab: int, seed: int) -> List[Req]:
    """The requests of :func:`backlog_lengths`, all due at once; the seed
    draws their token ids."""
    rng = rng_for(seed, 11)
    return [Req(i, _tokens(rng, p, vocab), o)
            for i, (p, o) in enumerate(backlog_lengths(tr))]


class ReplayTick(NamedTuple):
    """What one scheduler tick of a backlog does, by the replay."""

    live: int      # rows of the decode step
    calls: int     # prefill chunks started, riding ones included
    rode: int      # of them, staged to ride the decode step (0 or 1)
    tokens: int    # tokens generated: the live rows' and first tokens
    admitted: int
    finished: int
    context: int   # KV positions the decode step reads, over its rows


def replay_backlog(lengths: Sequence[Tuple[int, int]], slots: int, chunk: int,
                   max_ticks: Optional[int] = None
                   ) -> Tuple[int, List[ReplayTick]]:
    """``(fill, ticks)``: the schedule of a backlog of ``lengths`` on a plain
    engine of ``slots`` slots and prefill chunks of ``chunk``, replayed on
    the host.  A tick admits the queue's head into every free slot (lowest
    index first), gives every prefilling slot one chunk, oldest admission
    first — the first of them rides the decode step where a decode row is
    live, the others are calls of their own — and decodes one token for
    every slot whose prefill was done before the step; a final chunk yields
    the request's first token, and a slot that rode joins the rows a tick
    later.  ``fill`` is the number of leading ticks the runner spends in
    set-up: until ``slots`` prefills are done.  Stops when nothing is
    pending or after ``max_ticks``.

    It knows no token: a prefix hit between two prompts (the seed's) starts
    a prefill a token or more in, and may move that slot's chunks by a tick.
    """
    queue = deque(lengths)
    table: List[Optional[Dict[str, Any]]] = [None] * slots
    seq, prefills_done, fill = 0, 0, None
    ticks: List[ReplayTick] = []

    def take_chunk(i: int) -> Tuple[int, int]:
        """Slot ``i``'s next chunk is in: ``(tokens, finished)`` — a final
        chunk yields the first token, which may be all the request asks."""
        nonlocal prefills_done
        s = table[i]
        s["pos"] = min(s["pos"] + chunk, s["text"])
        if s["pos"] < s["text"]:
            return 0, 0
        s["prefilling"], s["made"] = False, 1
        prefills_done += 1
        if s["made"] < s["new"]:
            return 1, 0
        table[i] = None
        return 1, 1

    while (queue or any(table)) and (max_ticks is None
                                     or len(ticks) < max_ticks):
        if fill is None and prefills_done >= slots:
            fill = len(ticks)
        admitted = 0
        for i in range(slots):
            if table[i] is None and queue:
                p, o = queue.popleft()
                table[i] = {"text": p, "new": o, "pos": 0, "made": 0,
                            "prefilling": True, "seq": seq}
                seq += 1
                admitted += 1
        ride = any(s is not None and not s["prefilling"] for s in table)
        prefilling = sorted((i for i, s in enumerate(table)
                             if s is not None and s["prefilling"]),
                            key=lambda i: table[i]["seq"])
        staged = prefilling[0] if ride and prefilling else None
        done = [take_chunk(i) for i in prefilling if i != staged]
        live = [i for i, s in enumerate(table)
                if s is not None and not s["prefilling"]]
        context = sum(table[i]["pos"] + 1 for i in live)
        for i in live:
            s = table[i]
            s["pos"] += 1
            s["made"] += 1
            over = s["made"] >= s["new"]
            done.append((1, int(over)))
            if over:
                table[i] = None
        if staged is not None and live:  # after the step it rode
            done.append(take_chunk(staged))
        ticks.append(ReplayTick(
            len(live), len(prefilling), int(staged is not None),
            sum(t for t, _ in done), admitted, sum(f for _, f in done),
            context))
    return (len(ticks) if fill is None else fill), ticks


def open_loop(tr: Dict[str, Any], vocab: int, seed: int,
              seconds: float) -> List[Req]:
    """Arrivals over ``lead_in_s + seconds``: a base stream whose gaps are
    the exponential quantiles (scaled to fill the horizon exactly), plus a
    burst of ``burst.size`` simultaneous requests every ``burst.every_s``.
    ``rate_per_s`` counts both.  The seed shuffles gaps and lengths inside
    groups of ``shuffle_group`` consecutive requests."""
    rng = rng_for(seed, 12)
    horizon = tr["lead_in_s"] + float(seconds)
    burst = tr.get("burst") or {"every_s": 0, "size": 0}
    burst_at = []
    if burst["size"]:
        t = tr["lead_in_s"] + burst["every_s"] / 2.0
        while t < horizon:
            burst_at.append(t)
            t += burst["every_s"]
    n_total = int(round(tr["rate_per_s"] * horizon))
    n_base = n_total - len(burst_at) * burst["size"]
    if n_base < 1:
        raise ValueError("rate too low for its bursts")
    gaps = quantile_values({"dist": "exponential", "mean": 1.0}, n_base)
    gaps = gaps * (horizon / gaps.sum())
    G = tr.get("shuffle_group", 8)
    gaps = gaps[spread(n_base, 2)][group_shuffle(n_base, G, rng)]
    due = np.concatenate(
        [np.cumsum(gaps)] + [np.full(burst["size"], t) for t in burst_at])
    is_burst = np.arange(n_total) >= n_base
    order = np.argsort(due, kind="stable")
    pairs = _pairs(tr, n_total, 4)[group_shuffle(n_total, G, rng)]
    return [Req(i, _tokens(rng, p, vocab), int(o), float(due[j]),
                bool(is_burst[j]))
            for i, ((p, o), j) in enumerate(zip(pairs, order))]


def totals(reqs: Sequence[Req]) -> Dict[str, Any]:
    """What must not depend on the seed."""
    gaps = np.diff([0.0] + sorted(r.due for r in reqs if not r.burst))
    return {
        "requests": len(reqs),
        "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
        "output_tokens": int(sum(r.max_new for r in reqs)),
        "prompt_multiset": sorted(len(r.prompt) for r in reqs),
        "output_multiset": sorted(r.max_new for r in reqs),
        "gap_multiset": sorted(round(float(g), 6) for g in gaps),
        "burst_times": sorted(round(r.due, 6) for r in reqs if r.burst),
    }


# -------------------------------------------------------------- training
def markov_rows(n_rows: int, seq: int, vocab: int, seed: int) -> np.ndarray:
    """``(n_rows, seq + 1)`` int32 rows of a sparse first-order Markov
    chain over the whole vocabulary: every token has 4 possible successors
    drawn from a Zipf-like marginal, taken with probabilities
    .55/.25/.12/.08 — learnable structure, vectorised over rows, all rows
    different.  (``chip_smoke.py make_tokens``'s scheme; ``examples/lm``'s
    order-2 ``make_corpus`` needs a vocab**3 table and stops at vocab 64.)"""
    rng = rng_for(seed, 13)
    marginal = 1.0 / np.arange(1, vocab + 1) ** 1.1
    marginal /= marginal.sum()
    succ = rng.choice(vocab, size=(vocab, 4), p=marginal).astype(np.int32)
    branch = rng.choice(4, size=(n_rows, seq + 1), p=[.55, .25, .12, .08])
    out = np.empty((n_rows, seq + 1), np.int32)
    out[:, 0] = rng.choice(vocab, size=n_rows, p=marginal)
    for t in range(1, seq + 1):
        out[:, t] = succ[out[:, t - 1], branch[:, t]]
    return out

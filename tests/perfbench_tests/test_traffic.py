"""Traffic is a function of the seed, and its totals are not."""

import numpy as np
import pytest

from perfbench import traffic_gen as tg
from perfbench.manifest import Manifest

pytestmark = pytest.mark.tier1

SEEDS = (0, 1, 7, 2**31 + 11)


def _make(kind, tr, seed):
    if kind == "decode_backlog":
        return tg.decode_backlog(tr, 50257, seed)
    return tg.open_loop(tr, 50257, seed, 45.0)


@pytest.mark.parametrize("name", ["decode_backlog", "open_loop"])
def test_same_seed_same_requests(name):
    tr = Manifest().traffic(name)
    a, b = _make(name, tr, 5), _make(name, tr, 5)
    assert [(r.prompt, r.max_new, r.due) for r in a] == \
        [(r.prompt, r.max_new, r.due) for r in b]


@pytest.mark.parametrize("name", ["decode_backlog", "open_loop"])
def test_totals_do_not_depend_on_the_seed(name):
    tr = Manifest().traffic(name)
    runs = [_make(name, tr, s) for s in SEEDS]
    base = tg.totals(runs[0])
    for r in runs[1:]:
        assert tg.totals(r) == base
    lengths = [[(len(r.prompt), r.max_new) for r in run] for run in runs]
    if name == "decode_backlog":
        # the schedule is the mix's: the lengths come in the same order for
        # every seed, and only the token ids differ
        assert all(x == tg.backlog_lengths(tr) for x in lengths)
        assert all(a.prompt != b.prompt for a, b in zip(runs[0], runs[1]))
    else:
        # ... while the open loop's order and its token ids are the seed's
        assert lengths[0] != lengths[1]
        assert runs[0][0].prompt[:8] != runs[1][0].prompt[:8] or \
            len(runs[0][0].prompt) != len(runs[1][0].prompt)


def test_backlog_waves_hold_one_multiset_in_orders_that_differ():
    tr = Manifest().traffic("decode_backlog")
    S, lengths = tr["slots"], tg.backlog_lengths(tr)
    waves = [lengths[a:a + S] for a in range(0, len(lengths), S)]
    assert len(waves) == 1 + tr["queue_sets"]
    assert all(sorted(w) == sorted(waves[0]) for w in waves)
    assert all(a != b for a, b in zip(waves, waves[1:]))
    assert len({tuple(w) for w in waves}) == len(waves)
    # another salt is another schedule of the same multiset
    other = tg.backlog_lengths(dict(tr, order_salt=tr["order_salt"] + 1))
    assert other != lengths and sorted(other) == sorted(lengths)


def _replay(tr, lengths=None, **kw):
    cfg = Manifest().config("gpt2-xl")
    return tg.replay_backlog(lengths or tg.backlog_lengths(tr), tr["slots"],
                             cfg["serve"]["prefill_chunk"], **kw)


@pytest.mark.parametrize("seed", SEEDS)
def test_replay_of_any_seeds_requests_is_the_mixes_schedule(seed):
    """The replay takes lengths alone, and every seed's requests give it the
    same ticks: prefill calls, riding chunks, live rows, tokens."""
    tr = Manifest().traffic("decode_backlog")
    reqs = tg.decode_backlog(tr, 50257, seed)
    got = _replay(tr, [(len(r.prompt), r.max_new) for r in reqs])
    assert got == _replay(tr)
    fill, ticks = got
    # every request is served whole, and nothing else
    assert sum(t.admitted for t in ticks) == len(reqs)
    assert sum(t.finished for t in ticks) == len(reqs)
    assert sum(t.tokens for t in ticks) == sum(r.max_new for r in reqs)
    assert sum(t.calls for t in ticks) == sum(
        -(-len(r.prompt) // 32) for r in reqs)
    # the pool fill: every slot prefilled once, by calls of their own until
    # the shortest prompt decodes
    assert ticks[0] == tg.ReplayTick(0, 32, 0, 0, 32, 0, 0)
    assert 0 < fill < 32
    # one chunk rides at most, and only a step that has rows of its own
    assert all(t.rode <= min(1, t.calls, t.live) for t in ticks)
    assert all(t.rode == 1 for t in ticks[fill:]
               if t.calls and t.live == 32 - t.calls)


def test_replay_of_a_hand_sized_backlog():
    """Two slots, chunks of 4, three requests (prompt, output): (6, 2),
    (3, 3), (5, 1) — worked out by hand from the scheduler's rules."""
    fill, ticks = tg.replay_backlog([(6, 2), (3, 3), (5, 1)], 2, 4)
    T = tg.ReplayTick
    assert ticks == [
        # both admitted; no row is live, so two calls of their own; slot 1's
        # 3 tokens are its final chunk (first token) and it decodes at once
        T(live=1, calls=2, rode=0, tokens=2, admitted=2, finished=0,
          context=4),
        # slot 0's tail rides slot 1's step: first token after the step;
        # slot 1 makes its third token and retires
        T(live=1, calls=1, rode=1, tokens=2, admitted=0, finished=1,
          context=5),
        # (5, 1) takes slot 1 and its first chunk rides slot 0's step, which
        # makes slot 0's second token: retired
        T(live=1, calls=1, rode=1, tokens=1, admitted=1, finished=1,
          context=7),
        # no live row: the tail is a call of its own, and the one token the
        # request asks for is its first: over with no decode step
        T(live=0, calls=1, rode=0, tokens=1, admitted=0, finished=1,
          context=0),
    ]
    assert fill == 2  # two prefills were done when the third tick began


def test_the_queue_cannot_run_out_at_twice_the_ledgers_rate():
    """``queue_sets`` against ``run_seconds``: at twice the rate of the
    ledger's newest accepted line (PR 42: 1,312.6 tokens/s) the window still
    closes with ``queue_left_min`` requests waiting, and at the ledger's own
    rate with more than a third of the queue."""
    man = Manifest()
    tr = man.traffic("decode_backlog")
    fill, ticks = _replay(tr)
    queued = tr["slots"] * tr["queue_sets"]

    def left_after(tokens):
        done, admitted = 0.0, sum(t.admitted for t in ticks[:fill])
        for t in ticks[fill:]:
            if done >= tokens:
                break
            done += t.tokens
            admitted += t.admitted
        assert done >= tokens, "the queue ran out"
        return queued + tr["slots"] - admitted

    budget = 1312.6 * man.doc["run_seconds"]
    assert left_after(2 * budget) >= tr["queue_left_min"] >= tr["slots"]
    assert left_after(budget) > queued / 3


def test_the_traced_ticks_stand_for_the_window():
    """``trace_from_tick`` / ``trace_ticks`` under the fixed schedule: the
    stretch holds riding chunks, calls of their own and plain ticks in about
    the whole window's proportions (the window: as many ticks as 45 s hold
    at the ledger's rate, PR 42)."""
    tr = Manifest().traffic("decode_backlog")
    fill, ticks = _replay(tr, max_ticks=2000)
    window = ticks[fill:fill + 1900]
    a = tr["trace_from_tick"]
    traced = window[a:a + tr["trace_ticks"]]
    assert len(traced) == tr["trace_ticks"] >= 32

    def shares(ts):
        calls, rode = sum(t.calls for t in ts), sum(t.rode for t in ts)
        return (calls / len(ts), rode / calls,
                sum(t.calls == 0 for t in ts) / len(ts),
                sum(t.context for t in ts) / len(ts))

    got, want = shares(traced), shares(window)
    assert sum(t.calls - t.rode for t in traced) >= 2
    assert sum(t.rode for t in traced) >= 10
    assert sum(t.calls == 0 for t in traced) >= 5
    assert got[0] == pytest.approx(want[0], rel=0.05)
    assert abs(got[1] - want[1]) < 0.02
    assert abs(got[2] - want[2]) < 0.05
    assert got[3] == pytest.approx(want[3], rel=0.10)
    # the first two traced ticks are the recorded fixture's: a riding chunk
    # beside a call of its own, then a riding chunk alone
    assert [(t.calls, t.rode) for t in traced[:2]] == [(2, 1), (1, 1)]


def test_backlog_context_at_window_start_is_seed_invariant():
    tr = Manifest().traffic("decode_backlog")
    S = tr["slots"]
    first = [sorted((len(r.prompt), r.max_new)
                    for r in tg.decode_backlog(tr, 50257, s)[:S])
             for s in SEEDS]
    assert all(f == first[0] for f in first)
    reqs = tg.decode_backlog(tr, 50257, 3)
    assert len(reqs) == S * (1 + tr["queue_sets"]) == 512
    assert all(r.due == 0.0 for r in reqs)
    assert min(len(r.prompt) for r in reqs) >= tr["prompt"]["min"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= 1024


def test_open_loop_rate_bursts_and_local_shuffle():
    tr = Manifest().traffic("open_loop")
    a = tg.open_loop(tr, 50257, 1, 45.0)
    horizon = tr["lead_in_s"] + 45.0
    assert len(a) == round(tr["rate_per_s"] * horizon)
    assert all(0.0 < r.due <= horizon + 1e-9 for r in a)
    assert [r.due for r in a] == sorted(r.due for r in a)
    bursts = sorted({r.due for r in a if r.burst})
    assert all(sum(1 for r in a if r.burst and r.due == t)
               == tr["burst"]["size"] for t in bursts)
    assert np.allclose(np.diff(bursts), tr["burst"]["every_s"])
    # the seed moves an arrival only inside its group: the k-th arrival of
    # two seeds is never further apart than one group's gaps
    b = tg.open_loop(tr, 50257, 2, 45.0)
    base_a = [r.due for r in a if not r.burst]
    base_b = [r.due for r in b if not r.burst]
    G = tr["shuffle_group"]
    assert base_a[G - 1::G] == pytest.approx(base_b[G - 1::G])


@pytest.mark.parametrize("dist,n", [
    ({"dist": "loguniform", "min": 128, "max": 512}, 32),
    ({"dist": "lognormal", "median": 64, "sigma": 0.8, "min": 16, "max": 384}, 60),
])
def test_quantile_multisets_follow_their_distribution(dist, n):
    v = tg.quantile_ints(dist, n)
    assert list(v) == sorted(v)
    assert dist["min"] <= v[0] and v[-1] <= dist["max"]
    want = (dist["min"] * dist["max"]) ** 0.5 if dist["dist"] == "loguniform" \
        else dist["median"]
    assert abs(np.median(v) - want) / want < 0.08


def test_training_rows_are_seeded_distinct_and_fixed_in_size():
    a = tg.markov_rows(8, 64, 4096, 3)
    assert a.shape == (8, 65) and a.dtype == np.int32
    assert (a == tg.markov_rows(8, 64, 4096, 3)).all()
    assert (a != tg.markov_rows(8, 64, 4096, 2**31 + 3)).any()
    assert len({row.tobytes() for row in a}) == 8
    assert a.min() >= 0 and a.max() < 4096

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
    # ... while the order and the token ids do
    assert [len(r.prompt) for r in runs[0]] != [len(r.prompt) for r in runs[1]]
    assert runs[0][0].prompt[:8] != runs[1][0].prompt[:8] or \
        len(runs[0][0].prompt) != len(runs[1][0].prompt)


def test_backlog_context_at_window_start_is_seed_invariant():
    tr = Manifest().traffic("decode_backlog")
    S = tr["slots"]
    first = [sorted((len(r.prompt), r.max_new)
                    for r in tg.decode_backlog(tr, 50257, s)[:S])
             for s in SEEDS]
    assert all(f == first[0] for f in first)
    reqs = tg.decode_backlog(tr, 50257, 3)
    assert len(reqs) == S * (1 + tr["queue_sets"])
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

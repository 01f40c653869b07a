"""The tick-aligned rate and the tails."""

import pytest

from perfbench import estimators as est

pytestmark = pytest.mark.tier1


def test_whole_tick_rate_does_not_depend_on_where_the_clock_cuts():
    """Ticks of 0.279 s and 32 tokens: whatever --seconds is, the rate over
    whole ticks is 32 / 0.279; a count cut by the clock is off by up to a
    tick."""
    tick, work = 0.279, 32.0
    rates, cut_rates = [], []
    for seconds in (44.7, 45.0, 45.2, 50.9):
        log, t = [], 0.0
        while t < seconds:  # the window closes at the first boundary after
            log.append((t, t + tick, work))
            t += tick
        rates.append(est.whole_unit_rate(log)["rate"])
        cut_rates.append(work * int(seconds / tick) / seconds)
    assert rates == pytest.approx([work / tick] * 4, rel=1e-12)
    assert max(cut_rates) - min(cut_rates) > 0.001 * work / tick


def test_rate_counts_idle_gaps_and_all_work():
    log = [(0.0, 1.0, 10.0), (1.5, 2.5, 30.0)]
    r = est.whole_unit_rate(log)
    assert r["rate"] == pytest.approx(40.0 / 2.5)
    assert r["units"] == 2 and r["work"] == 40.0
    with pytest.raises(ValueError):
        est.whole_unit_rate([])


def test_percentile_is_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert est.percentile(v, 90) == 90
    assert est.percentile(v, 95) == 95
    assert est.percentile([5.0], 90) == 5.0
    assert est.percentile([3, 1, 2], 50) == 2
    assert est.percentile(list(range(1, 11)), 90) == 9
    with pytest.raises(ValueError):
        est.percentile([], 50)


def test_spread_is_the_contracts():
    import statistics

    v = [62.3, 62.6, 62.7, 62.5, 62.9, 62.4]
    q = statistics.quantiles(v, n=4)
    assert est.spread(v) == pytest.approx((q[2] - q[0]) / statistics.median(v))

"""Percentiles over every sample, and the spread the bounds use."""
import statistics

import numpy as np

from stats import percentile, spread


def test_percentile_matches_numpy_over_all_samples():
    rng = np.random.default_rng(0)
    xs = list(rng.lognormal(0, 1, 1001))
    for p in (0, 5, 50, 95, 99, 100):
        assert abs(percentile(xs, p) - float(np.percentile(xs, p))) < 1e-9
    # a tail sample moves the p95 of a small set: nothing is binned away
    small = [1.0] * 19 + [100.0]
    assert abs(percentile(small, 95) - (1.0 + 0.05 * 99.0)) < 1e-9


def test_percentile_edges():
    assert np.isnan(percentile([], 50))
    assert percentile([3.0], 95) == 3.0


def test_spread_uses_statistics_quartiles():
    vals = [10.0, 11.0, 12.0, 13.0, 20.0, 9.0]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert spread(vals) == (q3 - q1) / statistics.median(vals)

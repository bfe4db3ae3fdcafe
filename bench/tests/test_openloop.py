import numpy as np
import pytest

from harness import openloop


def test_latency_runs_from_due_time_through_a_stall():
    # a request due every 10 ms; the server stalls from 200 to 300 ms and
    # answers everything due meanwhile at 300 ms (+1 ms service)
    due = np.arange(0, 1.0, 0.01)
    done = np.where((due >= 0.2) & (due < 0.3), 0.301, due + 0.001)
    lat = openloop.latencies(due, done)
    assert lat.max() == pytest.approx(0.101)
    # 10 of 100 requests waited in the stall: the p95 sees it, the median
    # does not
    assert openloop.percentile(lat, 95) > 0.05
    assert openloop.percentile(lat, 50) == pytest.approx(0.001)


def test_a_failed_request_counts_as_infinitely_late():
    lat = openloop.latencies([0.0, 0.1], [0.001, np.nan])
    assert openloop.percentile(lat, 50) == pytest.approx(0.001)
    assert openloop.percentile(lat, 95) == np.inf


def test_schedule_sends_the_same_gaps_in_a_seeds_order():
    a = openloop.exponential_schedule(np.random.default_rng(3), 2000.0, 5.0,
                                      1.0)
    b = openloop.exponential_schedule(np.random.default_rng(4), 2000.0, 5.0,
                                      1.0)
    assert len(a) == len(b) == 10_000
    assert 0 < a.min() and a.max() < 5.0 and np.all(np.diff(a) > 0)
    ga, gb = np.diff(a), np.diff(b)
    assert not np.array_equal(ga, gb)
    q = [0.01, 0.1, 0.5, 0.9, 0.99]
    np.testing.assert_allclose(np.quantile(ga, q), np.quantile(gb, q),
                               rtol=1e-3)
    # exponential gaps: the standard deviation equals the mean
    assert abs(ga.std() / ga.mean() - 1) < 0.02
    # the same load in every block: ~2000 requests in each second
    per_s = np.histogram(a, bins=5, range=(0, 5))[0]
    assert per_s.min() > 1900 and per_s.max() < 2100


def test_nearest_rank_percentile():
    assert openloop.percentile(np.arange(1, 101), 95) == 95
    assert openloop.percentile(np.array([3.0]), 50) == 3.0

"""The open-loop schedule: due times from the seed, lag and latency."""
import numpy as np
import pytest

from bench.drivers import serve


def test_due_times_fixed_count_sorted_and_seeded():
    a = serve.due_times(2 ** 33 + 1, 250.0, 4.0)
    assert len(a) == 1000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 4.0
    assert np.array_equal(a, serve.due_times(2 ** 33 + 1, 250.0, 4.0))
    assert not np.array_equal(a, serve.due_times(2 ** 33 + 2, 250.0, 4.0))


def test_lag_and_latency_run_from_the_due_time():
    t0 = 100.0
    due = np.array([0.0, 0.5, 1.0])
    t_sub = np.array([100.001, 100.6, 101.0])
    service = np.array([0.010, 0.020, 0.0])
    ok = np.array([True, True, False])
    lat, lag, done = serve.timings(t0, due, t_sub, service, ok, t_end=103.0)
    assert lag == pytest.approx([0.001, 0.1, 0.0])
    assert lat == pytest.approx([0.011, 0.12, 2.0])
    assert done == pytest.approx([100.011, 100.62, 103.0])

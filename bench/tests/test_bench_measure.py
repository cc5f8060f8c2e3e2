"""Percentile and rate arithmetic on handmade request records."""
import math

import pytest

import _paths  # noqa: F401
import measure


def rec(due, done, ok=True):
    return {"due": due, "done": done, "ok": ok}


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert measure.percentile(xs, 50) == 50
    assert measure.percentile(xs, 95) == 95
    assert measure.percentile([3.0], 95) == 3.0
    assert measure.percentile([5, 1, 4, 2, 3], 50) == 3
    with pytest.raises(ValueError):
        measure.percentile([], 50)


def test_latencies_count_unanswered_as_missing():
    recs = [rec(0.0, 0.2), rec(1.0, 1.5), rec(2.0, None),
            rec(3.0, 3.1, ok=False)]
    lat = measure.latencies(recs)
    assert lat[:2] == pytest.approx([0.2, 0.5])
    assert lat[2] == math.inf and lat[3] == math.inf


def test_p95_sees_missing_requests():
    recs = [rec(i, i + 0.1) for i in range(94)] + \
           [rec(100 + i, None) for i in range(6)]
    lat = measure.latencies(recs)
    assert measure.percentile(lat, 50) == pytest.approx(0.1)
    assert measure.percentile(lat, 95) == math.inf
    recs = [rec(i, i + 0.1) for i in range(96)] + \
           [rec(100 + i, None) for i in range(4)]
    assert measure.percentile(measure.latencies(recs), 95) \
        == pytest.approx(0.1)


def test_served_rate_counts_answers_inside_the_window():
    recs = [rec(0.0, 1.0), rec(0.5, 9.9), rec(1.0, 10.5),
            rec(2.0, 3.0, ok=False), rec(3.0, None)]
    assert measure.served_rate(recs, 0.0, 10.0) == pytest.approx(0.2)


def test_served_rate_counts_work_in_flight_at_the_close():
    recs = [rec(0.0, 4.0), rec(0.0, 4.0), rec(4.0, 8.0), rec(4.0, 8.0),
            rec(8.0, 12.0), rec(8.0, 12.5), rec(9.0, None)]
    recs[4]["progress"] = 0.5
    recs[5]["progress"] = 0.5
    recs[6]["progress"] = 0.0
    assert measure.served_rate(recs, 0.0, 10.0) == pytest.approx(0.5)

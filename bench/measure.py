"""Arithmetic from a run's request records to its end-to-end metrics.

A request record is ``{"due": s, "done": s or None, "ok": bool}`` on the
run's clock.  A request that is due in the window and is not answered
(``done`` is None) or is answered with an error counts as missing every
latency limit: it enters the percentiles as ``inf``.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def latencies(records) -> list:
    """Seconds from due to answered, ``inf`` for a request unanswered
    or answered with an error."""
    return [(r["done"] - r["due"]) if (r["ok"] and r["done"] is not None)
            else math.inf for r in records]


def served_rate(records, t_open: float, t_close: float) -> float:
    """Requests served per second of the window: each request answered
    correctly within [t_open, t_close] counts 1, and a request still
    being served at the close counts the share of its work (engine
    waves, one per shard) done by then (``progress``), so that a window
    that closes between two cohorts of a closed loop loses nothing."""
    n = 0.0
    for r in records:
        if r["ok"] and r["done"] is not None \
                and t_open <= r["done"] <= t_close:
            n += 1.0
        elif r.get("done") is None or r["done"] > t_close:
            n += r.get("progress", 0.0)
    return n / (t_close - t_open)


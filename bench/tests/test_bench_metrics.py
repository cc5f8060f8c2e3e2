"""The readers of the program's span and filter counters, on handmade
results: per request answered, and nothing where the program records
no such counter."""
import pytest

import _paths  # noqa: F401
import run


def _rec(counts, answered=4, failed=1):
    recs = [{"ok": True}] * answered + [{"ok": False}] * failed
    return {"records": recs, "counts": counts}


COUNTS = {
    "h2d:solver_dispatch": 6, "d2h:wave_materialize": 10,
    "span_n:koios.stream": 2, "span_ns:koios.stream": 8_000_000,
    "self_ns:koios.stream": 7_000_000,
    "span_ns:koios.wave.launch": 30_000_000,
    "self_ns:koios.wave.launch": 20_000_000,
    "self_ns:koios.resume": 1_000_000, "self_ns:koios.verify": 2_000_000,
    "self_ns:koios.verify.weights": 3_000_000,
    "self_ns:koios.verify.pack": 4_000_000,
    "self_ns:koios.verify.solve": 6_000_000,
    "self_ns:koios.device_wait": 90_000_000,
    "self_ns:koios.finish": 5_000_000,
    "filter:candidates": 400, "filter:em_full": 30, "filter:no_em": 9,
}


@pytest.mark.parametrize("name,want", [
    ("stream_host_ms.closed", 8.0 / 4),
    ("dispatch_host_ms.closed", 20.0 / 4),
    ("continuation_host_ms.closed", (1 + 2 + 3 + 4 + 6) / 4),
    ("matched_share.closed", 100.0 * 30 / 400),
])
def test_reader(name, want):
    assert run.metric_reader(name)(_rec(COUNTS)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "stream_host_ms.closed", "dispatch_host_ms.closed",
    "continuation_host_ms.closed", "matched_share.closed"])
def test_reader_is_silent_without_the_counters(name):
    """A program without spans or filter counters (only transfers
    counted), or a window that answered nothing: no value, no raise."""
    read = run.metric_reader(name)
    assert read(_rec({"h2d:solver_dispatch": 6})) is None
    assert read(_rec({})) is None
    if name != "matched_share.closed":
        assert read(_rec(COUNTS, answered=0)) is None

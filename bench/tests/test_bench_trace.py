"""The trace reducer: busy union, device time by program, idle gaps by
host annotation; on handmade planes and on a small trace recorded on a
TPU v5e chip."""
import pytest

import _paths
import trace_reduce as tr

MS = 1_000_000


def test_reduce_handmade_planes():
    planes = {
        "device": {
            "/device:TPU:0": [("jit_fn(12)", 10 * MS, 20 * MS),
                              ("jit_fn(13)", 15 * MS, 30 * MS),
                              ("jit_cosine_rows(4)", 60 * MS, 70 * MS),
                              ("jit_fn(12)", 95 * MS, 120 * MS)]},
        "host": [("bench.window", 0, 100 * MS),
                 ("engine.step", 5 * MS, 45 * MS),
                 ("idle.wait_arrival", 45 * MS, 58 * MS),
                 ("engine.step", 58 * MS, 100 * MS)]}
    out = tr.reduce(planes)
    assert out["window_s"] == pytest.approx(0.1)
    # busy: [10, 30] + [60, 70] + [95, 100] inside the window
    assert out["busy_s"] == pytest.approx(0.035)
    assert out["by_program"] == pytest.approx(
        {"jit_fn": 0.010 + 0.015 + 0.005, "jit_cosine_rows": 0.010})
    gaps = dict(out["idle_gaps"])
    # [0,10]: step 5 ms vs none 5 ms -> step; [30,60]: step 15,
    # idle 13, step 2 -> engine.step 17; [70,95] step
    assert gaps["engine.step"] == pytest.approx(0.010 + 0.030 + 0.025)
    assert out["device_ops"][0][0] == "jit_fn"


def test_reduce_needs_the_window():
    with pytest.raises(ValueError):
        tr.reduce({"device": {}, "host": []})


def test_recorded_tpu_trace():
    path = _paths.DATA / "tiny.xplane.pb"
    planes = tr.load(str(path))
    assert any(p.startswith("/device:TPU") for p in planes["device"])
    out = tr.reduce(planes)
    assert 0.0 < out["busy_s"] < out["window_s"]
    names = {n for n, _ in out["device_ops"]}
    assert names >= {"jit__lambda"} or any("lambda" in n for n in names)
    gap_names = {n for n, _ in out["idle_gaps"]}
    assert gap_names <= {"engine.step", "idle.wait_arrival",
                         "engine.submit", "other"}
    assert "idle.wait_arrival" in gap_names

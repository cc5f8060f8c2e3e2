"""The readers of the exact solver's counters, ``solver_fill.dblp`` and
``solver_depth.dblp``, on handmade results: the fill share and the
lockstep scan iterations per request answered, and nothing where the
program records no such counter."""
import pytest

import _paths  # noqa: F401
import run


def _rec(counts, answered=4, failed=1):
    recs = [{"ok": True}] * answered + [{"ok": False}] * failed
    return {"records": recs, "counts": counts}


COUNTS = {"verify:solver_rows": 300,
          "verify:solver_cells_logical": 2_000_000,
          "verify:solver_cells_padded": 8_000_000,
          "verify:solver_steps": 90_000, "h2d:solver_dispatch": 6}


@pytest.mark.parametrize("name,want", [
    ("solver_fill.dblp", 100.0 * 2_000_000 / 8_000_000),
    ("solver_depth.dblp", 90_000 / 4),
])
def test_reader(name, want):
    assert run.metric_reader(name)(_rec(COUNTS)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["solver_fill.dblp", "solver_depth.dblp"])
def test_reader_is_silent_without_the_counters(name):
    """A program that counts no solver cells or steps (only solver rows
    and transfers), or a window that answered nothing: no value, no
    raise."""
    read = run.metric_reader(name)
    assert read(_rec({"verify:solver_rows": 300,
                      "h2d:solver_dispatch": 6})) is None
    assert read(_rec({})) is None
    if name == "solver_depth.dblp":
        assert read(_rec(COUNTS, answered=0)) is None
    else:
        assert read(_rec({"verify:solver_cells_logical": 0,
                          "verify:solver_cells_padded": 0})) is None


def test_the_dblp_cell_reports_both_solver_metrics():
    cell = run.load_cell("dblp.closed")
    names = [m["name"] for m in cell["per_layer"]]
    assert names == ["wave_size.closed", "wave_device_ms.closed",
                     "window_compiles.closed", "device_idle_share.closed",
                     "solver_fill.dblp", "solver_depth.dblp"]
    assert [m["name"] for m in cell["end_to_end"]] == ["qps", "setup_s"]
    assert cell["config"]["search"]["verifier"] == "hungarian"
    assert cell["mix"]["clients"] == 16

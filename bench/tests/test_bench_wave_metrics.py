"""The reader of the fused wave's solver-cell counter,
``wave_solver_cells.closed``, on handmade results: millions of cells per
request answered, and nothing where the program records no such counter
or the window answered nothing."""
import pytest

import _paths  # noqa: F401
import run

NAME = "wave_solver_cells.closed"


def _rec(counts, answered=4, failed=1):
    recs = [{"ok": True}] * answered + [{"ok": False}] * failed
    return {"records": recs, "counts": counts}


# one DBLP cohort of 16 with a 514-token query: 10 shard waves of two
# rounds over 16 x 16 blocks of 640 x 640
CELLS = 10 * 2 * 16 * 16 * 640 * 640


@pytest.mark.parametrize("answered,want", [(16, 131.072), (4, 524.288)])
def test_reader_gives_mcells_per_request_answered(answered, want):
    rec = _rec({"wave:solver_cells_padded": CELLS,
                "h2d:wave_dispatch[s0]": 10}, answered=answered)
    assert run.metric_reader(NAME)(rec) == pytest.approx(want)


def test_reader_is_silent_without_the_counter():
    """A program that counts no wave solver cells (only the host
    solver's), or a window that answered nothing: no value, no raise."""
    read = run.metric_reader(NAME)
    assert read(_rec({"verify:solver_cells_padded": 8_000_000,
                      "h2d:wave_dispatch[s0]": 10})) is None
    assert read(_rec({})) is None
    assert read(_rec({"wave:solver_cells_padded": CELLS},
                     answered=0)) is None


def test_the_metric_is_listed_for_the_twitter_cell():
    entry = run.load_cell("twitter.closed")["per_layer"][-1]
    assert entry["name"] == NAME
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == \
        ("Mcells", "lower", "program_counter", "fused wave", "qps")

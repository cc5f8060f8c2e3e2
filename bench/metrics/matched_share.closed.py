"""Share of the candidate sets that reached a full graph matching
(``filter:em_full`` over ``filter:candidates``, counted by
``repro.runtime.instrument`` as each tile finishes) over the window and
the wait after it: the paper's measure of how much work the filters
leave to the matcher."""


def read(rec):
    c = rec["counts"].get("filter:candidates")
    full = rec["counts"].get("filter:em_full")
    return 100.0 * full / c if c and full is not None else None

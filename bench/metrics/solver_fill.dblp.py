"""Share of the host continuation's exact-solver cells that hold a real
(query token, candidate token) pair: 100 x ``verify:solver_cells_logical``
(the sum of |Q| x |C| over every solver row) / ``verify:solver_cells_padded``
(the rows x columns of the blocks the solver batches work on, batch
padding included), both counted by ``repro.runtime.instrument`` over the
window and the wait after it.  Nothing where the program counts no
solver cells."""


def read(rec):
    logical = rec["counts"].get("verify:solver_cells_logical")
    padded = rec["counts"].get("verify:solver_cells_padded")
    if logical is None or not padded:
        return None
    return 100.0 * logical / padded

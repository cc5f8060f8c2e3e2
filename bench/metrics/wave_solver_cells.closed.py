"""Cells of the blocks that the fused wave's in-trace verification rounds
solve, padding included (``wave:solver_cells_padded`` of
``repro.runtime.instrument``: rounds x B x verify batch x nq_pad x
max(nq_pad, c_pad) per wave launch, from its static config), over the
window and the wait after it, per request answered, in millions.
Nothing where the program has no such counter."""


def read(rec):
    n = sum(1 for r in rec["records"] if r["ok"])
    cells = rec["counts"].get("wave:solver_cells_padded")
    return cells / 1e6 / n if n and cells is not None else None

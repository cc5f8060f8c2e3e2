"""Lockstep Dijkstra iterations of the host continuation's exact-solver
batches (``verify:solver_steps``, counted by ``repro.runtime.instrument``:
each batch's trip count of the vmapped shortest-path scan, summed over
batches) over the window and the wait after it, per request answered.
Nothing where the program counts no solver steps."""


def read(rec):
    n = sum(1 for r in rec["records"] if r["ok"])
    steps = rec["counts"].get("verify:solver_steps")
    return steps / n if n and steps is not None else None

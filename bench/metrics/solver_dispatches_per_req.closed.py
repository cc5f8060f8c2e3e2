"""Host verifier solver dispatches (``h2d:solver_dispatch``, counted by
``repro.runtime.instrument``) over the window and the wait after it, per
request answered."""


def read(rec):
    n = sum(1 for r in rec["records"] if r["ok"])
    return rec["counts"].get("h2d:solver_dispatch", 0) / n if n else None

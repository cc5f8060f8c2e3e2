"""Host time of the verification continuation, every device wait
excluded (self time of spans ``koios.resume``, ``koios.verify``,
``koios.verify.weights``, ``koios.verify.pack`` and
``koios.verify.solve`` of ``repro.runtime.instrument``) over the window
and the wait after it, per request answered."""
SPANS = ("koios.resume", "koios.verify", "koios.verify.weights",
         "koios.verify.pack", "koios.verify.solve")


def read(rec):
    n = sum(1 for r in rec["records"] if r["ok"])
    ns = [rec["counts"].get("self_ns:" + s) for s in SPANS]
    if not n or all(v is None for v in ns):
        return None
    return sum(v or 0 for v in ns) / 1e6 / n

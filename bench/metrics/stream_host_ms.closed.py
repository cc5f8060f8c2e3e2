"""Host time of the engine's stream builds (span ``koios.stream`` of
``repro.runtime.instrument``, the cache lookup and the stacked sweep of
the misses, device wait included) over the window and the wait after
it, per request answered."""


def read(rec):
    n = sum(1 for r in rec["records"] if r["ok"])
    ns = rec["counts"].get("span_ns:koios.stream")
    return ns / 1e6 / n if n and ns is not None else None

"""Host time of the fused waves' launches (self time of span
``koios.wave.launch`` of ``repro.runtime.instrument``: sizing, the
stream operands' build and upload, the dispatch) over the window and
the wait after it, per request answered."""


def read(rec):
    n = sum(1 for r in rec["records"] if r["ok"])
    ns = rec["counts"].get("self_ns:koios.wave.launch")
    return ns / 1e6 / n if n and ns is not None else None

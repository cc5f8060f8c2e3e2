"""Host-to-device dispatches plus device-to-host copies counted by
``repro.runtime.instrument`` over the window and the wait after it, per
request answered."""


def read(rec):
    n = sum(1 for r in rec["records"] if r["ok"])
    t = sum(v for k, v in rec["counts"].items()
            if k.startswith(("h2d:", "d2h:")))
    return t / n if n else None

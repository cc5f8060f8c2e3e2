"""Device time of the host continuation's exact solver batches
(``core/matching/hungarian.py``, module ``jit__hungarian_padded``) in the
traced part of the window, per request's worth of work done in it, from the
profiler trace.  The similarity blocks the verifier fetches run in
``jit_cosine_rows``, which the stream sweep shares, so they are not
counted here."""
PROGRAMS = ("jit__hungarian_padded",)


def read(rec):
    d = rec.get("device")
    n = rec["traced_work"]
    if not d or n <= 0:
        return None
    t = sum(v for k, v in d["by_program"].items() if k in PROGRAMS)
    return 1000.0 * t / n if t > 0 else None

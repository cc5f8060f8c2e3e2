"""Device time of the fused wave programs (``core/wave.py``, module
``jit_fn``) in the traced part of the window, per request's worth of
work done in it, from the profiler trace."""
PROGRAMS = ("jit_fn",)


def read(rec):
    d = rec.get("device")
    n = rec["traced_work"]
    if not d or n <= 0:
        return None
    t = sum(v for k, v in d["by_program"].items() if k in PROGRAMS)
    return 1000.0 * t / n if t > 0 else None

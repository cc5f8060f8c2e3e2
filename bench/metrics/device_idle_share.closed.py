"""Share of the traced window in which no program ran on the device:
1 - busy / window, from the profiler trace."""


def read(rec):
    d = rec.get("device")
    if not d or d["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])

"""Mean number of requests in an engine step's wave, over the window's
steps (``EngineCounters.wave_sizes``)."""


def read(rec):
    w = rec["wave_sizes"]
    return sum(w) / len(w) if w else None

"""Process start to window start: generation, uploads, warm-up and
compile-cache loads (host clock)."""


def read(rec):
    return rec["setup_s"]

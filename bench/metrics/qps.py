"""Requests answered correctly inside the window, per second of the
window (host clock)."""
import measure


def read(rec):
    return measure.served_rate(rec["records"], rec["t_open"], rec["t_close"])

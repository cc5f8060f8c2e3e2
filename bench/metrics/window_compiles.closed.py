"""Programs compiled, or loaded from the persistent cache, inside the
window (a ``jax.monitoring`` listener on backend compiles); 0 when set-up
warmed every shape the window uses."""


def read(rec):
    return rec["window_compiles"]

"""Backend compiles inside the measured window (JAX's monitoring events;
persistent-cache loads count too).  A warmed-up window should read 0.
Moves ``request_ms_p95``."""


def read(w):
    return float(w.compiles)

"""Tokens committed per scheduler round (``ServeStats.decode_tokens`` over
``decode_steps``, deltas over the window).  Moves ``tokens_per_s``."""


def read(w):
    steps = w.delta("decode_steps")
    return None if steps <= 0 else w.delta("decode_tokens") / steps

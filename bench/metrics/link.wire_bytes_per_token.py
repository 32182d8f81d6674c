"""Bytes over the simulated link (``transmitted_bytes`` delta) per served
token.  Moves ``request_ms_p95``."""


def read(w):
    return None if w.tokens <= 0 else w.delta("transmitted_bytes") / w.tokens

"""Simulated link milliseconds (``channel_latency_s`` delta) per served
token: the part of an edge user's wait that no device or host clock
shows.  Moves ``request_ms_p95``."""


def read(w):
    if w.tokens <= 0:
        return None
    return 1e3 * w.delta("channel_latency_s") / w.tokens

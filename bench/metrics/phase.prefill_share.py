"""Device time of the prefill phases (the edge, cloud and draft prefill
programs) over the traced window, per device.  Moves
``request_ms_p95``."""
from bench.trace_reduce import module_is, share

PREFILL = ("_edge_prefill_impl", "_cloud_prefill_impl", "_draft_prefill_impl")


def read(w):
    t = w.trace
    if t is None:
        return None
    return share(t.module_time(module_is(*PREFILL)) / t.n_devices(),
                 t.window_s)

"""Self time of the paged-attention Pallas kernel's calls
(``paged_flash_mq``) over device busy time.  Moves ``tokens_per_s``."""
from bench.trace_reduce import op_is, share


def read(w):
    t = w.trace
    if t is None:
        return None
    return share(t.op_time(op_is("paged_flash")) / t.n_devices(), t.busy_s)

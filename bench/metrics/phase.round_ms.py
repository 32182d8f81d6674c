"""Device milliseconds of one speculative round: the time of every
engine phase program that is not a prefill, per device, over the rounds
run (``spec_rounds`` delta).  The engine's phases are jitted private
methods, so their programs are named ``jit__<method>``; the draft and
verify phases are jitted through ``functools.partial`` and show as
``jit__unknown(<fingerprint>)``.  Programs of single JAX primitives
(``jit_concatenate``, ``jit_convert_element_type``) are left out.
Moves ``tokens_per_s``."""
from bench.trace_reduce import module_is

PREFILL = module_is("_edge_prefill_impl", "_cloud_prefill_impl",
                    "_draft_prefill_impl")


def in_round(module: str) -> bool:
    return module.startswith("jit__") and not PREFILL(module)


def read(w):
    t = w.trace
    rounds = w.delta("spec_rounds")
    if t is None or rounds <= 0:
        return None
    busy = t.module_time(in_round)
    return None if busy <= 0 else 1e3 * busy / t.n_devices() / rounds

"""Model FLOPs of the served tokens (``request_flops`` of the model's
``bench/archs/<arch>.py``) over the
window's host-clock seconds, as a share of the chips' bf16 peak
(``bench/peaks.json``).  Moves ``tokens_per_s``."""


def read(w):
    peak = w.peaks.get("bf16_flops")
    if not peak or w.tokens <= 0:
        return None
    return 100.0 * w.flops() / w.wall_s / (peak * w.n_devices)

"""Share of graded drafts the cloud accepted (``draft_hits`` over
``drafted_tokens``, deltas over the window).  Moves ``tokens_per_s``."""


def read(w):
    drafted = w.delta("drafted_tokens")
    return None if drafted <= 0 else 100.0 * w.delta("draft_hits") / drafted

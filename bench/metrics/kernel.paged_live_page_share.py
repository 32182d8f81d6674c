"""Share of the paged-attention kernel's page visits that read a live
key: ``kv_pages_live`` over ``kv_pages_visited`` (``ServeStats`` deltas
over the window).  The collaborative engine counts both for every kernel
call of its speculative rounds: the grid visits every row of the block
table over its full width, a live row holds keys in the pages below its
KV length.  A program that keeps no such counters reads nothing.  Moves
``tokens_per_s``."""


def read(w):
    visited = w.stats.get("kv_pages_visited", 0)
    if visited <= 0:
        return None
    return 100.0 * w.stats.get("kv_pages_live", 0) / visited

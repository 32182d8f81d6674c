"""Paged-KV bookkeeping (host side) for the serving engines.

KV cache layouts (see ``transformer.init_cache`` for shapes):

* **dense** — every slot owns ``max_len`` positions up front; the
  decode einsum streams the whole ``[B, max_len]`` cache each step.
* **paged** — slots own a block-table row into a shared page pool
  (``PageAllocator``); HBM is claimed page-by-page at admission and
  returned at retirement, and reads run the paged flash kernel
  (``kernels.paged_attention``) whose cost scales with *allocated*
  pages, not ``max_len``.
* **paged + INT8** — pages store 1 B/elem with per-slot symmetric
  scales calibrated from each prompt at prefill (paper Eq.1 applied to
  serving state); dequantization happens inside the kernel's QK/AV
  loops so the cache never materializes above 1 B/elem.

The pool's geometry depends only on ``(max_batch, max_len, page_size)``
— never on the collaborative cut — so a live re-partition
(``policy.AdaptivePolicy``) keeps the allocator, the block table, and
every slot's page claim; only the per-layer cache arrays are rebuilt.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


class PoolExhausted(RuntimeError):
    """Typed "no free pages" failure of ``PageAllocator.alloc``.

    Subclasses ``RuntimeError`` so pre-existing callers that catch the
    bare exhaustion keep working; the overload-robust scheduler catches
    it specifically — a mid-round exhaustion triggers victim preemption
    (``scheduler._SlotEngine``), never a crash."""


class PageAllocator:
    """LIFO free-list allocator over a fixed pool of KV-cache pages.

    Page 0 is never handed out: retired/idle slots keep a zeroed block
    table row, so their (masked, harmless) decode writes land in page 0
    instead of corrupting a page that has been re-allocated to a live
    request.
    """

    def __init__(self, num_pages: int):
        assert num_pages >= 2, "need at least one allocatable page"
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))
        self._live: set = set()

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def live(self) -> frozenset:
        return frozenset(self._live)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise PoolExhausted(
                f"KV page pool exhausted: need {n}, have {len(self._free)}")
        pages = [self._free.pop() for _ in range(n)]
        self._live.update(pages)
        return pages

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p not in self._live:
                raise ValueError(
                    f"free of page {p} which is not live (double free, or "
                    f"a page this allocator never handed out)")
            self._live.remove(p)
            self._free.append(p)


class _PagedPool:
    """Block table + allocator for one engine-side page pool.

    Pages for a request are claimed at admission — by default enough to
    cover its padded prompt plus its (known) generation budget, plus any
    speculative-round headroom; a demand-paged engine reserves only the
    padded prompt plus one round of headroom and grows the claim with
    ``ensure`` as the sequence crosses page boundaries — and returned
    the moment the scheduler retires (or preempts) the slot.  The
    collaborative engine shares one pool (one block table) across its
    edge-prefix, cloud-suffix, and draft caches: all three see identical
    page geometry, so a verify-round rollback is the same length
    decrement on every cache.
    """

    def __init__(self, max_batch: int, pages_per_slot: int, num_pages: int,
                 page_size: int):
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.allocator = PageAllocator(num_pages)
        self.bt = np.zeros((max_batch, pages_per_slot), np.int32)
        self._slot_pages: Dict[int, List[int]] = {}
        self._dev: Optional[jax.Array] = None
        # per-owner (tenant) page accounting for the fleet engine's
        # weighted-fair sharing: admission tags each slot with an owner,
        # ensure()-growth and retirement keep the count current
        self._slot_owner: Dict[int, str] = {}
        self._owner_pages: Dict[str, int] = {}
        self._masked: Dict[Tuple[int, ...], jax.Array] = {}

    @classmethod
    def build(cls, max_batch: int, max_len: int, page_size: int,
              num_pages: Optional[int] = None) -> "_PagedPool":
        """Standard sizing: worst case ``max_batch`` full-length slots
        plus the reserved dump page.

        **Intentional-undersizing contract**: an explicit ``num_pages``
        below the standard sizing bounds *concurrency*, never
        feasibility — admission backpressures until retirements return
        pages (``scheduler._SlotEngine._can_admit``), and a demand-paged
        engine additionally oversubscribes the pool against worst-case
        budgets and preempts on ``PoolExhausted``.  A pool that cannot
        hold even one max-length slot (``pages_per_slot``) plus the
        reserved dump page can never serve anything and is rejected
        here, at construction, instead of stalling the first request."""
        pages_per_slot = _cdiv(max_len, page_size)
        if num_pages is None:
            num_pages = max_batch * pages_per_slot + 1
        elif num_pages < pages_per_slot + 1:
            raise ValueError(
                f"KV page pool num_pages={num_pages} can never admit a "
                f"single max-length slot: max_len={max_len} at "
                f"page_size={page_size} needs pages_per_slot="
                f"{pages_per_slot} plus the reserved dump page "
                f"(>= {pages_per_slot + 1}); undersizing below "
                f"{max_batch * pages_per_slot + 1} only bounds concurrency")
        return cls(max_batch, pages_per_slot, num_pages, page_size)

    def pages_needed(self, plen: int, max_new: int, padded_len: int) -> int:
        return _cdiv(max(int(plen) + int(max_new), int(padded_len)),
                     self.page_size)

    def can_admit(self, shapes: Sequence[Tuple[int, int]],
                  padded_len: int) -> bool:
        """Would a prefill group of (plen, max_new) shapes fit the free
        list right now?"""
        return sum(self.pages_needed(p, m, padded_len)
                   for p, m in shapes) <= self.allocator.num_free

    def live_cache_bytes(self, cache: Dict[str, jax.Array]) -> int:
        """Bytes resident in currently-allocated pages (+ scales) of the
        paged ``cache`` this pool indexes — the demand-paging footprint,
        as opposed to the pool's capacity."""
        per_page = int(np.prod(cache["k_pages"].shape[2:])) \
            * cache["k_pages"].dtype.itemsize
        n_layers = cache["k_pages"].shape[0]
        scales = sum(v.size * v.dtype.itemsize
                     for k, v in cache.items() if "scale" in k)
        return 2 * n_layers * len(self.allocator.live) * per_page + scales

    def admit(self, slots: Sequence[int], plens: Sequence[int],
              max_news: Sequence[int], padded_len: int,
              owner: Optional[str] = None) -> jax.Array:
        """Allocate pages for a prefill group; returns the group's block
        table rows [n, pages_per_slot].  ``owner`` tags the slots for
        per-tenant page accounting (``owner_pages``)."""
        for s, pl_, mn in zip(slots, plens, max_news):
            pages = self.allocator.alloc(
                self.pages_needed(pl_, mn, padded_len))
            self._slot_pages[int(s)] = pages
            if owner is not None:
                self._slot_owner[int(s)] = owner
                self._owner_pages[owner] = \
                    self._owner_pages.get(owner, 0) + len(pages)
            self.bt[s, :] = 0
            self.bt[s, :len(pages)] = pages
        self._dev = None
        self._masked.clear()
        # trim to the pages the padded prompt can touch: the prefill's
        # q-block read costs O(table width), so handing it the full
        # pages_per_slot row would make prefill scale with max_len
        # instead of the bucket (the generation's later pages are only
        # reachable by decode, which re-reads through table_dev)
        width = max(1, _cdiv(padded_len, self.page_size))
        # explicit copy: jax on CPU may zero-copy-alias numpy buffers, and
        # ``bt`` is mutated on the host while async decode steps are still
        # in flight — sharing it would race
        return jnp.array(self.bt[np.asarray(slots)][:, :width], copy=True)

    def rows(self, slots: Sequence[int], padded_len: int) -> jax.Array:
        """Current block-table rows for ``slots``, trimmed like ``admit``
        to the pages a ``padded_len``-position replay can touch — for
        rebuilding a cache over positions the slots already own (the
        draft-cache rebuild on a warm k raise).  Copied, never aliased,
        for the same async-mutation reason as ``admit``."""
        width = max(1, _cdiv(int(padded_len), self.page_size))
        return jnp.array(self.bt[np.asarray(slots)][:, :width], copy=True)

    def pages_held(self, slot: int) -> int:
        return len(self._slot_pages.get(int(slot), ()))

    def ensure(self, slot: int, n_positions: int) -> bool:
        """Demand-grow ``slot``'s page claim to cover ``n_positions``
        cache positions; returns True iff new pages were allocated.
        Raises ``PoolExhausted`` — with the slot's existing claim and
        block-table row untouched — when the free list cannot cover the
        growth, which is the scheduler's cue to preempt a victim."""
        s = int(slot)
        pages = self._slot_pages.get(s)
        assert pages is not None, f"slot {s} holds no pages"
        need = _cdiv(int(n_positions), self.page_size)
        if need <= len(pages):
            return False
        grown = self.allocator.alloc(need - len(pages))
        self.bt[s, len(pages):need] = grown
        pages.extend(grown)
        owner = self._slot_owner.get(s)
        if owner is not None:
            self._owner_pages[owner] += len(grown)
        self._dev = None
        self._masked.clear()
        return True

    def retire(self, slot: int) -> None:
        pages = self._slot_pages.pop(int(slot), None)
        if pages is not None:
            self.allocator.free(pages)
            owner = self._slot_owner.pop(int(slot), None)
            if owner is not None:
                self._owner_pages[owner] -= len(pages)
            self.bt[slot, :] = 0
            self._dev = None
            self._masked.clear()

    # -- pool-pressure observability (public: no private poking) -------------
    def free_pages(self) -> int:
        """Allocatable pages on the free list right now — the quantity
        admission backpressure and the fairness policy key off."""
        return self.allocator.num_free

    def utilization(self) -> float:
        """Fraction of allocatable pages currently claimed by live slots
        (the reserved dump page is excluded from the denominator)."""
        cap = self.allocator.num_pages - 1
        return (cap - self.allocator.num_free) / max(cap, 1)

    def owner_pages(self, owner: str) -> int:
        """Pages currently held by ``owner``-tagged slots (see ``admit``)."""
        return self._owner_pages.get(owner, 0)

    def slot_owner(self, slot: int) -> Optional[str]:
        return self._slot_owner.get(int(slot))

    def table_dev(self) -> jax.Array:
        """Block table on device, trimmed to the pages actually in use
        (rounded up to a power of two, so decode retraces are bounded by
        log2(pages_per_slot) widths, not every occupancy) — the decode
        read then costs O(allocated pages), not O(max_len).  Cached
        until the next admit/retire.  Copied, never aliased: the host
        mutates ``bt`` while earlier async decode steps may still be
        reading the device buffer."""
        if self._dev is None:
            used = max((len(p) for p in self._slot_pages.values()),
                       default=1)
            width = 1
            while width < used:
                width *= 2
            width = min(width, self.pages_per_slot)
            self._dev = jnp.array(self.bt[:, :width], copy=True)
        return self._dev

    def table_for(self, slots: Sequence[int]) -> jax.Array:
        """Like ``table_dev`` but with every row *outside* ``slots``
        zeroed, so slots riding along in somebody else's batched phase
        call write into the allocator's reserved dump page instead of
        their own pages — the convention the resync replay established,
        now the backbone of the fleet engine's cross-tenant batched
        rounds (a (cut, k) group's phase call spans the full slot axis
        but must only touch the group's pages).  Cached per group until
        the next admit/ensure/retire invalidates the table."""
        key = tuple(sorted(int(s) for s in slots))
        if key not in self._masked:
            full = np.asarray(self.table_dev())
            masked = np.zeros_like(full)
            masked[list(key)] = full[list(key)]
            self._masked[key] = jnp.array(masked, copy=True)
        return self._masked[key]


def page_visits(pos: Sequence[int], k: int, rows: int, width: int,
                page_size: int, max_len: int, draft_calls: int,
                verify_calls: int) -> Tuple[int, int]:
    """(Row, page) grid steps of one speculative round's paged-attention
    calls, and how many of them hold a live key.  The kernel's grid runs
    all ``rows`` of the block table over its ``width`` pages
    (``kernels.paged_attention``), one step carrying ``hb`` kv heads
    (every head at decode and verify widths), so a step here has no
    head factor.  A live row holds keys only in its pages below its KV
    length, ``ceil(keys / page_size)`` of them: the kernel's own rule,
    ``p <= (max(length, 1) - 1) // page_size``.  The kernel issues no
    DMA and runs no math on the other steps, so visited − live counts
    the steps it skips, less those of idle rows, which are counted as
    visited but walk the reserved dump page up to a stale length.
    ``pos``: each live row's committed length less one.  Draft pass
    i < k makes ``draft_calls`` calls that read ``pos + i + 1`` keys,
    the verify ``verify_calls`` that read ``pos + k``."""
    keys = np.minimum(np.asarray(pos)[:, None] + np.arange(1, k + 1),
                      max_len)
    pages = -(-keys // page_size)
    visited = (k * draft_calls + verify_calls) * rows * width
    live = draft_calls * pages.sum() + verify_calls * pages[:, -1].sum()
    return visited, int(live)


def _paged_prefill_view(cache: Dict[str, jax.Array], n_layers: int, n: int,
                        n_kv: int) -> Dict[str, jax.Array]:
    """Group-local view of a paged cache for one prefill call: the
    shared page pool plus fresh scale rows for the ``n``-row group (the
    prefill calibrates them; scatter back with _paged_prefill_merge)."""
    group = {"k_pages": cache["k_pages"], "v_pages": cache["v_pages"]}
    if "k_scale" in cache:
        group["k_scale"] = jnp.zeros((n_layers, n, n_kv), jnp.float32)
        group["v_scale"] = jnp.zeros_like(group["k_scale"])
    return group


def _paged_prefill_merge(cache: Dict[str, jax.Array],
                         group: Dict[str, jax.Array],
                         slots: jax.Array) -> Dict[str, jax.Array]:
    cache = dict(cache, k_pages=group["k_pages"], v_pages=group["v_pages"])
    if "k_scale" in cache:
        cache["k_scale"] = cache["k_scale"].at[:, slots].set(
            group["k_scale"])
        cache["v_scale"] = cache["v_scale"].at[:, slots].set(
            group["v_scale"])
    return cache

"""Cloud-only batched serving engine (the non-collaborative baseline).

One KV cache over the full stack: dense fp by default, ``paged=True``
for the block-table page pool, ``int8_kv=True`` for 1 B/elem storage
with per-slot scales calibrated at prefill.  Rides the same
``_SlotEngine`` continuous-batching scheduler as the collaborative
engine."""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import transformer as TF
from repro.serve.kvcache import (_PagedPool, _paged_prefill_merge,
                                 _paged_prefill_view)
from repro.serve.scheduler import _jit_phase, _SlotEngine
from repro.serve.sharding import place_cloud_engine

Params = Any


class ServingEngine(_SlotEngine):
    """Cloud-only batched engine (greedy decode, continuous batching).

    ``paged=True`` swaps the dense per-slot cache for the block-table
    page pool (+ ``int8_kv=True`` for 1 B/elem pages with per-slot
    scales); ``cache_dtype`` overrides the dense cache's storage dtype
    (e.g. bf16 for the fp16-cache baseline in the benchmarks);
    ``mesh`` TP-shards the params and KV pool over its ``model`` axis
    (see ``serve.sharding``) and runs every phase under the mesh."""

    def __init__(self, params: Params, cfg: TF.LMConfig, *,
                 max_batch: int = 4, max_len: int = 128,
                 paged: bool = False, page_size: int = 16,
                 int8_kv: bool = False, num_pages: Optional[int] = None,
                 cache_dtype=None,
                 mesh: Optional[jax.sharding.Mesh] = None):
        super().__init__(cfg, max_batch=max_batch, max_len=max_len)
        self.mesh = mesh
        self.params = params
        self.paged = paged
        self.page_size = page_size
        self.int8_kv = int8_kv
        if paged:
            self._pool = _PagedPool.build(max_batch, max_len, page_size,
                                          num_pages)
            self._cache = TF.init_cache(
                self.cfg, max_batch, max_len, paged=True,
                page_size=page_size, quantized=int8_kv,
                num_pages=self._pool.allocator.num_pages, dtype=cache_dtype)
            self._prefill = _jit_phase(self._paged_prefill_impl, donate=(2,),
                                       mesh=mesh)
        else:
            self._cache = TF.init_cache(self.cfg, max_batch, max_len=max_len,
                                        dtype=cache_dtype,
                                        quantized=int8_kv)
            self._prefill = _jit_phase(self._prefill_impl, donate=(2,),
                                       mesh=mesh)
        self._decode = _jit_phase(self._decode_impl, donate=(2,), mesh=mesh)
        if mesh is not None:
            place_cloud_engine(self)

    def _prefill_impl(self, params, toks, cache, slots, cur, pos, plens):
        self.trace_counts["prefill"] += 1
        n, _ = toks.shape
        small = TF.init_cache(self.cfg, n, max_len=self.max_len,
                              quantized=self.int8_kv,
                              dtype=cache["k"].dtype)
        logits, small = TF.prefill(params, toks, self.cfg, cache=small,
                                   last_pos=plens - 1)
        cache = dict(cache, **{k: cache[k].at[:, slots].set(small[k])
                               for k in ("k", "v")})
        cur = cur.at[slots].set(jnp.argmax(logits, -1).astype(jnp.int32))
        pos = pos.at[slots].set(plens)
        return cache, cur, pos

    def _paged_prefill_impl(self, params, toks, cache, bt_rows, slots, cur,
                            pos, plens):
        self.trace_counts["prefill"] += 1
        group = _paged_prefill_view(cache, self.cfg.n_layers, toks.shape[0],
                                    self.cfg.n_kv)
        logits, group = TF.prefill(params, toks, self.cfg, cache=group,
                                   block_tables=bt_rows, last_pos=plens - 1)
        cache = _paged_prefill_merge(cache, group, slots)
        cur = cur.at[slots].set(jnp.argmax(logits, -1).astype(jnp.int32))
        pos = pos.at[slots].set(plens)
        return cache, cur, pos

    def _decode_impl(self, params, cur, cache, pos, bt):
        self.trace_counts["decode"] += 1
        logits, cache = TF.decode_step(params, cur, cache, pos, self.cfg,
                                       block_tables=bt)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return nxt, cache, jnp.minimum(pos + 1, self.max_len - 1)

    def _admit(self, toks, plens, max_news, slots, cur, pos, samplings=None):
        assert not any(s is not None and s.sampled
                       for s in (samplings or [])), \
            "cloud-only baseline is greedy; sampled serving lives in " \
            "CollaborativeServingEngine (serve.sampling)"
        if self.paged:
            bt_rows = self._pool.admit(slots, plens, max_news, toks.shape[1])
            self._cache, cur, pos = self._prefill(
                self.params, toks, self._cache, bt_rows, jnp.asarray(slots),
                cur, pos, jnp.asarray(plens))
        else:
            self._cache, cur, pos = self._prefill(
                self.params, toks, self._cache, jnp.asarray(slots), cur, pos,
                jnp.asarray(plens))
        return cur, pos

    def _decode_all(self, cur, pos, n_active):
        bt = self._pool.table_dev() if self.paged else None
        cur, self._cache, pos = self._decode(self.params, cur,
                                             self._cache, pos, bt)
        return cur, pos

    def _retire(self, slot):
        if self.paged:
            self._pool.retire(slot)

    def _can_admit(self, group_shapes, plen, max_new, bucket):
        if not self.paged:
            return True
        return self._pool.can_admit(group_shapes + [(plen, max_new)], bucket)

    def cache_bytes(self, *, live_only: bool = False) -> int:
        """Cache footprint in bytes.  ``live_only`` counts just the
        pages currently allocated to requests (the demand-paging win)."""
        if self.paged and live_only:
            return self._pool.live_cache_bytes(self._cache)
        return sum(v.size * v.dtype.itemsize for v in self._cache.values())

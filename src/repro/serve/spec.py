"""Speculative draft/verify machinery of the collaborative engine.

With ``spec_k = k > 1`` the serial decode loop restructures into
draft/verify rounds that amortize the channel RTT and per-message
framing over up to k tokens:

1. **Draft (edge, local).**  Starting from the last committed token,
   the edge runs the *full* split model k times at low precision — its
   INT8 prefix over the paged INT8 edge cache, then a lightweight INT8
   copy of the cloud-suffix weights (the same fake-quant lattice the
   prefix uses) over a local *draft* KV cache that shares the edge
   block table.  Each step emits the Eq.(1)-quantized boundary delta
   and greedily drafts the next token from the local suffix.
2. **Uplink (one transfer).**  The edge ships the concatenated
   ``[B, k, D]`` quantized boundary blob — each of the k rows framed
   with its own per-row scale/zero-point so the cloud dequantizes
   exactly what a serial step would have seen — plus the k-1 draft
   tokens the cloud must grade (4 B each).  One channel traversal.
3. **Verify (cloud, one batched step).**  The cloud suffix runs all k
   positions in a single multi-token cached step (the paged kernel's
   q-block form, intra-block causal mask) and takes the longest prefix
   of drafts matching its own greedy tokens: a round commits between 1
   and k tokens and ``k = 1`` degenerates to the non-speculative step.
4. **Rollback (both sides, O(1)).**  Rejected positions are *not*
   erased: both sides keep their per-slot committed length — stale page
   entries are masked by causality and overwritten in place.
5. **Downlink (one transfer).**  The cloud returns the accept mask
   (``ceil(k/8)`` B/row) and the corrected token (4 B/row).

``_SpecDraftMixin`` hosts the jitted implementations; the draft length
k is a trace constant (scan length / verify q-block width), so each k a
policy may pick gets its own jitted pair, built on first use and cached
— an online ``spec_k`` switch after warm-up never recompiles.

Rounds carrying a temperature>0 slot run the ``*_sample`` twins (their
own per-k jit cache): the draft proposes seeded categorical draws from
its filtered distribution ``q`` and ships the graded positions' ``q``
rows alongside the blob (priced as extra uplink), and the verify grades
by **rejection sampling** (``serve.sampling.grade_and_correct``) instead
of argmax match — keeping the cloud's sampling distribution exact while
greedy rows in the same batch still commit bit-identical argmax tokens.

The mixin also hosts the **degradation** phases of the resilient engine
(``serve.resilience``), which reuse the same draft machinery with the
verify removed: when the cloud is unreachable, the edge's INT8 suffix
copy stops *drafting* and starts *serving* — ``_edge_only_step_impl``
is one full local step (prefix → boundary → suffix → token, zero wire
bytes), ``_edge_only_prefill_impl`` admits a request entirely on the
edge, and the two ``_resync_*`` phases replay buffered boundary rows
through the cloud suffix in one multi-token cached step per slot group
(the verify's q-block form with the grading removed) to rebuild its
paged KV on reconnect.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import dequantize
from repro.models import layers as ML
from repro.models import transformer as TF
from repro.serve import sampling as S
from repro.serve.kvcache import _paged_prefill_merge, _paged_prefill_view
from repro.serve.scheduler import _bucket_len, _jit_phase


def _at_k(impl, k: int):
    """``impl`` with the draft length bound to ``k``, under ``impl``'s
    name, so its compiled program is ``jit_<name>`` in a device trace
    (a bare ``partial`` compiles as ``jit__unknown``)."""
    fn = partial(impl, k)
    fn.__name__ = fn.__qualname__ = impl.__name__
    return fn


class _SpecDraftMixin:
    """Draft/verify phase implementations, mixed into
    ``CollaborativeServingEngine`` (which provides cfg, caches, the
    boundary lattice ``_quant_boundary``, and the scheduler hooks) and
    into the per-cut runtimes of ``serve.fleet``.  Every impl operates
    over the *full* slot axis with a block table picking which slots'
    pages are written — which is exactly what lets the fleet engine
    verify many tenants' rounds in ONE batched call: tenants at the
    same (cut, k) share the call, everyone else's rows ride along
    masked to the dump page (``_PagedPool.table_for``)."""

    def _spec_fns(self, k: int):
        if k not in self._spec_jits:
            mesh = getattr(self, "mesh", None)
            draft = _jit_phase(_at_k(self._spec_draft_impl, k),
                               donate=(5, 6), mesh=mesh)
            verify = _jit_phase(_at_k(self._verify_impl, k), donate=(6,),
                                mesh=mesh)
            self._spec_jits[k] = (draft, verify)
        return self._spec_jits[k]

    def _spec_sample_fns(self, k: int):
        """Sampled twin of ``_spec_fns``: per-k cached jitted
        (draft, rejection-sampling verify) pair for rounds carrying at
        least one temperature>0 slot.  Greedy rows ride along on the
        argmax branch inside the same call (``serve.sampling``)."""
        if not hasattr(self, "_spec_sample_jits"):
            self._spec_sample_jits: Dict[int, Tuple[Any, Any]] = {}
        if k not in self._spec_sample_jits:
            mesh = getattr(self, "mesh", None)
            draft = _jit_phase(_at_k(self._spec_draft_sample_impl, k),
                               donate=(5, 6), mesh=mesh)
            verify = _jit_phase(_at_k(self._verify_sample_impl, k),
                                donate=(7,), mesh=mesh)
            self._spec_sample_jits[k] = (draft, verify)
        return self._spec_sample_jits[k]

    def _draft_prefill_impl(self, blocks, blob, qp, cache, slots, bt_rows,
                            plens):
        """Fill the edge's local draft cache: the INT8 suffix copy runs
        the same dequantized boundary blob the cloud saw, so the draft
        model starts every round from the committed prefix state."""
        cfg = self.cfg
        h = dequantize(blob, qp).astype(cfg.dtype)         # Eq.(2), locally
        n = h.shape[0]
        if self.edge_paged:
            group = _paged_prefill_view(cache, self.n_cloud, n, cfg.n_kv)
            _, group = TF.run_blocks(blocks, h, cfg, rope=self._rope(),
                                     cache=group, cache_index=jnp.int32(0),
                                     qctx=self._edge_qctx,
                                     block_tables=bt_rows,
                                     calibrate_kv=self.edge_int8,
                                     kv_lengths=plens)
            cache = _paged_prefill_merge(cache, group, slots)
        else:
            small = TF.init_cache(cfg, n, self.max_len, layers=self.n_cloud,
                                  quantized=self.edge_int8)
            _, small = TF.run_blocks(blocks, h, cfg, rope=self._rope(),
                                     cache=small, cache_index=jnp.int32(0),
                                     qctx=self._edge_qctx)
            cache = dict(cache, **{k: cache[k].at[:, slots].set(small[k])
                                   for k in ("k", "v")})
        return cache

    def _spec_draft_impl(self, k, edge_blocks, draft_blocks, embed, tail,
                         cur, e_cache, d_cache, pos, bt):
        """k sequential local steps on the edge: INT8 prefix → Eq.(1)
        delta → local INT8 suffix copy → greedy draft token.  One jit'd
        ``lax.scan``, so a whole round costs one dispatch.  Emits the
        stacked ``[k, B, D]`` boundary blob with per-(row, position)
        quant params — bitwise the frames k serial steps would have
        shipped — and the k draft tokens."""
        self.trace_counts["spec_draft"] += 1
        cfg = self.cfg
        rope = self._rope()

        def step(carry, _):
            tok, p, ec, dc = carry
            x = ML.embed(embed, tok[:, None]).astype(cfg.dtype)
            h, ec = TF.run_blocks(edge_blocks, x, cfg, rope=rope, cache=ec,
                                  cache_index=p, qctx=self._edge_qctx,
                                  block_tables=bt)
            blob, qp = self._quant_boundary(h)              # per row
            hq = dequantize(blob, qp).astype(cfg.dtype)  # what the cloud sees
            y, dc = TF.run_blocks(draft_blocks, hq, cfg, rope=rope, cache=dc,
                                  cache_index=p, qctx=self._edge_qctx,
                                  block_tables=bt)
            logits = TF.lm_head(tail, y)[:, 0]
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            p = jnp.minimum(p + 1, self.max_len - 1)
            return (nxt, p, ec, dc), (blob[:, 0], qp.scale, qp.zero_point,
                                      nxt)

        (_, _, e_cache, d_cache), (blobs, scales, zps, drafts) = \
            jax.lax.scan(step, (cur, pos, e_cache, d_cache), None,
                         length=k)
        return blobs, scales, zps, drafts, e_cache, d_cache

    def _spec_draft_sample_impl(self, k, edge_blocks, draft_blocks, embed,
                                tail, cur, e_cache, d_cache, pos, bt, temps,
                                top_ps, seeds, offsets):
        """Sampled draft scan: step i proposes a ``DRAFT``-stream draw
        from the local suffix's filtered distribution ``q`` at absolute
        output index ``offsets + i`` (greedy rows keep the argmax, on
        the same raw logits tensor so their tokens stay bit-identical).
        Also emits the stacked ``[k, B, V]`` f32 ``q`` rows the verify
        grades against — an extra uplink the engine prices per graded
        position (``costmodel.speculative_round_time(draft_q_bytes)``).
        """
        self.trace_counts["spec_draft"] += 1
        cfg = self.cfg
        rope = self._rope()

        def step(carry, i):
            tok, p, ec, dc = carry
            x = ML.embed(embed, tok[:, None]).astype(cfg.dtype)
            h, ec = TF.run_blocks(edge_blocks, x, cfg, rope=rope, cache=ec,
                                  cache_index=p, qctx=self._edge_qctx,
                                  block_tables=bt)
            blob, qp = self._quant_boundary(h)              # per row
            hq = dequantize(blob, qp).astype(cfg.dtype)  # what the cloud sees
            y, dc = TF.run_blocks(draft_blocks, hq, cfg, rope=rope, cache=dc,
                                  cache_index=p, qctx=self._edge_qctx,
                                  block_tables=bt)
            logits = TF.lm_head(tail, y)[:, 0]
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)
            q = S.filtered_probs(logits.astype(jnp.float32), temps, top_ps)
            draw = S.sample_rows(q, S.token_keys(seeds, offsets + i,
                                                 S.DRAFT))
            nxt = jnp.where(temps > 0.0, draw, greedy)
            p = jnp.minimum(p + 1, self.max_len - 1)
            return (nxt, p, ec, dc), (blob[:, 0], qp.scale, qp.zero_point,
                                      nxt, q)

        (_, _, e_cache, d_cache), (blobs, scales, zps, drafts, qs) = \
            jax.lax.scan(step, (cur, pos, e_cache, d_cache), jnp.arange(k))
        return blobs, scales, zps, drafts, qs, e_cache, d_cache

    def _draft_rebuild_impl(self, edge_blocks, draft_blocks, embed, toks,
                            d_cache, slots, bt_rows, plens):
        """Recompute the draft suffix K/V for live slots from committed
        prefix state: re-run the committed rows (prompt + committed
        tokens) through the edge prefix over a *throwaway* dense scratch
        cache — the real edge cache already holds these positions and
        must not be touched — then replay the boundary blob through the
        draft suffix exactly like a draft prefill.  Draft contents only
        steer the acceptance rate, never the committed stream, so the
        dense-scratch attention path is safe here."""
        self.trace_counts["draft_rebuild"] += 1
        cfg = self.cfg
        n, s = toks.shape
        x = ML.embed(embed, toks).astype(cfg.dtype)
        scratch = TF.init_cache(cfg, n, self.max_len, layers=self.n_edge,
                                quantized=self.edge_int8)
        h, _ = TF.run_blocks(edge_blocks, x, cfg, rope=self._rope(),
                             cache=scratch, cache_index=jnp.int32(0),
                             qctx=self._edge_qctx)
        ranged = jnp.where(jnp.arange(s)[None, :, None] <
                           plens[:, None, None], h, h[:, :1])
        blob, qp = self._quant_boundary(h, ranged)
        return self._draft_prefill_impl(draft_blocks, blob, qp, d_cache,
                                        slots, bt_rows, plens)

    def _rebuild_draft_caches(self) -> None:
        """Host driver for a warm k raise (satellite of the mesh PR):
        instead of draining the live slots — whose draft caches were
        never filled during k=1 rounds — rebuild each slot's draft K/V
        from its committed prefix (prompt + committed tokens minus the
        not-yet-processed last one), bucketing rows like admission so
        trace shapes stay bounded."""
        live = self._sched_active
        if not live:
            return
        if not hasattr(self, "_draft_rebuild"):
            self._draft_rebuild = _jit_phase(self._draft_rebuild_impl,
                                             donate=(4,),
                                             mesh=getattr(self, "mesh", None))
        slots = sorted(live)
        rows = []
        for s in slots:
            r, _c = live[s]
            committed = self._sched_committed(r)
            rows.append(np.concatenate([np.asarray(r.prompt, np.int32),
                                        committed[:-1].astype(np.int32)]))
        order = sorted(range(len(slots)), key=lambda i: len(rows[i]))
        i = 0
        while i < len(order):
            bucket = _bucket_len(len(rows[order[i]]), self.max_len)
            grp = [order[i]]
            i += 1
            while i < len(order) and _bucket_len(
                    len(rows[order[i]]), self.max_len) == bucket:
                grp.append(order[i])
                i += 1
            toks = np.zeros((len(grp), bucket), np.int32)
            for j, g in enumerate(grp):
                toks[j, :len(rows[g])] = rows[g]
            plens = np.asarray([len(rows[g]) for g in grp], np.int32)
            gslots = np.asarray([slots[g] for g in grp], np.int32)
            bt_rows = None
            if self._pool is not None:
                bt_rows = self._pool.rows(gslots, bucket)
            self._draft_cache = self._draft_rebuild(
                self.edge_blocks, self.draft_blocks, self.embed,
                jnp.asarray(toks), self._draft_cache, jnp.asarray(gslots),
                bt_rows, jnp.asarray(plens))
        self.stats.draft_rebuilds += 1

    # -- degradation phases (serve.resilience) ------------------------------
    def _edge_only_step_impl(self, edge_blocks, draft_blocks, embed, tail,
                             cur, e_cache, d_cache, pos, bt):
        """One full local step: INT8 prefix → Eq.(1) boundary → INT8
        suffix copy → greedy token.  Identical math to one unrolled
        ``_spec_draft_impl`` iteration — which is what makes edge-only
        tokens bit-identical to cloud tokens in the lossless mode — but
        also emits the dequantized f32 boundary row, which the resilient
        engine buffers for the resync replay, and the quantized
        ``(blob, qp)`` frame so a round that loses its uplink mid-flight
        can commit the already-computed step without re-running it."""
        self.trace_counts["edge_only"] += 1
        cfg = self.cfg
        rope = self._rope()
        x = ML.embed(embed, cur[:, None]).astype(cfg.dtype)
        h, e_cache = TF.run_blocks(edge_blocks, x, cfg, rope=rope,
                                   cache=e_cache, cache_index=pos,
                                   qctx=self._edge_qctx, block_tables=bt)
        blob, qp = self._quant_boundary(h)
        hq = dequantize(blob, qp)                 # Eq.(2): the cloud's view
        y, d_cache = TF.run_blocks(draft_blocks, hq.astype(cfg.dtype), cfg,
                                   rope=rope, cache=d_cache, cache_index=pos,
                                   qctx=self._edge_qctx, block_tables=bt)
        logits = TF.lm_head(tail, y)[:, 0]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        new_pos = jnp.minimum(pos + 1, self.max_len - 1)
        return blob, qp, hq[:, 0].astype(jnp.float32), nxt, e_cache, \
            d_cache, new_pos

    def _edge_only_prefill_impl(self, blocks, tail, blob, qp, cache, slots,
                                bt_rows, plens, cur, pos):
        """Admit a request with the cloud down: the draft suffix plays
        the cloud's role — same boundary blob, local lm_head — so the
        slot starts generating immediately with zero wire bytes."""
        cfg = self.cfg
        h = dequantize(blob, qp).astype(cfg.dtype)
        n = h.shape[0]
        group = _paged_prefill_view(cache, self.n_cloud, n, cfg.n_kv)
        y, group = TF.run_blocks(blocks, h, cfg, rope=self._rope(),
                                 cache=group, cache_index=jnp.int32(0),
                                 qctx=self._edge_qctx, block_tables=bt_rows,
                                 calibrate_kv=self.edge_int8,
                                 kv_lengths=plens)
        cache = _paged_prefill_merge(cache, group, slots)
        logits = TF.lm_head(tail, y[jnp.arange(n), plens - 1][:, None])[:, 0]
        cur = cur.at[slots].set(jnp.argmax(logits, -1).astype(jnp.int32))
        pos = pos.at[slots].set(plens)
        return cache, cur, pos

    def _edge_only_step_sample_impl(self, edge_blocks, draft_blocks, embed,
                                    tail, cur, e_cache, d_cache, pos, bt,
                                    temps, top_ps, seeds, offsets):
        """Sampled edge-only step: the committed token is a ``CLOUD``-
        stream draw from the draft suffix's filtered distribution — the
        *same* key the cloud's serial step would consume at this output
        index, so in the lossless mode (identical suffix logits) the
        degraded stream reproduces the cloud's sampled stream bitwise,
        and a post-resync replay can never fork it."""
        self.trace_counts["edge_only"] += 1
        cfg = self.cfg
        rope = self._rope()
        x = ML.embed(embed, cur[:, None]).astype(cfg.dtype)
        h, e_cache = TF.run_blocks(edge_blocks, x, cfg, rope=rope,
                                   cache=e_cache, cache_index=pos,
                                   qctx=self._edge_qctx, block_tables=bt)
        blob, qp = self._quant_boundary(h)
        hq = dequantize(blob, qp)                 # Eq.(2): the cloud's view
        y, d_cache = TF.run_blocks(draft_blocks, hq.astype(cfg.dtype), cfg,
                                   rope=rope, cache=d_cache, cache_index=pos,
                                   qctx=self._edge_qctx, block_tables=bt)
        logits = TF.lm_head(tail, y)[:, 0]
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)
        p = S.filtered_probs(logits.astype(jnp.float32), temps, top_ps)
        draw = S.sample_rows(p, S.token_keys(seeds, offsets, S.CLOUD))
        nxt = jnp.where(temps > 0.0, draw, greedy)
        new_pos = jnp.minimum(pos + 1, self.max_len - 1)
        return blob, qp, hq[:, 0].astype(jnp.float32), nxt, e_cache, \
            d_cache, new_pos

    def _edge_only_prefill_sample_impl(self, blocks, tail, blob, qp, cache,
                                       slots, bt_rows, plens, cur, pos,
                                       temps, top_ps, seeds):
        """Sampled twin of ``_edge_only_prefill_impl``: the first token
        (absolute output index 0) is the same ``CLOUD``-stream draw the
        cloud's own sampled prefill would commit."""
        cfg = self.cfg
        h = dequantize(blob, qp).astype(cfg.dtype)
        n = h.shape[0]
        group = _paged_prefill_view(cache, self.n_cloud, n, cfg.n_kv)
        y, group = TF.run_blocks(blocks, h, cfg, rope=self._rope(),
                                 cache=group, cache_index=jnp.int32(0),
                                 qctx=self._edge_qctx, block_tables=bt_rows,
                                 calibrate_kv=self.edge_int8,
                                 kv_lengths=plens)
        cache = _paged_prefill_merge(cache, group, slots)
        logits = TF.lm_head(tail, y[jnp.arange(n), plens - 1][:, None])[:, 0]
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)
        p = S.filtered_probs(logits.astype(jnp.float32), temps, top_ps)
        draw = S.sample_rows(p, S.token_keys(seeds, jnp.zeros_like(seeds),
                                             S.CLOUD))
        cur = cur.at[slots].set(jnp.where(temps > 0.0, draw, greedy))
        pos = pos.at[slots].set(plens)
        return cache, cur, pos

    def _resync_replay_impl(self, blocks, h, cache, pos, bt):
        """Rebuild the cloud suffix KV for slots that were live before
        the outage: one multi-token cached step over the ``[B, R, D]``
        buffered boundary rows at each slot's own resume position
        (vector ``cache_index`` — the verify's q-block form).  Slots not
        in the replay group ride along with a zeroed block-table row, so
        their (masked) writes land in the allocator's dump page."""
        self.trace_counts["resync"] += 1
        cfg = self.cfg
        _, cache = TF.run_blocks(blocks, h.astype(cfg.dtype), cfg,
                                 rope=self._rope(), cache=cache,
                                 cache_index=pos, block_tables=bt)
        return cache

    def _resync_prefill_impl(self, blocks, h, cache, slots, bt_rows, lens):
        """Rebuild the cloud suffix KV for slots *admitted during* the
        outage: prefill-style from position 0, calibrating the per-slot
        INT8 scales the cloud never got to compute (every buffered row
        is a real token — no bucket padding — so ``lens`` spans them
        all)."""
        self.trace_counts["resync"] += 1
        cfg = self.cfg
        n = h.shape[0]
        group = _paged_prefill_view(cache, self.n_cloud, n, cfg.n_kv)
        _, group = TF.run_blocks(blocks, h.astype(cfg.dtype), cfg,
                                 rope=self._rope(), cache=group,
                                 cache_index=jnp.int32(0),
                                 block_tables=bt_rows,
                                 calibrate_kv=self.cloud_int8,
                                 kv_lengths=lens)
        return _paged_prefill_merge(cache, group, slots)

    def _verify_impl(self, k, blocks, tail, blobs, scales, zps, drafts,
                     cache, pos, bt):
        """One batched multi-token cloud step over all k drafted
        positions, with longest-prefix acceptance: position i's greedy
        token ``t_i`` is compared against draft ``d_i``; the round
        commits ``t_1..t_{j+1}`` where j is the number of leading
        matches — the token at the first divergence is the *corrected*
        token, so every round commits at least one exact greedy token.
        Rejected cache positions are rolled back by the returned
        per-slot position (a length decrement; stale page entries stay
        masked by causality until overwritten)."""
        self.trace_counts["verify"] += 1
        cfg = self.cfg
        # Eq.(2) per (row, position): same lattice the serial path ships
        h = (blobs.astype(jnp.float32) - zps[..., None]) * scales[..., None]
        h = h.transpose(1, 0, 2).astype(cfg.dtype)              # [B, k, D]
        x, cache = TF.run_blocks(blocks, h, cfg, rope=self._rope(),
                                 cache=cache, cache_index=pos,
                                 block_tables=bt)
        logits = TF.lm_head(tail, x)                            # [B, k, V]
        t = jnp.argmax(logits, -1).astype(jnp.int32)            # [B, k]
        d = drafts.T                                            # [B, k]
        ok = (d[:, :k - 1] == t[:, :k - 1]).astype(jnp.int32)
        n_commit = 1 + jnp.sum(jnp.cumprod(ok, axis=1), axis=1)  # [B]
        new_cur = jnp.take_along_axis(t, (n_commit - 1)[:, None],
                                      axis=1)[:, 0]
        new_pos = jnp.minimum(pos + n_commit, self.max_len - 1)
        return t, n_commit, new_cur, cache, new_pos

    def _verify_sample_impl(self, k, blocks, tail, blobs, scales, zps,
                            drafts, qs, cache, pos, bt, temps, top_ps, seeds,
                            offsets):
        """Rejection-sampling verify: the same batched multi-token cloud
        step as ``_verify_impl``, graded by ``sampling.grade_and_correct``
        — sampled rows accept draft i with prob ``min(1, p_i(d)/q_i(d))``
        and correct from the normalized residual (bonus draw from ``p``
        if all graded drafts survive), greedy rows grade by argmax match
        and commit the identical tokens the greedy verify would.  The
        committed stream is distributionally exact vs serial cloud
        sampling (see ``serve.sampling``)."""
        self.trace_counts["verify"] += 1
        cfg = self.cfg
        h = (blobs.astype(jnp.float32) - zps[..., None]) * scales[..., None]
        h = h.transpose(1, 0, 2).astype(cfg.dtype)              # [B, k, D]
        x, cache = TF.run_blocks(blocks, h, cfg, rope=self._rope(),
                                 cache=cache, cache_index=pos,
                                 block_tables=bt)
        logits = TF.lm_head(tail, x)                            # [B, k, V]
        t = jnp.argmax(logits, -1).astype(jnp.int32)            # [B, k]
        d = drafts.T                                            # [B, k]
        B, _, V = logits.shape
        p = S.filtered_probs(logits.astype(jnp.float32).reshape(B * k, V),
                             jnp.repeat(temps, k),
                             jnp.repeat(top_ps, k)).reshape(B, k, V)
        q = qs.transpose(1, 0, 2)                               # [B, k, V]
        toks, n_commit = S.grade_and_correct(p, q, d, temps > 0.0, t,
                                             seeds, offsets)
        new_cur = jnp.take_along_axis(toks, (n_commit - 1)[:, None],
                                      axis=1)[:, 0]
        new_pos = jnp.minimum(pos + n_commit, self.max_len - 1)
        return toks, n_commit, new_cur, cache, new_pos

"""Fault-tolerant collaborative serving: degradation and resync.

``ResilientCollaborativeEngine`` is ``CollaborativeServingEngine`` with
the cloud allowed to *disappear*.  Three pieces compose:

* **Reliable transport** (``transport.ReliableTransport``) — every
  boundary message gets a sequence number, a telemetry-derived
  deadline, and a bounded retry budget with exponential backoff.  When
  a send exhausts its budget it raises ``CloudUnreachable`` — the
  engine's signal, not its crash.
* **Graceful degradation** — on that signal the engine declares the
  cloud down and keeps streaming *edge-only*: the ``_CutBank``'s INT8
  copy of the cloud-suffix weights (normally the speculative draft
  model) becomes the serving model.  Zero wire bytes per token; the
  committed tokens are counted in ``ServeStats.edge_only_tokens``.  In
  the lossless ``a_bits=None`` mode the draft suffix *is* the cloud
  suffix bit for bit, so the stream does not change — property-tested
  in ``tests/test_chaos_serve.py``.
* **Resync on reconnect** — while down, the engine buffers each live
  slot's dequantized f32 boundary rows (exactly what the cloud suffix
  would have consumed).  A periodic single-attempt probe detects
  recovery; the buffered rows then replay through the cloud suffix in
  one multi-token cached step per slot group (vector ``cache_index`` —
  the verify machinery's q-block form), rebuilding the cloud's paged KV
  to the committed stream, after which draft/verify rounds resume.

Protocol fine print, chosen so state never forks:

* The draft cache is kept **hot** even in serial (k=1) rounds — the
  edge runs its suffix copy alongside every uplink — so failover needs
  no warm-up and loses no round.  That is the standby's price:
  one local INT8 suffix step per token.
* A downlink lost *after* the cloud committed (a verify result or a
  prefill ack) keeps the result: sequence numbers make the eventual
  retransmit idempotent, and the cloud-side state is already the truth.
* An uplink lost *mid-round* commits the round's local drafts instead
  of dropping them — the boundary rows are already computed, so the
  failed round costs nothing but the wire it never got.
* The policy is suspended while down (a re-partition would invalidate
  the replay rows, which are boundary activations *at the current
  cut*), and probing replaces it between rounds.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.quant import dequantize
from repro.serve.engine import CollaborativeServingEngine
from repro.serve.kvcache import _cdiv
from repro.serve.scheduler import _jit_phase
from repro.serve.transport import (_MSG_BYTES, _QP_BYTES, _TOK_BYTES,
                                   CloudUnreachable, ReliableTransport)

__all__ = ["ResilientCollaborativeEngine"]


class ResilientCollaborativeEngine(CollaborativeServingEngine):
    """Collaborative serving that survives drops, stalls, and outages.

    Accepts every ``CollaborativeServingEngine`` argument plus:

    ``transport``     a ``ReliableTransport`` to use (default: one is
                      built around the given channel with default retry
                      budget/deadline parameters);
    ``probe_every``   while down, send one heartbeat probe every this
                      many scheduler turns (each failed probe costs one
                      deadline of simulated waiting — which is also what
                      advances a fault schedule's clock toward the end
                      of an outage window).

    Requires the paged layouts (the resync replay addresses cloud KV
    through the shared block table)."""

    def __init__(self, params, cfg, *, transport: Optional[
            ReliableTransport] = None, probe_every: int = 2, **kw):
        super().__init__(params, cfg, **kw)
        assert self.edge_paged and self.cloud_paged, \
            "resilient serving needs the paged KV layouts (resync " \
            "replays through the shared block table)"
        if transport is None:
            transport = ReliableTransport(self.transport.channel,
                                          self.transport.telemetry)
        self.transport = transport
        self.probe_every = max(1, int(probe_every))
        # edge-only serving rides the draft machinery; provision it even
        # for a spec_k=1 engine (the standby must exist before the fault)
        if self._spec_max == 1:
            self._spec_max = 2
            self._spec_jits = {}
            self._draft_prefill = _jit_phase(self._draft_prefill_impl,
                                             donate=(3,), mesh=self.mesh)
            self._set_cut(self.cut, count=False)
        self._edge_only_step = _jit_phase(self._edge_only_step_impl,
                                          donate=(5, 6), mesh=self.mesh)
        self._edge_only_admit = _jit_phase(self._edge_only_prefill_impl,
                                           donate=(4,), mesh=self.mesh)
        self._resync_replay = _jit_phase(self._resync_replay_impl,
                                         donate=(2,), mesh=self.mesh)
        self._resync_prefill = _jit_phase(self._resync_prefill_impl,
                                          donate=(2,), mesh=self.mesh)
        self.cloud_down = False
        self._down_since: Optional[float] = None
        self._rounds_down = 0
        self._live_slots: Set[int] = set()
        # slot -> [start position, list of [r, D] f32 boundary-row chunks]
        self._replay: Dict[int, List] = {}
        # per-round availability trace: (sim time, tokens, cloud state)
        self.round_log: List[dict] = []

    # -- outage state machine ------------------------------------------------
    def _enter_outage(self, pos) -> None:
        if self.cloud_down:
            return
        self.cloud_down = True
        self._rounds_down = 0
        self._down_since = getattr(self.channel, "clock_s", None)
        p = np.asarray(pos)
        # every live slot resumes cloud KV from its position at the loss
        self._replay = {s: [int(p[s]), []] for s in self._live_slots}

    def _policy_tick(self, n_active: int) -> bool:
        # while down the control loop is probe-and-resync: a cut switch
        # would invalidate the replay rows (boundary at the current cut)
        if self.cloud_down:
            self._rounds_down += 1
            if self._rounds_down % self.probe_every == 0:
                self._try_reconnect()
            return False
        return super()._policy_tick(n_active)

    def _try_reconnect(self) -> None:
        ok, _ = self.transport.probe(self.stats)
        if not ok:
            return
        try:
            self._resync()
        except CloudUnreachable:
            return      # relapsed mid-resync: buffers intact, stay down
        clock = getattr(self.channel, "clock_s", None)
        if clock is not None and self._down_since is not None:
            self.stats.outage_s += clock - self._down_since
        self.cloud_down = False
        self._down_since = None
        self._rounds_down = 0
        self._replay = {}
        self.stats.resyncs += 1

    def _resync(self) -> None:
        """Replay every live slot's buffered boundary rows through the
        cloud suffix, rebuilding its paged KV to the committed stream.
        Slots sharing a replay length run as one multi-token cached
        step; outage-admitted slots (start position 0) additionally
        calibrate the cloud's per-slot INT8 scales, prefill-style."""
        groups: Dict[Tuple[int, bool], List] = {}
        for s, (p0, chunks) in self._replay.items():
            if not chunks:
                continue
            rows = np.concatenate(chunks, axis=0)      # [R, D] f32
            groups.setdefault((rows.shape[0], p0 == 0), []).append(
                (s, p0, rows))
        itemsize = 1 if self.a_bits is not None else 4
        for (r_len, fresh), members in sorted(groups.items()):
            slots = np.asarray([s for s, _, _ in members], np.int32)
            # the wire carries the rows re-framed on the Eq.(1) lattice
            # (they are dequantized lattice points — requantization is
            # exact), one message per group; a loss here aborts the
            # resync and the engine stays down with its buffers
            self.transport.charge(
                self.stats,
                len(members) * r_len * (self.cfg.d_model * itemsize
                                        + _QP_BYTES) + _MSG_BYTES,
                phase="decode", log=False)
            if fresh:
                w = max(1, _cdiv(r_len, self.page_size))
                bt_rows = jnp.array(self._pool.bt[slots][:, :w], copy=True)
                h = jnp.asarray(np.stack([r for _, _, r in members]))
                self._cloud_cache = self._resync_prefill(
                    self.cloud_blocks, h, self._cloud_cache,
                    jnp.asarray(slots), bt_rows,
                    jnp.full((len(members),), r_len, jnp.int32))
            else:
                hb = np.zeros((self.max_batch, r_len, self.cfg.d_model),
                              np.float32)
                posb = np.zeros((self.max_batch,), np.int32)
                bt = np.zeros_like(self._pool.bt)
                need = 1
                for s, p0, rows in members:
                    hb[s], posb[s] = rows, p0
                    bt[s] = self._pool.bt[s]
                    need = max(need, _cdiv(p0 + r_len, self.page_size))
                w = 1
                while w < need:
                    w *= 2
                w = min(w, self._pool.pages_per_slot)
                self._cloud_cache = self._resync_replay(
                    self.cloud_blocks, jnp.asarray(hb), self._cloud_cache,
                    jnp.asarray(posb), jnp.array(bt[:, :w], copy=True))

    # -- scheduler hooks, fault-aware ---------------------------------------
    def _round_width(self):
        # edge-only rounds are serial regardless of spec_k
        return 1 if (self.cloud_down or self.spec_k == 1) else self.spec_k

    def _edge_step(self, cur, pos, bt, slots):
        """One local step of the hot standby — sampled slots draw their
        token from the ``CLOUD`` stream on the draft suffix's filtered
        distribution (``serve.spec``), so a lossless edge-only stream is
        bitwise the cloud's serial sampled stream."""
        if (self._samp_t[slots] > 0).any():
            temps, top_ps, seeds = self._samp_vecs()
            fn = self._samp_jit("edge_only_step",
                                self._edge_only_step_sample_impl,
                                donate=(5, 6), mesh=self.mesh)
            return fn(self.edge_blocks, self.draft_blocks, self.embed,
                      self.tail, cur, self._edge_cache, self._draft_cache,
                      pos, bt, temps, top_ps, seeds, self._offsets())
        return self._edge_only_step(self.edge_blocks, self.draft_blocks,
                                    self.embed, self.tail, cur,
                                    self._edge_cache, self._draft_cache,
                                    pos, bt)

    def _admit(self, toks, plens, max_news, slots, cur, pos, samplings=None):
        self._note_samplings(slots, samplings)
        bt_rows = self._pool.admit(slots, plens,
                                   self._admit_reserve(max_news),
                                   toks.shape[1])
        slots_j, plens_j = jnp.asarray(slots), jnp.asarray(plens)
        blob, qp, self._edge_cache = self._edge_prefill(
            self.edge_blocks, self.embed, toks, self._edge_cache, slots_j,
            bt_rows, plens_j)
        if not self.cloud_down:
            try:
                self.transport.account_blob(
                    self.stats, blob, phase="prefill",
                    row_elems=plens.astype(np.int64) * self.cfg.d_model)
                if (self._samp_t[slots] > 0).any():
                    fn = self._samp_jit("cloud_prefill",
                                        self._cloud_prefill_sample_impl,
                                        donate=(4,), mesh=self.mesh)
                    self._cloud_cache, cur, pos = fn(
                        self.cloud_blocks, self.tail, blob, qp,
                        self._cloud_cache, slots_j, bt_rows, cur, pos,
                        plens_j, jnp.asarray(self._samp_t[slots]),
                        jnp.asarray(self._samp_p[slots]),
                        jnp.asarray(self._samp_s[slots]))
                else:
                    self._cloud_cache, cur, pos = self._cloud_prefill(
                        self.cloud_blocks, self.tail, blob, qp,
                        self._cloud_cache, slots_j, bt_rows, cur, pos,
                        plens_j)
                # the standby drafts regardless of the current spec_k
                self._draft_cache = self._draft_prefill(
                    self.draft_blocks, blob, qp, self._draft_cache, slots_j,
                    bt_rows, plens_j)
                self._live_slots.update(int(s) for s in slots)
                try:
                    self.transport.account_downlink(self.stats,
                                                    toks.shape[0],
                                                    phase="prefill")
                except CloudUnreachable:
                    # cloud committed the prefill; only the ack is lost —
                    # the seq-numbered retransmit is idempotent, keep it
                    self._enter_outage(pos)
                return cur, pos
            except CloudUnreachable:
                self._enter_outage(pos)
        # cloud down: the draft suffix serves the admission alone
        if (self._samp_t[slots] > 0).any():
            fn = self._samp_jit("edge_only_admit",
                                self._edge_only_prefill_sample_impl,
                                donate=(4,), mesh=self.mesh)
            self._draft_cache, cur, pos = fn(
                self.draft_blocks, self.tail, blob, qp, self._draft_cache,
                slots_j, bt_rows, plens_j, cur, pos,
                jnp.asarray(self._samp_t[slots]),
                jnp.asarray(self._samp_p[slots]),
                jnp.asarray(self._samp_s[slots]))
        else:
            self._draft_cache, cur, pos = self._edge_only_admit(
                self.draft_blocks, self.tail, blob, qp, self._draft_cache,
                slots_j, bt_rows, plens_j, cur, pos)
        rows = np.asarray(dequantize(blob, qp), np.float32)
        for i, s in enumerate(slots):
            self._replay[int(s)] = [0, [rows[i, :int(plens[i])]]]
        self._live_slots.update(int(s) for s in slots)
        self.stats.edge_only_tokens += len(slots)
        return cur, pos

    def _round(self, cur, pos, slots):
        if self.cloud_down:
            return self._edge_only_round(cur, pos, slots)
        if self.spec_k == 1:
            return self._serial_round(cur, pos, slots)
        return self._spec_round(cur, pos, slots)

    def _serial_round(self, cur, pos, slots):
        n_active = len(slots)
        bt = self._pool.table_dev()
        sampled = bool((self._samp_t[slots] > 0).any())
        # the edge half also advances the draft suffix — the hot standby
        blob, qp, hq, nxt, self._edge_cache, self._draft_cache, pos_e = \
            self._edge_step(cur, pos, bt, slots)
        try:
            self.transport.account_blob(self.stats, blob, phase="decode",
                                        rows=n_active)
        except CloudUnreachable:
            self._enter_outage(pos)
            return self._commit_local(nxt, pos_e, hq, slots)
        if sampled:
            temps, top_ps, seeds = self._samp_vecs()
            fn = self._samp_jit("cloud_decode",
                                self._cloud_decode_sample_impl,
                                donate=(4,), mesh=self.mesh)
            cur, self._cloud_cache, pos = fn(
                self.cloud_blocks, self.tail, blob, qp, self._cloud_cache,
                pos, bt, temps, top_ps, seeds, self._offsets())
        else:
            cur, self._cloud_cache, pos = self._cloud_decode(
                self.cloud_blocks, self.tail, blob, qp, self._cloud_cache,
                pos, bt)
        try:
            self.transport.account_downlink(self.stats, n_active)
        except CloudUnreachable:
            self._enter_outage(pos)   # committed cloud-side: keep the token
        return cur, pos, cur[:, None], None

    def _spec_round(self, cur, pos, slots):
        k, n_active = self.spec_k, len(slots)
        bt = self._pool.table_dev()
        sampled = bool((self._samp_t[slots] > 0).any())
        if sampled:
            temps, top_ps, seeds = self._samp_vecs()
            offs = self._offsets()
            draft_fn, verify_fn = self._spec_sample_fns(k)
            (blobs, scales, zps, drafts, qs, self._edge_cache,
             self._draft_cache) = draft_fn(
                self.edge_blocks, self.draft_blocks, self.embed, self.tail,
                cur, self._edge_cache, self._draft_cache, pos, bt, temps,
                top_ps, seeds, offs)
        else:
            draft_fn, verify_fn = self._spec_fns(k)
            (blobs, scales, zps, drafts, self._edge_cache,
             self._draft_cache) = draft_fn(
                self.edge_blocks, self.draft_blocks, self.embed, self.tail,
                cur, self._edge_cache, self._draft_cache, pos, bt)
        n_samp = int((self._samp_t[slots] > 0).sum())
        try:
            self.transport.charge(
                self.stats,
                n_active * (k * (self.cfg.d_model * blobs.dtype.itemsize
                                 + _QP_BYTES)
                            + (k - 1) * _TOK_BYTES) + _MSG_BYTES
                + n_samp * (k - 1) * self.cfg.vocab * 4,
                phase="decode")
        except CloudUnreachable:
            # the round's drafts are computed and locally consistent —
            # commit all k instead of wasting the round.  Sampled rows
            # commit their DRAFT-stream draws: in the lossless mode the
            # draft distribution *is* the cloud distribution, so the
            # committed tokens stay distributionally exact (the stream
            # itself is the documented chunking caveat, serve.sampling)
            self._enter_outage(pos)
            h = (np.asarray(blobs, np.float32)
                 - np.asarray(zps, np.float32)[..., None]) \
                * np.asarray(scales, np.float32)[..., None]   # [k, B, D]
            for s in slots:
                self._replay[int(s)][1].append(h[:, int(s), :])
            self.stats.edge_only_tokens += k * n_active
            counts = np.full((self.max_batch,), k, np.int64)
            return drafts[-1], jnp.minimum(pos + k, self.max_len - 1), \
                jnp.transpose(drafts), counts
        if sampled:
            toks, n_commit, cur, self._cloud_cache, pos = verify_fn(
                self.cloud_blocks, self.tail, blobs, scales, zps, drafts,
                qs, self._cloud_cache, pos, bt, temps, top_ps, seeds, offs)
        else:
            toks, n_commit, cur, self._cloud_cache, pos = verify_fn(
                self.cloud_blocks, self.tail, blobs, scales, zps, drafts,
                self._cloud_cache, pos, bt)
        counts = np.asarray(n_commit)
        try:
            self.transport.account_downlink(self.stats, n_active, k=k)
        except CloudUnreachable:
            self._enter_outage(pos)   # verify committed: keep its result
        self.stats.spec_rounds += 1
        hits = int(np.minimum(counts[slots] - 1, k - 1).sum())
        self.stats.drafted_tokens += (k - 1) * n_active
        self.stats.draft_hits += hits
        self.telemetry.observe_round((k - 1) * n_active, hits)
        return cur, pos, toks, counts

    def _edge_only_round(self, cur, pos, slots):
        bt = self._pool.table_dev()
        _, _, hq, nxt, self._edge_cache, self._draft_cache, pos = \
            self._edge_step(cur, pos, bt, slots)
        return self._commit_local(nxt, pos, hq, slots)

    def _commit_local(self, nxt, pos, hq, slots):
        rows = np.asarray(hq, np.float32)                    # [B, D]
        for s in slots:
            self._replay[int(s)][1].append(rows[int(s)][None, :])
        self.stats.edge_only_tokens += len(slots)
        return nxt, pos, nxt[:, None], None

    def _retire(self, slot):
        super()._retire(slot)
        self._live_slots.discard(int(slot))
        # a request finished on edge-only tokens owes the cloud nothing
        self._replay.pop(int(slot), None)

    def _after_round(self, n_active: int, committed: int) -> None:
        self.round_log.append({
            "t_s": float(getattr(self.channel, "clock_s", 0.0)),
            "committed": committed,
            "cloud_down": self.cloud_down,
        })

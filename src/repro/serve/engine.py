"""Batched LM serving with KV caches + collaborative (cloud-edge) mode —
the deployment side of the paper, composed from the ``serve`` package:

* ``serve.scheduler`` — the slot/bucket/round continuous-batching loop
  (``_SlotEngine``) both engines ride, including the re-partition
  barrier of the online control loop;
* ``serve.kvcache``   — page-pool bookkeeping for the paged INT8 KV
  layouts (``PageAllocator``/``_PagedPool``);
* ``serve.transport`` — channel framing + wire accounting
  (``ServeStats``) and the EWMA link telemetry;
* ``serve.cloud``     — the cloud-only baseline ``ServingEngine``;
* ``serve.spec``      — the speculative draft/verify round machinery
  (wire protocol documented there);
* ``serve.policy``    — the telemetry → costmodel/autotune → engine
  re-tuning policy (``AdaptivePolicy``) + ``DeadlineAdmission``;
* ``serve.overload``  — the overload-robustness hooks (demand paging,
  pressure faults, deadline admission) mixed in ahead of the
  scheduler's defaults.

``CollaborativeServingEngine`` is the paper's mode rebuilt around
*incremental decode*: the INT8 edge prefix (first ``cut_layer+1``
blocks, fake-quant lattice == the Pallas int8 kernel's math) and the
FP32 cloud suffix each own a KV cache covering only their block
sub-range, defaulting to the paged INT8 layout over **one shared block
table**.  Each decode round ships a per-row-quantized ``[B, 1, D]``
boundary delta (Eq.1/2) uplink and the sampled token downlink; with
``spec_k = k > 1`` the serial loop restructures into the draft/verify
rounds of ``serve.spec``.  A ``policy.AdaptivePolicy`` closes the
auto-tuning loop online: ``spec_k`` switches between rounds, the cut
layer switches at request-admission boundaries out of the prequantized
``_CutBank`` (pointer swap, never a requantization), and ``a_bits=None``
runs the boundary lossless so fp re-partitions are output-transparent
(property-tested in ``tests/test_adaptive_serve.py``).

This module re-exports the package's public surface, so the historical
``from repro.serve.engine import X`` keeps working.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import CLOUD_TITANXP_CLASS, Channel
from repro.models import layers as ML
from repro.models import transformer as TF
from repro.serve import trace
# re-export shims: the pre-split monolith lived at repro.serve.engine and
# external code imports these names from here
from repro.serve.cloud import ServingEngine
from repro.serve.kvcache import (PageAllocator, PoolExhausted, _cdiv,
                                 _PagedPool, _paged_prefill_merge,
                                 _paged_prefill_view, page_visits)
from repro.serve.policy import (AdaptivePolicy, DeadlineAdmission, Decision,
                                _CutBank)
from repro.serve.scheduler import (Request, _bucket_len, _jit_phase,
                                   _SlotEngine)
from repro.serve.faults import FaultyChannel, PressureSchedule
from repro.serve.overload import _OverloadMixin
from repro.serve.phases import _SplitPhases
from repro.serve.sampling import SamplingParams
from repro.serve.seedpath import _SeedPathMixin
from repro.serve.sharding import place_collab_engine, tp_size
from repro.serve.spec import _SpecDraftMixin
from repro.serve.transport import (_MSG_BYTES, _QP_BYTES, _TOK_BYTES,
                                   CloudUnreachable, DriftingChannel,
                                   LinkTelemetry, ReliableTransport,
                                   ServeStats, Transport)

Params = Any

__all__ = ["ServingEngine", "CollaborativeServingEngine", "PageAllocator",
           "PoolExhausted", "ServeStats", "Request", "SamplingParams",
           "Transport", "LinkTelemetry", "DriftingChannel", "AdaptivePolicy",
           "DeadlineAdmission", "Decision", "FaultyChannel",
           "PressureSchedule", "ReliableTransport", "CloudUnreachable",
           "_MSG_BYTES", "_QP_BYTES", "_TOK_BYTES"]


class CollaborativeServingEngine(_SpecDraftMixin, _SeedPathMixin,
                                 _OverloadMixin, _SplitPhases, _SlotEngine):
    """Paper mode with incremental decode over split, shared-table paged
    INT8 KV caches (see the module docstring), plus the online tuning
    loop.

    ``edge_paged=False`` / ``edge_int8=False`` / ``cloud_paged=False`` /
    ``cloud_int8=False`` fall back to the dense / fp layouts (the
    PR-1-era configuration, kept as the equivalence oracle in tests).

    ``spec_k > 1`` turns each decode step into a speculative
    draft/verify round; ``spec_k=1`` (default) is PR 1's serial step,
    bit for bit.  ``spec_k="auto"`` asks ``autotune.tune_spec_k`` for
    the starting round length *and* keeps it self-correcting: the
    engine's measured ``acceptance_rate()`` feeds back into the tuner
    between requests, replacing the ``spec_acceptance`` prior.

    ``policy="auto"`` (or an explicit ``AdaptivePolicy``) closes the
    full loop: link telemetry re-tunes both ``spec_k`` (between rounds)
    and ``cut_layer`` (at request-admission boundaries, via the
    re-partition barrier + ``_CutBank``).  ``candidate_cuts`` overrides
    the default cut grid {0, mid, last-1} ∪ {cut_layer}.  Every k switch
    is immediate between rounds: raising out of k=1 with live requests
    — whose draft caches were never filled, k=1 rounds being the cheap
    serial step — rebuilds their draft K/V from committed prefix state
    (``serve.spec._rebuild_draft_caches``) instead of paying the drain
    barrier; only cut switches still drain.

    ``mesh`` places the engine on a ``("data", "model")`` device mesh
    (``launch.mesh.make_serve_mesh``): the cloud suffix weights and
    paged KV pool shard tensor-parallel over ``model`` while everything
    edge-side replicates, and every phase runs as a mesh-jitted
    computation (``serve.sharding``; the paged kernels, edge-side too,
    then run ``shard_map``'d, which a Mosaic call needs on a mesh); the
    auto policy prices the mesh via a TP-scaled cloud device model."""

    def __init__(self, params: Params, cfg: TF.LMConfig, *, cut_layer: int,
                 channel: Optional[Channel] = None, max_len: int = 128,
                 a_bits: Optional[int] = 8, max_batch: int = 4,
                 edge_paged: bool = True, edge_int8: bool = True,
                 cloud_paged: bool = True, cloud_int8: bool = True,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 spec_k: Union[int, str] = 1, spec_acceptance: float = 0.8,
                 policy: Union[AdaptivePolicy, str, None] = None,
                 candidate_cuts: Optional[Tuple[int, ...]] = None,
                 demand_paged: bool = False,
                 pressure: Optional[PressureSchedule] = None,
                 admission: Union[DeadlineAdmission, str, None] = None,
                 mesh: Optional[jax.sharding.Mesh] = None):
        assert 0 <= cut_layer < cfg.n_layers, \
            f"cut_layer {cut_layer} outside [0, {cfg.n_layers})"
        super().__init__(cfg, max_batch=max_batch, max_len=max_len)
        self.mesh = mesh
        self.cut = cut_layer
        self.transport = Transport(channel)
        self.a_bits = a_bits
        self.edge_paged = edge_paged
        self.edge_int8 = edge_int8
        self.cloud_paged = cloud_paged
        self.cloud_int8 = cloud_int8
        self.page_size = page_size
        # the channel the offline tuners assume before telemetry locks on
        # (a DriftingChannel contributes its t=0 phase — the site survey)
        initial_ch = self.transport.channel
        initial_ch = getattr(initial_ch, "phase", initial_ch)

        spec_auto = spec_k == "auto"
        if spec_auto:
            from repro.core.autotune import spec_k_for_lm
            spec_k = spec_k_for_lm(cfg, cut_layer, batch=max_batch,
                                   channel=initial_ch,
                                   acceptance=spec_acceptance)[0].k
        assert isinstance(spec_k, int) and spec_k >= 1, spec_k
        self.spec_k = spec_k

        # -- control plane ---------------------------------------------------
        if policy == "auto":
            assert cut_layer <= cfg.n_layers - 2, \
                "the adaptive policy needs at least one cloud block at " \
                "every candidate cut"
            cuts = candidate_cuts or tuple(sorted(
                {0, (cfg.n_layers - 1) // 2, cfg.n_layers - 2, cut_layer}))
            # a TP mesh scales the cloud term of the policy's cost grid:
            # FLOPs/device (+ the per-layer all-reduce when link_bw > 0),
            # so a bigger mesh discovers its own edge-ward optimal cut
            policy = AdaptivePolicy(cfg, batch=max_batch, cuts=cuts,
                                    ks=(1, 2, 4, 8),
                                    cloud=CLOUD_TITANXP_CLASS.scaled(
                                        tp_size(mesh)),
                                    fallback_channel=initial_ch,
                                    acceptance_prior=spec_acceptance)
        elif policy is None and spec_auto:
            # spec_k="auto" alone: k-only self-correction between requests
            policy = AdaptivePolicy(cfg, batch=max_batch, cuts=None,
                                    ks=(1, 2, 4, 8, 16),
                                    fallback_channel=initial_ch,
                                    acceptance_prior=spec_acceptance,
                                    k_between_requests_only=True)
        self.policy: Optional[AdaptivePolicy] = policy or None
        if self.policy is not None and self.policy.cuts is not None:
            assert cut_layer in self.policy.cuts, \
                f"cut_layer {cut_layer} not in candidate cuts " \
                f"{self.policy.cuts}"
        # largest k any controller may pick — draft machinery and page
        # headroom are provisioned for it once, up front
        self._spec_max = self.spec_k if self.policy is None \
            else max(self.spec_k, *self.policy.ks)

        self.embed = params["embed"]
        self.tail = {"final_norm": params["final_norm"],
                     "lm_head": params["lm_head"]}
        # edge weights are INT8-quantized at deployment: the bank bakes
        # the fake-quant lattice into the stored params once
        # (quantize_weights=False at runtime — same math, no per-step
        # weight requantization); a_bits=None serves the edge lossless
        # act_axis=0: per-slot activation ranges — a shared-batch range
        # would couple each request's Eq.(1) lattice to its neighbours'
        # (and to stale values in idle slots), making decode output
        # depend on batch composition and slot-reuse history
        self._edge_qctx = None if a_bits is None else \
            ML.QuantCtx(mode="dynamic", a_bits=a_bits,
                        quantize_weights=False, act_axis=0)
        deploy_qctx = None if a_bits is None else \
            ML.QuantCtx(mode="dynamic", a_bits=a_bits)
        # one shared page pool / block table for every split cache; its
        # geometry is cut-independent, so it survives re-partitions
        self._pool: Optional[_PagedPool] = None
        if edge_paged or cloud_paged:
            self._pool = _PagedPool.build(max_batch, max_len, page_size,
                                          num_pages)
        # overload robustness (demand paging / pressure faults / deadline
        # admission) — hook implementations live in serve.overload
        self._init_overload(cfg, demand_paged=demand_paged,
                            pressure=pressure, admission=admission,
                            max_batch=max_batch, initial_ch=initial_ch,
                            spec_acceptance=spec_acceptance, a_bits=a_bits)
        # every cut the engine may ever serve goes into the bank up front
        # (policy candidates, or explicit candidate_cuts for externally
        # scripted re-partitions)
        bank_cuts = {cut_layer} | set(candidate_cuts or ())
        if self.policy is not None and self.policy.cuts is not None:
            bank_cuts |= set(self.policy.cuts)
        self._bank = _CutBank(params, cfg, bank_cuts, deploy_qctx)
        self._set_cut(cut_layer, count=False)

        self._edge = jax.jit(self._edge_impl)
        self._cloud = jax.jit(self._cloud_impl)
        self._edge_prefill = _jit_phase(self._edge_prefill_impl, donate=(3,),
                                        mesh=mesh)
        self._cloud_prefill = _jit_phase(self._cloud_prefill_impl,
                                         donate=(4,), mesh=mesh)
        self._edge_decode = _jit_phase(self._edge_decode_impl, donate=(3,),
                                       mesh=mesh)
        self._cloud_decode = _jit_phase(self._cloud_decode_impl, donate=(4,),
                                        mesh=mesh)
        if self._spec_max > 1:
            self._draft_prefill = _jit_phase(self._draft_prefill_impl,
                                             donate=(3,), mesh=mesh)
            # per-k jitted draft/verify (k is the scan length / q-block
            # width, a trace constant); built on first use of each k
            self._spec_jits: Dict[int, Tuple[Any, Any]] = {}
        # per-slot sampling state (serve.sampling): host mirrors of each
        # slot's (temperature, top_p, seed), refreshed at admission; the
        # device copies are cached until the slot mix changes.  Jitted
        # sampled phases are built lazily — all-greedy traffic never
        # traces them and runs the original phases untouched.
        self._samp_t = np.zeros((max_batch,), np.float32)
        self._samp_p = np.ones((max_batch,), np.float32)
        self._samp_s = np.zeros((max_batch,), np.int32)
        self._samp_dev: Optional[Tuple[jax.Array, ...]] = None
        self._samp_jits: Dict[str, Any] = {}

    # -- wire plumbing -------------------------------------------------------
    @property
    def channel(self):
        return self.transport.channel

    @channel.setter
    def channel(self, ch) -> None:
        self.transport.channel = ch

    @property
    def telemetry(self) -> LinkTelemetry:
        return self.transport.telemetry

    # -- online re-tuning ----------------------------------------------------
    def _set_cut(self, cut: int, *, count: bool = True) -> None:
        """Re-partition at ``cut`` — only ever called with no occupied
        slots (construction, or the scheduler's drained admission
        boundary).  Weights come out of the bank (pointer swap); the
        split caches are re-initialized for the new layer sub-ranges
        (their contents belong to retired requests); the page pool,
        block table, telemetry, and jitted phase callables all carry
        over (jax re-traces per layer-count automatically and caches
        each cut's traces, so flapping between two cuts compiles each
        side once)."""
        cfg = self.cfg
        self.cut = cut
        self.n_edge = cut + 1
        self.n_cloud = cfg.n_layers - self.n_edge
        self.edge_blocks, self.cloud_blocks, self.draft_blocks = \
            self._bank.get(cut)
        n_pool = self._pool.allocator.num_pages if self._pool else None
        if self.edge_paged:
            self._edge_cache = TF.init_cache(
                cfg, self.max_batch, self.max_len, layers=self.n_edge,
                paged=True, quantized=self.edge_int8,
                page_size=self.page_size, num_pages=n_pool)
        else:
            self._edge_cache = TF.init_cache(cfg, self.max_batch,
                                             self.max_len,
                                             layers=self.n_edge,
                                             quantized=self.edge_int8)
        if self.cloud_paged:
            self._cloud_cache = TF.init_cache(
                cfg, self.max_batch, self.max_len, layers=self.n_cloud,
                paged=True, quantized=self.cloud_int8,
                page_size=self.page_size, num_pages=n_pool)
        else:
            self._cloud_cache = TF.init_cache(cfg, self.max_batch,
                                              self.max_len,
                                              layers=self.n_cloud)
        if self._spec_max > 1:
            # the edge's draft model: the bank's INT8 copy of the
            # cloud-suffix weights, over a draft cache in the edge's
            # layout sharing the edge block table
            if self.edge_paged:
                self._draft_cache = TF.init_cache(
                    cfg, self.max_batch, self.max_len, layers=self.n_cloud,
                    paged=True, quantized=self.edge_int8,
                    page_size=self.page_size, num_pages=n_pool)
            else:
                self._draft_cache = TF.init_cache(
                    cfg, self.max_batch, self.max_len, layers=self.n_cloud,
                    quantized=self.edge_int8)
        if self.mesh is not None:
            # TP-shard the cloud half, replicate the edge half — one
            # placement pass per (re-)partition (serve.sharding)
            place_collab_engine(self)
        if count:
            self.stats.cut_switches += 1

    def _policy_tick(self, n_active: int) -> bool:
        if self.policy is None:
            return False
        live = self._sched_active or {}
        frac = (sum(1.0 for s in live if self._samp_t[s] > 0) / len(live)
                if live else 0.0)
        # kwarg only when sampled traffic exists: duck-typed policies
        # predating sampling keep working on greedy workloads
        kw = {"sampled_frac": frac} if frac > 0.0 else {}
        d = self.policy.decide(self.telemetry, cut=self.cut,
                               spec_k=self.spec_k, **kw)
        if d.spec_k != self.spec_k:
            if self.policy.k_between_requests_only and n_active > 0:
                pass                 # defer to the next drained tick
            else:
                if d.spec_k > 1 and self.spec_k == 1 and n_active > 0:
                    # k=1 rounds run the cheap serial step and leave the
                    # draft cache stale for the *live* slots: rebuild it
                    # from their committed prefix state instead of
                    # paying the old drain barrier (serve.spec)
                    self._rebuild_draft_caches()
                self.spec_k = d.spec_k
                self.stats.spec_k_switches += 1
        if d.cut != self.cut:
            if n_active:
                self.stats.policy_holds += 1
                return True          # re-partition barrier: drain first
            self._set_cut(d.cut)
        return False

    def _round_headroom(self) -> int:
        return self._spec_max - 1

    # boundary lattice + split-cache phase impls: _SplitPhases (shared
    # with the per-cut runtimes of serve.fleet)

    # -- sampling plumbing (serve.sampling) ---------------------------------
    def _note_samplings(self, slots, samplings) -> None:
        """Refresh the per-slot sampling mirrors at admission (a greedy
        or ``None`` request zeroes its slot, so slot reuse can never
        leak a previous tenant's temperature)."""
        for i, s in enumerate(slots):
            sp = None if samplings is None else samplings[i]
            sp = sp if (sp is not None and sp.sampled) else None
            self._samp_t[s] = sp.temperature if sp else 0.0
            self._samp_p[s] = sp.top_p if sp else 1.0
            self._samp_s[s] = sp.seed if sp else 0
        self._samp_dev = None

    def _samp_vecs(self) -> Tuple[jax.Array, ...]:
        if self._samp_dev is None:
            self._samp_dev = (jnp.asarray(self._samp_t),
                              jnp.asarray(self._samp_p),
                              jnp.asarray(self._samp_s))
        return self._samp_dev

    def _offsets(self) -> jax.Array:
        """[max_batch] absolute output index each live slot's next round
        starts at (its committed count) — what pins every sampled draw's
        key to (seed, index, stream) across preemption/replay."""
        off = np.zeros((self.max_batch,), np.int32)
        for s, (_r, c) in (self._sched_active or {}).items():
            off[s] = c
        return jnp.asarray(off)

    def _samp_jit(self, name: str, impl, donate=(), mesh=None):
        if name not in self._samp_jits:
            self._samp_jits[name] = _jit_phase(impl, donate=donate,
                                               mesh=mesh)
        return self._samp_jits[name]

    # -- scheduler hooks ----------------------------------------------------
    def _admit(self, toks, plens, max_news, slots, cur, pos, samplings=None):
        self._note_samplings(slots, samplings)
        bt_rows = None
        if self._pool is not None:
            bt_rows = self._pool.admit(slots, plens,
                                       self._admit_reserve(max_news),
                                       toks.shape[1])
        slots_j = jnp.asarray(slots)
        plens_j = jnp.asarray(plens)
        blob, qp, self._edge_cache = self._edge_prefill(
            self.edge_blocks, self.embed, toks, self._edge_cache, slots_j,
            bt_rows, plens_j)
        self.transport.account_blob(
            self.stats, blob, phase="prefill",
            row_elems=plens.astype(np.int64) * self.cfg.d_model)
        if (self._samp_t[slots] > 0).any():
            fn = self._samp_jit("cloud_prefill",
                                self._cloud_prefill_sample_impl,
                                donate=(4,), mesh=self.mesh)
            self._cloud_cache, cur, pos = fn(
                self.cloud_blocks, self.tail, blob, qp, self._cloud_cache,
                slots_j, bt_rows, cur, pos, plens_j,
                jnp.asarray(self._samp_t[slots]),
                jnp.asarray(self._samp_p[slots]),
                jnp.asarray(self._samp_s[slots]))
        else:
            self._cloud_cache, cur, pos = self._cloud_prefill(
                self.cloud_blocks, self.tail, blob, qp, self._cloud_cache,
                slots_j, bt_rows, cur, pos, plens_j)
        if self._spec_max > 1 and self.spec_k > 1:
            # requests served at k=1 never draft (and a later raise
            # drains them first — see _policy_tick), so the draft
            # prefill is pure overhead unless the engine is drafting now
            self._draft_cache = self._draft_prefill(
                self.draft_blocks, blob, qp, self._draft_cache, slots_j,
                bt_rows, plens_j)
        self.transport.account_downlink(self.stats, toks.shape[0],
                                        phase="prefill")
        return cur, pos

    def _decode_all(self, cur, pos, n_active):
        bt = self._pool.table_dev() if self._pool is not None else None
        blob, qp, self._edge_cache = self._edge_decode(
            self.edge_blocks, self.embed, cur, self._edge_cache, pos, bt)
        self.transport.account_blob(self.stats, blob, phase="decode",
                                    rows=n_active)
        cur, self._cloud_cache, pos = self._cloud_decode(
            self.cloud_blocks, self.tail, blob, qp, self._cloud_cache, pos,
            bt)
        self.transport.account_downlink(self.stats, n_active)
        return cur, pos

    def _decode_all_sample(self, cur, pos, n_active):
        """Serial (k=1) step with a sampled slot aboard: identical edge
        pass and wire bytes, the committed token is the ``CLOUD``-stream
        draw (greedy rows keep their argmax, bit for bit)."""
        bt = self._pool.table_dev() if self._pool is not None else None
        blob, qp, self._edge_cache = self._edge_decode(
            self.edge_blocks, self.embed, cur, self._edge_cache, pos, bt)
        self.transport.account_blob(self.stats, blob, phase="decode",
                                    rows=n_active)
        temps, top_ps, seeds = self._samp_vecs()
        fn = self._samp_jit("cloud_decode", self._cloud_decode_sample_impl,
                            donate=(4,), mesh=self.mesh)
        cur, self._cloud_cache, pos = fn(
            self.cloud_blocks, self.tail, blob, qp, self._cloud_cache, pos,
            bt, temps, top_ps, seeds, self._offsets())
        self.transport.account_downlink(self.stats, n_active)
        return cur, pos

    def _round(self, cur, pos, slots):
        sampled = bool((self._samp_t[slots] > 0).any())
        # k=1 is the fully-async serial step (PR 1's path, bit for bit)
        # whether or not draft machinery exists — drafting costs a full
        # local model pass per token, so it only runs when k > 1
        if self.spec_k == 1:
            if not sampled:
                return super()._round(cur, pos, slots)
            cur, pos = self._decode_all_sample(cur, pos, len(slots))
            return cur, pos, cur[:, None], None
        k, n_active = self.spec_k, len(slots)
        bt = self._pool.table_dev() if self._pool is not None else None
        with trace.span("engine.dispatch"):
            if sampled:
                temps, top_ps, seeds = self._samp_vecs()
                offs = self._offsets()
                draft_fn, verify_fn = self._spec_sample_fns(k)
                (blobs, scales, zps, drafts, qs, self._edge_cache,
                 self._draft_cache) = draft_fn(
                    self.edge_blocks, self.draft_blocks, self.embed,
                    self.tail, cur, self._edge_cache, self._draft_cache, pos,
                    bt, temps, top_ps, seeds, offs)
            else:
                draft_fn, verify_fn = self._spec_fns(k)
                (blobs, scales, zps, drafts, self._edge_cache,
                 self._draft_cache) = draft_fn(
                    self.edge_blocks, self.draft_blocks, self.embed,
                    self.tail, cur, self._edge_cache, self._draft_cache, pos,
                    bt)
            # one uplink message: k per-row-framed [1, D] deltas + the k-1
            # graded drafts, amortizing the header (and the RTT) over a
            # round; a sampled row also ships the k-1 graded positions'
            # f32 draft distributions the rejection test needs (priced as
            # draft_q_bytes by costmodel.speculative_round_time)
            n_samp = int((self._samp_t[slots] > 0).sum())
            self.transport.charge(
                self.stats,
                n_active * (k * (self.cfg.d_model * blobs.dtype.itemsize
                                 + _QP_BYTES)
                            + (k - 1) * _TOK_BYTES) + _MSG_BYTES
                + n_samp * (k - 1) * self.cfg.vocab * 4,
                phase="decode")
            if sampled:
                toks, n_commit, cur, self._cloud_cache, pos = verify_fn(
                    self.cloud_blocks, self.tail, blobs, scales, zps, drafts,
                    qs, self._cloud_cache, pos, bt, temps, top_ps, seeds,
                    offs)
            else:
                toks, n_commit, cur, self._cloud_cache, pos = verify_fn(
                    self.cloud_blocks, self.tail, blobs, scales, zps, drafts,
                    self._cloud_cache, pos, bt)
        # the edge needs the accept counts to schedule the next round, so
        # this sync is part of the protocol, not a host-loop artifact
        with trace.span("engine.sync"):
            counts = np.asarray(n_commit)
        self.transport.account_downlink(self.stats, n_active, k=k)
        self.stats.spec_rounds += 1
        hits = int(np.minimum(counts[slots] - 1, k - 1).sum())
        self.stats.drafted_tokens += (k - 1) * n_active
        self.stats.draft_hits += hits
        self.telemetry.observe_round((k - 1) * n_active, hits)
        if bt is not None:
            # per round: k draft passes through the edge and draft layers
            # (paged with the edge), one verify through the cloud layers
            live_pos = [len(r.prompt) + c - 1
                        for r, c in self._sched_active.values()]
            visited, live = page_visits(
                live_pos, k, self.max_batch, bt.shape[1], self.page_size,
                self.max_len, (self.n_edge + self.n_cloud) * self.edge_paged,
                self.n_cloud * self.cloud_paged)
            self.stats.kv_pages_visited += visited
            self.stats.kv_pages_live += live
        return cur, pos, toks, counts

    def _retire(self, slot):
        if self._pool is not None:
            self._pool.retire(slot)

    def _can_admit(self, group_shapes, plen, max_new, bucket):
        if self._pool is None:
            return True
        shapes = [(p, int(self._admit_reserve(np.int64(m))))
                  for p, m in group_shapes + [(plen, max_new)]]
        return self._pool.can_admit(shapes, bucket)

    def edge_cache_bytes(self, *, live_only: bool = False) -> int:
        """Edge KV footprint; ``live_only`` counts allocated pages only."""
        if self.edge_paged and live_only:
            return self._pool.live_cache_bytes(self._edge_cache)
        return sum(v.size * v.dtype.itemsize
                   for v in self._edge_cache.values())

    # seed recompute path (forward / generate_recompute): serve.seedpath

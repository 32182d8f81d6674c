"""Slot-based continuous-batching scheduler shared by both engines.

Requests queue up, prompts are right-padded to power-of-two *buckets*
and same-bucket prompts are prefilled together into free cache slots
(bounding the number of distinct compiled prefill shapes — see
``trace_counts``), every **round** advances all occupied slots at their
own positions (vector ``cache_index``) by one or more committed tokens,
and a finished request frees its slot — and its KV pages — for the next
queued prompt mid-flight, including *mid-round* when a round commits
past its budget.  Sampled tokens stay on device for the whole
generation; the host sees them once, after the last round (a
speculative engine additionally syncs one small per-round accept-count
vector, which the edge needs anyway to schedule the next round).

The scheduler also hosts the engine-side half of the online re-tuning
loop: ``_policy_tick`` runs at the top of every scheduler turn, where a
policy may switch the speculative draft length immediately (between
rounds) and request a **re-partition barrier** — admission pauses until
the occupied slots drain, the cut switch applies at that
request-admission boundary, and the queue resumes on the new partition.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as ML
from repro.models import transformer as TF
from repro.serve import trace
from repro.serve.kvcache import PoolExhausted
from repro.serve.transport import ServeStats


def _bucket_len(plen: int, max_len: int) -> int:
    """Power-of-two prefill bucket (floor 8, capped at ``max_len``)."""
    b = 8
    while b < plen:
        b *= 2
    return min(b, max_len)


def _jit_phase(fn, donate: Tuple[int, ...] = (), mesh=None):
    """``jax.jit`` with the KV-cache argument(s) donated, so the page-pool
    scatter of every prefill/decode/verify updates the cache *in place*
    on TPU/GPU (the engines never touch a donated buffer again).  XLA:CPU
    ignores donation and warns per call, so off-accelerator we jit plain.

    ``mesh``: the phase is traced, called and ``.lower``-ed under
    ``jax.set_mesh(mesh)``, so GSPMD propagates the committed input
    shardings (``serve.sharding``) and the paged kernels, which GSPMD
    cannot partition, run ``shard_map``'d (``kernels.paged_attention``)."""
    accel = jax.default_backend() in ("tpu", "gpu")
    jf = jax.jit(fn, donate_argnums=donate if accel else ())
    if mesh is None:
        return jf

    def under_mesh(call):
        def run(*args, **kwargs):
            with jax.set_mesh(mesh):
                return call(*args, **kwargs)
        return run

    mesh_call = under_mesh(jf)
    mesh_call.lower = under_mesh(jf.lower)
    return mesh_call


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int = 16
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # None or temperature=0 → bit-identical greedy (serve.sampling)
    sampling: Optional["SamplingParams"] = None  # noqa: F821
    # -- overload-robust serving (all optional; defaults = legacy batch) --
    priority: int = 0             # higher admits first / preempts last
    deadline_s: Optional[float] = None   # absolute, on the simulated clock
    arrival_s: float = 0.0        # when the request becomes admissible
    # -- multi-tenant fleet serving (serve.fleet) -------------------------
    tenant: Optional[str] = None  # owning edge/tenant; None = single-tenant
    shed: bool = False            # refused by deadline-aware admission
    preemptions: int = 0          # times this request was suspended
    admit_s: Optional[float] = None      # first admission time
    finish_s: Optional[float] = None     # retirement time
    # scheduler internals
    _seq: int = dataclasses.field(default=0, repr=False)
    _enq_s: float = dataclasses.field(default=0.0, repr=False)
    _parked: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)  # committed tokens across a preemption


def _remove_is(lst: List, item) -> None:
    """Remove by identity (dataclass ``==`` compares field values, and
    two requests may legitimately carry identical fields)."""
    for i, x in enumerate(lst):
        if x is item:
            del lst[i]
            return


class _SlotEngine:
    """Continuous-batching scheduler base class.

    Subclasses implement ``_admit`` (prefill a prompt group into specific
    slots), ``_decode_all`` (advance every slot one token) and/or
    ``_round`` (advance every slot by a *variable* number of committed
    tokens — the speculative draft/verify round), and may hook
    ``_retire`` (a slot's request finished — e.g. return its KV pages),
    ``_can_admit`` (admission backpressure), and ``_policy_tick``
    (online re-tuning).  The scheduler keeps the current token and
    position of every slot on device; request outputs are transferred to
    the host once, after the final round.

    The loop is organised around **rounds**: admission commits one token
    per new slot (the prefill's argmax), and every scheduler turn after
    that commits ``counts[s]`` tokens per occupied slot, where the
    non-speculative engines statically commit one (``counts is None`` —
    no device sync, the loop stays fully async) and a speculative round
    returns the verify step's per-slot accept counts.  Per-slot
    accepted-length bookkeeping trims a round that overshoots a
    request's budget and retires the slot mid-stream ("retire on
    accept"), so the next queued prompt gets the slot and its pages.

    Admission pads each prompt group to a power-of-two bucket
    (``_bucket_len``), so the number of distinct prefill trace shapes is
    bounded by O(log2(max_len) · max_batch) instead of growing with
    every unique prompt length.  ``trace_counts`` counts actual
    retraces of the jit'd phase functions; tests pin it.
    """

    _pool = None      # paged engines: their ``kvcache._PagedPool``

    def __init__(self, cfg: TF.LMConfig, *, max_batch: int, max_len: int):
        self.cfg = dataclasses.replace(cfg, remat=False)
        self.max_batch = max_batch
        self.max_len = max_len
        self.stats = ServeStats()
        self.trace_counts = {"prefill": 0, "decode": 0, "spec_draft": 0,
                             "verify": 0, "edge_only": 0, "resync": 0,
                             "draft_rebuild": 0}
        # populated by _run while a generate call is live (see there)
        self._sched_active = None
        self._sched_committed = None

    # -- subclass interface -------------------------------------------------
    def _admit(self, toks: jax.Array, plens: np.ndarray, max_news: np.ndarray,
               slots: np.ndarray, cur: jax.Array, pos: jax.Array,
               samplings=None) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def _decode_all(self, cur: jax.Array, pos: jax.Array,
                    n_active: int) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def _round(self, cur: jax.Array, pos: jax.Array, slots: np.ndarray,
               ) -> Tuple[jax.Array, jax.Array, jax.Array,
                          Optional[np.ndarray]]:
        """Advance the occupied ``slots`` by one round.

        Returns ``(cur, pos, tokens, counts)``: ``tokens`` is the
        ``[max_batch, k]`` device block of tokens the round produced and
        ``counts`` the per-slot number of *committed* leading tokens —
        ``None`` means "statically one per slot" (the non-speculative
        path, which therefore never blocks on the device)."""
        cur, pos = self._decode_all(cur, pos, len(slots))
        return cur, pos, cur[:, None], None

    def _round_headroom(self) -> int:
        """Cache positions a round may write *past* a request's budget
        (speculative drafting overshoots by up to k-1); admission
        reserves them so overshoot writes can never alias another
        request's pages."""
        return 0

    def _retire(self, slot: int) -> None:
        """Hook: the request in ``slot`` finished (free paged KV, etc.)."""

    def _after_round(self, n_active: int, committed: int) -> None:
        """Hook: one decode round just finished, having committed
        ``committed`` tokens across ``n_active`` slots.  The resilient
        engine logs (simulated time, committed, cloud state) here — the
        per-round availability trace the chaos benchmark integrates
        over its outage window."""

    def _can_admit(self, group_shapes: List[Tuple[int, int]], plen: int,
                   max_new: int, bucket: int) -> bool:
        """Hook: may this request join the prefill group right now?
        ``group_shapes`` are the (plen, max_new) pairs already accepted
        into the group this round.  Paged engines refuse when the page
        pool can't cover the whole group, backpressuring admission until
        retirements return pages."""
        return True

    def _policy_tick(self, n_active: int) -> bool:
        """Hook: one turn of the online re-tuning control loop, called at
        the top of every scheduler turn (and therefore between rounds,
        and with ``n_active == 0`` between requests/generate calls).

        Returns True to **pause admission** this turn — the re-partition
        barrier: a pending cut-layer switch needs the occupied slots to
        drain before it can apply (split KV caches change layer
        ownership), so the engine stops admitting, finishes the live
        requests, applies the switch at the now-empty admission
        boundary, and resumes.  Implementations MUST return False when
        ``n_active == 0`` (apply any pending switch instead), or the
        scheduler would livelock; the loop asserts this."""
        return False

    def _tick_resources(self) -> None:
        """Hook: top of every scheduler turn, before admission — a
        pressure-injecting engine applies its ``faults.PressureSchedule``
        to the page allocator here, at the current simulated time."""

    def _now(self) -> float:
        """Hook: current simulated time.  Clockless engines serve one
        batch at t=0; clocked engines mirror their channel's
        ``clock_s``."""
        return 0.0

    def _wait(self, seconds: float) -> bool:
        """Hook: advance the simulated clock by ``seconds`` (a scheduler
        stall or an inter-arrival gap), charging ``stats.stall_wait_s``.
        Returns False when the engine has no clock to advance — the
        scheduler then falls back to batch semantics (every queued
        request is treated as already arrived)."""
        return seconds <= 0

    def _on_stall(self) -> bool:
        """Hook: the engine is drained but admission still can't fit the
        next request.  Return True after waiting out a *transient* cause
        (e.g. a ``PressureSchedule`` window squeezing the pool) — the
        scheduler retries; False means the stall is permanent and the
        scheduler raises."""
        return False

    def _round_width(self) -> int:
        """Cache positions one round may write per slot (the speculative
        draft length); demand paging grows each slot's claim to cover
        them before the round runs."""
        return 1

    def _ensure_slot(self, slot: int, horizon: int) -> None:
        """Hook: grow ``slot``'s page claim to cover ``horizon`` cache
        positions before the coming round writes them; raises
        ``kvcache.PoolExhausted`` when the pool can't (the scheduler
        preempts a victim and retries).  Default: worst-case reservation
        at admission — nothing to grow."""

    def _preempt(self, slot: int) -> None:
        """Hook: ``slot`` is being suspended mid-flight — release its KV
        pages, but keep the request resumable (the scheduler has already
        parked its committed tokens and re-queues it)."""
        self._retire(slot)

    def _admission_policy(self, req: Request, *, now: float,
                          queue_tokens: float) -> bool:
        """Hook: may ``req`` be admitted at all?  False sheds it — a
        deadline-aware engine predicts the finish time from the live
        cost model and refuses requests that are already doomed.
        ``queue_tokens`` is the generation budget still owed to work
        admitted ahead of it."""
        return True

    # -- shared helpers -----------------------------------------------------
    def _rope(self):
        return ML.rope_table(self.max_len, self.cfg.hd,
                             base=self.cfg.rope_base, dtype=self.cfg.dtype)

    @staticmethod
    def _eff_prompt(r: Request) -> np.ndarray:
        """The token row a (re-)admission prefills: the prompt — extended
        for a preempted request with all but the last committed token.
        This is multi-token cached replay: the batched prefill rebuilds
        the suspended slot's KV in one call, and its argmax re-derives
        the last committed token, so resume recomputes no committed
        position one-by-one."""
        if r._parked is None or len(r._parked) == 0:
            return np.asarray(r.prompt, np.int32)
        return np.concatenate([np.asarray(r.prompt, np.int32),
                               r._parked[:-1]])

    def _eff_plen(self, r: Request) -> int:
        return len(r.prompt) + (0 if r._parked is None
                                else max(0, len(r._parked) - 1))

    # -- scheduler ----------------------------------------------------------
    def generate(self, prompts: List[np.ndarray], *, max_new_tokens: int = 16,
                 sampling=None) -> List[List[int]]:
        """Decode a list of prompts with continuous batching.  ``sampling``
        is one ``SamplingParams`` for all prompts, or a per-prompt list;
        ``None`` (default) is greedy."""
        samps = (list(sampling) if isinstance(sampling, (list, tuple))
                 else [sampling] * len(prompts))
        return self.generate_requests(
            [Request(uid=i, prompt=np.asarray(p),
                     max_new_tokens=max_new_tokens, sampling=s)
             for i, (p, s) in enumerate(zip(prompts, samps))])

    def generate_requests(self, reqs: List[Request]) -> List[List[int]]:
        """Run caller-built ``Request``s — priorities, deadlines,
        arrival times — through the scheduler; returns their token
        streams in input order.  A shed request comes back empty with
        ``r.shed`` set; completion metadata lands on ``admit_s`` /
        ``finish_s`` / ``preemptions``."""
        if reqs:
            with trace.span("serve.call", requests=len(reqs)):
                self._run(reqs)
        return [r.out_tokens for r in reqs]

    def _run(self, reqs: List[Request]) -> None:
        for i, r in enumerate(reqs):
            r._seq = i
            r._enq_s = float(r.arrival_s)
        queue: List[Request] = list(reqs)
        active: Dict[int, Tuple[Request, int]] = {}  # slot -> (req, n_committed)
        free = list(range(self.max_batch))
        cur = jnp.zeros((self.max_batch,), jnp.int32)
        pos = jnp.zeros((self.max_batch,), jnp.int32)
        # every admission and every round logs (token block [B, k], takes);
        # token blocks stay on device until one concat+transfer at the end
        rounds: List[Tuple[jax.Array, List[Tuple[Request, int, int]]]] = []

        def parked_tokens(r: Request) -> np.ndarray:
            """Pull ``r``'s committed tokens off the logged round blocks
            — the one host sync a preemption costs."""
            chunks = [np.asarray(t[s, :n])
                      for t, takes in rounds
                      for rr, s, n in takes if rr is r and n > 0]
            return (np.concatenate(chunks).astype(np.int32) if chunks
                    else np.zeros((0,), np.int32))

        # live view for engine hooks that rebuild per-slot device state
        # mid-run (e.g. the draft-cache rebuild on a warm k raise): the
        # active map plus the one host sync that recovers a live slot's
        # committed tokens
        self._sched_active = active
        self._sched_committed = parked_tokens

        def preempt(slot: int) -> None:
            r, _c = active.pop(slot)
            r._parked = parked_tokens(r)
            r._enq_s = self._now()
            r.preemptions += 1
            self.stats.preemptions += 1
            self._preempt(slot)
            free.append(slot)
            queue.append(r)

        for _ in trace.turns(lambda: bool(queue or active)):
            with trace.span("sched.policy"):
                self._tick_resources()
                hold = self._policy_tick(len(active))
            assert not (hold and not active), \
                "_policy_tick must not pause admission on a drained engine"
            now = self._now()
            elig = sorted((r for r in queue if r.arrival_s <= now + 1e-12),
                          key=lambda r: (-r.priority, r._seq))
            if not elig and queue and not active and not hold:
                # nothing has arrived yet: advance the clock to the next
                # arrival, or — on a clockless engine — fall back to
                # batch semantics (everything queued is already here)
                nxt = min(r.arrival_s for r in queue)
                if self._wait(nxt - now):
                    continue
                elig = sorted(queue, key=lambda r: (-r.priority, r._seq))
            # admit eligible prompts into free slots, grouping by prefill
            # bucket so one batched, fixed-shape prefill call covers the
            # whole group; a paged engine may refuse (pool backpressure)
            # and a pending re-partition holds admission entirely — the
            # request then waits for retirements
            stalled = False
            stall_req: Optional[Request] = None
            while free and elig and not stalled and not hold:
                bucket = _bucket_len(self._eff_plen(elig[0]), self.max_len)
                group: List[Request] = []
                rows: List[np.ndarray] = []
                slots: List[int] = []
                shapes: List[Tuple[int, int]] = []
                while free and elig and _bucket_len(
                        self._eff_plen(elig[0]), self.max_len) == bucket:
                    r = elig[0]
                    row = self._eff_prompt(r)
                    eff_new = (r.max_new_tokens if r._parked is None
                               else r.max_new_tokens - len(r._parked) + 1)
                    assert (len(row) + eff_new
                            + self._round_headroom()) <= self.max_len, \
                        "prompt + generation (+ draft headroom) exceeds " \
                        "cache max_len"
                    if r._parked is None and r.deadline_s is not None:
                        # budget owed to work that will actually run
                        # ahead of this request: equal-or-higher
                        # priority only — lower-priority slots are
                        # preemptable, so they don't gate its finish
                        owed = (sum(rr.max_new_tokens - cc
                                    for rr, cc in active.values()
                                    if rr.priority >= r.priority)
                                + sum(m for _, m in shapes))
                        if not self._admission_policy(
                                r, now=now, queue_tokens=float(owed)):
                            # predicted to finish past its deadline even
                            # if admitted this instant: shed it instead
                            # of letting it poison the pool
                            r.shed = True
                            r.done = True
                            self.stats.shed += 1
                            elig.pop(0)
                            _remove_is(queue, r)
                            continue
                    if not self._can_admit(shapes, len(row), eff_new,
                                           bucket):
                        stalled = True
                        stall_req = r
                        break
                    shapes.append((len(row), eff_new))
                    group.append(r)
                    rows.append(row)
                    elig.pop(0)
                    _remove_is(queue, r)
                    slots.append(free.pop(0))
                if not group:
                    break
                with trace.span("sched.admit", bucket=bucket,
                                group=len(group)) as sp:
                    if sp is not None:
                        sp.set_metadata(uids="_".join(str(r.uid)
                                                      for r in group))
                    toks = np.zeros((len(group), bucket), np.int32)
                    for i, row in enumerate(rows):
                        toks[i, :len(row)] = row
                    plens = np.asarray([len(row) for row in rows], np.int32)
                    max_news = np.asarray([m for _, m in shapes], np.int32)
                    slots_a = np.asarray(slots, np.int32)
                    toks_j = jnp.asarray(toks)
                    cur, pos = self._admit(
                        toks_j, plens, max_news, slots_a, cur, pos,
                        samplings=[r.sampling for r in group])
                    self.stats.prefill_calls += 1
                    self.stats.prefill_tokens += int(plens.sum())
                    resumes = [(s, r) for r, s in zip(group, slots)
                               if r._parked is not None]
                    if resumes:
                        # the replay prefill re-derives the last committed
                        # token; pin the stream to the parked value so resume
                        # can never diverge (INT8 recalibration over the
                        # longer prefix may legitimately flip the argmax —
                        # lossless mode is bitwise identical either way,
                        # which the preemption property tests pin)
                        rs = jnp.asarray([s for s, _ in resumes], jnp.int32)
                        lasts = jnp.asarray([int(r._parked[-1])
                                             for _, r in resumes], jnp.int32)
                        cur = cur.at[rs].set(lasts)
                    # a fresh request's first committed token is the prefill
                    # argmax; a resumed request's tokens are already logged
                    # in its pre-preemption rounds
                    fresh = [(r, s, 1) for r, s in zip(group, slots)
                             if r._parked is None]
                    if fresh:
                        rounds.append((cur[:, None], fresh))
                    for r, s in zip(group, slots):
                        active[s] = (r, 1 if r._parked is None
                                     else len(r._parked))
                        if r.admit_s is None:
                            r.admit_s = now
                        self.stats.queue_wait_s += max(0.0, now - r._enq_s)
                        r._parked = None
            if stalled and not active:
                # a drained engine that still can't admit: either a
                # transient squeeze (wait it out on the simulated clock
                # and retry) or a genuinely impossible request
                if not self._on_stall():
                    r = stall_req
                    raise RuntimeError(
                        f"KV page pool too small for request uid={r.uid} "
                        f"(prompt {len(r.prompt)} + {r.max_new_tokens} new "
                        f"tokens) even with every slot idle")
                continue
            # retire requests whose budget just filled — before the next
            # round, so no request pays for a round it never reads and
            # its slot (and KV pages) free one round earlier for the queue
            for s in [s for s, (r, c) in active.items()
                      if c >= r.max_new_tokens]:
                r, _ = active.pop(s)
                r.done = True
                r.finish_s = self._now()
                if (r.deadline_s is not None
                        and r.finish_s > r.deadline_s + 1e-9):
                    self.stats.deadline_misses += 1
                self._retire(s)
                free.append(s)
            # demand paging: grow every live slot's claim to cover the
            # positions the coming round will write; on PoolExhausted,
            # preempt victims — lowest priority first, then most
            # remaining budget — until the growth fits (possibly
            # preempting the grower itself, which also resolves it)
            if active:
                with trace.span("sched.page"):
                    k = self._round_width()
                    for s in sorted(active,
                                    key=lambda t: (-active[t][0].priority, t)):
                        if s not in active:
                            continue  # already someone else's victim
                        r, c = active[s]
                        horizon = min(len(r.prompt) + c - 1 + k, self.max_len)
                        while s in active:
                            try:
                                self._ensure_slot(s, horizon)
                                break
                            except PoolExhausted:
                                victims = sorted(
                                    active,
                                    key=lambda t: (
                                        active[t][0].priority,
                                        -(active[t][0].max_new_tokens
                                          - active[t][1]),
                                        t))
                                preempt(victims[0])
            if active:
                act_slots = np.asarray(sorted(active), np.int32)
                with trace.span("sched.round", round=self.stats.decode_steps,
                                slots=len(act_slots),
                                k=self._round_width()) as sp:
                    if sp is not None and self._pool is not None:
                        sp.set_metadata(width=self._pool.table_dev().shape[1])
                    cur, pos, toks_r, counts = self._round(cur, pos,
                                                           act_slots)
                with trace.span("sched.commit") as sp:
                    takes = []
                    for s in act_slots:
                        r, c = active[int(s)]
                        n = 1 if counts is None else int(counts[s])
                        n = min(n, r.max_new_tokens - c)  # trim overshoot
                        active[int(s)] = (r, c + n)
                        takes.append((r, int(s), n))
                    rounds.append((toks_r, takes))
                    self.stats.decode_steps += 1
                    committed = sum(n for _, _, n in takes)
                    self.stats.decode_tokens += committed
                    self._after_round(len(takes), committed)
                    if sp is not None:
                        sp.set_metadata(uids="_".join(
                            f"{r.uid}:{n}" for r, _, n in takes if n))
        self._sched_active = None
        self._sched_committed = None
        # single device→host transfer for the whole run
        if not rounds:
            return  # everything shed before a single token committed
        with trace.span("sched.finalize", blocks=len(rounds)):
            all_toks = np.asarray(
                jnp.concatenate([t for t, _ in rounds], axis=1))
            col = 0
            for toks_r, takes in rounds:
                for r, s, n in takes:
                    r.out_tokens.extend(all_toks[s, col:col + n].tolist())
                col += toks_r.shape[1]

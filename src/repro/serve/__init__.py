"""Serving subsystem: continuous-batching engines for the paper's
cloud-edge collaborative deployment, as a package of focused layers.

    scheduler   slot/bucket/round continuous batching (``_SlotEngine``)
    kvcache     paged INT8 KV bookkeeping (``PageAllocator``)
    transport   channel framing + wire accounting + link telemetry,
                plus the reliable (seq/deadline/retry) transport
    faults      seeded/scripted channel fault injection
    policy      online (cut_layer, spec_k) re-tuning control plane +
                deadline-aware admission prediction
    overload    demand paging / preemption / deadline-shedding hooks
                (``_OverloadMixin``)
    engine      ``ServingEngine`` / ``CollaborativeServingEngine``
    resilience  ``ResilientCollaborativeEngine`` — edge-only graceful
                degradation through outages + cloud KV resync
    fleet       ``FleetServingEngine`` — N tenant edges on one shared
                cloud engine: cross-tenant batched verify over one
                weight bank / page pool, weighted-fair sharing
    trace       host spans of the serving loop on the device trace's
                clock (off unless ``trace.enable(True)``)

``repro.serve.engine`` re-exports the whole public surface, so both
``from repro.serve import X`` and the historical
``from repro.serve.engine import X`` work (the resilient engine lives
one layer above ``engine`` and is exported from the package only).
"""
from repro.serve.engine import (AdaptivePolicy, CollaborativeServingEngine,
                                CloudUnreachable, DeadlineAdmission,
                                Decision, DriftingChannel, FaultyChannel,
                                LinkTelemetry, PageAllocator, PoolExhausted,
                                PressureSchedule, ReliableTransport, Request,
                                SamplingParams, ServeStats, ServingEngine,
                                Transport)
from repro.serve.faults import FaultOutcome
from repro.serve.fleet import FleetServingEngine, TenantSpec
from repro.serve.policy import FleetFairness
from repro.serve.resilience import ResilientCollaborativeEngine

__all__ = ["ServingEngine", "CollaborativeServingEngine",
           "ResilientCollaborativeEngine", "FleetServingEngine",
           "TenantSpec", "FleetFairness", "PageAllocator", "PoolExhausted",
           "ServeStats", "Request", "SamplingParams", "Transport",
           "ReliableTransport",
           "CloudUnreachable", "LinkTelemetry", "DriftingChannel",
           "FaultyChannel", "FaultOutcome", "PressureSchedule",
           "AdaptivePolicy", "DeadlineAdmission", "Decision"]

"""End-to-end driver: serve a small LM with batched requests, cloud-only
vs cloud-edge collaborative at the auto-tuned partition point.

This is the paper's deployment story on the LM family: Algorithm 1 picks
the cut from the layer graph + device/channel models, then the
collaborative engine runs the INT8 edge prefix and the FP32 cloud suffix
over *split* KV caches — one split prefill, then one quantized
[B, 1, D] boundary delta per generated token (Eq.1/2), so wire traffic
per token is O(1) in sequence length instead of re-shipping the whole
boundary blob.  On a high-RTT link the engine can further restructure
decode into speculative draft/verify rounds (spec_k, auto-tuned by
autotune.tune_spec_k): the edge drafts k tokens locally through an INT8
copy of the cloud suffix, and the cloud verifies all k in one batched
step — the channel round trip is paid per round instead of per token.

The final section closes the tuning loop *online*: link telemetry
(EWMA bandwidth/RTT/acceptance estimated from the serving traffic
itself) feeds the cost-model grid between rounds, and the engine
re-tunes spec_k and the cut layer while requests drain through a
channel swing — Algorithm 1 as a control plane instead of a
preprocessing step.

Run:  PYTHONPATH=src python examples/collaborative_serve.py
      PYTHONPATH=src python examples/collaborative_serve.py --overload
      (the flag appends the overload-robustness demo: a priority burst
      preempting a best-effort wave on a 2x oversubscribed KV pool)
      PYTHONPATH=src python examples/collaborative_serve.py --mesh 4
      (serves the collaborative engine with the cloud suffix + paged KV
      pool tensor-parallel over N emulated host devices)
      PYTHONPATH=src python examples/collaborative_serve.py --fleet 4
      (appends the multi-tenant demo: N simulated edges with
      heterogeneous links and per-tenant (cut, k) share ONE cloud
      engine — cross-tenant batched verify over a shared weight bank
      and KV page pool)
      PYTHONPATH=src python examples/collaborative_serve.py --sample
      (appends the temperature>0 demo: verify becomes exact rejection
      sampling against the cloud distribution, seeded for bit-identical
      replay; temperature=0 keeps the greedy fast path)
"""
import argparse
import os
import time

# --mesh N needs N XLA host-platform devices, and the device count is
# fixed the moment jax is imported — pre-parse just that flag here
_MESH = argparse.ArgumentParser(add_help=False)
_MESH.add_argument("--mesh", type=int, default=1)
_MESH = max(1, _MESH.parse_known_args()[0].mesh)
if _MESH > 1 and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={_MESH}").strip()

import jax
import numpy as np

from repro.core.autotune import AutoTuner
from repro.core.costmodel import (CLOUD_TITANXP_CLASS, Channel,
                                  EDGE_TX2_CLASS)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import LMConfig, init_lm, make_graph
from repro.serve import FaultyChannel, Request
from repro.serve.engine import CollaborativeServingEngine, ServingEngine

CFG = LMConfig(name="edge-lm-25m", n_layers=6, d_model=256, n_heads=8,
               n_kv=4, d_ff=1024, vocab=2048, max_seq=128, remat=False)


def overload_demo(params, cut_layer):
    """Overload robustness: a late priority burst against a best-effort
    wave on a KV page pool sized at ~half the batch's worst-case demand.
    The naive engine reserves worst-case pages at admission and
    head-of-line blocks the burst; the robust engine demand-pages,
    preempts best-effort slots (replay-based resume — no tokens lost),
    and sheds only requests its cost model predicts are already doomed."""
    base = Channel.from_kbps(500, rtt_ms=10)
    # 4 slots x (9 prompt + 32 new) wants ~24 usable pages; pool has 10
    pool = dict(page_size=8, max_batch=4, max_len=64, num_pages=11)

    # calibrate the burst deadline from a lone-request service time
    fch = FaultyChannel(base, seed=0)
    lone = CollaborativeServingEngine(params, CFG, cut_layer=cut_layer,
                                      channel=fch, **pool)
    rng = np.random.RandomState(7)
    lone.generate([rng.randint(0, CFG.vocab, 9).astype(np.int32)],
                  max_new_tokens=12)
    deadline = 3.0 * float(fch.clock_s)
    print(f"\noverload demo: pool {pool['num_pages']} pages "
          f"(~2x oversubscribed), priority deadline {deadline:.2f}s")

    def traffic():
        r = np.random.RandomState(7)
        mk = lambda: r.randint(0, CFG.vocab, 9).astype(np.int32)  # noqa: E731
        reqs = [Request(uid=i, prompt=mk(), max_new_tokens=32, priority=0,
                        arrival_s=0.05 * i) for i in range(6)]
        reqs += [Request(uid=10 + i, prompt=mk(), max_new_tokens=12,
                         priority=1, arrival_s=0.3 + 0.05 * i,
                         deadline_s=0.3 + 0.05 * i + deadline)
                 for i in range(2)]
        return reqs

    for name, kw in [("naive", {}),
                     ("robust", dict(demand_paged=True,
                                     admission="deadline"))]:
        fch = FaultyChannel(base, seed=0)
        eng = CollaborativeServingEngine(params, CFG, cut_layer=cut_layer,
                                         channel=fch, **pool, **kw)
        reqs = traffic()
        eng.generate_requests(reqs)
        pri = [r for r in reqs if r.priority > 0]
        ontime = sum(1 for r in pri
                     if r.finish_s is not None and r.finish_s <= r.deadline_s)
        s = eng.stats
        print(f"  {name:>6}: {s.decode_tokens} tokens in "
              f"{float(fch.clock_s):.2f}s sim — priority on-time "
              f"{ontime}/{len(pri)}, preemptions={s.preemptions}, "
              f"shed={s.shed}, deadline_misses={s.deadline_misses}, "
              f"p99 admit wait "
              f"{max((r.admit_s - r.arrival_s) for r in reqs if r.admit_s is not None):.2f}s")
    print("  (identical traffic; the robust engine's preemption/resume "
          "is bit-transparent — see tests/test_overload_serve.py)")


def fleet_demo(params, cut_layer, n_tenants):
    """Multi-tenant fleet serving: ``n_tenants`` simulated edges — each
    with its own link, clock, telemetry, and (cut, spec_k) — stream at
    ONE shared cloud engine.  Weights come out of a single prequantized
    bank (no per-tenant copies), KV lives in one shared page pool under
    weighted-fair sharing, and every scheduler turn coalesces all
    tenants' due rounds into one batched verify per (cut, k) group —
    aggregate throughput scales far beyond N independent engines (see
    benchmarks/fleet_serve.py for the measured headline)."""
    from repro.core.costmodel import Channel as Ch
    from repro.serve import FleetServingEngine, TenantSpec

    links = [(2000, 20), (1000, 40), (500, 60), (250, 80)]
    cuts = [cut_layer, max(0, cut_layer - 1)]
    ks = [4, 1]
    tenants = [
        TenantSpec(f"edge{i}",
                   FaultyChannel(Ch.from_kbps(links[i % 4][0],
                                              rtt_ms=links[i % 4][1]),
                                 seed=i),
                   cut_layer=cuts[i % 2], spec_k=ks[i % 2])
        for i in range(n_tenants)]
    fleet = FleetServingEngine(params, CFG, tenants,
                               max_batch=2 * n_tenants, max_len=64,
                               page_size=8)
    rng = np.random.RandomState(5)
    prompts = {t.name: [rng.randint(0, CFG.vocab, 12).astype(np.int32)
                        for _ in range(2)] for t in tenants}
    print(f"\nfleet demo: {n_tenants} tenants on one cloud engine "
          f"(shared weight bank @ cuts {sorted(set(t.cut_layer for t in tenants))}, "
          f"one KV pool, cross-tenant batched verify)")
    t0 = time.perf_counter()
    fleet.generate(prompts, max_new_tokens=8)
    wall = time.perf_counter() - t0
    for t in tenants:
        st = fleet.tenant(t.name).stats
        print(f"  {t.name:>6}: cut={t.cut_layer} k={t.spec_k} — "
              f"{st.decode_tokens:3d} committed tokens, "
              f"{st.transmitted_bytes / 1e3:5.1f}KB wire, "
              f"sim clock {fleet.tenant(t.name).now():.2f}s")
    agg = fleet.stats
    print(f"  fleet: {agg.decode_tokens} committed tokens in {wall:.2f}s "
          f"wall over {fleet.round_calls} batched round dispatches — each "
          f"turn verifies every due tenant in one paged multi-query call "
          f"per (cut, k) group; benchmarks/fleet_serve.py measures the "
          f"aggregate speedup vs independent engines.  Pool peak "
          f"utilization {agg.pool_utilization_peak:.0%}")


def sampling_demo(params, cut_layer):
    """Temperature>0 serving: the verify step becomes exact rejection
    sampling against the cloud distribution — outputs are distributed
    exactly as non-speculative cloud sampling (tests/test_sampled_spec
    holds the TV-distance gate), the speculative round structure and its
    per-round RTT win are unchanged, and the per-request seed makes the
    stream replay bit-identically across engines and restarts."""
    from repro.serve.engine import SamplingParams
    ch = Channel.from_kbps(500, rtt_ms=50)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, CFG.vocab, 12).astype(np.int32)
               for _ in range(4)]
    sp = [SamplingParams(temperature=0.9, top_p=0.95, seed=i)
          for i in range(4)]

    def fresh():
        return CollaborativeServingEngine(params, CFG, cut_layer=cut_layer,
                                          channel=ch, max_len=64,
                                          max_batch=4, spec_k=4)
    a = fresh()
    outs = a.generate(prompts, max_new_tokens=8, sampling=sp)
    replay = fresh().generate(prompts, max_new_tokens=8, sampling=sp)
    greedy = fresh().generate(prompts, max_new_tokens=8)
    t0 = fresh().generate(prompts, max_new_tokens=8,
                          sampling=[SamplingParams(temperature=0.0)] * 4)
    print(f"\nsampled decode (T=0.9, top_p=0.95, k=4): draft acceptance "
          f"{a.stats.acceptance_rate():.0%} under stochastic "
          f"accept-with-prob-min(1,p/q) grading")
    print(f"  seeded replay bit-identical across engines: {outs == replay}")
    print(f"  temperature=0 request == greedy fast path: {t0 == greedy} "
          f"(sampled rows never perturb greedy ones)")
    print(f"  first sampled stream: {outs[0]}")


def main(overload: bool = False, mesh_n: int = 1, fleet_n: int = 0,
         sample: bool = False):
    enable_compile_cache()
    print(f"model: {CFG.name} ({CFG.param_count() / 1e6:.1f}M params)")
    mesh = None
    if mesh_n > 1:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(model=mesh_n)
        print(f"cloud mesh: {dict(mesh.shape)} over "
              f"{len(jax.devices())} host devices (suffix weights + paged "
              f"KV pool shard over 'model'; the edge side replicates)")
    params = init_lm(jax.random.PRNGKey(0), CFG)

    # --- Algorithm 1: choose the cut for this environment ---------------
    graph = make_graph(CFG, batch=1, seq=32)
    channel = Channel.from_kbps(250, rtt_ms=20)
    tuner = AutoTuner(graph, EDGE_TX2_CLASS, CLOUD_TITANXP_CLASS)
    best, perfs = tuner.tune(channel)
    print(f"auto-tuned cut @250KB/s: {best.point} "
          f"(upload {best.transmit_bytes / 1e3:.1f}KB, "
          f"edge download {best.edge_model_bytes / 1e3:.0f}KB, "
          f"storage reduction {best.storage_reduction:.1%})")
    cut_layer = 0
    if best.point.startswith("blk"):
        cut_layer = int(best.point.split("/")[0][3:])

    # --- batched serving (continuous batching: 8 requests, 4 slots) -----
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, CFG.vocab, 16).astype(np.int32)
               for _ in range(8)]

    cloud = ServingEngine(params, CFG, max_batch=4, max_len=64)
    t0 = time.perf_counter()
    ref = cloud.generate(prompts, max_new_tokens=8)
    t_cloud = time.perf_counter() - t0
    print(f"\ncloud-only: {len(prompts)} requests x 8 tokens in "
          f"{t_cloud:.2f}s  ({cloud.stats.prefill_calls} prefills, "
          f"{cloud.stats.decode_steps} decode steps)")

    collab = CollaborativeServingEngine(params, CFG, cut_layer=cut_layer,
                                        channel=channel, max_len=64,
                                        max_batch=4, mesh=mesh)
    t0 = time.perf_counter()
    got = collab.generate(prompts, max_new_tokens=8)
    t_collab = time.perf_counter() - t0
    agree = np.mean([a == b for r, g in zip(ref, got)
                     for a, b in zip(r, g)])
    s = collab.stats
    print(f"collaborative (cut after block {cut_layer}): {t_collab:.2f}s "
          f"(simulated wire {s.channel_latency_s:.2f}s)")
    print(f"  wire: {s.prefill_bytes / 1e3:.1f}KB one-time prefill + "
          f"{s.bytes_per_decode_token():.0f} B per generated token "
          f"(constant — the [B,1,D] Eq.(1) delta)")
    print(f"token agreement with cloud-only greedy: {agree:.1%} "
          f"(INT8 edge noise can flip near-ties)")

    # --- speculative draft/verify rounds on a high-RTT link -------------
    rtt_channel = Channel.from_kbps(250, rtt_ms=100)
    from repro.core.autotune import spec_k_for_lm
    tuned = spec_k_for_lm(CFG, cut_layer, batch=4, channel=rtt_channel)[0]
    spec = CollaborativeServingEngine(params, CFG, cut_layer=cut_layer,
                                      channel=rtt_channel, max_len=64,
                                      max_batch=4, spec_k=min(tuned.k, 4))
    spec.generate(prompts[:4], max_new_tokens=8)
    base = CollaborativeServingEngine(params, CFG, cut_layer=cut_layer,
                                      channel=rtt_channel, max_len=64,
                                      max_batch=4)
    base.generate(prompts[:4], max_new_tokens=8)
    print(f"\nspeculative rounds @100ms RTT (auto-tuned k={tuned.k}, "
          f"running k={spec.spec_k}): draft acceptance "
          f"{spec.stats.acceptance_rate():.0%}, simulated channel "
          f"{spec.stats.channel_latency_s:.2f}s vs "
          f"{base.stats.channel_latency_s:.2f}s per-token — the RTT is "
          f"paid per round, not per token")

    # --- contrast with the seed recompute path --------------------------
    rec_prompts, rec_new = prompts[:4], 8
    rec = CollaborativeServingEngine(params, CFG, cut_layer=cut_layer,
                                     channel=channel, max_len=64)
    rec.generate_recompute(rec_prompts, max_new_tokens=rec_new)
    per_tok_rec = rec.stats.transmitted_bytes / (rec_new * len(rec_prompts))
    print(f"\nrecompute-from-scratch baseline would ship "
          f"{per_tok_rec / 1e3:.1f}KB per token (grows with sequence); "
          f"incremental decode ships "
          f"{s.bytes_per_decode_token() / 1e3:.3f}KB — "
          f"{per_tok_rec / s.bytes_per_decode_token():.0f}x less")

    # --- close the tuning loop online: serve through a channel swing ----
    # telemetry -> policy -> engine: the link telemetry estimates
    # bandwidth/RTT from the traffic itself, the policy re-runs the
    # cost-model grid, and the engine swaps spec_k between rounds and
    # the cut layer at admission boundaries (prequantized weight bank)
    # (clamp like the launcher: every candidate cut keeps a cloud block)
    adaptive = CollaborativeServingEngine(
        params, CFG, cut_layer=min(cut_layer, CFG.n_layers - 2),
        channel=channel, max_len=64, max_batch=4, policy="auto")
    for label, ch in [("good link", Channel.from_kbps(2000, rtt_ms=5)),
                      ("congested", Channel.from_kbps(200, rtt_ms=150)),
                      ("recovered", Channel.from_kbps(2000, rtt_ms=5))]:
        adaptive.channel = ch
        adaptive.generate(prompts, max_new_tokens=8)
        tel = adaptive.telemetry
        print(f"{label:>10}: engine now (cut={adaptive.cut}, "
              f"k={adaptive.spec_k}); telemetry est "
              f"{(tel.bandwidth_bytes_per_s or 0) / 1e3:.0f}KB/s "
              f"rtt {(tel.rtt_s or 0) * 1e3:.0f}ms")
    st = adaptive.stats
    print(f"online re-tuning: {st.spec_k_switches} draft-length + "
          f"{st.cut_switches} cut switches while serving "
          f"{st.decode_tokens} tokens (acceptance "
          f"{st.acceptance_rate():.0%}) — see benchmarks/adaptive_serve.py "
          f"for the drifting-channel win over fixed cuts")

    # --- temperature>0 serving (opt-in: --sample) -----------------------
    if sample:
        sampling_demo(params, min(cut_layer, CFG.n_layers - 2))

    # --- overload robustness (opt-in: --overload) -----------------------
    if overload:
        overload_demo(params, min(cut_layer, CFG.n_layers - 2))

    # --- multi-tenant fleet serving (opt-in: --fleet N) -----------------
    if fleet_n > 0:
        fleet_demo(params, min(max(cut_layer, 1), CFG.n_layers - 2),
                   fleet_n)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--overload", action="store_true",
                    help="append the overload-robustness demo: a priority "
                         "burst preempting a best-effort wave on a 2x "
                         "oversubscribed KV page pool")
    ap.add_argument("--mesh", type=int, default=1,
                    help="tensor-parallel degree for the cloud suffix and "
                         "paged KV pool (emulated host devices on CPU)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="append the multi-tenant demo: N simulated edges "
                         "with heterogeneous links share one cloud engine "
                         "(cross-tenant batched verify, shared weight "
                         "bank + KV page pool)")
    ap.add_argument("--sample", action="store_true",
                    help="append the temperature>0 demo: rejection-sampled "
                         "verify (exact cloud distribution), seeded "
                         "bit-identical replay, greedy fast-path parity")
    args = ap.parse_args()
    main(overload=args.overload, mesh_n=args.mesh, fleet_n=args.fleet,
         sample=args.sample)

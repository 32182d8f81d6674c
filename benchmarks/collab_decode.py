"""Incremental collaborative decode benchmark.

Measures the split-KV-cache collaborative engine (per-token [B, 1, D]
boundary delta over the wire) against the seed recompute-from-scratch
path (whole split forward re-run per token, whole boundary blob
retransmitted), and records the per-phase split plus the analytic
roofline prediction.  Writes ``BENCH_collab_decode.json`` so future PRs
have a perf trajectory to regress against.

    PYTHONPATH=src python -m benchmarks.collab_decode
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core.costmodel import (CLOUD_TITANXP_CLASS, Channel,
                                  EDGE_TX2_CLASS, collab_decode_step_time)
from repro.models.transformer import LMConfig, init_lm
from repro.serve.engine import CollaborativeServingEngine, ServeStats

OUT = Path("BENCH_collab_decode.json")

CFG = LMConfig(name="collab-bench-lm", n_layers=6, d_model=256, n_heads=8,
               n_kv=4, d_ff=1024, vocab=2048, max_seq=256, remat=False)
CUT = 1
BATCH = 4
PLEN = 32
NEW = 16


def _engine(params, channel):
    return CollaborativeServingEngine(params, CFG, cut_layer=CUT,
                                      channel=channel, max_len=PLEN + NEW,
                                      max_batch=BATCH)


def run(print_fn=print) -> dict:
    params = init_lm(jax.random.PRNGKey(0), CFG)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, CFG.vocab, PLEN).astype(np.int32)
               for _ in range(BATCH)]
    channel = Channel.from_kbps(250, rtt_ms=20)

    # -- incremental split-cache path (warm-up compile, then measure) ------
    # keep the warmed instance: the phase jits are bound methods, so a
    # fresh engine would retrace and the measurement would pay compile
    inc = _engine(params, channel)
    inc.generate(prompts, max_new_tokens=2)          # compile all phases
    inc.stats = ServeStats()
    t0 = time.perf_counter()
    inc.generate(prompts, max_new_tokens=NEW)
    t_inc = time.perf_counter() - t0
    inc_stats = inc.stats.report()

    # -- seed recompute path (re-runs the full split forward per token) ----
    # generate_recompute syncs every step (np.asarray of the argmax), so
    # the wall clock needs no extra fence
    rec = _engine(params, channel)
    t0 = time.perf_counter()
    rec.generate_recompute(prompts, max_new_tokens=NEW)
    t_rec = time.perf_counter() - t0
    rec_tokens = NEW * BATCH

    # -- analytic prediction (roofline devices + channel) ------------------
    blk = CFG.block_param_count()
    head = CFG.vocab * CFG.d_model + CFG.d_model
    pred = collab_decode_step_time(
        edge_flops=2 * blk * (CUT + 1) * BATCH,
        cloud_flops=2 * (blk * (CFG.n_layers - CUT - 1) + head) * BATCH,
        blob_bytes=BATCH * (CFG.d_model + 8),
        edge=EDGE_TX2_CLASS, cloud=CLOUD_TITANXP_CLASS, channel=channel,
        return_bytes=4 * BATCH)

    result = {
        "config": {"model": CFG.name, "cut_layer": CUT, "batch": BATCH,
                   "prompt_len": PLEN, "new_tokens": NEW},
        "incremental": {
            "wall_s": t_inc,
            "us_per_token": t_inc / (NEW * BATCH) * 1e6,
            "bytes_per_token": inc_stats["bytes_per_decode_token"],
            "prefill_bytes": inc_stats["prefill_bytes"],
            "channel_latency_s": inc_stats["channel_latency_s"],
        },
        "recompute_baseline": {
            "wall_s": t_rec,
            "us_per_token": t_rec / rec_tokens * 1e6,
            "bytes_per_token": rec.stats.transmitted_bytes / rec_tokens,
            "channel_latency_s": rec.stats.channel_latency_s,
        },
        "speedup_wall": t_rec / max(t_inc, 1e-9),
        "wire_reduction": (rec.stats.transmitted_bytes / rec_tokens)
                          / max(inc_stats["bytes_per_decode_token"], 1e-9),
        "predicted_step": {"decode_s": pred.decode_s,
                           "channel_s": pred.channel_s},
    }
    OUT.write_text(json.dumps(result, indent=1))

    i, r = result["incremental"], result["recompute_baseline"]
    print_fn(f"incremental: {i['us_per_token']:9.1f} us/token  "
             f"{i['bytes_per_token']:7.1f} B/token  "
             f"(wire {i['channel_latency_s']:.3f}s)")
    print_fn(f"recompute:   {r['us_per_token']:9.1f} us/token  "
             f"{r['bytes_per_token']:7.1f} B/token  "
             f"(wire {r['channel_latency_s']:.3f}s)")
    print_fn(f"speedup {result['speedup_wall']:.1f}x wall, "
             f"{result['wire_reduction']:.1f}x less wire traffic per token "
             f"-> {OUT}")
    return result


if __name__ == "__main__":
    run()

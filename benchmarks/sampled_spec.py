"""Sampled (temperature>0) speculative decode benchmark.

Sweeps decode temperature at a fixed draft length and measures what
stochastic acceptance does to the speculative win: each graded draft is
now accepted with probability ``min(1, p/q)`` instead of by exact argmax
match, so rising temperature taxes the acceptance rate — and the sampled
rounds additionally ship the drafter's k-1 f32 q rows uplink for the
rejection test.  Per temperature the sweep reports, against the
non-speculative (spec_k=1) *sampled* cloud baseline of the same
temperature:

  * measured acceptance rate (greedy row at t=0 for reference — the
    bit-identical fast path);
  * modeled end-to-end time per accepted token (wall + simulated
    channel) and the speedup over the serial baseline;
  * wire bytes per accepted token (the q-row surcharge shows up here);
  * the k ``autotune.tune_spec_k`` would pick at the measured stochastic
    acceptance with the q-bytes priced in (``lm_round_args
    (sampled_frac=1)``).

Row keys are dot-free (``t00``/``t05``/``t10``) so ``benchmarks.run``'s
dotted drift-guard paths can address them.  Writes
``BENCH_sampled_spec.json``; the drift guard tracks ``acceptance.t10``
and ``e2e_speedup_vs_serial.t10``.

    PYTHONPATH=src python -m benchmarks.sampled_spec
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core.autotune import spec_k_for_lm
from repro.core.costmodel import Channel
from repro.models.transformer import LMConfig, init_lm
from repro.serve.engine import (CollaborativeServingEngine, SamplingParams,
                                ServeStats)

OUT = Path("BENCH_sampled_spec.json")

CFG = LMConfig(name="sampled-bench-lm", n_layers=6, d_model=256, n_heads=8,
               n_kv=4, d_ff=1024, vocab=2048, max_seq=256, remat=False)
CUT = 1
K = 4
BATCH = 4
PLEN = 32
NEW = 16
CHANNEL = Channel.from_kbps(500, rtt_ms=100)


def _engine(params, k, max_len):
    return CollaborativeServingEngine(params, CFG, cut_layer=CUT,
                                      channel=CHANNEL, max_len=max_len,
                                      max_batch=BATCH, spec_k=k)


def _sampling(temp):
    if temp <= 0:
        return None                           # the greedy fast path
    return [SamplingParams(temperature=temp, top_p=0.95, seed=i)
            for i in range(BATCH)]


def _measure(eng, prompts, new_tokens, temp):
    eng.generate(prompts, max_new_tokens=2, sampling=_sampling(temp))
    eng.stats = ServeStats()
    t0 = time.perf_counter()
    eng.generate(prompts, max_new_tokens=new_tokens,
                 sampling=_sampling(temp))
    wall = time.perf_counter() - t0
    s = eng.stats
    acc = max(s.decode_tokens, 1)
    return {
        "wall_s": wall,
        "accepted_tokens": s.decode_tokens,
        "rounds": s.decode_steps,
        "acceptance_rate": s.acceptance_rate(),
        "e2e_us_per_accepted_token": (wall + s.channel_latency_s) / acc * 1e6,
        "wire_bytes_per_accepted_token": s.wire_bytes_per_accepted_token(),
        "channel_latency_s": s.channel_latency_s,
    }


def run(print_fn=print, quick: bool = False) -> dict:
    temps = (0.0, 1.0) if quick else (0.0, 0.5, 1.0)
    new_tokens = 8 if quick else NEW
    max_len = PLEN + NEW + K
    params = init_lm(jax.random.PRNGKey(0), CFG)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, CFG.vocab, PLEN).astype(np.int32)
               for _ in range(BATCH)]

    spec_eng = _engine(params, K, max_len)
    serial_eng = _engine(params, 1, max_len)
    rows, acceptance, speedup, tuned_k = {}, {}, {}, {}
    for temp in temps:
        key = f"t{temp:.1f}".replace(".", "")        # t00 / t05 / t10
        serial = _measure(serial_eng, prompts, new_tokens, temp)
        row = _measure(spec_eng, prompts, new_tokens, temp)
        row["e2e_speedup_vs_serial"] = (serial["e2e_us_per_accepted_token"]
                                        / row["e2e_us_per_accepted_token"])
        row["serial"] = serial
        # what the tuner would pick at the measured stochastic
        # acceptance, q-row uplink priced in for sampled traffic
        best, _ = spec_k_for_lm(CFG, CUT, batch=BATCH, channel=CHANNEL,
                                acceptance=row["acceptance_rate"],
                                ks=(1, 2, 4, 8),
                                sampled_frac=0.0 if temp <= 0 else 1.0)
        row["tuned_k_at_measured_acceptance"] = best.k
        rows[key] = row
        acceptance[key] = row["acceptance_rate"]
        speedup[key] = row["e2e_speedup_vs_serial"]
        tuned_k[key] = best.k
        print_fn(f"T={temp:.1f}: acc {row['acceptance_rate']:.2f}  e2e "
                 f"{row['e2e_us_per_accepted_token']:8.0f} us/tok "
                 f"({row['e2e_speedup_vs_serial']:.2f}x vs serial)  wire "
                 f"{row['wire_bytes_per_accepted_token']:.0f} B/tok  "
                 f"tuner k={best.k}")

    result = {
        "config": {"model": CFG.name, "cut_layer": CUT, "spec_k": K,
                   "batch": BATCH, "prompt_len": PLEN,
                   "new_tokens": new_tokens, "channel_kbps": 500,
                   "rtt_ms": 100, "top_p": 0.95, "quick": quick},
        "rows": rows,
        "acceptance": acceptance,
        "e2e_speedup_vs_serial": speedup,
        "tuned_k": tuned_k,
    }
    OUT.write_text(json.dumps(result, indent=1))
    print_fn(f"-> {OUT}")
    return result


if __name__ == "__main__":
    run()

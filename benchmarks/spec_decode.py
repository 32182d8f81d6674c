"""Speculative collaborative decode benchmark.

Measures the draft/verify-round engine (edge drafts k tokens locally
through the INT8 suffix copy, one [B, k, D] uplink blob, one batched
cloud verify with longest-prefix acceptance) against the per-token
incremental collaborative decode (PR 1's path — exactly the ``spec_k=1``
configuration of the same engine, bit for bit), on an RTT-dominated
channel where the per-token path pays two channel traversals per token.

Reported per *accepted* token, both axes of the win:
  * wall-clock (compute only — the channel is simulated) and *modeled*
    end-to-end time (wall + simulated channel latency, where the k-fold
    RTT amortization shows up);
  * wire bytes (uplink deltas + graded drafts, plus the downlink
    accept-mask + corrected token — `ServeStats` counts both).

Also records the measured draft acceptance rate, feeds it back into
``autotune.tune_spec_k``, and reports the k the auto-tuner would pick
for this channel.  Writes ``BENCH_spec_decode.json`` so future PRs have
a perf trajectory to regress against.

    PYTHONPATH=src python -m benchmarks.spec_decode
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
from repro.serve.engine import CollaborativeServingEngine, ServeStats

OUT = Path("BENCH_spec_decode.json")

CFG = LMConfig(name="spec-bench-lm", n_layers=6, d_model=256, n_heads=8,
               n_kv=4, d_ff=1024, vocab=2048, max_seq=256, remat=False)
CUT = 1
BATCH = 4
PLEN = 32
NEW = 16
# RTT-dominated wireless link: 500 KB/s with a 100 ms round trip
# (congested cellular / satellite class) — at one uplink + one downlink
# per round, the per-token path pays 200 ms/token in RTT alone before a
# single byte moves, which is exactly what drafting k tokens amortizes
CHANNEL = Channel.from_kbps(500, rtt_ms=100)


def _engine(params, k, max_len):
    return CollaborativeServingEngine(params, CFG, cut_layer=CUT,
                                      channel=CHANNEL, max_len=max_len,
                                      max_batch=BATCH, spec_k=k)


def _measure(eng, prompts, new_tokens):
    eng.generate(prompts, max_new_tokens=2)          # compile all phases
    eng.stats = ServeStats()
    t0 = time.perf_counter()
    outs = eng.generate(prompts, max_new_tokens=new_tokens)
    wall = time.perf_counter() - t0
    s = eng.stats
    acc = max(s.decode_tokens, 1)
    return outs, {
        "wall_s": wall,
        "accepted_tokens": s.decode_tokens,
        "rounds": s.decode_steps,
        "acceptance_rate": s.acceptance_rate(),
        "wall_us_per_accepted_token": wall / acc * 1e6,
        "e2e_us_per_accepted_token": (wall + s.channel_latency_s) / acc * 1e6,
        "uplink_bytes_per_accepted_token": s.bytes_per_decode_token(),
        "wire_bytes_per_accepted_token": s.wire_bytes_per_accepted_token(),
        "channel_latency_s": s.channel_latency_s,
    }


def run(print_fn=print, quick: bool = False) -> dict:
    ks = (2, 4) if quick else (2, 4, 8)
    new_tokens = 8 if quick else NEW
    max_len = PLEN + NEW + max(ks)       # speculative overshoot headroom
    params = init_lm(jax.random.PRNGKey(0), CFG)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, CFG.vocab, PLEN).astype(np.int32)
               for _ in range(BATCH)]

    # -- per-token baseline: spec_k=1 IS PR 1's incremental path ----------
    base_eng = _engine(params, 1, max_len)
    base_out, base = _measure(base_eng, prompts, new_tokens)

    sweep = {}
    best_k, best_e2e = 1, base["e2e_us_per_accepted_token"]
    for k in ks:
        eng = _engine(params, k, max_len)
        outs, row = _measure(eng, prompts, new_tokens)
        # greedy-token fidelity vs the per-token path (INT8 caches see
        # the verify's batched lattice, so near-ties may flip — the fp
        # configurations are bit-identical, see test_spec_decode)
        agree = sum(a == b for r, g in zip(base_out, outs)
                    for a, b in zip(r, g)) / (BATCH * new_tokens)
        row["token_agreement_vs_k1"] = agree
        row["wall_speedup_vs_k1"] = (base["wall_us_per_accepted_token"]
                                     / row["wall_us_per_accepted_token"])
        row["e2e_speedup_vs_k1"] = (base["e2e_us_per_accepted_token"]
                                    / row["e2e_us_per_accepted_token"])
        row["wire_reduction_vs_k1"] = (base["wire_bytes_per_accepted_token"]
                                       / row["wire_bytes_per_accepted_token"])
        sweep[k] = row
        if row["e2e_us_per_accepted_token"] < best_e2e:
            best_k, best_e2e = k, row["e2e_us_per_accepted_token"]
        print_fn(f"k={k}: acc {row['acceptance_rate']:.2f}  "
                 f"wall {row['wall_us_per_accepted_token']:8.0f} us/tok "
                 f"({row['wall_speedup_vs_k1']:.2f}x)  e2e "
                 f"{row['e2e_us_per_accepted_token']:8.0f} us/tok "
                 f"({row['e2e_speedup_vs_k1']:.2f}x)  wire "
                 f"{row['wire_bytes_per_accepted_token']:.0f} B/tok "
                 f"({row['wire_reduction_vs_k1']:.2f}x)  "
                 f"agree {agree:.0%}")

    # -- auto-tuner: what k does the model pick at the measured acceptance?
    meas_acc = float(np.mean([sweep[k]["acceptance_rate"] for k in ks]))
    tuned, perfs = spec_k_for_lm(CFG, CUT, batch=BATCH, channel=CHANNEL,
                                 acceptance=meas_acc)
    print_fn(f"per-token baseline: wall "
             f"{base['wall_us_per_accepted_token']:.0f} us/tok, e2e "
             f"{base['e2e_us_per_accepted_token']:.0f} us/tok, wire "
             f"{base['wire_bytes_per_accepted_token']:.0f} B/tok")
    print_fn(f"auto-tuner picks k={tuned.k} at measured acceptance "
             f"{meas_acc:.2f} (predicted "
             f"{tuned.s_per_token * 1e3:.1f} ms/token); measured best "
             f"k={best_k}")

    result = {
        "config": {"model": CFG.name, "cut_layer": CUT, "batch": BATCH,
                   "prompt_len": PLEN, "new_tokens": new_tokens,
                   "channel_kbps": 500, "rtt_ms": 100, "quick": quick},
        "per_token_baseline": base,
        "speculative": {str(k): v for k, v in sweep.items()},
        "measured_acceptance": meas_acc,
        "autotuned_k": tuned.k,
        "autotuned_s_per_token": tuned.s_per_token,
        "predicted": {str(p.k): {"s_per_token": p.s_per_token,
                                 "round_s": p.breakdown.total_s,
                                 "expected_tokens": p.breakdown.tokens}
                      for p in perfs},
    }
    OUT.write_text(json.dumps(result, indent=1))
    print_fn(f"-> {OUT}")
    return result


if __name__ == "__main__":
    run()

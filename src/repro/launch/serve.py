"""Serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --smoke \
        --requests 8 --collaborative --cut auto --bandwidth 250 \
        --spec-k auto --adaptive

Cloud-only mode runs the batched KV-cache engine; ``--collaborative``
splits the stack at the (auto-tuned or given) block and runs the paper's
INT8-edge / FP32-cloud mixed-precision pipeline over a simulated
wireless channel.  ``--spec-k`` turns decode into draft/verify rounds
(``auto`` self-corrects from measured acceptance between requests);
``--adaptive`` closes the whole tuning loop online — link telemetry
re-tunes both the draft length and the cut layer while serving.

``--temperature``/``--top-p``/``--sample-seed`` sample instead of
greedy decode: verify becomes exact rejection sampling against the
cloud distribution (outputs match non-speculative cloud sampling),
and the per-request seeds make every stream replay bit-identically.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.core.autotune import AutoTuner
from repro.core.costmodel import (CLOUD_TITANXP_CLASS, Channel,
                                  EDGE_TX2_CLASS)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_lm, make_graph
from repro.serve.engine import (CollaborativeServingEngine, SamplingParams,
                                ServingEngine)


def auto_cut(cfg, channel: Channel, prompt_len: int) -> int:
    """Algorithm 1's partition point for ``cfg`` over ``channel``, as
    the last edge block (a cut at the input keeps block 0 on the
    edge)."""
    graph = make_graph(cfg, batch=1, seq=prompt_len)
    best, _ = AutoTuner(graph, EDGE_TX2_CLASS, CLOUD_TITANXP_CLASS).tune(
        channel)
    cut_layer = (int(best.point.split("/")[0][3:])
                 if best.point.startswith("blk") else 0)
    print(f"auto-tuned cut (Algorithm 1): {best.point} "
          f"-> edge blocks 0..{cut_layer}")
    return cut_layer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--collaborative", action="store_true")
    ap.add_argument("--cut", default="auto")
    ap.add_argument("--bandwidth", type=float, default=250.0,
                    help="wireless KB/s for the collaborative channel")
    ap.add_argument("--rtt", type=float, default=20.0,
                    help="wireless round-trip time in ms")
    ap.add_argument("--spec-k", default="1",
                    help="speculative draft length: an int, or 'auto' to "
                         "tune from the channel and keep self-correcting "
                         "from measured acceptance")
    ap.add_argument("--adaptive", action="store_true",
                    help="online control loop: telemetry re-tunes spec_k "
                         "between rounds and the cut layer at admission "
                         "boundaries")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="decode temperature; 0 keeps the greedy fast "
                         "path, >0 turns verify into exact rejection "
                         "sampling against the cloud distribution")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus cutoff applied to the cloud "
                         "distribution before sampling (1.0 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base PRNG seed; request i samples with "
                         "seed+i so outputs replay bit-identically")
    args = ap.parse_args(argv)
    enable_compile_cache()

    spec = get_arch(args.arch)
    assert spec.family == "lm", "serving launcher targets the LM family"
    cfg = spec.smoke if args.smoke else spec.full
    print(f"serving {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab}")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, args.prompt_len).astype(np.int32)
               for _ in range(args.requests)]
    spec_k = args.spec_k if args.spec_k == "auto" else int(args.spec_k)
    max_len = args.prompt_len + args.max_new + 24
    # per-request seeds so every output stream replays bit-identically;
    # temperature 0 stays on the greedy fast path (sampling=None)
    sampling = None
    if args.temperature > 0:
        sampling = [SamplingParams(temperature=args.temperature,
                                   top_p=args.top_p,
                                   seed=args.sample_seed + i)
                    for i in range(args.requests)]

    if not args.collaborative:
        if sampling is not None:
            raise SystemExit("--temperature>0 needs --collaborative: the "
                             "rejection-sampling verify lives in the "
                             "collaborative engine")
        eng = ServingEngine(params, cfg, max_batch=4, max_len=max_len)
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=args.max_new)
        dt = time.perf_counter() - t0
        print(f"cloud-only: {args.requests} reqs x {args.max_new} tokens "
              f"in {dt:.2f}s ({eng.stats.decode_steps} decode steps)")
        print("first output:", outs[0])
        return

    channel = Channel.from_kbps(args.bandwidth, rtt_ms=args.rtt)
    if args.cut == "auto":
        cut_layer = auto_cut(cfg, channel, args.prompt_len)
    else:
        cut_layer = int(args.cut)
    if args.adaptive and cut_layer > cfg.n_layers - 2:
        cut_layer = cfg.n_layers - 2
        print(f"adaptive mode: clamping cut to {cut_layer} so every "
              f"candidate partition keeps a cloud block")
    eng = CollaborativeServingEngine(
        params, cfg, cut_layer=cut_layer, channel=channel, max_len=max_len,
        spec_k=spec_k, policy="auto" if args.adaptive else None)
    if sampling is not None:
        print(f"sampling: temperature={args.temperature} "
              f"top_p={args.top_p} seeds {args.sample_seed}.."
              f"{args.sample_seed + args.requests - 1} "
              f"(exact cloud distribution via rejection-sampled verify)")
    t0 = time.perf_counter()
    outs = eng.generate(prompts, max_new_tokens=args.max_new,
                        sampling=sampling)
    dt = time.perf_counter() - t0
    print(f"collaborative: {dt:.2f}s, int8 wire bytes "
          f"{eng.stats.transmitted_bytes / 1e3:.1f}KB "
          f"({eng.stats.prefill_bytes / 1e3:.1f}KB prefill + "
          f"{eng.stats.bytes_per_decode_token():.0f} B/token incremental "
          f"decode), simulated channel "
          f"time {eng.stats.channel_latency_s:.2f}s")
    if eng.spec_k > 1 or eng.policy is not None:
        print(f"control loop: spec_k={eng.spec_k} cut={eng.cut} "
              f"(switches: k={eng.stats.spec_k_switches}, "
              f"cut={eng.stats.cut_switches}; draft acceptance "
              f"{eng.stats.acceptance_rate():.0%})")
    print("first output:", outs[0])


if __name__ == "__main__":
    main()

"""Quickstart: the paper's full pipeline on a small CNN in ~a minute.

  1. build a model + its layer graph
  2. find candidate partition points (§2.2 rules)
  3. auto-tune the cut for several wireless bandwidths (Algorithm 1)
  4. run collaborative inference: INT8 edge → simulated channel → FP32 cloud

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.autotune import AutoTuner
from repro.core.collab import CollaborativeEngine
from repro.core.costmodel import (CLOUD_TITANXP_CLASS, Channel,
                                  EDGE_TX2_CLASS)
from repro.core.partition import partition_report
from repro.launch.compile_cache import enable_compile_cache
from repro.models import legacy


def main():
    enable_compile_cache()
    print("== AlexNet (paper Table 3 subject), ImageNet-sized input ==\n")
    graph = legacy.alexnet_graph()
    print(partition_report(graph))

    print("\n== Algorithm 1: best cut per wireless bandwidth ==")
    tuner = AutoTuner(graph, EDGE_TX2_CLASS, CLOUD_TITANXP_CLASS)
    print(f"{'bandwidth':>12} {'best cut':>10} {'total (s)':>10} "
          f"{'upload (KB)':>12} {'edge model (KB)':>16} {'storage red.':>12}")
    for kbps in (50, 100, 250, 500, 1000, 10000):
        ch = Channel.from_kbps(kbps)
        best, _ = tuner.tune(ch)
        print(f"{kbps:>10} KB/s {best.point:>10} {best.total_s:>10.3f} "
              f"{best.transmit_bytes / 1e3:>12.1f} "
              f"{best.edge_model_bytes / 1e3:>16.1f} "
              f"{best.storage_reduction:>11.1%}")
    sp = tuner.speedup_vs_cloud_only(Channel.from_kbps(250))
    print(f"\nspeed-up vs cloud-only @250KB/s: {sp:.2f}x "
          f"(paper reports 1.7x for AlexNet)")

    print("\n== collaborative inference on device (small CNN, real compute) ==")
    from tests.test_collab import tiny_cnn, _input
    model = tiny_cnn()
    x = _input(batch=1)
    truth = model.full_apply(x)
    for cut in ("input", "conv1", "conv2", "head"):
        eng = CollaborativeEngine(model, cut,
                                  channel=Channel.from_kbps(250),
                                  calib_batches=[_input(seed=9)])
        y, rec = eng.infer(x)
        rel = float(jnp.linalg.norm(y - truth) / jnp.linalg.norm(truth))
        print(f"  cut={cut:6s} blob={rec.blob_bytes:6d}B ({rec.precision}) "
              f"sim-latency={rec.simulated_latency_s * 1e3:7.2f}ms "
              f"rel-err vs fp32={rel:.4f}")
    print("\nDone. The INT8 edge keeps the output within quantization noise.")


if __name__ == "__main__":
    main()

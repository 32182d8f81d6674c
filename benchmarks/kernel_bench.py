"""Quantized-kernel micro-benchmarks.

Two measurements:
  1. wall-clock of the XLA INT8 path vs FP32 matmul on this host (real
     computation — shows the int8 arithmetic works end to end), and
  2. the analytic MXU model for the Pallas kernel (the TPU target):
     int8 394 TOP/s vs bf16 197 TFLOP/s per chip, fused epilogue saving
     3 extra HBM round-trips of the accumulator.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import compute_qparams, quantize
from repro.kernels.ref import int8_matmul_ref


def _time(fn, *args, iters=5) -> float:
    jax.block_until_ready(fn(*args))           # compile + drain the queue
    t0 = time.perf_counter()
    for _ in range(iters):
        # fence every iteration: async dispatch would otherwise overlap
        # device work with the host loop and under-report per-iter time
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def run(print_fn=print, *, m=512, k=1024, n=512) -> dict:
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.uniform(-2, 2, (m, k)).astype(np.float32))
    w = jnp.asarray(rng.uniform(-1, 1, (k, n)).astype(np.float32))
    qa, qw = compute_qparams(a), compute_qparams(w, axis=1)
    a_q, w_q = quantize(a, qa), quantize(w, qw)

    f32 = jax.jit(lambda x, y: x @ y)
    int8 = jax.jit(lambda x, y: int8_matmul_ref(x, y, qa, qw))

    t_f32 = _time(f32, a, w)
    t_int8 = _time(int8, a_q, w_q)
    err = float(jnp.linalg.norm(int8(a_q, w_q) - a @ w)
                / jnp.linalg.norm(a @ w))

    flops = 2 * m * k * n
    mxu_bf16_s = flops / 197e12
    mxu_int8_s = flops / 394e12
    # unfused epilogue: acc int32 + dequant f32 + requant int8 round-trips
    hbm_extra = m * n * (4 + 4 + 1) / 819e9
    print_fn(f"host XLA  fp32 matmul {m}x{k}x{n}: {t_f32 * 1e6:9.1f} us")
    print_fn(f"host XLA  int8 matmul (+ asym corr): {t_int8 * 1e6:9.1f} us "
             f"(rel err vs fp32 {err:.4f})")
    print_fn(f"MXU model bf16: {mxu_bf16_s * 1e6:7.2f} us   int8: "
             f"{mxu_int8_s * 1e6:7.2f} us (2.0x)")
    print_fn(f"fused epilogue saves {hbm_extra * 1e6:.2f} us of HBM traffic "
             f"per call (acc+dequant+requant round-trips)")
    return {"t_f32_us": t_f32 * 1e6, "t_int8_us": t_int8 * 1e6,
            "rel_err": err, "mxu_speedup": 2.0,
            "epilogue_saving_us": hbm_extra * 1e6}


def run_paged(print_fn=print, *, batch=4, n_heads=8, n_kv=4, hd=32,
              page_size=16, seq=128) -> dict:
    """Paged-attention microbenchmark: one decode step's attention read.

    The dense baseline is what ``_sdpa`` does each decode step over a
    pre-allocated fp16 cache: stream all ``max_len`` positions, mask the
    tail.  The paged INT8 path streams only the pages a request actually
    allocated (``seq`` long here) at 1 B/elem.  Sweeping ``max_len``
    shows the dense cost growing with the pre-allocation while the paged
    cost stays flat — the same asymptotics the Pallas kernel has on TPU,
    measured here through the XLA reference path (the off-TPU
    production fallback).  The Pallas kernel itself is checked for
    parity against that reference in interpret mode."""
    import math

    from repro.kernels.paged_attention import (paged_attention_ref,
                                               paged_flash_decode)

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(batch, n_heads, hd).astype(np.float32))
    pages_per = seq // page_size
    n_pages = batch * pages_per + 1
    kp = jnp.asarray(rng.randint(-127, 128,
                                 (n_pages, n_kv, page_size, hd))
                     .astype(np.int8))
    vp = jnp.asarray(rng.randint(-127, 128,
                                 (n_pages, n_kv, page_size, hd))
                     .astype(np.int8))
    bt = jnp.asarray(np.arange(1, n_pages).reshape(batch, pages_per)
                     .astype(np.int32))
    lens = jnp.full((batch,), seq, jnp.int32)
    ks = jnp.full((batch, n_kv), 0.03, jnp.float32)
    vs = jnp.full((batch, n_kv), 0.03, jnp.float32)

    paged = jax.jit(lambda *a: paged_attention_ref(*a))
    t_paged = _time(paged, q, kp, vp, bt, lens, ks, vs)
    err = float(jnp.abs(
        paged_flash_decode(q, kp, vp, bt, lens, ks, vs, interpret=True)
        - paged(q, kp, vp, bt, lens, ks, vs)).max())
    paged_bytes = 2 * batch * pages_per * page_size * n_kv * hd

    def dense_step(qd, k, v, ln):
        g = n_heads // n_kv
        qg = qd.reshape(batch, n_kv, g, hd).astype(jnp.float32) \
            / math.sqrt(hd)
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        s = jnp.einsum("bhgd,blhd->bhgl", qg, kf)
        mask = jnp.arange(k.shape[1])[None, None, None, :] \
            < ln[:, None, None, None]
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgl,blhd->bhgd", p, vf)

    rows = []
    for max_len in (256, 1024, 4096):
        kd = jnp.asarray(rng.randn(batch, max_len, n_kv, hd)
                         .astype(np.float16))
        vd = jnp.asarray(rng.randn(batch, max_len, n_kv, hd)
                         .astype(np.float16))
        dense = jax.jit(dense_step)
        t_dense = _time(dense, q, kd, vd, lens)
        dense_bytes = 2 * batch * max_len * n_kv * hd * 2
        rows.append({"max_len": max_len,
                     "t_dense_fp16_us": t_dense * 1e6,
                     "t_paged_int8_us": t_paged * 1e6,
                     "speedup": t_dense / t_paged,
                     "dense_cache_bytes": dense_bytes,
                     "paged_cache_bytes": paged_bytes})
        print_fn(f"max_len {max_len:5d}: dense fp16 {t_dense * 1e6:9.1f} us "
                 f"{dense_bytes / 1024:8.0f} KiB | paged int8 "
                 f"{t_paged * 1e6:9.1f} us {paged_bytes / 1024:6.0f} KiB "
                 f"({t_dense / t_paged:5.1f}x)")
    print_fn(f"pallas kernel (interpret) vs XLA ref max err: {err:.2e}")
    return {"sweep": rows, "kernel_ref_err": err,
            "paged_speedup_at_4096": rows[-1]["speedup"]}


if __name__ == "__main__":
    run()
    run_paged()

"""Pallas TPU kernel: fused INT8 matmul with dequant→act→requant epilogue.

TPU adaptation of the paper's gemmlowp edge GEMM (§2.1, "On-device
Computation" steps 1-4). The MXU consumes int8 operand tiles natively with
int32 accumulation; instead of the paper's four separate passes
(int GEMM → Eq.2 dequantize → activation → Eq.1 requantize), everything
after the GEMM runs as a *fused epilogue* on the final K-step, so the
int32 accumulator never round-trips through HBM.

Tiling: grid = (M/bm, N/bn, K/bk), K innermost. Per-block VMEM residency:
  A-tile   int8  [bm, bk]
  B-tile   int8  [bk, bn]
  acc      int32 [bm, bn]  (scratch, lives across the K axis)
  rowsum_a int32 [bm, 1]   (scratch — zero-point correction term)
  colsum_b int32 [1,  bn]  (scratch)
Default (bm, bn, bk) = (256, 256, 256) →
  64 KiB + 64 KiB + 256 KiB + ~1 KiB ≈ 0.4 MiB « 16 MiB VMEM,
with all matmul dims multiples of 128 to keep the 128×128 systolic array
fully occupied (int8 packs 32×128 sublane tiles).

The asymmetric (paper Eq.1 has independent T_min/T_max for inputs AND
weights) correction is exact:

  real = sa·sb·(acc − za·colsum(Bq) − zb·rowsum(Aq) + za·zb·K)

with per-channel weight scale/zero-point supported as (1, bn) vectors.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = {
    None: lambda x: x,
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _kernel(
    # refs, in BlockSpec order
    a_ref, b_ref,              # int8 tiles
    sa_ref, za_ref,            # (1,1) f32 activation scale / zero-point
    sb_ref, zb_ref,            # (1,bn) f32 weight scale / zero-point
    bias_ref,                  # (1,bn) f32
    so_ref, zo_ref,            # (1,1) f32 output requant params
    out_ref,                   # [bm,bn] int8 or f32
    acc_ref, rs_ref, cs_ref,   # scratch
    *,
    k_steps: int,
    true_k: int,
    act: Optional[str],
    requant: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rs_ref[...] = jnp.zeros_like(rs_ref)
        cs_ref[...] = jnp.zeros_like(cs_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    rs_ref[...] += jnp.sum(a.astype(jnp.int32), axis=1, keepdims=True)
    cs_ref[...] += jnp.sum(b.astype(jnp.int32), axis=0, keepdims=True)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        sa = sa_ref[0, 0]
        za = za_ref[0, 0]
        sb = sb_ref[...]                       # (1, bn)
        zb = zb_ref[...]
        acc = acc_ref[...].astype(jnp.float32)
        rs = rs_ref[...].astype(jnp.float32)   # (bm, 1)
        cs = cs_ref[...].astype(jnp.float32)   # (1, bn)
        real = (sa * sb) * (acc - za * cs - zb * rs + za * zb * float(true_k))
        real = real + bias_ref[...]
        real = _ACTS[act](real)
        if requant:
            so = so_ref[0, 0]
            zo = zo_ref[0, 0]
            q = jnp.round(real / so + zo)
            info = jnp.iinfo(out_ref.dtype)
            out_ref[...] = jnp.clip(q, info.min, info.max).astype(out_ref.dtype)
        else:
            out_ref[...] = real.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block", "act", "requant", "true_k", "interpret"))
def int8_matmul_pallas(
    a_q: jax.Array,            # int8 [M, K]   (M, K multiples of block)
    b_q: jax.Array,            # int8 [K, N]
    sa: jax.Array, za: jax.Array,         # () f32
    sb: jax.Array, zb: jax.Array,         # (N,) f32
    bias: jax.Array,                      # (N,) f32
    so: jax.Array, zo: jax.Array,         # () f32
    *,
    true_k: int,
    block: tuple[int, int, int] = (256, 256, 256),
    act: Optional[str] = None,
    requant: bool = False,
    interpret: bool = False,
) -> jax.Array:
    m, k = a_q.shape
    _, n = b_q.shape
    bm, bn, bk = block
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, block)
    grid = (m // bm, n // bn, k // bk)

    sa2 = sa.reshape(1, 1).astype(jnp.float32)
    za2 = za.reshape(1, 1).astype(jnp.float32)
    sb2 = sb.reshape(1, n).astype(jnp.float32)
    zb2 = zb.reshape(1, n).astype(jnp.float32)
    bias2 = bias.reshape(1, n).astype(jnp.float32)
    so2 = so.reshape(1, 1).astype(jnp.float32)
    zo2 = zo.reshape(1, 1).astype(jnp.float32)

    out_dtype = jnp.int8 if requant else jnp.float32
    kernel = functools.partial(
        _kernel, k_steps=grid[2], true_k=true_k, act=act, requant=requant)

    scalar_spec = pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0))
    colvec_spec = pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            scalar_spec, scalar_spec,       # sa, za
            colvec_spec, colvec_spec,       # sb, zb
            colvec_spec,                    # bias
            scalar_spec, scalar_spec,       # so, zo
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, 1), jnp.int32),
            pltpu.VMEM((1, bn), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_q, b_q, sa2, za2, sb2, zb2, bias2, so2, zo2)

"""Public jit'd wrappers around the Pallas kernels.

``int8_matmul`` / ``quantized_dense`` handle arbitrary shapes by padding to
block multiples (zero int8 padding is exact for the asymmetric correction —
padded K entries contribute 0 to acc, rowsum and colsum, and the za·zb·K
term uses the *true* K).  Off TPU they run the kernel through the Pallas
interpreter, which is for tests only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import QuantParams, compute_qparams, quantize
from repro.kernels.int8_matmul import int8_matmul_pallas

__all__ = ["int8_matmul", "quantized_dense", "default_interpret"]


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """Pallas runs compiled on TPU, interpreted (Python/CPU) elsewhere."""
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _pick_block(m: int, n: int, k: int, want: tuple[int, int, int], *,
                interpret: bool) -> tuple[int, int, int]:
    """Shrink the requested block toward the problem size.

    Compiled, the TPU lowering accepts only blocks whose (M, N, K) dims
    are multiples of (32, 128, 128) for int8 operands, so a block never
    shrinks below that and the operands are padded up to it instead.
    The interpreter takes any block, so small test shapes keep small
    blocks there."""
    floors = (8, 8, 8) if interpret else (32, 128, 128)

    def fit(dim, b, floor):
        while b > dim and b > floor:
            b //= 2
        b = max(b, floor)
        return b if interpret else -(-b // floor) * floor

    return tuple(fit(d, b, f) for d, b, f in zip((m, n, k), want, floors))


def int8_matmul(
    a_q: jax.Array,
    b_q: jax.Array,
    qa: QuantParams,
    qb: QuantParams,
    *,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    out_qp: Optional[QuantParams] = None,
    block: tuple[int, int, int] = (256, 256, 256),
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused quantized matmul: int8[M,K] @ int8[K,N] → f32 or int8 [M,N]."""
    if interpret is None:
        interpret = default_interpret()
    m, k = a_q.shape
    _, n = b_q.shape
    bm, bn, bk = _pick_block(m, n, k, block, interpret=interpret)
    a_p = _pad_to(a_q, (bm, bk))
    b_p = _pad_to(b_q, (bk, bn))
    n_pad = b_p.shape[1]

    sb = jnp.broadcast_to(jnp.atleast_1d(qb.scale), (n,))
    zb = jnp.broadcast_to(jnp.atleast_1d(qb.zero_point), (n,))
    sb = _pad_to(sb, (bn,))
    # pad zb/bias with zeros; padded cols are sliced off anyway
    zb = _pad_to(zb, (bn,))
    bias_v = jnp.zeros((n,), jnp.float32) if bias is None else bias
    bias_v = _pad_to(bias_v.astype(jnp.float32), (bn,))

    requant = out_qp is not None
    so = out_qp.scale if requant else jnp.float32(1.0)
    zo = out_qp.zero_point if requant else jnp.float32(0.0)

    out = int8_matmul_pallas(
        a_p, b_p,
        jnp.asarray(qa.scale), jnp.asarray(qa.zero_point),
        sb, zb, bias_v, jnp.asarray(so), jnp.asarray(zo),
        true_k=k, block=(bm, bn, bk), act=act, requant=requant,
        interpret=interpret)
    return out[:m, :n]


def quantized_dense(
    x: jax.Array,
    w_q: jax.Array,
    qx: QuantParams,
    qw: QuantParams,
    *,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    out_qp: Optional[QuantParams] = None,
    block: tuple[int, int, int] = (256, 256, 256),
    interpret: Optional[bool] = None,
) -> jax.Array:
    """fp activations → Eq.1 quantize → fused int8 matmul → epilogue.

    This is one full "layer" of the paper's on-device computation.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x_q = quantize(x2, qx)
    out = int8_matmul(x_q, w_q, qx, qw, bias=bias, act=act, out_qp=out_qp,
                      block=block, interpret=interpret)
    return out.reshape(*lead, out.shape[-1])

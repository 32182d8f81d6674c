"""Scalar INT8 quantization core — paper §2.1, Eq. (1)/(2).

The paper quantizes with *asymmetric affine* (min/max-threshold) scalar
quantization:

    Eq.(1)  Data_Q(x) = (x - T_min) / |T_max - T_min| * Range_LP   (clipped)
    Eq.(2)  Data(x)   = |T_max - T_min| / Range_LP * Data_Q(x) + T_min

which is the standard affine scheme with

    scale      = (T_max - T_min) / Range_LP
    zero_point = round(-T_min / scale)
    q          = clip(round(x / scale + zero_point), q_min, q_max)
    x̂          = scale * (q - zero_point)

We keep *both* the paper's unsigned representation (q ∈ [0, 255]) and a
signed one (q ∈ [-128, 127], the MXU's native int8 operand format); they
differ only by a constant shift of 128 folded into the zero point.

Everything here is pure JAX (jit/grad/vmap-safe); the Pallas kernels in
``repro.kernels`` consume the same ``QuantParams``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "QuantParams",
    "compute_qparams",
    "quantize",
    "dequantize",
    "fake_quant",
    "MinMaxCalibrator",
    "PercentileCalibrator",
    "EMACalibrator",
    "quantize_pytree",
    "dequantize_pytree",
    "pytree_quant_bytes",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QuantParams:
    """Affine quantization parameters for one tensor.

    ``scale``/``zero_point`` are scalars (per-tensor) or 1-D arrays of
    length ``shape[axis]`` (per-channel).  ``signed`` selects the int8
    representation: unsigned [0, 255] is the paper's Eq.(1); signed
    [-128, 127] is the same lattice shifted by 128 (MXU operand format).
    """

    scale: jax.Array          # f32, () or (C,)
    zero_point: jax.Array     # f32 (kept float; rounded at use), () or (C,)
    axis: Optional[int] = None
    bits: int = 8
    signed: bool = True

    # -- pytree plumbing (axis/bits/signed are static) ------------------
    def tree_flatten(self):
        return (self.scale, self.zero_point), (self.axis, self.bits, self.signed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        scale, zero_point = children
        axis, bits, signed = aux
        return cls(scale=scale, zero_point=zero_point, axis=axis, bits=bits,
                   signed=signed)

    # -- derived constants ----------------------------------------------
    @property
    def qmin(self) -> int:
        return -(2 ** (self.bits - 1)) if self.signed else 0

    @property
    def qmax(self) -> int:
        return 2 ** (self.bits - 1) - 1 if self.signed else 2 ** self.bits - 1

    @property
    def range_lp(self) -> int:
        """The paper's Range_LP (255 for INT8)."""
        return 2 ** self.bits - 1

    @property
    def storage_dtype(self):
        if self.bits <= 8:
            return jnp.int8 if self.signed else jnp.uint8
        return jnp.int16 if self.signed else jnp.uint16

    def _bcast(self, arr: jax.Array, ndim: int) -> jax.Array:
        """Broadcast a per-channel vector against an ndim-rank tensor."""
        if self.axis is None or jnp.ndim(arr) == 0:
            return arr
        shape = [1] * ndim
        shape[self.axis] = -1
        return arr.reshape(shape)


def _minmax_to_qparams(t_min: jax.Array, t_max: jax.Array, *, bits: int,
                       signed: bool, axis: Optional[int]) -> QuantParams:
    """Thresholds → (scale, zero_point), the paper's "Step 1"."""
    t_min = jnp.minimum(t_min, 0.0)   # keep 0 representable (exact zero pad)
    t_max = jnp.maximum(t_max, 0.0)
    range_lp = float(2 ** bits - 1)
    span = jnp.maximum(t_max - t_min, 1e-12)
    scale = span / range_lp
    qmin = -(2 ** (bits - 1)) if signed else 0
    zero_point = jnp.round(qmin - t_min / scale)
    zero_point = jnp.clip(zero_point, qmin, qmin + range_lp)
    return QuantParams(scale=scale.astype(jnp.float32),
                       zero_point=zero_point.astype(jnp.float32),
                       axis=axis, bits=bits, signed=signed)


def compute_qparams(x: jax.Array, *, axis: Optional[int] = None,
                    bits: int = 8, signed: bool = True,
                    symmetric: bool = False) -> QuantParams:
    """One-shot min/max calibration of a single tensor (paper Step 1)."""
    if axis is None:
        t_min = jnp.min(x)
        t_max = jnp.max(x)
    else:
        red = tuple(d for d in range(x.ndim) if d != axis)
        t_min = jnp.min(x, axis=red)
        t_max = jnp.max(x, axis=red)
    if symmetric:
        amax = jnp.maximum(jnp.abs(t_min), jnp.abs(t_max))
        t_min, t_max = -amax, amax
    return _minmax_to_qparams(t_min, t_max, bits=bits, signed=signed, axis=axis)


def quantize(x: jax.Array, qp: QuantParams) -> jax.Array:
    """Paper Eq.(1): real → low-precision lattice, with saturation."""
    scale = qp._bcast(qp.scale, x.ndim)
    zp = qp._bcast(qp.zero_point, x.ndim)
    q = jnp.round(x / scale + zp)
    q = jnp.clip(q, qp.qmin, qp.qmax)
    return q.astype(qp.storage_dtype)


def dequantize(q: jax.Array, qp: QuantParams) -> jax.Array:
    """Paper Eq.(2): lattice → real."""
    scale = qp._bcast(qp.scale, q.ndim)
    zp = qp._bcast(qp.zero_point, q.ndim)
    return (q.astype(jnp.float32) - zp) * scale


@partial(jax.custom_vjp, nondiff_argnums=())
def _ste_roundtrip(x, scale, zp, qmin, qmax):
    q = jnp.clip(jnp.round(x / scale + zp), qmin, qmax)
    return (q - zp) * scale


def _ste_fwd(x, scale, zp, qmin, qmax):
    out = _ste_roundtrip(x, scale, zp, qmin, qmax)
    # Gradient passes wherever the *rounded* value is representable, i.e.
    # qmin - 0.5 <= x/scale + zp <= qmax + 0.5 (clipped-STE).
    t = x / scale + zp
    inside = jnp.logical_and(t >= qmin - 0.5, t <= qmax + 0.5)
    return out, (inside,)


def _ste_bwd(res, g):
    (inside,) = res
    # Straight-through: pass gradient where the value was representable,
    # zero where it saturated (clipped-STE).
    gx = jnp.where(inside, g, 0.0)
    return gx, None, None, None, None


_ste_roundtrip.defvjp(_ste_fwd, _ste_bwd)


def fake_quant(x: jax.Array, qp: QuantParams) -> jax.Array:
    """Quantize→dequantize with a straight-through gradient (QAT), in
    ``x``'s dtype: a bf16 model keeps bf16 weights and activations (the
    f32 qparams would otherwise promote them)."""
    scale = qp._bcast(qp.scale, x.ndim)
    zp = qp._bcast(qp.zero_point, x.ndim)
    return _ste_roundtrip(x, scale, zp, float(qp.qmin),
                          float(qp.qmax)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Calibrators — the "off-line quantization Step 1" of the paper, run over a
# stream of calibration batches.
# ---------------------------------------------------------------------------


class MinMaxCalibrator:
    """Running global min/max over observed batches."""

    def __init__(self, *, axis: Optional[int] = None, bits: int = 8,
                 signed: bool = True, symmetric: bool = False):
        self.axis, self.bits, self.signed = axis, bits, signed
        self.symmetric = symmetric
        self._min = None
        self._max = None

    def _reduce(self, x):
        if self.axis is None:
            return jnp.min(x), jnp.max(x)
        red = tuple(d for d in range(x.ndim) if d != self.axis)
        return jnp.min(x, axis=red), jnp.max(x, axis=red)

    def observe(self, x: jax.Array) -> None:
        lo, hi = self._reduce(x)
        if self._min is None:
            self._min, self._max = lo, hi
        else:
            self._min = jnp.minimum(self._min, lo)
            self._max = jnp.maximum(self._max, hi)

    def qparams(self) -> QuantParams:
        assert self._min is not None, "observe() at least one batch first"
        t_min, t_max = self._min, self._max
        if self.symmetric:
            amax = jnp.maximum(jnp.abs(t_min), jnp.abs(t_max))
            t_min, t_max = -amax, amax
        return _minmax_to_qparams(t_min, t_max, bits=self.bits,
                                  signed=self.signed, axis=self.axis)


class PercentileCalibrator:
    """Clip thresholds at a percentile of the observed magnitude
    distribution — robust to activation outliers (per-tensor only)."""

    def __init__(self, percentile: float = 99.9, *, bits: int = 8,
                 signed: bool = True):
        assert 50.0 < percentile <= 100.0
        self.percentile, self.bits, self.signed = percentile, bits, signed
        self._samples: list[np.ndarray] = []
        self._budget = 1 << 22   # cap retained samples

    def observe(self, x: jax.Array) -> None:
        flat = np.asarray(x, dtype=np.float32).ravel()
        if flat.size > 65536:   # subsample deterministically
            stride = flat.size // 65536
            flat = flat[::stride]
        self._samples.append(flat)
        total = sum(s.size for s in self._samples)
        while total > self._budget and len(self._samples) > 1:
            total -= self._samples.pop(0).size

    def qparams(self) -> QuantParams:
        assert self._samples
        allv = np.concatenate(self._samples)
        lo = np.percentile(allv, 100.0 - self.percentile)
        hi = np.percentile(allv, self.percentile)
        return _minmax_to_qparams(jnp.float32(lo), jnp.float32(hi),
                                  bits=self.bits, signed=self.signed, axis=None)


class EMACalibrator:
    """Exponential-moving-average min/max (TensorRT-style smoothing)."""

    def __init__(self, momentum: float = 0.95, *, axis: Optional[int] = None,
                 bits: int = 8, signed: bool = True):
        self.momentum, self.axis, self.bits, self.signed = momentum, axis, bits, signed
        self._min = None
        self._max = None

    def observe(self, x: jax.Array) -> None:
        if self.axis is None:
            lo, hi = jnp.min(x), jnp.max(x)
        else:
            red = tuple(d for d in range(x.ndim) if d != self.axis)
            lo, hi = jnp.min(x, axis=red), jnp.max(x, axis=red)
        if self._min is None:
            self._min, self._max = lo, hi
        else:
            m = self.momentum
            self._min = m * self._min + (1 - m) * lo
            self._max = m * self._max + (1 - m) * hi

    def qparams(self) -> QuantParams:
        assert self._min is not None
        return _minmax_to_qparams(self._min, self._max, bits=self.bits,
                                  signed=self.signed, axis=self.axis)


# ---------------------------------------------------------------------------
# Pytree helpers — quantize a whole parameter tree (the edge engine's model
# download is the quantized tree; the paper's "model storage reduction").
# ---------------------------------------------------------------------------


def _leaf_axis(path, leaf) -> Optional[int]:
    """Per-channel along the output-feature axis for rank>=2 kernels."""
    if leaf.ndim >= 2:
        return leaf.ndim - 1
    return None


def quantize_pytree(params, *, bits: int = 8, signed: bool = True,
                    per_channel: bool = True, symmetric_weights: bool = False):
    """Quantize every float leaf. Returns (q_tree, qp_tree)."""

    def one(path, leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf, None
        axis = _leaf_axis(path, leaf) if per_channel else None
        qp = compute_qparams(leaf, axis=axis, bits=bits, signed=signed,
                             symmetric=symmetric_weights)
        return quantize(leaf, qp), qp

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    qs, qps = [], []
    for path, leaf in flat:
        q, qp = one(path, leaf)
        qs.append(q)
        qps.append(qp)
    return (jax.tree_util.tree_unflatten(treedef, qs),
            jax.tree_util.tree_unflatten(treedef, qps))


def dequantize_pytree(q_tree, qp_tree):
    def one(q, qp):
        if qp is None:
            return q
        return dequantize(q, qp)
    return jax.tree_util.tree_map(one, q_tree, qp_tree,
                                  is_leaf=lambda x: x is None)


def pytree_quant_bytes(params, *, bits: int = 8) -> tuple[int, int]:
    """(fp32_bytes, quantized_bytes incl. per-tensor scale/zp overhead)."""
    fp = 0
    qb = 0
    for leaf in jax.tree_util.tree_leaves(params):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        fp += n * 4
        qb += (n * bits + 7) // 8 + 8   # +8B for scale/zero_point
    return fp, qb

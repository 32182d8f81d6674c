"""Plain float32 reference of the collaborative decoder, and the check.

What the configuration states is served: a decoder whose first
``cut + 1`` blocks run on the edge on an INT8 lattice, whose boundary
activation crosses the link quantized, and whose KV caches hold INT8
entries.  This module computes that model in float32, every matmul at
``HIGHEST`` precision, in plain ``jax.numpy`` with no kernel, page pool
or batching, and imports nothing of the program.  The math of one block
is the architecture's (``block`` of ``bench/archs/<arch>.py``); this
module runs the stack of blocks, the boundary, the final norm and the
head, and hands each block call its lattice (``OnLattice``) and its KV
cache (``_kv_protocol``).  The lattice it applies is the deployment's,
as stated:

* edge weights: asymmetric min/max INT8 per output channel, per layer;
* edge matmul inputs: asymmetric min/max INT8, one range per request over
  a whole prefill call (its bucket-padded prompt, pad token 0) and one
  range per token in decode;
* boundary: asymmetric INT8, one range per request over its real prompt
  positions at prefill and one per token in decode;
* KV caches: symmetric INT8 per (request, kv head), the scale set from
  the real prompt positions at prefill; later positions reuse it and
  saturate at +-127.

Thresholds and lattice points are values of the configuration's dtype
(a bf16 deployment stores bf16 scales and bf16 dequantized values); all
other arithmetic is float32.

The check feeds the reference each sampled request's prompt and the
tokens the program served, teacher-forced, and reads at every served
position the gap by which the served token's logit lies below the
reference's best.  A greedy server that computes what the configuration
states serves tokens whose gaps are rounding-sized; a token produced
wrongly, or a path computed in a lower precision, opens a wider gap.

Three numbers are read from those gaps (``numbers``): the widest, the
mean over every sampled position, and the share of positions whose
served token is not the reference's first choice.  Rounding moves a few
near-ties, so the widest gap swings from seed to seed; a path computed
in a lower precision moves many positions, which the mean and the share
show.

``controls`` also computes the configuration a precision step down
(``CONTROLS``) and reads, at each position of the same streams, the gap
of the token that the lower precision puts first: ``step_down`` takes
every part one step down (the INT8 edge lattice, boundary and KV caches
to INT4, the bf16 cloud suffix and head onto an INT8 lattice);
``cloud_int8`` takes only the bf16 cloud suffix and head to INT8, the
step a later change would most likely take.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HI = None   # jax.lax.Precision.HIGHEST, bound on first use


@dataclasses.dataclass(frozen=True)
class Lattice:
    """Where the configuration cuts, and the bits of each part's lattice
    (``cloud_bits=None``: the cloud suffix and head in the configuration's
    dtype, on no lattice)."""
    cut: int                  # last edge block
    edge_bits: int = 8
    boundary_bits: int = 8
    kv_bits: int = 8
    cloud_bits: Optional[int] = None


def _step_down(lat: Lattice) -> Lattice:
    return Lattice(cut=lat.cut, edge_bits=lat.edge_bits // 2,
                   boundary_bits=lat.boundary_bits // 2,
                   kv_bits=lat.kv_bits // 2, cloud_bits=8)


def _cloud_int8(lat: Lattice) -> Lattice:
    return dataclasses.replace(lat, cloud_bits=8)


CONTROLS = {"step_down": _step_down, "cloud_int8": _cloud_int8}


@dataclasses.dataclass
class Sample:
    prompt: np.ndarray        # int32 prompt ids
    served: np.ndarray        # int32 tokens the program served
    bucket: int               # the prefill call's padded length


def _jax():
    import jax
    import jax.numpy as jnp
    global HI
    HI = jax.lax.Precision.HIGHEST
    return jax, jnp


# -- the INT8 lattice --------------------------------------------------------

def _as(v, dt):
    """``v`` as a value of the configuration's dtype (kept in float32)."""
    _, jnp = _jax()
    return v.astype(dt).astype(jnp.float32)


def _affine(t_min, t_max, dt, bits):
    """Asymmetric min/max (scale, zero point), computed in ``dt``."""
    _, jnp = _jax()
    qmin = -(2 ** (bits - 1))
    levels = float(2 ** bits - 1)
    t_min = _as(jnp.minimum(_as(t_min, dt), 0.0), dt)
    t_max = _as(jnp.maximum(_as(t_max, dt), 0.0), dt)
    span = _as(jnp.maximum(_as(t_max - t_min, dt), _as(jnp.float32(1e-12),
                                                        dt)), dt)
    scale = _as(span / levels, dt)
    zp = _as(qmin - _as(t_min / scale, dt), dt)
    zp = jnp.clip(jnp.round(zp), qmin, qmin + levels)
    return scale, zp


def _fq(x, scale, zp, dt, bits):
    """Quantize and dequantize onto the lattice; the result is a ``dt``
    value."""
    _, jnp = _jax()
    qmin, qmax = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(x / scale + zp), qmin, qmax)
    return _as((q - zp) * scale, dt)


def _fq_weight(w, dt, bits):
    """Per output channel (last axis), range over the input axis (the
    one before it); any axes before that are stacked weights, each with
    ranges of its own."""
    _, jnp = _jax()
    scale, zp = _affine(jnp.min(w, axis=-2), jnp.max(w, axis=-2), dt, bits)
    return _fq(w, scale[..., None, :], zp[..., None, :], dt, bits)


def _fq_act(x, row_mask, dt, bits):
    """``x`` [S, F].  ``row_mask`` [S]: one range over the masked rows
    (a prefill call); ``None``: one range per row (decode tokens)."""
    _, jnp = _jax()
    if row_mask is None:
        lo, hi = jnp.min(x, axis=1), jnp.max(x, axis=1)
        scale, zp = _affine(lo, hi, dt, bits)
        return _fq(x, scale[:, None], zp[:, None], dt, bits)
    m = row_mask[:, None]
    lo = jnp.min(jnp.where(m, x, jnp.inf))
    hi = jnp.max(jnp.where(m, x, -jnp.inf))
    scale, zp = _affine(lo, hi, dt, bits)
    return _fq(x, scale, zp, dt, bits)


def _kv_scale(k, valid, dt, bits):
    """Symmetric per kv head from the valid rows: k [S, H, D]."""
    _, jnp = _jax()
    a = jnp.where(valid[:, None, None], jnp.abs(k), 0.0)
    amax = _as(jnp.maximum(_as(jnp.max(a, axis=(0, 2)), dt), 1e-6), dt)
    return _as(amax / float(2 ** (bits - 1) - 1), dt)             # [H]


def _kv_q(k, scale, dt, bits, *, prefill):
    """INT8 KV entry, dequantized.  Prefill divides in ``dt`` (the
    calibrated scale and the key are both ``dt`` values there); later
    positions divide in float32 by the stored scale."""
    _, jnp = _jax()
    qmax = 2 ** (bits - 1) - 1
    r = k / scale[None, :, None]
    if prefill:
        r = _as(r, dt)
    return jnp.clip(jnp.round(r), -qmax, qmax) * scale[None, :, None]


# -- math every architecture uses ------------------------------------------

def rope(x, pos, theta):
    _, jnp = _jax()
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs[None]          # [S, half]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def rmsnorm(x, scale, eps):
    _, jnp = _jax()
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def mm(x, w):
    _, jnp = _jax()
    return jnp.einsum("sd,df->sf", x, w, precision=HI)


# -- what a block is handed --------------------------------------------------

class OnLattice:
    """The weight and activation lattice one block call runs on, for the
    architecture's block to apply: ``weight`` to each of its weights
    [..., in, out] (asymmetric, one range per output channel over the
    input axis and one more per index of any leading axis: an expert
    stack [E, in, out] gets one per expert and output channel, and
    [E * in, out] one per output channel over every expert), ``act`` to
    each matmul input [S, F] (one range over the rows of ``row_mask``;
    ``row_mask`` None: one per row).  ``bits`` None: no lattice, both
    are the identity."""

    def __init__(self, dt, bits: Optional[int], row_mask):
        self.dt, self.bits, self.row_mask = dt, bits, row_mask

    def weight(self, w):
        return w if self.bits is None else _fq_weight(w, self.dt, self.bits)

    def act(self, h):
        if self.bits is None:
            return h
        return _fq_act(h, self.row_mask, self.dt, self.bits)


def _kv_protocol(dt, bits, pos, *, prefill_valid, kv_prev, kv_valid,
                 kv_scale):
    """The KV cache of one block call, for the architecture's block to
    apply to its keys and values [S, H, D] at positions ``pos`` [S]:
    ``kv(k, v)`` puts them on the KV lattice and returns the keys and
    values the call's rows attend over [L, H, D], which of them each row
    sees ([S, L] bool, causal), and the state the block returns.

    Prefill (``kv_prev`` None): keys are this call's rows, valid where
    ``prefill_valid``, and the KV scale is calibrated from them.  Decode:
    ``kv_prev`` = (k, v) [P, H, D] of the prompt, valid where
    ``kv_valid``, with their ``kv_scale``; this call's keys reuse it,
    saturate, and attend causally among themselves.  The state is the
    dequantized (k, v) and their scales."""
    _, jnp = _jax()

    def kv(k, v):
        if kv_prev is None:
            ks = _kv_scale(k, prefill_valid, dt, bits)
            vs = _kv_scale(v, prefill_valid, dt, bits)
            kd = _kv_q(k, ks, dt, bits, prefill=True)
            vd = _kv_q(v, vs, dt, bits, prefill=True)
            keys, vals = kd, vd
            valid = prefill_valid[None, :] & (pos[None, :] <= pos[:, None])
        else:
            ks, vs = kv_scale
            kd = _kv_q(k, ks, dt, bits, prefill=False)
            vd = _kv_q(v, vs, dt, bits, prefill=False)
            keys = jnp.concatenate([kv_prev[0], kd], axis=0)
            vals = jnp.concatenate([kv_prev[1], vd], axis=0)
            s = k.shape[0]
            own = jnp.arange(s)
            valid = jnp.concatenate(
                [jnp.broadcast_to(kv_valid[None, :], (s, kv_valid.shape[0])),
                 own[None, :] <= own[:, None]], axis=1)
        return keys, vals, valid, ((kd, vd), (ks, vs))

    return kv


def _scan(block, m, blocks, x, pos, bits, kv_bits, *, row_mask,
          prefill_valid=None, kv_prev=None, kv_valid=None, kv_scale=None):
    """Run a stack of the architecture's ``block``s in order, all on a
    lattice of ``bits`` (static; None: on none), each block's weights in
    float32.  Returns x and, stacked per block, the dequantized (k, v)
    and their scales."""
    jax, jnp = _jax()
    dt = jnp.dtype(m.dtype)
    lat = OnLattice(dt, bits, row_mask)

    def body(x, inp):
        bp, prev, sc = inp
        bp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), bp)
        kv = _kv_protocol(dt, kv_bits, pos, prefill_valid=prefill_valid,
                          kv_prev=prev, kv_valid=kv_valid, kv_scale=sc)
        return block(m, bp, x, pos, lat, kv)

    x, (kvs, scales) = jax.lax.scan(body, x, (blocks, kv_prev, kv_scale))
    return x, kvs, scales


def _boundary(h, row_mask, dt, bits):
    return _fq_act(h, row_mask, dt, bits)


def _head(m, params, x, bits):
    jax, jnp = _jax()
    dt = jnp.dtype(m.dtype)
    x = rmsnorm(x, params["final_norm"]["scale"].astype(jnp.float32),
                m.rms_norm_eps)
    w = params["lm_head"]["w"].astype(jnp.float32)
    if bits is not None:
        w = _fq_weight(w, dt, bits)
        x = _fq_act(x, None, dt, bits)
    return mm(x, w)


def _forward(block, m, lat: Lattice, params, toks_p, plen, bucket, dec):
    """Logits at every served position of one request: row 0 at the last
    prompt position, row i at prompt length + i - 1 (input ``dec[i-1]``).
    ``toks_p`` [P]: prompt padded with 0; ``dec`` [T]: served tokens but
    the last, padded."""
    jax, jnp = _jax()
    dt = jnp.dtype(m.dtype)
    blocks = params["blocks"]
    emb = params["embed"]["emb"]

    p_len = toks_p.shape[0]
    pos = jnp.arange(p_len)
    in_bucket = pos < bucket
    real = pos < plen
    x = emb[toks_p].astype(jnp.float32)
    cut, eb, cb, kvb = lat.cut, lat.edge_bits, lat.cloud_bits, lat.kv_bits
    e_blocks = jax.tree_util.tree_map(lambda a: a[:cut + 1], blocks)
    c_blocks = jax.tree_util.tree_map(lambda a: a[cut + 1:], blocks)

    # prefill: the edge over the bucket-padded prompt, the boundary, the
    # cloud; the first served token comes from the last prompt position
    h, e_kv, e_sc = _scan(block, m, e_blocks, x, pos, eb, kvb,
                          row_mask=in_bucket, prefill_valid=real)
    h = _boundary(h, real, dt, lat.boundary_bits)
    y, c_kv, c_sc = _scan(block, m, c_blocks, h, pos, cb, kvb,
                          row_mask=in_bucket, prefill_valid=real)
    first = _head(m, params, jax.lax.dynamic_slice_in_dim(y, plen - 1, 1),
                  cb)

    # decode: every later served token, teacher-forced, one lattice range
    # per token, over the prompt's INT8 KV
    t = dec.shape[0]
    dpos = plen + jnp.arange(t)
    x = emb[dec].astype(jnp.float32)
    h, _, _ = _scan(block, m, e_blocks, x, dpos, eb, kvb, row_mask=None,
                    kv_prev=e_kv, kv_valid=real, kv_scale=e_sc)
    h = _boundary(h, None, dt, lat.boundary_bits)
    y, _, _ = _scan(block, m, c_blocks, h, dpos, cb, kvb, row_mask=None,
                    kv_prev=c_kv, kv_valid=real, kv_scale=c_sc)
    rest = _head(m, params, y, cb)
    return jnp.concatenate([first, rest], axis=0)                # [1+T, V]


@functools.lru_cache(maxsize=8)
def _compiled(block, m, lat: Lattice, controls: Tuple[str, ...]):
    jax, jnp = _jax()

    def gaps(params, toks_p, plen, bucket, dec, served):
        ref = _forward(block, m, lat, params, toks_p, plen, bucket, dec)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        out = [best - got]
        for name in controls:
            low = _forward(block, m, CONTROLS[name](lat), params, toks_p,
                           plen, bucket, dec)
            pick = jnp.argmax(low, axis=-1)
            out.append(best - jnp.take_along_axis(ref, pick[:, None],
                                                  axis=-1)[:, 0])
        return out

    return jax.jit(gaps)


def served_gaps(block, m, lattice: Lattice, params, samples: Sequence[Sample],
                *, prompt_len: int, served_len: int,
                controls: Sequence[str] = ()
                ) -> Tuple[List[np.ndarray], Dict[str, List[np.ndarray]]]:
    """Per sample, the gap (reference best logit minus the served token's
    logit) at each served position; and per control named, the gap of the
    control's first choice.  ``prompt_len``/``served_len`` fix the padded
    shapes, so one compiled program serves every request of a cell."""
    _, jnp = _jax()
    fn = _compiled(block, m, lattice, tuple(controls))
    prog: List[np.ndarray] = []
    ctrl: Dict[str, List[np.ndarray]] = {c: [] for c in controls}
    for smp in samples:
        n = len(smp.served)
        toks_p = np.zeros((prompt_len,), np.int32)
        toks_p[:len(smp.prompt)] = smp.prompt
        dec = np.zeros((served_len - 1,), np.int32)
        dec[:n - 1] = smp.served[:-1]
        served = np.zeros((served_len,), np.int32)
        served[:n] = smp.served
        out = fn(params, jnp.asarray(toks_p), jnp.int32(len(smp.prompt)),
                 jnp.int32(smp.bucket), jnp.asarray(dec), jnp.asarray(served))
        prog.append(np.asarray(out[0])[:n])
        for c, o in zip(controls, out[1:]):
            ctrl[c].append(np.asarray(o)[:n])
    return prog, ctrl


NUMBERS = ("max_logit_gap", "mean_logit_gap", "argmax_miss_share")


def numbers(gaps: Sequence[np.ndarray]) -> Dict[str, float]:
    """The numbers the check compares, from per-sample gaps: the widest
    gap, the mean gap over every position, and the share (%) of positions
    whose token is not the reference's first choice."""
    g = np.concatenate([np.asarray(x, np.float64) for x in gaps])
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "argmax_miss_share": float(100.0 * np.mean(g > 0.0))}

"""Functional layer library (pure JAX, MaxText-style init/apply pairs).

Every parametric layer threads an optional ``QuantCtx`` so the whole model
zoo supports the paper's mixed-precision mode: when a layer runs on the
*edge engine*, its weights are fake-quantized per-channel INT8 and its
input activations per-tensor INT8 (paper §2.1 steps 1-4 — fake-quant of
the same lattice the MXU int8 kernel consumes, so accuracy semantics match
the integer path bit-for-bit up to f32 rounding); on the *cloud engine*
``qctx=None`` and everything stays full precision.

Calibration (``mode="calib"``) records per-activation min/max off-line,
exactly the paper's profiling step; ``mode="static"`` replays the
calibrated thresholds; ``mode="dynamic"`` computes them per batch.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import (MinMaxCalibrator, QuantParams, compute_qparams,
                              fake_quant)

Params = Dict[str, Any]
_ACTS = {None: lambda x: x, "relu": jax.nn.relu, "gelu": jax.nn.gelu,
         "silu": jax.nn.silu, "tanh": jnp.tanh}


# ---------------------------------------------------------------------------
# Quantization context
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QuantCtx:
    mode: str = "dynamic"            # "dynamic" | "static" | "calib"
    w_bits: int = 8
    a_bits: int = 8
    per_channel: bool = True
    # dynamic-mode activation range axis: None = one range per tensor
    # (paper Eq.1 on a single stream); 0 = one range per leading-axis
    # row.  Batched serving engines MUST use 0 — a per-tensor range over
    # a multi-slot batch couples every request's lattice to its
    # neighbours' (and to garbage in idle slots), breaking per-request
    # determinism.  For B=1 the two are identical.
    act_axis: Optional[int] = None
    scales: Optional[Dict[str, QuantParams]] = None     # static mode
    recorder: Optional[Dict[str, MinMaxCalibrator]] = None  # calib mode
    # weights already sit on the deployment lattice (prequantized once,
    # e.g. serve.policy._CutBank), so per-call re-quantization would be
    # redundant compute — only activations stay dynamic
    quantize_weights: bool = True

    def weight(self, name: str, w: jax.Array) -> jax.Array:
        if not self.quantize_weights:
            return w
        axis = (w.ndim - 1) if self.per_channel else None
        qp = compute_qparams(w, axis=axis, bits=self.w_bits)
        return fake_quant(w, qp)

    def act(self, name: str, x: jax.Array) -> jax.Array:
        if self.mode == "calib":
            rec = self.recorder.setdefault(
                name, MinMaxCalibrator(bits=self.a_bits))
            rec.observe(x)
            return x
        if self.mode == "static":
            qp = self.scales.get(name)
            if qp is None:           # unseen activation: pass through
                return x
        else:
            qp = compute_qparams(x, axis=self.act_axis, bits=self.a_bits)
        return fake_quant(x, qp)

    def finalize_calibration(self) -> Dict[str, QuantParams]:
        assert self.mode == "calib" and self.recorder is not None
        return {k: c.qparams() for k, c in self.recorder.items()}


def make_calib_ctx(**kw) -> QuantCtx:
    return QuantCtx(mode="calib", recorder={}, **kw)


def q(qctx: Optional[QuantCtx], name: str, x: jax.Array) -> jax.Array:
    return x if qctx is None else qctx.act(name, x)


def qw(qctx: Optional[QuantCtx], name: str, w: jax.Array) -> jax.Array:
    return w if qctx is None else qctx.weight(name, w)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def _fan_in_init(key, shape, fan_in, dtype):
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def dense_init(key, d_in: int, d_out: int, *, bias: bool = True,
               dtype=jnp.float32) -> Params:
    p = {"w": _fan_in_init(key, (d_in, d_out), d_in, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def conv2d_init(key, k: int, c_in: int, c_out: int, *, bias: bool = True,
                dtype=jnp.float32) -> Params:
    p = {"w": _fan_in_init(key, (k, k, c_in, c_out), k * k * c_in, dtype)}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype)
    return p


def norm_init(dim: int, *, bias: bool = True, dtype=jnp.float32) -> Params:
    p = {"scale": jnp.ones((dim,), dtype)}
    if bias:
        p["b"] = jnp.zeros((dim,), dtype)
    return p


def embed_init(key, vocab: int, dim: int, *, dtype=jnp.float32) -> Params:
    return {"emb": (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)}


# ---------------------------------------------------------------------------
# Apply functions
# ---------------------------------------------------------------------------


def dense(p: Params, x: jax.Array, *, qctx: Optional[QuantCtx] = None,
          name: str = "dense", act: Optional[str] = None) -> jax.Array:
    x = q(qctx, f"{name}/in", x)
    w = qw(qctx, f"{name}/w", p["w"])
    y = jnp.einsum("...i,io->...o", x, w)
    if "b" in p:
        y = y + p["b"]
    return _ACTS[act](y)


def conv2d(p: Params, x: jax.Array, *, stride: int = 1, padding="SAME",
           qctx: Optional[QuantCtx] = None, name: str = "conv",
           act: Optional[str] = None, groups: int = 1) -> jax.Array:
    """NHWC conv. On TPU this is an MXU matmul after im2col."""
    x = q(qctx, f"{name}/in", x)
    w = qw(qctx, f"{name}/w", p["w"])
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    if "b" in p:
        y = y + p["b"]
    return _ACTS[act](y)


def layernorm(p: Params, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm(p: Params, x: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * p["scale"]
    return y


def groupnorm(p: Params, x: jax.Array, *, groups: int = 32,
              eps: float = 1e-5) -> jax.Array:
    """NHWC group norm (diffusion U-Net default)."""
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    y = xg.reshape(n, h, w, c) * p["scale"]
    if "b" in p:
        y = y + p["b"]
    return y


def embed(p: Params, ids: jax.Array) -> jax.Array:
    return jnp.take(p["emb"], ids, axis=0)


# -- rotary position embedding ----------------------------------------------


def rope_table(seq_len: int, head_dim: int, *, base: float = 10000.0,
               dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    half = head_dim // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    ang = jnp.outer(t, freqs)                       # [S, half]
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, H, D]; cos/sin: [S, D/2] shared across the batch, or
    [B, S, D/2] when every sequence sits at its own position (per-slot
    decode in the continuous-batching scheduler)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 3:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    else:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


# -- attention ----------------------------------------------------------------


def attention_init(key, d_model: int, n_heads: int, n_kv: int,
                   head_dim: Optional[int] = None, *, bias: bool = False,
                   dtype=jnp.float32) -> Params:
    hd = head_dim or d_model // n_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * hd, bias=bias, dtype=dtype),
        "wk": dense_init(ks[1], d_model, n_kv * hd, bias=bias, dtype=dtype),
        "wv": dense_init(ks[2], d_model, n_kv * hd, bias=bias, dtype=dtype),
        "wo": dense_init(ks[3], n_heads * hd, d_model, bias=bias, dtype=dtype),
    }


def _sdpa(qh: jax.Array, kh: jax.Array, vh: jax.Array, *,
          causal: bool, q_offset: int | jax.Array = 0,
          q_chunk: Optional[int] = None,
          score_pspec: Optional[tuple] = None) -> jax.Array:
    """q: [B,Sq,H,D], k/v: [B,Skv,H,D] (kv already head-repeated).

    ``q_chunk`` bounds the live score tensor to [B,H,chunk,Skv] by
    scanning over query blocks (flash-attention-style tiling at the XLA
    level) — required for the 32k-prefill shapes where the full [S,S]
    f32 score tensor would not fit HBM.
    """
    if q_chunk is not None and qh.shape[1] > q_chunk \
            and qh.shape[1] % q_chunk == 0:
        b, sq, h, d = qh.shape
        qc = qh.reshape(b, sq // q_chunk, q_chunk, h, d)

        def one(args):
            q_blk, blk_idx = args
            off = q_offset + blk_idx * q_chunk
            return _sdpa(q_blk, kh, vh, causal=causal, q_offset=off)

        out = jax.lax.map(one, (qc.transpose(1, 0, 2, 3, 4),
                                jnp.arange(sq // q_chunk)))
        return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)

    scale = 1.0 / math.sqrt(qh.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
    if score_pspec is not None:
        # pin scores [B,H,q,KV] with KV sharded: forces GSPMD into the
        # flash-decoding partial-softmax strategy (tiny psum collectives)
        # instead of gathering the whole cache per layer.
        from jax.sharding import PartitionSpec as P
        logits = jax.lax.with_sharding_constraint(logits, P(*score_pspec))
    if causal:
        sq, sk = qh.shape[1], kh.shape[1]
        if jnp.ndim(q_offset) == 1:
            # per-batch offsets [B]: each slot decodes at its own position
            qpos = jnp.arange(sq)[None, :, None] + q_offset[:, None, None]
            kpos = jnp.arange(sk)[None, None, :]
            mask = kpos <= qpos                       # [B, Sq, Skv]
            logits = jnp.where(mask[:, None], logits,
                               jnp.finfo(logits.dtype).min)
        else:
            qpos = jnp.arange(sq)[:, None] + q_offset
            kpos = jnp.arange(sk)[None, :]
            mask = kpos <= qpos
            logits = jnp.where(mask[None, None], logits,
                               jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    probs = probs.astype(vh.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vh)


def attention(p: Params, x: jax.Array, *, n_heads: int, n_kv: int,
              causal: bool = True,
              rope: Optional[Tuple[jax.Array, jax.Array]] = None,
              kv_cache: Optional[Dict[str, jax.Array]] = None,
              cache_index: Optional[jax.Array] = None,
              qctx: Optional[QuantCtx] = None, name: str = "attn",
              q_chunk: Optional[int] = None,
              kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,
              score_pspec: Optional[tuple] = None,
              block_tables: Optional[jax.Array] = None,
              calibrate_kv: bool = False,
              kv_lengths: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """GQA attention.  With ``kv_cache`` given, x is the new-token slice
    (decode: S=1); cache is updated at ``cache_index`` and attention runs
    over the full cache length.

    ``kv_scales`` (k_scale, v_scale per kv head, [H]) enables the INT8
    KV cache: new entries are symmetrically quantized on write (paper
    Eq.1, zero-point-free) and dequantized on read — on TPU the convert
    fuses into the QK/AV matmuls so the cache streams at 1 B/elem.

    A *paged* cache (``"k_pages"`` key, see ``transformer.init_cache``)
    additionally takes ``block_tables`` [B, pages_per_seq] mapping each
    row's logical pages to physical pages in the shared pool.  Writes
    scatter into table-mapped pages; decode reads (S=1) go through the
    paged flash-decode kernel, and S>1 reads (multi-token prefill, the
    speculative verify block) go through the same kernel's q-block form
    with intra-block causal masking.  With an INT8 page
    pool, ``calibrate_kv=True`` (prefill) derives fresh per-(row, head)
    symmetric scales from the prompt's K/V instead of reading the
    ``k_scale``/``v_scale`` cache entries that decode steps replay.
    ``kv_lengths`` [B] gives each row's true token count during a
    bucket-padded prefill so padding positions cannot inflate the
    calibrated ranges."""
    b, s, d = x.shape
    hd = p["wq"]["w"].shape[1] // n_heads
    qh = dense(p["wq"], x, qctx=qctx, name=f"{name}/q").reshape(b, s, n_heads, hd)
    kh = dense(p["wk"], x, qctx=qctx, name=f"{name}/k").reshape(b, s, n_kv, hd)
    vh = dense(p["wv"], x, qctx=qctx, name=f"{name}/v").reshape(b, s, n_kv, hd)

    # vector cache_index [B] = per-slot positions (continuous batching).
    # S may exceed 1: a speculative verify step writes/attends a k-token
    # block starting at each slot's own position.
    vec_index = (cache_index is not None and jnp.ndim(cache_index) == 1)

    q_offset = 0
    if rope is not None:
        cos, sin = rope
        if kv_cache is not None and cache_index is not None:
            if vec_index:
                tpos = cache_index[:, None] + jnp.arange(s)[None]  # [B, S]
                cos_q = jnp.take(cos, tpos, axis=0)                # [B,S,·]
                sin_q = jnp.take(sin, tpos, axis=0)
            else:
                cos_q = jax.lax.dynamic_slice_in_dim(cos, cache_index, s,
                                                     axis=0)
                sin_q = jax.lax.dynamic_slice_in_dim(sin, cache_index, s,
                                                     axis=0)
        else:
            cos_q, sin_q = cos[:s], sin[:s]
        qh = apply_rope(qh, cos_q, sin_q)
        kh = apply_rope(kh, cos_q, sin_q)

    if kv_cache is not None and "k_pages" in kv_cache:
        assert block_tables is not None, "paged cache needs block_tables"
        out, new_cache = _paged_cache_attention(
            kv_cache, qh, kh, vh, block_tables=block_tables,
            cache_index=cache_index, vec_index=vec_index,
            calibrate_kv=calibrate_kv, kv_lengths=kv_lengths,
            n_heads=n_heads, n_kv=n_kv, q_chunk=q_chunk, dtype=x.dtype)
        out = out.reshape(b, s, n_heads * hd)
        out = dense(p["wo"], out, qctx=qctx, name=f"{name}/o")
        return out, new_cache

    new_cache = None
    if kv_cache is not None:
        if kv_scales is not None:
            ks, vs = kv_scales                     # [H] per kv head
            k_w = jnp.clip(jnp.round(kh / ks[None, None, :, None]),
                           -127, 127).astype(kv_cache["k"].dtype)
            v_w = jnp.clip(jnp.round(vh / vs[None, None, :, None]),
                           -127, 127).astype(kv_cache["v"].dtype)
        else:
            k_w = kh.astype(kv_cache["k"].dtype)
            v_w = vh.astype(kv_cache["v"].dtype)
        if vec_index:
            b_idx = jnp.arange(b)[:, None]
            tpos = cache_index[:, None] + jnp.arange(s)[None]     # [B, S]
            k_all = kv_cache["k"].at[b_idx, tpos].set(k_w)
            v_all = kv_cache["v"].at[b_idx, tpos].set(v_w)
        else:
            k_all = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["k"], k_w, cache_index, axis=1)
            v_all = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["v"], v_w, cache_index, axis=1)
        new_cache = {"k": k_all, "v": v_all}
        if kv_scales is not None:
            kh = k_all.astype(x.dtype) * ks.astype(x.dtype)[None, None, :,
                                                            None]
            vh = v_all.astype(x.dtype) * vs.astype(x.dtype)[None, None, :,
                                                            None]
        else:
            kh, vh = k_all.astype(x.dtype), v_all.astype(x.dtype)
        q_offset = cache_index

    if n_kv != n_heads:
        rep = n_heads // n_kv
        kh = jnp.repeat(kh, rep, axis=2)
        vh = jnp.repeat(vh, rep, axis=2)

    out = _sdpa(qh, kh, vh, causal=causal, q_offset=q_offset,
                q_chunk=q_chunk, score_pspec=score_pspec)
    out = out.reshape(b, s, n_heads * hd)
    out = dense(p["wo"], out, qctx=qctx, name=f"{name}/o")
    return out, new_cache


def _paged_cache_attention(cache: Dict[str, jax.Array], qh: jax.Array,
                           kh: jax.Array, vh: jax.Array, *,
                           block_tables: jax.Array,
                           cache_index: Optional[jax.Array],
                           vec_index: bool, calibrate_kv: bool,
                           kv_lengths: Optional[jax.Array],
                           n_heads: int, n_kv: int,
                           q_chunk: Optional[int], dtype
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Write new K/V into block-table pages, then attend.

    qh/kh/vh: [B, S, H(, kv), D] post-RoPE.  Decode (S=1) reads back
    through ``kernels.paged_attention``; S>1 blocks (prefill, the
    speculative verify step) read back through its multi-query form —
    one paged read path for every phase.
    """
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_multiquery_attention)

    b, s = kh.shape[:2]
    page_size = cache["k_pages"].shape[2]
    quantized = "k_scale" in cache

    if quantized:
        if calibrate_kv:
            # per-slot Eq.(1) symmetric calibration from the prompt's
            # own K/V range — [B, n_kv], replayed by every decode step.
            # Bucket-padding positions are masked out of the reduction:
            # their K/V (pad-token embeddings at tail RoPE phases) must
            # not set a request's scale for its whole lifetime.
            ak, av = jnp.abs(kh), jnp.abs(vh)
            if kv_lengths is not None:
                valid = (jnp.arange(s)[None, :]
                         < kv_lengths[:, None])[:, :, None, None]
                ak = jnp.where(valid, ak, 0.0)
                av = jnp.where(valid, av, 0.0)
            ks = jnp.maximum(jnp.max(ak, axis=(1, 3)), 1e-6) / 127.0
            vs = jnp.maximum(jnp.max(av, axis=(1, 3)), 1e-6) / 127.0
        else:
            ks, vs = cache["k_scale"], cache["v_scale"]
        k_w = jnp.clip(jnp.round(kh / ks[:, None, :, None]),
                       -127, 127).astype(cache["k_pages"].dtype)
        v_w = jnp.clip(jnp.round(vh / vs[:, None, :, None]),
                       -127, 127).astype(cache["v_pages"].dtype)
    else:
        k_w = kh.astype(cache["k_pages"].dtype)
        v_w = vh.astype(cache["v_pages"].dtype)

    # logical position of every written token, [B, S]
    if vec_index:
        t = cache_index[:, None] + jnp.arange(s)[None]
    else:
        t = jnp.broadcast_to(
            (cache_index + jnp.arange(s))[None], (b, s))
    page = jnp.take_along_axis(block_tables, t // page_size, axis=1)
    off = t % page_size
    # pages are [n_pages, n_kv, page_size, hd]: the [B, S] index pair
    # (page, off) selects a [n_kv, hd] column of one page per token
    k_pages = cache["k_pages"].at[page, :, off].set(k_w)
    v_pages = cache["v_pages"].at[page, :, off].set(v_w)

    new_cache = {"k_pages": k_pages, "v_pages": v_pages}
    if quantized:
        new_cache["k_scale"], new_cache["v_scale"] = ks, vs

    if s == 1:
        # flash-decode over the page pool (1 B/elem streamed, dequant
        # inside the QK/AV loops); lengths include the token just written
        vec = cache_index if vec_index else jnp.full((b,), cache_index)
        out = paged_attention(qh[:, 0].astype(jnp.float32), k_pages,
                              v_pages, block_tables, vec + 1,
                              ks if quantized else None,
                              vs if quantized else None)
        return out[:, None].astype(dtype), new_cache

    # q-block read (speculative verify / multi-token prefill): the S
    # queries attend cache + the just-written block through the paged
    # kernel's intra-block causal mask.  Query i of row b sits at
    # q_start[b] + i; ``kv_lengths`` (true prompt lengths) keeps bucket
    # padding out of a prefill read, while a verify read's stale entries
    # beyond each query's position — rolled-back drafts of an earlier
    # round — are masked by causality.  Reading back through the pages
    # also means prefill sees the cache's INT8 lattice, so prefill
    # logits match what decode later reconstructs from the same pages.
    start = cache_index if vec_index else jnp.full((b,), cache_index)
    lengths = (start + s) if kv_lengths is None else kv_lengths
    out = paged_multiquery_attention(qh.astype(jnp.float32), k_pages,
                                     v_pages, block_tables,
                                     lengths.astype(jnp.int32), start,
                                     ks if quantized else None,
                                     vs if quantized else None)
    return out.astype(dtype), new_cache


# -- MLPs ---------------------------------------------------------------------


def swiglu_init(key, d_model: int, d_ff: int, *, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {"wi": dense_init(ks[0], d_model, d_ff, bias=False, dtype=dtype),
            "wg": dense_init(ks[1], d_model, d_ff, bias=False, dtype=dtype),
            "wo": dense_init(ks[2], d_ff, d_model, bias=False, dtype=dtype)}


def swiglu(p: Params, x: jax.Array, *, qctx: Optional[QuantCtx] = None,
           name: str = "mlp") -> jax.Array:
    h = dense(p["wi"], x, qctx=qctx, name=f"{name}/wi")
    g = dense(p["wg"], x, qctx=qctx, name=f"{name}/wg", act="silu")
    return dense(p["wo"], h * g, qctx=qctx, name=f"{name}/wo")


def mlp_init(key, d_model: int, d_ff: int, *, bias: bool = True,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 2)
    return {"wi": dense_init(ks[0], d_model, d_ff, bias=bias, dtype=dtype),
            "wo": dense_init(ks[1], d_ff, d_model, bias=bias, dtype=dtype)}


def mlp(p: Params, x: jax.Array, *, act: str = "gelu",
        qctx: Optional[QuantCtx] = None, name: str = "mlp") -> jax.Array:
    h = dense(p["wi"], x, qctx=qctx, name=f"{name}/wi", act=act)
    return dense(p["wo"], h, qctx=qctx, name=f"{name}/wo")


# -- Mixture of Experts (GShard-style, dropless-capacity top-k) ---------------


def moe_init(key, d_model: int, d_ff: int, n_experts: int,
             *, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    std_in = 1.0 / math.sqrt(d_model)
    std_out = 1.0 / math.sqrt(d_ff)

    def ex(k, shape, std):
        return (jax.random.normal(k, shape) * std).astype(dtype)

    return {
        "router": dense_init(ks[0], d_model, n_experts, bias=False, dtype=dtype),
        "wi": ex(ks[1], (n_experts, d_model, d_ff), std_in),
        "wg": ex(ks[2], (n_experts, d_model, d_ff), std_in),
        "wo": ex(ks[3], (n_experts, d_ff, d_model), std_out),
    }


def _route(router: Params, xt: jax.Array, n_e: int, top_k: int):
    logits = jnp.einsum("td,de->te", xt, router["w"])
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # [T, E]
    gate_k, idx_k = jax.lax.top_k(gates, top_k)                   # [T, K]
    gate_k = gate_k / jnp.maximum(jnp.sum(gate_k, -1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch): E * sum(fraction * prob)
    density = jnp.mean(
        jax.nn.one_hot(idx_k[:, 0], n_e, dtype=jnp.float32), axis=0)
    aux = n_e * jnp.sum(density * jnp.mean(gates, axis=0))
    return gate_k, idx_k, aux


def _grouped_ffn(xt: jax.Array, gate_k: jax.Array, idx_k: jax.Array,
                 wi: jax.Array, wg: jax.Array, wo: jax.Array, *,
                 top_k: int, capacity_factor: float) -> jax.Array:
    """Sort-based static-capacity grouped FFN.

    Sorts (token, k) slots by expert, gathers each expert's first C
    tokens into a dense [E, C, D] buffer (C = T·k·cf/E), runs plain
    einsum GEMMs (E·C·D·F = active FLOPs × cf — never the O(T·E·C)
    one-hot dispatch), and scatter-adds gated results back.  Tokens past
    an expert's capacity are dropped, exactly GShard's overflow rule.
    """
    t, d = xt.shape
    n_e = wi.shape[0]
    # floor keeps tiny decode batches (a handful of tokens per shard)
    # from dropping on routing collisions
    cap = max(int(capacity_factor * t * top_k / n_e),
              min(t * top_k, 32))

    flat_e = idx_k.reshape(-1)                                    # [T*K]
    order = jnp.argsort(flat_e)                                   # stable
    tok_sorted = order // top_k                                   # [T*K]
    group_sizes = jnp.bincount(flat_e, length=n_e)
    starts = jnp.concatenate([jnp.zeros((1,), group_sizes.dtype),
                              jnp.cumsum(group_sizes)[:-1]])
    slot = starts[:, None] + jnp.arange(cap)[None, :]             # [E, C]
    valid = jnp.arange(cap)[None, :] < group_sizes[:, None]
    slot = jnp.clip(slot, 0, t * top_k - 1)
    tok_for_slot = jnp.take(tok_sorted, slot.reshape(-1))         # [E*C]
    gate_sorted = jnp.take(gate_k.reshape(-1), order)
    gate_slot = jnp.take(gate_sorted, slot.reshape(-1)).reshape(n_e, cap)
    gate_slot = jnp.where(valid, gate_slot, 0.0).astype(xt.dtype)

    xe = jnp.take(xt, tok_for_slot, axis=0).reshape(n_e, cap, d)  # [E, C, D]
    h = jnp.einsum("ecd,edf->ecf", xe, wi)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
    ye = jnp.einsum("ecf,efd->ecd", h * g, wo)                    # [E, C, D']
    ye = ye * gate_slot[..., None]
    return jnp.zeros((t, ye.shape[-1]), ye.dtype).at[tok_for_slot].add(
        ye.reshape(n_e * cap, -1))


def moe(p: Params, x: jax.Array, *, top_k: int,
        capacity_factor: float = 1.25,
        qctx: Optional[QuantCtx] = None, name: str = "moe",
        ) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE (sort + static-capacity grouped GEMM).

    x: [B, S, D] → ([B, S, D], aux_loss).  Experts are a brother-branch
    structure in the paper's sense: cuts inside an expert are excluded
    (repro.core.partition), but the combine output is a legal cut.
    """
    b, s, d = x.shape
    n_e = p["router"]["w"].shape[1]
    xt = x.reshape(b * s, d)
    gate_k, idx_k, aux = _route(p["router"], xt, n_e, top_k)
    wi = qw(qctx, f"{name}/wi", p["wi"])
    wg = qw(qctx, f"{name}/wg", p["wg"])
    wo = qw(qctx, f"{name}/wo", p["wo"])
    yt = _grouped_ffn(xt, gate_k, idx_k, wi, wg, wo, top_k=top_k,
                      capacity_factor=capacity_factor)
    return yt.reshape(b, s, d), aux


def moe_sharded(p: Params, x: jax.Array, *, top_k: int,
                batch_spec, model_axis: str = "model",
                capacity_factor: float = 1.25,
                qctx: Optional[QuantCtx] = None, name: str = "moe",
                ) -> Tuple[jax.Array, jax.Array]:
    """``moe`` under ``jax.shard_map``: the explicit-SPMD form for
    production meshes.

    XLA's auto-partitioner replicates the sort/gather/ragged_dot pattern
    (data-dependent indices defeat propagation), exploding memory and
    compute ~mesh-size-fold.  Here we pin the layout manually:
      * tokens stay sharded over the DP axes (``batch_spec``) — each
        shard routes and sorts only its local tokens (local dispatch,
        exactly GShard's per-core grouping);
      * expert FFN dim is tensor-parallel over ``model_axis``: wi/wg
        enter as [E, D, F/tp], wo as [E, F/tp, D];
      * the wo contraction is completed with a psum_scatter over
        ``model_axis``, leaving the output d_model-sharded (matches the
        residual-stream act_pspec), then re-gathered by the caller.

    Requires the ambient mesh (trace under ``jax.set_mesh(mesh)``).
    """
    b, s, d = x.shape
    n_e = p["router"]["w"].shape[1]

    def local_moe(router_w, wi, wg, wo, x_loc):
        bl, sl, _ = x_loc.shape
        xt = x_loc.reshape(bl * sl, d)
        gate_k, idx_k, aux = _route(router_w, xt, n_e, top_k)
        # wo enters F/tp-sharded; the grouped FFN's output is a partial
        # sum over the F contraction — complete it with a psum_scatter
        # that leaves the result d_model-sharded over tp.
        yt_partial = _grouped_ffn(xt, gate_k, idx_k, wi, wg, wo,
                                  top_k=top_k,
                                  capacity_factor=capacity_factor)
        yt = jax.lax.psum_scatter(yt_partial, model_axis,
                                  scatter_dimension=1, tiled=True)
        aux = jax.lax.pmean(aux, batch_spec) if batch_spec else aux
        aux = jax.lax.pmean(aux, model_axis)
        return yt.reshape(bl, sl, yt.shape[-1]), aux

    from jax.sharding import PartitionSpec as P
    wi = qw(qctx, f"{name}/wi", p["wi"])
    wg = qw(qctx, f"{name}/wg", p["wg"])
    wo = qw(qctx, f"{name}/wo", p["wo"])
    y, aux = jax.shard_map(
        local_moe,
        in_specs=(P(), P(None, None, model_axis), P(None, None, model_axis),
                  P(None, model_axis, None), P(batch_spec, None, None)),
        out_specs=(P(batch_spec, None, model_axis), P()),
        check_vma=False,
    )(p["router"], wi, wg, wo, x)
    return y, aux


# -- vision helpers -----------------------------------------------------------


def patch_embed_init(key, patch: int, c_in: int, d_model: int,
                     *, dtype=jnp.float32) -> Params:
    return conv2d_init(key, patch, c_in, d_model, dtype=dtype)


def patch_embed(p: Params, img: jax.Array, *, patch: int,
                qctx: Optional[QuantCtx] = None,
                name: str = "patch") -> jax.Array:
    y = conv2d(p, img, stride=patch, padding="VALID", qctx=qctx, name=name)
    b, h, w, c = y.shape
    return y.reshape(b, h * w, c)


def maxpool2d(x: jax.Array, *, window: int, stride: int,
              padding="SAME") -> jax.Array:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), padding)


def avgpool2d(x: jax.Array, *, window: int, stride: int,
              padding="SAME") -> jax.Array:
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, window, window, 1),
        (1, stride, stride, 1), padding)
    ones = jnp.ones_like(x)
    c = jax.lax.reduce_window(
        ones, 0.0, jax.lax.add, (1, window, window, 1),
        (1, stride, stride, 1), padding)
    return s / c

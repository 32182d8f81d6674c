"""Decoder-only LM family: dense (llama-arch) and MoE variants.

Covers the four assigned LM architectures (phi3-medium-14b, deepseek-7b,
qwen3-moe-30b-a3b, grok-1-314b): RoPE + GQA attention + SwiGLU (or MoE)
blocks, RMSNorm, untied LM head.

Layers are *stacked* (every block-param leaf carries a leading [L] axis)
and the forward pass scans over them — one compiled block body regardless
of depth, which is what makes the 512-device dry-run of a 64-layer model
compile in seconds (MaxText does the same).  Training wraps the block in
``jax.checkpoint`` (remat).

Partition-analysis view: each decoder block is wrapped by two residual
shortcuts, so by the paper's shortcut rule the only candidate cuts are
block boundaries (plus embed / final-norm / head) — see ``make_graph``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.graph import LayerGraph
from repro.models import layers as L
from repro.models.layers import QuantCtx

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    moe: Optional[MoESpec] = None
    rope_base: float = 10000.0
    max_seq: int = 8192
    dtype: Any = jnp.float32          # params + compute dtype
    remat: bool = True
    q_chunk: Optional[int] = None     # flash-style q tiling for long prefill
    scan_unroll: int = 1              # lax.scan unroll (dry-run: n_layers,
                                      # so cost_analysis sees every layer)
    act_pspec: Optional[tuple] = None  # residual-stream sharding constraint,
                                       # e.g. (("pod","data"), None, "model");
                                       # resolved against the ambient mesh
    moe_shard: Optional[tuple] = None  # (batch_spec, model_axis): run MoE
                                       # under shard_map (production meshes)
    score_pspec: Optional[tuple] = None  # decode attention score layout,
                                         # e.g. (ba, None, None, "model")

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    # -- parameter / FLOP accounting (MODEL_FLOPS = 6·N·D uses these) ------
    def block_param_count(self) -> int:
        d, hd = self.d_model, self.hd
        attn = d * (self.n_heads * hd) * 2 + d * (self.n_kv * hd) * 2
        norms = 2 * d
        if self.moe:
            ffn = self.moe.n_experts * 3 * d * self.d_ff + d * self.moe.n_experts
        else:
            ffn = 3 * d * self.d_ff
        return attn + ffn + norms

    def block_active_param_count(self) -> int:
        if not self.moe:
            return self.block_param_count()
        d, hd = self.d_model, self.hd
        attn = d * (self.n_heads * hd) * 2 + d * (self.n_kv * hd) * 2
        ffn = self.moe.top_k * 3 * d * self.d_ff + d * self.moe.n_experts
        return attn + ffn + 2 * d

    def param_count(self) -> int:
        return (self.vocab * self.d_model * 2 + self.d_model
                + self.n_layers * self.block_param_count())

    def active_param_count(self) -> int:
        return (self.vocab * self.d_model * 2 + self.d_model
                + self.n_layers * self.block_active_param_count())


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_block(key, cfg: LMConfig) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.norm_init(cfg.d_model, bias=False, dtype=cfg.dtype),
        "attn": L.attention_init(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv,
                                 cfg.hd, dtype=cfg.dtype),
        "ln2": L.norm_init(cfg.d_model, bias=False, dtype=cfg.dtype),
    }
    if cfg.moe:
        p["moe"] = L.moe_init(ks[1], cfg.d_model, cfg.d_ff,
                              cfg.moe.n_experts, dtype=cfg.dtype)
    else:
        p["mlp"] = L.swiglu_init(ks[1], cfg.d_model, cfg.d_ff, dtype=cfg.dtype)
    return p


def init_lm(key, cfg: LMConfig) -> Params:
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(block_keys)
    return {
        "embed": L.embed_init(k_emb, cfg.vocab, cfg.d_model, dtype=cfg.dtype),
        "blocks": blocks,
        "final_norm": L.norm_init(cfg.d_model, bias=False, dtype=cfg.dtype),
        "lm_head": L.dense_init(k_head, cfg.d_model, cfg.vocab, bias=False,
                                dtype=cfg.dtype),
    }


# ---------------------------------------------------------------------------
# Block + forward
# ---------------------------------------------------------------------------


def _constrain(x: jax.Array, cfg: LMConfig) -> jax.Array:
    if cfg.act_pspec is None:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*cfg.act_pspec))


def block_apply(p: Params, x: jax.Array, cfg: LMConfig, *,
                rope: Tuple[jax.Array, jax.Array],
                cache: Optional[Dict[str, jax.Array]] = None,
                cache_index: Optional[jax.Array] = None,
                qctx: Optional[QuantCtx] = None,
                kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,
                block_tables: Optional[jax.Array] = None,
                calibrate_kv: bool = False,
                kv_lengths: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    x = _constrain(x, cfg)
    h, new_cache = L.attention(
        p["attn"], L.rmsnorm(p["ln1"], x), n_heads=cfg.n_heads,
        n_kv=cfg.n_kv, causal=True, rope=rope, kv_cache=cache,
        cache_index=cache_index, qctx=qctx, q_chunk=cfg.q_chunk,
        kv_scales=kv_scales, block_tables=block_tables,
        calibrate_kv=calibrate_kv, kv_lengths=kv_lengths,
        score_pspec=cfg.score_pspec if cache is not None else None)
    # constrain the projection outputs too: the TP contraction's partial
    # sums then reduce-scatter straight into the sharded residual stream
    # instead of all-reducing a replicated copy (§Perf hillclimb #2)
    x = x + _constrain(h, cfg)
    z = L.rmsnorm(p["ln2"], x)
    if cfg.moe and cfg.moe_shard is not None:
        h, aux = L.moe_sharded(p["moe"], z, top_k=cfg.moe.top_k,
                               batch_spec=cfg.moe_shard[0],
                               model_axis=cfg.moe_shard[1],
                               capacity_factor=cfg.moe.capacity_factor,
                               qctx=qctx)
    elif cfg.moe:
        h, aux = L.moe(p["moe"], z, top_k=cfg.moe.top_k,
                       capacity_factor=cfg.moe.capacity_factor, qctx=qctx)
    else:
        h, aux = L.swiglu(p["mlp"], z, qctx=qctx), jnp.float32(0.0)
    return _constrain(x + _constrain(h, cfg), cfg), new_cache, aux


def forward(params: Params, tokens: jax.Array, cfg: LMConfig, *,
            qctx: Optional[QuantCtx] = None) -> Tuple[jax.Array, jax.Array]:
    """Full causal forward → (logits [B,S,V], moe aux loss)."""
    b, s = tokens.shape
    x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    rope = L.rope_table(s, cfg.hd, base=cfg.rope_base, dtype=cfg.dtype)

    def body(carry, bp):
        x, aux = carry
        x, _, a = block_apply(bp, x, cfg, rope=rope, qctx=qctx)
        return (x, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0.0)),
                               params["blocks"], unroll=cfg.scan_unroll)
    x = L.rmsnorm(params["final_norm"], x)
    logits = L.dense(params["lm_head"], x, name="lm_head")
    return logits, aux


def lm_loss(params: Params, batch: Dict[str, jax.Array], cfg: LMConfig,
            *, aux_weight: float = 0.01,
            qctx: Optional[QuantCtx] = None) -> jax.Array:
    logits, aux = forward(params, batch["tokens"], cfg, qctx=qctx)
    logits = logits.astype(jnp.float32)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    return nll + aux_weight * aux / cfg.n_layers


# ---------------------------------------------------------------------------
# Serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg: LMConfig, batch: int, max_len: int, dtype=None,
               *, quantized: bool = False,
               layers: Optional[int] = None,
               paged: bool = False, page_size: int = 16,
               num_pages: Optional[int] = None) -> Dict[str, jax.Array]:
    """Allocate a KV cache.  Three layouts:

    * **dense** (default): ``{"k", "v"}`` of shape
      ``[L, batch, max_len, n_kv, hd]`` — every slot pre-allocates
      ``max_len`` positions.
    * **dense + ``quantized=True``**: same shape at INT8 with
      per-(layer, kv-head) symmetric ``k_scale``/``v_scale`` ``[L, n_kv]``
      (calibrated off-line in deployment; init'd to a generic RMS).
    * **``paged=True``**: ``{"k_pages", "v_pages"}`` of shape
      ``[L, num_pages, n_kv, page_size, hd]`` — a shared pool of pages
      addressed through a per-slot block table (see
      ``serve.engine.PageAllocator``); HBM is claimed page-by-page on
      demand instead of ``max_len`` up front.  With ``quantized=True``
      the pages are INT8 and the scales are *per-slot*
      ``[L, batch, n_kv]``, calibrated from each prompt at prefill
      (``attention(calibrate_kv=True)``).  Page 0 is reserved as the
      dump page idle slots harmlessly write into.

    ``layers`` overrides the leading layer axis — cut-aware serving gives
    the edge prefix and the cloud suffix each their own cache covering
    only their block sub-range."""
    n_layers = cfg.n_layers if layers is None else layers
    if paged:
        n_pages = num_pages if num_pages is not None else (
            batch * ((max_len + page_size - 1) // page_size) + 1)
        pdtype = jnp.int8 if quantized else (dtype or cfg.dtype)
        shape = (n_layers, n_pages, cfg.n_kv, page_size, cfg.hd)
        c = {"k_pages": jnp.zeros(shape, pdtype),
             "v_pages": jnp.zeros(shape, pdtype)}
        if quantized:
            c["k_scale"] = jnp.full((n_layers, batch, cfg.n_kv), 0.05,
                                    jnp.float32)
            c["v_scale"] = jnp.full((n_layers, batch, cfg.n_kv), 0.05,
                                    jnp.float32)
        return c
    if quantized:
        shape = (n_layers, batch, max_len, cfg.n_kv, cfg.hd)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.full((n_layers, cfg.n_kv), 0.05,
                                    jnp.float32),
                "v_scale": jnp.full((n_layers, cfg.n_kv), 0.05,
                                    jnp.float32)}
    dtype = dtype or cfg.dtype
    shape = (n_layers, batch, max_len, cfg.n_kv, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def run_blocks(blocks: Params, x: jax.Array, cfg: LMConfig, *,
               rope: Tuple[jax.Array, jax.Array],
               cache: Optional[Dict[str, jax.Array]] = None,
               cache_index: Optional[jax.Array] = None,
               qctx: Optional[QuantCtx] = None,
               block_tables: Optional[jax.Array] = None,
               calibrate_kv: bool = False,
               kv_lengths: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Scan a *sub-range* of stacked decoder blocks over hidden states.

    This is the cut-aware workhorse shared by the monolithic serving path
    and the collaborative engines: the edge prefix and the cloud suffix
    each call it on their own block slice + KV cache.  ``cache_index``
    may be a scalar (uniform position) or a [B] vector of per-slot
    positions; with a vector index ``x`` may carry S > 1 tokens per row
    — the speculative verify step runs all k drafted positions of every
    slot through one cached call, each query causally masked to its own
    ``cache_index + i`` (and a rejected suffix is rolled back simply by
    not advancing the caller's per-slot position).  INT8 caches
    (``k_scale`` entries) are handled uniformly; paged caches
    (``k_pages`` entries, see ``init_cache``) additionally need
    ``block_tables`` and pass ``calibrate_kv=True`` at prefill so
    per-slot INT8 scales are derived from the prompt — prefill reads,
    like decode and verify reads, go through the paged kernel
    (``kernels.paged_attention``), so every phase shares one lattice and
    one read path.
    """
    if cache is None:
        def body_nc(x, bp):
            y, _, _ = block_apply(bp, x, cfg, rope=rope, qctx=qctx)
            return y, None

        x, _ = jax.lax.scan(body_nc, x, blocks, unroll=cfg.scan_unroll)
        return x, None

    def body(x, scan_in):
        bp, c = scan_in
        c = dict(c)
        scales = None
        if "k_scale" in c and "k_pages" not in c:
            scales = (c.pop("k_scale"), c.pop("v_scale"))
        x, new_c, _ = block_apply(bp, x, cfg, rope=rope, cache=c,
                                  cache_index=cache_index, qctx=qctx,
                                  kv_scales=scales,
                                  block_tables=block_tables,
                                  calibrate_kv=calibrate_kv,
                                  kv_lengths=kv_lengths)
        if scales is not None:
            new_c = dict(new_c, k_scale=scales[0], v_scale=scales[1])
        return x, new_c

    x, new_cache = jax.lax.scan(body, x, (blocks, cache),
                                unroll=cfg.scan_unroll)
    return x, new_cache


def lm_head(params: Params, x: jax.Array) -> jax.Array:
    """Final-norm + untied head over hidden states [B, S, D]."""
    x = L.rmsnorm(params["final_norm"], x)
    return L.dense(params["lm_head"], x, name="lm_head")


def _cache_span(cache: Dict[str, jax.Array],
                block_tables: Optional[jax.Array]) -> int:
    """Longest position the cache layout can address (for RoPE tables)."""
    if "k" in cache:
        return cache["k"].shape[2]
    return block_tables.shape[1] * cache["k_pages"].shape[3]


def prefill(params: Params, tokens: jax.Array, cfg: LMConfig, *,
            cache: Dict[str, jax.Array],
            qctx: Optional[QuantCtx] = None,
            block_tables: Optional[jax.Array] = None,
            last_pos: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Process the full prompt; returns (last-token logits, filled cache).

    ``last_pos`` [B]: per-row index of the last *real* token — used by
    the bucketed scheduler, whose prompts arrive right-padded to a
    power-of-two; without it the logits come from position S-1.
    Paged caches (``k_pages``) need ``block_tables`` and calibrate their
    per-slot INT8 scales from this prompt."""
    b, s = tokens.shape
    span = _cache_span(cache, block_tables)
    x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    rope = L.rope_table(span, cfg.hd, base=cfg.rope_base, dtype=cfg.dtype)
    x, new_cache = run_blocks(params["blocks"], x, cfg, rope=rope,
                              cache=cache, cache_index=jnp.int32(0),
                              qctx=qctx, block_tables=block_tables,
                              calibrate_kv="k_pages" in cache,
                              kv_lengths=(None if last_pos is None
                                          else last_pos + 1))
    if last_pos is not None:
        x = x[jnp.arange(b), last_pos][:, None]
    else:
        x = x[:, -1:]
    logits = lm_head(params, x)
    return logits[:, 0], new_cache


def decode_step(params: Params, token: jax.Array, cache: Dict[str, jax.Array],
                cache_index: jax.Array, cfg: LMConfig, *,
                qctx: Optional[QuantCtx] = None,
                block_tables: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One autoregressive step: token [B] int32 → logits [B, V].
    ``cache_index`` is a scalar (uniform position) or a [B] vector of
    per-slot positions (continuous batching).  Handles bf16,
    INT8-quantized, and paged caches (scale entries ride along in the
    cache dict and are sliced per layer by the scan; paged caches route
    the read through the paged flash-decode kernel — the S=1 case of
    the q-block kernel the speculative verify uses via ``run_blocks``)."""
    span = _cache_span(cache, block_tables)
    x = L.embed(params["embed"], token[:, None]).astype(cfg.dtype)
    rope = L.rope_table(span, cfg.hd, base=cfg.rope_base, dtype=cfg.dtype)
    x, new_cache = run_blocks(params["blocks"], x, cfg, rope=rope,
                              cache=cache, cache_index=cache_index,
                              qctx=qctx, block_tables=block_tables)
    logits = lm_head(params, x)
    return logits[:, 0], new_cache


def split_blocks(params: Params, cfg: LMConfig, cut_layer: int,
                 ) -> Tuple[Params, Params]:
    """Split the stacked block params at the paper's partition point:
    (edge prefix = blocks[0..cut], cloud suffix = blocks[cut+1..L))."""
    assert 0 <= cut_layer < cfg.n_layers

    def take(lo, hi):
        return jax.tree_util.tree_map(lambda v: v[lo:hi], params["blocks"])

    return take(0, cut_layer + 1), take(cut_layer + 1, cfg.n_layers)


# ---------------------------------------------------------------------------
# Partition-analysis graph (paper §2.2 applied to a decoder stack)
# ---------------------------------------------------------------------------


def make_graph(cfg: LMConfig, *, batch: int, seq: int) -> LayerGraph:
    """Block-interior nodes carry the residual structure so the shortcut
    rule excludes them; block boundaries survive as candidates."""
    g = LayerGraph(cfg.name)
    d, hd = cfg.d_model, cfg.hd
    tok = batch * seq
    g.add("input", "input", [], (batch, seq))
    g.add("embed", "embed", ["input"], (batch, seq, d),
          param_elems=cfg.vocab * d, flops=0)
    prev = "embed"
    attn_proj_flops = 2 * tok * d * (cfg.n_heads * hd) * 2 \
        + 2 * tok * d * (cfg.n_kv * hd) * 2
    attn_sdpa_flops = 2 * batch * cfg.n_heads * seq * seq * hd * 2
    if cfg.moe:
        ffn_flops = 2 * tok * 3 * d * cfg.d_ff * cfg.moe.top_k \
            * cfg.moe.capacity_factor
        ffn_params = cfg.moe.n_experts * 3 * d * cfg.d_ff \
            + d * cfg.moe.n_experts
    else:
        ffn_flops = 2 * tok * 3 * d * cfg.d_ff
        ffn_params = 3 * d * cfg.d_ff
    for i in range(cfg.n_layers):
        a = g.add(f"blk{i}/attn", "attention", [prev], (batch, seq, d),
                  flops=attn_proj_flops + attn_sdpa_flops,
                  param_elems=cfg.block_param_count() - ffn_params - 2 * d)
        add1 = g.add(f"blk{i}/add1", "add", [a, prev], (batch, seq, d))
        f = g.add(f"blk{i}/ffn", "moe" if cfg.moe else "mlp", [add1],
                  (batch, seq, d), flops=ffn_flops,
                  param_elems=ffn_params + 2 * d)
        prev = g.add(f"blk{i}/add2", "add", [f, add1], (batch, seq, d))
    g.add("lm_head", "dense", [prev], (batch, seq, cfg.vocab),
          flops=2 * tok * d * cfg.vocab, param_elems=d * cfg.vocab + d)
    g.validate()
    return g


# ---------------------------------------------------------------------------
# Collaborative-serving segments (block granularity)
# ---------------------------------------------------------------------------


def make_segments(params: Params, cfg: LMConfig, *, seq: int):
    """SegmentedModel view: embed → per-block → head.  Cache-less forward
    (collaborative prefill/classification-style use)."""
    from repro.core.collab import Segment, SegmentedModel

    rope_const = L.rope_table(seq, cfg.hd, base=cfg.rope_base, dtype=cfg.dtype)

    def embed_apply(p, tokens, *, qctx=None):
        return L.embed(p, tokens).astype(cfg.dtype)

    def mk_block_apply():
        def apply(p, x, *, qctx=None):
            y, _, _ = block_apply(p, x, cfg, rope=rope_const, qctx=qctx)
            return y
        return apply

    def head_apply(p, x, *, qctx=None):
        x = L.rmsnorm(p["final_norm"], x)
        return L.dense(p["lm_head"], x, qctx=qctx, name="lm_head")

    segs = [Segment("embed", embed_apply, params["embed"])]
    for i in range(cfg.n_layers):
        bp = jax.tree_util.tree_map(lambda v, i=i: v[i], params["blocks"])
        # the block's residual add2 fuses into its ffn node (§2.2 rule 1),
        # so the candidate point carrying the block boundary is blk{i}/ffn
        segs.append(Segment(f"blk{i}/ffn", mk_block_apply(), bp))
    segs.append(Segment("lm_head", head_apply,
                        {"final_norm": params["final_norm"],
                         "lm_head": params["lm_head"]}))
    g = make_graph(cfg, batch=1, seq=seq)
    return SegmentedModel(name=cfg.name, graph=g, segments=segs)

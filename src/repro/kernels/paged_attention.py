"""Pallas TPU kernel: paged flash attention over an INT8 block-table
KV cache — one query row (decode) or a small q-block (speculative
verify, multi-token prefill).

Serving-side counterpart of ``int8_matmul``: where that kernel keeps the
paper's edge GEMMs at 1 B/elem, this one keeps the *KV cache* at 1 B/elem
end-to-end.  The cache is a pool of fixed-size pages
``[n_pages, n_kv, page_size, head_dim]`` (int8, or fp for the unquantized
variant); each sequence owns a row of a block table mapping its logical
page index to a physical page, so HBM is allocated on demand instead of
``max_len`` up front.  Pages are head-major inside: one (page, kv head)
tile ``[page_size, head_dim]`` is contiguous, which is the block shape
the TPU lowering accepts (its last two dims equal the array's).

One attention call = one grid step per (batch row, block of ``hb`` kv
heads, logical page):

  grid = (B, n_kv // hb, pages_per_seq), pages innermost ("arbitrary" —
  the online-softmax state m/l/acc lives in VMEM scratch across the
  page axis)

The ``hb`` heads of one page are contiguous, so one step DMAs them as a
single ``(1, hb, page_size, hd)`` block (64 KiB
for K at 32 heads, page 16, hd 128).  ``hb`` follows from the shape,
not from a setting (``_heads_per_step``): the largest divisor of the
local ``n_kv`` whose ``hb · S · group`` rows of the q, acc, m and l
tiles stay within ``_VMEM_ROWS`` (2048).  Decode and verify (S ≤ 8)
fold every head (32 for deepseek-7b, 10 for phi3-medium-14b); a
128-token prefill bucket takes 16 of 32, a 512-token one 4.  Under ``shard_map`` the rule
sees the shard's own ``n_kv``.

The block table, per-row KV lengths, and per-row *query start positions*
ride in scalar-prefetch SMEM so the K/V BlockSpec index maps can redirect
the page DMA, and stop it past a row's last live page
``last = (max(lengths[b], 1) - 1) // page_size``:

  index_map = lambda b, h, p, bt, ln, qs: (bt[b, min(p, last)], h, 0, 0)

A step past ``last`` keeps the block index of the step before it, so
the pipeline issues no DMA, and its body (under ``p * page_size <
lengths[b]``) does not run: such a page would have added exactly
``w = 0`` and ``alpha = exp(0) = 1``, so skipping it changes no bit of
the result.  Init at ``p == 0`` and emit at the last step stay put.

The q tile carries all S query rows of the block (S=1 for plain decode):
query i of row b sits at absolute position ``q_start[b] + i`` and may
attend KV positions ``<= q_start[b] + i`` that are also ``< lengths[b]``
— the *intra-block causal mask* that makes the same kernel serve

* **decode** (S=1, ``q_start = lengths - 1``): the PR-2 behavior, bit
  for bit;
* **speculative verify** (S=k drafts written at ``q_start = committed
  length``): k queries attend cache + the in-flight draft block, and a
  rejected suffix is "rolled back" simply by never advancing the
  committed length past it — stale page entries are masked out by
  causality on every later read;
* **paged multi-token prefill** (S=prompt bucket, ``q_start = 0``):
  prompts attend their just-written pages directly, so prefill and
  decode share one read path (and one set of INT8 scales).

INT8 K/V are dequantized *inside* the QK/AV loops: the page tile is
converted to f32 on load, the per-(row, kv-head) K scale (with
1/sqrt(hd)) multiplies the scores and the V scale the output at emit —
exact, since each is constant over a (row, head)'s keys.  The scales
(per (layer, kv-head), optionally calibrated per slot, so shaped
[B, n_kv]) arrive as ``(1, hb, 1, 1)`` VMEM blocks of [B, n_kv, 1, 1].
HBM only ever streams 1 B/elem.  GQA runs grouped: the q heads sharing
a kv head form the sublane dim of the score tile, a q-block of S tokens
stacks to an (S·group, hd) tile per head, and QK and AV are contractions
batched over the ``hb`` heads.

Off-TPU, for tests only, there are two stand-ins: ``interpret=True``
runs the very same kernel through the Pallas interpreter (the parity
tests), while the serving engines default to
``paged_attention_ref``/``paged_attention_mq_ref`` — XLA implementations
of identical math.  ``paged_attention`` / ``paged_multiquery_attention``
dispatch.

VMEM residency per grid step (hd = 128, rows = hb · S · group ≤ 2048;
the last dim pads to 128 lanes):
  K, V pages  int8 [hb, page_size, hd] x2, double-buffered: 64 KiB each
                at hb = 32, page 16
  q, out      [hb, S·G, hd], double-buffered      ≤ 1 MiB each (f32)
  acc         f32 [hb, S·G, hd]                   ≤ 1 MiB
  m, l        f32 [hb, S·G, 1]                    ≤ 1 MiB each (padded)
  scores      f32 [hb, S·G, page_size]            ≤ 1 MiB per temporary
On a v5e chip, 4096 rows ran with bf16 queries but ran out of VMEM with
the engines' f32 queries (a described v5e compiles both, so the
compile tests cannot see that edge); 2048 f32 rows hold less than the
4096 bf16 rows that ran.  On real TPU prefer page_size a multiple of
32 (int8 sublane) and S·group padded to 8 — the interpret/ref paths
accept any size.

Tensor-parallel: ``paged_flash_mq_sharded``/``paged_flash_decode_sharded``
run the kernel inside ``shard_map`` over a mesh.  When ``n_kv`` divides
the ``model`` axis, pool, scales and query heads partition by kv head,
so each shard streams only its own KV slice and no collective is needed
(attention is per-head independent; GQA groups never straddle shards).
Otherwise every shard runs the whole kernel on a replicated pool.  On
the pallas path the dispatchers route through them whenever the caller
traces under a mesh (``jax.set_mesh`` — the serving engines' mesh phases
do, see ``serve.scheduler._jit_phase``): GSPMD cannot partition a Mosaic
call, so no kernel may reach it outside ``shard_map``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

__all__ = ["paged_attention", "paged_multiquery_attention",
           "paged_flash_decode", "paged_flash_mq",
           "paged_flash_decode_sharded", "paged_flash_mq_sharded",
           "paged_attention_ref", "paged_attention_mq_ref"]

# finite stand-in for -inf: (-1e30) - (-1e30) = 0 keeps exp() NaN-free on
# fully-masked pages, where true -inf would poison the running max
_MASKED = -1e30

# module default for `paged_attention(impl=None)`; tests may override to
# "pallas_interpret" to drive the real kernel through the model stack
_DEFAULT_IMPL = "auto"


def _kernel(bt_ref, len_ref, qs_ref,    # scalar-prefetch: table, lens, q0
            q_ref, k_ref, v_ref,        # [1,hb,S·G,hd], [1,hb,P,hd] x2
            ks_ref, vs_ref,             # [1,hb,1,1] per-(row, kv-head) scales
            o_ref,                      # [1,hb,S·G,hd]
            m_ref, l_ref, acc_ref,      # scratch: online-softmax state
            *, page_size: int, group: int, sm_scale: float):
    b, p = pl.program_id(0), pl.program_id(2)
    length = len_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages past the row's last live one: their K/V block index is the
    # last live page's (no DMA), and this body, which would add exactly
    # w = 0 and alpha = 1, does not run
    @pl.when(p * page_size < length)
    def _step():
        # HBM streamed the pages at 1 B/elem; the per-(row, head) K scale
        # and 1/sqrt(hd) multiply the [hb, S·G, P] scores instead of the
        # [hb, P, hd] tile, the V scale waits for _emit (both exact: each
        # is constant over a (row, head)'s keys)
        q = q_ref[0].astype(jnp.float32)                      # [hb, S·G, hd]
        k = k_ref[0].astype(jnp.float32)                      # [hb, P, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.einsum("hqd,hkd->hqk", q, k,
                       preferred_element_type=jnp.float32) \
            * (ks_ref[0] * sm_scale)                          # [hb, S·G, P]
        shape = s.shape
        pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        # row r of a head's tile is query token r // group at absolute
        # position q_start + r // group: intra-block causality + the KV
        # length bound
        qpos = qs_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1) // group
        valid = jnp.logical_and(pos <= qpos, pos < length)
        s = jnp.where(valid, s, _MASKED)

        m_prev = m_ref[...]                                   # [hb, S·G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit re-mask: on an all-masked row exp(s - m) would be exp(0)
        w = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(w, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hqk,hkd->hqd", w, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] * vs_ref[0] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


# rows (kv heads x query rows) of one grid step's q, acc, m and l tiles
# that fit the scoped VMEM next to the K/V pages and the score
# temporaries at f32 queries, hd 128 (module docstring: residency)
_VMEM_ROWS = 2048


def _heads_per_step(n_kv: int, rows: int) -> int:
    """kv heads one grid step carries: the largest divisor of ``n_kv``
    whose ``rows``-row tiles (S·group each) stay within ``_VMEM_ROWS``,
    and at least one."""
    return max(d for d in range(1, n_kv + 1)
               if n_kv % d == 0 and (d == 1 or d * rows <= _VMEM_ROWS))


def _norm_scales(scale: Optional[jax.Array], batch: int,
                 n_kv: int) -> jax.Array:
    """Broadcast per-cache scales to the kernel's [B, n_kv] layout.

    Accepts None (fp pages: identity), [n_kv] (per-(layer, head) deploy
    calibration) or [B, n_kv] (per-slot calibration at prefill)."""
    if scale is None:
        return jnp.ones((batch, n_kv), jnp.float32)
    scale = jnp.asarray(scale, jnp.float32)
    if scale.ndim == 1:
        scale = jnp.broadcast_to(scale[None], (batch, n_kv))
    return scale


def _flash_mq(q, k_pages, v_pages, block_tables, lengths, q_start,
              k_scale, v_scale, *, hb: int, interpret: bool) -> jax.Array:
    """``paged_flash_mq`` with ``hb`` kv heads a grid step."""
    b, s, n_heads, hd = q.shape
    _, n_kv, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    group = n_heads // n_kv
    assert group * n_kv == n_heads, (n_heads, n_kv)
    assert n_kv % hb == 0, (n_kv, hb)

    # [B, n_kv, S·group, hd]: the q heads sharing a kv head — for every
    # query token of the block — form the sublane dim of one head's tile
    qg = q.reshape(b, s, n_kv, group, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, n_kv, s * group, hd)
    ks = _norm_scales(k_scale, b, n_kv).reshape(b, n_kv, 1, 1)
    vs = _norm_scales(v_scale, b, n_kv).reshape(b, n_kv, 1, 1)

    def row_map(b_, h, p, bt, ln, qs):
        return b_, h, 0, 0

    def page_map(b_, h, p, bt, ln, qs):
        # clamp to the row's last live page: a dead step keeps the block
        # index of the step before it, so the pipeline issues no DMA
        last = jax.lax.div(jnp.maximum(ln[b_], 1) - 1, page_size)
        return bt[b_, jnp.minimum(p, last)], h, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_kv // hb, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, hb, s * group, hd), row_map),
            pl.BlockSpec((1, hb, page_size, hd), page_map),
            pl.BlockSpec((1, hb, page_size, hd), page_map),
            pl.BlockSpec((1, hb, 1, 1), row_map),
            pl.BlockSpec((1, hb, 1, 1), row_map),
        ],
        out_specs=pl.BlockSpec((1, hb, s * group, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((hb, s * group, 1), jnp.float32),   # running max
            pltpu.VMEM((hb, s * group, 1), jnp.float32),   # running denom
            pltpu.VMEM((hb, s * group, hd), jnp.float32),  # un-normalized
        ],
    )
    kernel = functools.partial(_kernel, page_size=page_size, group=group,
                               sm_scale=1.0 / math.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, q_start.astype(jnp.int32), qg, k_pages,
      v_pages, ks, vs)
    return out.reshape(b, n_kv, s, group, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, s, n_heads, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_mq(
    q: jax.Array,                  # [B, S, n_heads, hd]
    k_pages: jax.Array,            # [n_pages, n_kv, page_size, hd] int8|fp
    v_pages: jax.Array,
    block_tables: jax.Array,       # [B, pages_per_seq] int32
    lengths: jax.Array,            # [B] int32, # of valid KV entries
    q_start: jax.Array,            # [B] int32, abs position of query row 0
    k_scale: Optional[jax.Array] = None,   # [n_kv] or [B, n_kv]
    v_scale: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention of an S-query block over the paged cache →
    [B, S, n_heads, hd] (query i attends positions <= q_start + i)."""
    _, s, n_heads, _ = q.shape
    n_kv = k_pages.shape[1]
    hb = _heads_per_step(n_kv, s * (n_heads // n_kv))
    return _flash_mq(q, k_pages, v_pages, block_tables, lengths, q_start,
                     k_scale, v_scale, hb=hb, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_decode(
    q: jax.Array,                  # [B, n_heads, hd]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """One flash-decode step over the paged cache → [B, n_heads, hd]
    (the S=1 case of ``paged_flash_mq``: the single query sits at the
    last valid position, so the causal mask degenerates to the length
    bound and PR-2 semantics are preserved exactly)."""
    out = paged_flash_mq(q[:, None], k_pages, v_pages, block_tables,
                         lengths, lengths - 1, k_scale, v_scale,
                         interpret=interpret)
    return out[:, 0]


def paged_attention_mq_ref(
    q: jax.Array,                  # [B, S, n_heads, hd]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    q_start: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Pure-XLA oracle for the q-block kernel — same math, gather-based.

    Also the production path off-TPU: it touches only the pages named in
    the block table (HBM/DRAM traffic ∝ allocated pages, not max_len),
    so the engines' CPU benchmarks measure the same asymptotics the TPU
    kernel delivers."""
    b, s, n_heads, hd = q.shape
    _, n_kv, page_size, _ = k_pages.shape
    group = n_heads // n_kv
    span = block_tables.shape[1] * page_size

    def gather(pages):                  # [B, span, n_kv, hd] f32
        g = pages[block_tables].transpose(0, 1, 3, 2, 4)
        return g.reshape(b, span, n_kv, hd).astype(jnp.float32)

    k, v = gather(k_pages), gather(v_pages)
    ks = _norm_scales(k_scale, b, n_kv)
    vs = _norm_scales(v_scale, b, n_kv)
    k = k * ks[:, None, :, None]
    v = v * vs[:, None, :, None]

    qg = q.reshape(b, s, n_kv, group, hd).astype(jnp.float32) / math.sqrt(hd)
    logits = jnp.einsum("bsngd,blnd->bnsgl", qg, k)
    pos = jnp.arange(span)
    qpos = q_start[:, None] + jnp.arange(s)[None, :]            # [B, S]
    mask = jnp.logical_and(
        pos[None, None, :] <= qpos[:, :, None],
        pos[None, None, :] < lengths[:, None, None])            # [B, S, L]
    logits = jnp.where(mask[:, None, :, None, :], logits, _MASKED)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnsgl,blnd->bsngd", p, v)
    return out.reshape(b, s, n_heads, hd).astype(q.dtype)


def paged_attention_ref(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """S=1 oracle (decode): the query sits at the last valid position."""
    out = paged_attention_mq_ref(q[:, None], k_pages, v_pages, block_tables,
                                 lengths, lengths - 1, k_scale, v_scale)
    return out[:, 0]


def paged_flash_mq_sharded(
    q: jax.Array,                  # [B, S, n_heads, hd]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    q_start: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    *,
    mesh: jax.sharding.Mesh,
    interpret: bool = False,
) -> jax.Array:
    """``paged_flash_mq`` inside ``shard_map`` over ``mesh``, the only
    way a Mosaic call may run on a mesh (GSPMD cannot partition one).
    When ``n_kv`` divides the ``model`` axis, the page pool, scales and
    query heads partition by kv head, so each shard DMAs and dequantizes
    ONLY its own 1 B/elem KV slice — per-device KV bandwidth drops by
    the TP degree, and no inter-shard collective is needed (attention is
    independent per kv head; each kv head's q group stays on its shard).
    Otherwise (guard mirrors ``launch.shardings``) the heads replicate
    and every shard runs the whole kernel.  Batch rides the ``data``
    axis when it divides."""
    b, s, n_heads, hd = q.shape
    n_kv = k_pages.shape[1]
    tp = int(mesh.shape["model"]) if "model" in mesh.axis_names else 1
    h_ax = "model" if tp > 1 and n_kv % tp == 0 else None
    # normalize to [B, n_kv] OUTSIDE the map so scales partition by head
    ks = _norm_scales(k_scale, b, n_kv)
    vs = _norm_scales(v_scale, b, n_kv)
    b_ax = None
    if "data" in mesh.axis_names and b % int(mesh.shape["data"]) == 0:
        b_ax = "data"

    fn = jax.shard_map(
        functools.partial(paged_flash_mq, interpret=interpret),
        mesh=mesh,
        in_specs=(P(b_ax, None, h_ax, None),           # q (heads split)
                  P(None, h_ax, None, None),           # k_pages (kv split)
                  P(None, h_ax, None, None),           # v_pages
                  P(b_ax, None),                       # block tables
                  P(b_ax), P(b_ax),                    # lengths, q_start
                  P(b_ax, h_ax), P(b_ax, h_ax)),       # scales
        out_specs=P(b_ax, None, h_ax, None),
        check_vma=False,
    )
    return fn(q, k_pages, v_pages, block_tables,
              lengths, q_start.astype(jnp.int32), ks, vs)


def paged_flash_decode_sharded(
    q: jax.Array,                  # [B, n_heads, hd]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    *,
    mesh: jax.sharding.Mesh,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel decode step (S=1 case of the sharded q-block)."""
    out = paged_flash_mq_sharded(q[:, None], k_pages, v_pages, block_tables,
                                 lengths, lengths - 1, k_scale, v_scale,
                                 mesh=mesh, interpret=interpret)
    return out[:, 0]


def _resolve_impl(impl: Optional[str]) -> str:
    impl = impl or _DEFAULT_IMPL
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def paged_multiquery_attention(
    q: jax.Array,                  # [B, S, n_heads, hd]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    q_start: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    *,
    impl: Optional[str] = None,
) -> jax.Array:
    """Dispatching front door for an S-query block (speculative verify,
    paged multi-token prefill): Pallas kernel on TPU, XLA ref elsewhere.

    ``impl``: "auto" (default), "pallas", "pallas_interpret", or "ref".
    On the pallas paths a caller tracing under a mesh gets the
    ``shard_map``'d kernel over that mesh."""
    impl = _resolve_impl(impl)
    if impl == "ref":
        return paged_attention_mq_ref(q, k_pages, v_pages, block_tables,
                                      lengths, q_start, k_scale, v_scale)
    interpret = impl == "pallas_interpret"
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return paged_flash_mq_sharded(
            q, k_pages, v_pages, block_tables, lengths, q_start,
            k_scale, v_scale, mesh=mesh, interpret=interpret)
    return paged_flash_mq(q, k_pages, v_pages, block_tables, lengths,
                          q_start, k_scale, v_scale, interpret=interpret)


def paged_attention(
    q: jax.Array,                  # [B, n_heads, hd]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    *,
    impl: Optional[str] = None,
) -> jax.Array:
    """Dispatching front door for decode: the S=1 case of
    ``paged_multiquery_attention``, its query at the last valid
    position."""
    out = paged_multiquery_attention(q[:, None], k_pages, v_pages,
                                     block_tables, lengths, lengths - 1,
                                     k_scale, v_scale, impl=impl)
    return out[:, 0]

"""Role-based sharding rules (MaxText-style logical axes, path-driven).

Strategy per family:
  * params: ZeRO-3/FSDP over the batch axes (``data``, plus ``pod`` when
    multi-pod) on the input-feature dim × tensor-parallel over ``model``
    on the output-feature dim; output projections (wo/proj_out) flip the
    two so the TP collective pattern is all-reduce after the second
    matmul (Megatron).
  * MoE experts: expert-parallel over ``model`` when the expert count
    divides it (qwen3: 128/16=8 experts per group); otherwise the expert
    FFN dim takes the TP axis (grok: 8 experts, d_ff 32768/16).
  * stacked-layer leading axes ([L, ...] from scan) are never sharded.
  * activations/batch: shard dim 0 over the batch axes; decode KV caches
    shard batch when divisible, else spread sequence over everything.

Every rule is divisibility-guarded: a dim that does not divide its mesh
axes stays unsharded (GSPMD would pad, we prefer exact layouts).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import batch_axes, fsdp_axes

__all__ = ["param_shardings", "data_sharding", "replicated",
           "cache_sharding", "cache_spec", "logits_sharding",
           "spec_for_param", "paged_pool_spec", "paged_scale_spec",
           "paged_pool_shardings"]


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _fit(dim: int, mesh: Mesh, axes: Tuple[str, ...]) -> Optional[Any]:
    """Return axes (str or tuple) if dim divides their product, else None."""
    if not axes:
        return None
    if dim % _axes_size(mesh, axes) == 0:
        return axes[0] if len(axes) == 1 else axes
    return None


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _path_str(path) -> str:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
    return "/".join(out)


_STACKED_ROOTS = ("blocks", "double", "single")
_OUT_PROJ_TOKENS = ("wo", "proj_out", "out", "xo")


def spec_for_param(path_str: str, shape: Tuple[int, ...], mesh: Mesh,
                   *, zero1: bool = False) -> P:
    """``zero1=True`` replicates params over the DP axes (ZeRO-1: only
    optimizer state and grads are sharded by the update math) — kills
    the per-layer FSDP weight all-gathers at the cost of a full param
    copy per model-parallel group.  Right trade for small-params cells
    (see EXPERIMENTS.md §Perf)."""
    fsdp = () if zero1 else fsdp_axes(mesh)
    toks = path_str.split("/")
    stacked = toks[0] in _STACKED_ROOTS
    dims = list(shape)
    lead: list = []
    if stacked and dims:
        lead = [None]                      # [L, ...] layer axis unsharded
        dims = dims[1:]

    def mk(*spec):
        return P(*lead, *spec)

    rank = len(dims)
    if rank <= 1:
        return mk(*([None] * rank))

    is_out_proj = any(t in _OUT_PROJ_TOKENS for t in toks[-2:])

    if rank == 2:
        d_in, d_out = dims
        if toks[-1] == "emb":              # embedding table [V, D]
            return mk(_fit(d_in, mesh, ("model",)),
                      _fit(d_out, mesh, fsdp))
        if is_out_proj:
            return mk(_fit(d_in, mesh, ("model",)),
                      _fit(d_out, mesh, fsdp))
        return mk(_fit(d_in, mesh, fsdp),
                  _fit(d_out, mesh, ("model",)))

    if rank == 3:
        # MoE experts: expert dim unsharded (ragged grouped-GEMM needs
        # every group's weights addressable); FSDP on d_model, TP on the
        # expert FFN dim — uniform for wi/wg [E, D, F] and wo [E, F, D].
        e, a, b = dims
        if "wo" in toks[-2:]:              # [E, F, D]
            return mk(None, _fit(a, mesh, ("model",)), _fit(b, mesh, fsdp))
        return mk(None, _fit(a, mesh, fsdp), _fit(b, mesh, ("model",)))

    if rank == 4:                          # conv [k, k, cin, cout]
        k1, k2, cin, cout = dims
        return mk(None, None, _fit(cin, mesh, fsdp),
                  _fit(cout, mesh, ("model",)))

    return mk(*([None] * rank))


def param_shardings(abstract_params: Any, mesh: Mesh, *,
                    zero1: bool = False) -> Any:
    """NamedSharding tree matching an (abstract) param tree."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(abstract_params)
    out = []
    for path, leaf in flat:
        spec = spec_for_param(_path_str(path), tuple(leaf.shape), mesh,
                              zero1=zero1)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(tdef, out)


def data_sharding(mesh: Mesh, ndim: int, *, batch_dim: int = 0,
                  batch: Optional[int] = None) -> NamedSharding:
    """Batch-parallel input sharding; replicates when batch doesn't fit."""
    ba = batch_axes(mesh)
    spec = [None] * ndim
    if ba and (batch is None or batch % _axes_size(mesh, ba) == 0):
        spec[batch_dim] = ba if len(ba) > 1 else ba[0]
    return NamedSharding(mesh, P(*spec))


def logits_sharding(mesh: Mesh, ndim: int, *, batch: int,
                    vocab: int) -> NamedSharding:
    ba = batch_axes(mesh)
    spec: list = [None] * ndim
    if ba and batch % _axes_size(mesh, ba) == 0:
        spec[0] = ba if len(ba) > 1 else ba[0]
    if vocab % mesh.shape["model"] == 0:
        spec[-1] = "model"
    return NamedSharding(mesh, P(*spec))


def cache_spec(mesh: Mesh, *, batch: int, seq: int, n_kv: int,
               head_dim: int = 128) -> P:
    """PartitionSpec for a dense KV cache [L, B, S, H, D]: batch over
    (pod,data) when divisible, head_dim over model (decode writes a
    dynamic S slice — sharding S would force SPMD full-rematerialization
    of the update; sharding D keeps the dynamic-update-slice local).
    batch=1 spreads S over the batch axes instead."""
    ba = batch_axes(mesh)
    b_ax = None
    s_ax = None
    if ba and batch % _axes_size(mesh, ba) == 0:
        b_ax = ba if len(ba) > 1 else ba[0]
    else:
        s_ax = _fit(seq, mesh, ba)
    d_ax = _fit(head_dim, mesh, ("model",))
    h_ax = None
    if d_ax is None:
        h_ax = _fit(n_kv, mesh, ("model",))
    return P(None, b_ax, s_ax, h_ax, d_ax)


def cache_sharding(mesh: Mesh, *, batch: int, seq: int, n_kv: int,
                   head_dim: int = 128) -> NamedSharding:
    """``cache_spec`` wrapped as a NamedSharding (the historical API)."""
    return NamedSharding(mesh, cache_spec(mesh, batch=batch, seq=seq,
                                          n_kv=n_kv, head_dim=head_dim))


def paged_pool_spec(mesh: Mesh, *, n_pages: int, n_kv: int,
                    head_dim: int) -> P:
    """PartitionSpec for a paged KV page pool
    ``[L, n_pages, n_kv, page_size, head_dim]``: kv heads over ``model``
    — each TP shard stores, dequantizes, and attends only its own KV
    slice — and the page dim over the batch axes when divisible (pages
    are slot-owned, so this is "slots on the data axis" at page
    granularity).  The layer dim and ``page_size`` stay unsharded (a
    page is the DMA unit); when ``n_kv`` doesn't divide the TP degree
    the pool replicates — splitting ``head_dim`` instead would tear the
    per-head dequant·softmax·gather apart and forces SPMD to fully
    rematerialize the gathered pages (measured, not hypothetical)."""
    h_ax = _fit(n_kv, mesh, ("model",))
    p_ax = _fit(n_pages, mesh, batch_axes(mesh))
    return P(None, p_ax, h_ax, None, None)


def paged_scale_spec(mesh: Mesh, *, batch: int, n_kv: int) -> P:
    """Per-slot INT8 scale rows ``[L, B, n_kv]`` of a paged cache: shard
    like the pool they calibrate — kv heads over ``model``, slots over
    the batch axes — under the same divisibility guards."""
    h_ax = _fit(n_kv, mesh, ("model",))
    ba = batch_axes(mesh)
    b_ax = None
    if ba and batch % _axes_size(mesh, ba) == 0:
        b_ax = ba if len(ba) > 1 else ba[0]
    return P(None, b_ax, h_ax)


def paged_pool_shardings(cache: Any, mesh: Mesh) -> Any:
    """NamedSharding tree for a paged cache dict (``k_pages``/``v_pages``
    + optional ``k_scale``/``v_scale`` — see ``transformer.init_cache``)."""
    out = {}
    for k, v in cache.items():
        if k.endswith("_pages"):
            _, n_pages, n_kv, _, hd = v.shape
            spec = paged_pool_spec(mesh, n_pages=n_pages, n_kv=n_kv,
                                   head_dim=hd)
        elif k.endswith("_scale"):
            _, b, n_kv = v.shape
            spec = paged_scale_spec(mesh, batch=b, n_kv=n_kv)
        else:
            spec = P()
        out[k] = NamedSharding(mesh, spec)
    return out

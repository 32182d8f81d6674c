"""Tensor-parallel placement of the cloud side of a serving engine.

The collaborative engine's cloud suffix is the fast half of the
partition; this module lets it actually scale with devices by placing
every piece of engine-owned device state onto a ``("data", "model")``
``jax.sharding.Mesh`` once, at construction / re-partition time:

* **cloud suffix weights** — Megatron-style TP via the role-based rules
  of ``launch.shardings.spec_for_param`` (``zero1=True``: serving
  replicates over the data axis, no FSDP): QKV/FFN-in column-split over
  ``model``, proj/FFN-out row-split, so each layer costs two
  all-reduces;
* **lm_head** — vocab column-split over ``model`` when divisible (the
  argmax reduces over the vocab dim, GSPMD inserts the gather);
* **paged cloud KV pool** — kv heads over ``model`` / pages over
  ``data`` via ``launch.shardings.paged_pool_shardings``, so each TP
  shard stores and dequantizes only its own INT8 KV slice;
* **everything edge-side** (embed, edge/draft blocks and caches) —
  replicated onto the *same* mesh.  This is load-bearing, not cosmetic:
  one jitted phase closes over both halves, and jax refuses committed
  arguments spanning different device sets.  Replication keeps the edge
  math bit-identical to the unsharded engine on every shard.

Why this preserves the committed streams (the property the mesh tests
pin): the scheduler commits only tokens that equal the *cloud's own
greedy stream* (longest-prefix acceptance + the corrected token), and
cloud argmaxes are stable across TP degrees at serving precision —
edge-side math is replicated, so drafts and boundary blobs are
bit-identical by construction and only affect the acceptance rate.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.shardings import (cache_spec, paged_pool_shardings,
                                    spec_for_param, _path_str)

__all__ = ["tp_size", "replicate_to_mesh", "suffix_block_shardings",
           "tail_shardings", "cloud_cache_shardings", "collab_shardings",
           "place_collab_engine", "place_cloud_engine"]


def tp_size(mesh: Optional[Mesh]) -> int:
    """The tensor-parallel degree a serve mesh gives the cloud suffix."""
    if mesh is None:
        return 1
    return int(mesh.shape["model"]) if "model" in mesh.axis_names else 1


def replicate_to_mesh(tree: Any, mesh: Mesh) -> Any:
    """Place a pytree fully replicated on every device of ``mesh``."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def _role_shardings(tree: Any, mesh: Mesh, root: str) -> Any:
    """NamedSharding tree from the role-based param rules, each leaf's
    path resolved under ``root``."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(tdef, [
        NamedSharding(mesh, spec_for_param(root + _path_str(path),
                                           tuple(leaf.shape), mesh,
                                           zero1=True))
        for path, leaf in flat])


def suffix_block_shardings(blocks: Any, mesh: Mesh) -> Any:
    """TP shardings of a stacked ``[L, ...]`` suffix block tree (paths
    resolved under a ``blocks/`` root so the stacked-layer lead dim
    stays unsharded)."""
    return _role_shardings(blocks, mesh, "blocks/")


def tail_shardings(tail: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """The head: ``lm_head`` vocab-column-split when divisible (rank-2
    generic rule), norms replicated."""
    return {name: _role_shardings(sub, mesh, f"{name}/")
            for name, sub in tail.items()}


def cloud_cache_shardings(cache: Dict[str, jax.Array],
                          mesh: Mesh) -> Dict[str, NamedSharding]:
    """A cloud KV cache: paged pools shard kv-heads over ``model`` and
    pages over ``data`` (divisibility-guarded); dense caches shard via
    ``cache_spec`` on k/v, scales replicated."""
    if "k_pages" in cache:
        return paged_pool_shardings(cache, mesh)
    out = {}
    for k, v in cache.items():
        if k in ("k", "v"):
            _, b, s, h, d = v.shape
            spec = cache_spec(mesh, batch=b, seq=s, n_kv=h, head_dim=d)
        else:
            spec = P()
        out[k] = NamedSharding(mesh, spec)
    return out


def collab_shardings(eng, mesh: Mesh) -> Dict[str, Any]:
    """Sharding of every piece of a ``CollaborativeServingEngine``'s
    device state, by attribute name: cloud half TP-sharded, edge half
    replicated (a whole-tree ``NamedSharding``)."""
    rep = NamedSharding(mesh, P())
    out = {"embed": rep, "tail": tail_shardings(eng.tail, mesh),
           "edge_blocks": rep,
           "cloud_blocks": suffix_block_shardings(eng.cloud_blocks, mesh),
           "_edge_cache": rep,
           "_cloud_cache": cloud_cache_shardings(eng._cloud_cache, mesh)}
    if eng.draft_blocks is not None:
        out["draft_blocks"] = rep
    if getattr(eng, "_draft_cache", None) is not None:
        out["_draft_cache"] = rep
    return out


def place_collab_engine(eng) -> None:
    """Place ALL of a ``CollaborativeServingEngine``'s device state onto
    its mesh in one pass (``collab_shardings``).  Called at construction
    and after every re-partition (``_set_cut``), so a cut switch
    re-shards the new suffix slice.  Placing the edge half too
    (replicated) is required: every phase jit must see one consistent
    committed device set (see the module docstring)."""
    if eng.mesh is None:
        return
    for name, sharding in collab_shardings(eng, eng.mesh).items():
        setattr(eng, name, jax.device_put(getattr(eng, name), sharding))


def place_cloud_engine(eng) -> None:
    """Mesh placement for the cloud-only ``ServingEngine``: the full
    param stack TP-shards under the role-based rules (``blocks`` is the
    stacked ``[L, ...]`` tree, so its layer dim stays unsharded) and the
    KV cache shards like the collaborative cloud cache."""
    mesh = eng.mesh
    if mesh is None:
        return
    from repro.launch.shardings import param_shardings
    eng.params = jax.device_put(
        eng.params, param_shardings(eng.params, mesh, zero1=True))
    eng._cache = jax.device_put(eng._cache,
                                cloud_cache_shardings(eng._cache, mesh))

"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod: 2×16×16 = 512 chips, axes (pod, data, model) — the ``pod`` axis
is the slow (DCN-class) dimension; data-parallel replicas and ZeRO
sharding may span it (see repro.launch.shardings).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "make_serve_mesh",
           "batch_axes", "fsdp_axes"]


def _mk_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _mk_mesh((data, model), ("data", "model"))


def make_serve_mesh(model: int = 1, data: int = 1) -> jax.sharding.Mesh:
    """Cloud-verify TP mesh for the serving engines: ``model`` is the
    tensor-parallel degree the cloud suffix (and the paged KV pool's
    kv-head dim) shards over, ``data`` the slot-parallel axis.  Clamps
    like ``make_host_mesh`` so tests on few devices stay runnable, but
    keeps the requested ``model`` degree whenever enough devices exist —
    the serving meshes are (1, N) in practice."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = max(1, min(data, n // model))
    return _mk_mesh((data, model), ("data", "model"))


def batch_axes(mesh: jax.sharding.Mesh) -> tuple:
    """Axes the global batch shards over (DP/FSDP dimension)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axes(mesh: jax.sharding.Mesh) -> tuple:
    """Axes parameters are ZeRO-sharded over (== the batch axes)."""
    return batch_axes(mesh)

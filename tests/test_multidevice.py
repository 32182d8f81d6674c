"""Multi-device parity tests — run in a SUBPROCESS with 8 forced host
devices (the main test process must keep the real 1-device view)."""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, dataclasses
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.launch.mesh import _mk_mesh

    mesh = _mk_mesh((4, 2), ("data", "model"))

    # ---- 1. sharded MoE == unsharded MoE (same routing, same math) ----
    from repro.models import layers as L
    key = jax.random.PRNGKey(0)
    d, f, E, K = 16, 32, 4, 2
    p = L.moe_init(key, d, f, E)
    x = jnp.asarray(np.random.RandomState(1).randn(8, 6, d), np.float32)
    y_ref, aux_ref = L.moe(p, x, top_k=K, capacity_factor=4.0)

    with mesh, jax.set_mesh(mesh):
        y_sh, aux_sh = jax.jit(lambda p, x: L.moe_sharded(
            p, x, top_k=K, batch_spec="data", model_axis="model"))(p, x)
    # sharded path routes per data-shard (2 tokens fewer per capacity
    # group); with generous capacity results must match closely
    err = float(jnp.linalg.norm(y_sh - y_ref) / jnp.linalg.norm(y_ref))
    assert err < 2e-2, f"moe_sharded mismatch: {err}"
    assert abs(float(aux_sh) - float(aux_ref)) < 0.5

    # ---- 2. LM train step under production-style shardings ------------
    from repro.launch.steps import build_cell
    from repro.launch.mesh import batch_axes
    cell = build_cell("qwen3-moe-30b-a3b", "train_4k", mesh, smoke=True)
    compiled = cell.lower().compile()

    rng = np.random.RandomState(0)
    def conc(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            if jnp.issubdtype(x.dtype, jnp.integer):
                return jnp.asarray(rng.randint(0, 9, x.shape).astype(x.dtype))
            return jnp.asarray(np.abs(rng.randn(*x.shape)).astype(x.dtype)
                               * 0.02)
        return x
    with mesh, jax.set_mesh(mesh):
        args = jax.tree_util.tree_map(
            conc, cell.args,
            is_leaf=lambda v: isinstance(v, jax.ShapeDtypeStruct))
        params, opt, metrics = compiled(*args)
    assert bool(jnp.isfinite(metrics["loss"])), metrics
    print("MULTIDEVICE_OK")
""")


@pytest.mark.slow
def test_multidevice_parity_subprocess():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=420, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "JAX_PLATFORMS": "cpu",
                          "HOME": "/root"})
    assert "MULTIDEVICE_OK" in proc.stdout, (
        proc.stdout[-2000:], proc.stderr[-3000:])
